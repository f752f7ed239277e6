#!/usr/bin/env python3
"""Hold what the window served against the configuration's plain reference.

    python3 chipbench/parity.py <config.json> <sample.json> <out.json> [--weights-seed N]

``run.py`` starts this as a child once the window has closed, the worker's
peak memory has been read and the stack has stopped, so the chip is free (the
CPU under ``--rehearse``). The sample holds sequences the window finished,
packed into rows of one fixed length (``pick_sample``):
``{"groups": {"<label>": [[{"tokens": [...], "served": [[a, b], ...]}, ...], ...]}}``;
a run has one group, ``parity_seeds.py`` one per sample. A sequence is a
request's prompt with the tokens served after it, ``served`` the spans of it
that the window's requests produced; a session's last turn carries the spans
of the turns it resends (``sequences``). The reference
(``references/<doc["reference"]>.py``) makes the cell's weights from the
configuration's ``served.weights_seed`` and runs once over each row (teacher
forced: one flipped token does not cascade). Every request is greedy, so each
served token should be the reference's best; rounding flips it where two
logits are close. The number compared is the gap, in logits, by which the
served token lies below the reference's best at its position: 0 where they
agree.

    max_logit_gap    the widest gap over the sample's served tokens
    mean_logit_gap   the mean over them: steadier, and what a lower precision moves
    flipped_share    share of served tokens that are not the reference's best (not judged)

``verdict`` holds the two against the cell's limits (``limits/<cell>.json``; the
toy's are in its ``parity`` block). The arithmetic needs no JAX and is tested
on hand-made numbers.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

JUDGED = ("max_logit_gap", "mean_logit_gap")


def readings(gaps: list[float]) -> dict:
    """The numbers compared, from the per-token gaps of a sample."""
    if not gaps:
        return {"tokens": 0}
    return {"tokens": len(gaps), "max_logit_gap": max(gaps), "mean_logit_gap": sum(gaps) / len(gaps),
            "flipped_share": sum(1 for g in gaps if g > 0) / len(gaps)}


def verdict(read: dict, limits: dict) -> list[str]:
    """Why the sample does not agree with the reference; empty where it does.
    A reading at its limit passes, one over it does not; a sample with nothing
    to compare, or a limit that is missing, does not pass."""
    if not read.get("tokens"):
        return ["no served token was compared"]
    why = []
    for name in JUDGED:
        if name not in limits:
            why.append(f"no limit for {name}: limits/<cell>.json has to give one")
        elif not read[name] <= limits[name]:
            why.append(f"{name} {read[name]:.6g} over its limit {limits[name]:g}")
    return why


def sequences(records: list[dict]) -> list[dict]:
    """What the window finished, as sequences for the reference: a request's
    prompt and answer, with the span the answer fills. Where a later request
    resent an earlier one's prompt and answer as its history (a session's next
    turn), the later sequence takes over the earlier one's spans and the
    earlier one goes: one pass reads every turn's served tokens, each behind
    the history it was served after."""
    seqs: list[dict | None] = []
    ends: dict[tuple, int] = {}  # the whole of a prompt and its answer -> where its sequence is
    for r in records:  # in sending order
        if r["status"] != "ok" or not r.get("answer") or not r.get("prompt"):
            continue
        tokens, served = r["prompt"] + r["answer"], []
        before = ends.pop(tuple(r["prompt"][:r["history_tokens"]]), None) if r.get("history_tokens") else None
        if before is not None:
            served, seqs[before] = seqs[before]["served"], None
        ends[tuple(tokens)] = len(seqs)
        seqs.append({"tokens": tokens, "served": served + [[len(r["prompt"]), len(tokens)]]})
    return [s for s in seqs if s is not None]


def pick_sample(seqs: list[dict], seed: int, rows: int, row_tokens: int) -> tuple[list[list[dict]], list[dict]]:
    """``rows`` rows of at most ``row_tokens`` tokens, filled with whole
    sequences: the longest first, always, then the others in an order drawn
    from the seed, each into the first row that has room for it. Returns the
    rows and the sequences left over (``parity_seeds.py`` draws a second,
    disjoint sample from those)."""
    import random

    fits = [s for s in seqs if len(s["tokens"]) <= row_tokens]
    if not fits:
        return [], []
    longest = max(range(len(fits)), key=lambda i: (len(fits[i]["tokens"]), -i))
    rest = [i for i in range(len(fits)) if i != longest]
    random.Random(seed).shuffle(rest)
    placed, room, left = [[] for _ in range(rows)], [row_tokens] * rows, []
    for i in [longest] + rest:
        n = len(fits[i]["tokens"])
        row = next((k for k in range(rows) if room[k] >= n), None)
        if row is None:
            left.append(fits[i])
        else:
            placed[row].append(fits[i])
            room[row] -= n
    return [row for row in placed if row], left


HEAD_ROWS = 1024  # served positions go to the head in blocks of this many: few shapes compile


def row_gaps(ref, doc: dict, params, row: list[dict], row_tokens: int) -> list[float]:
    """The gap of every served token of a row's sequences, from one pass."""
    import jax.numpy as jnp
    import numpy as np

    tokens, starts, at, served = [], [], [], []
    for s in row:
        first = len(tokens)
        starts.append(first)
        tokens += s["tokens"]
        for a, b in s["served"]:  # the logits at i are for the token at i + 1
            at += range(first + a - 1, first + b - 1)
            served += s["tokens"][a:b]
    tokens += [0] * (row_tokens - len(tokens))
    pad = -len(at) % HEAD_ROWS
    logits = ref.forward(doc, params, tokens, positions=at + [0] * pad, starts=starts)
    got = jnp.take_along_axis(logits, jnp.asarray(served + [0] * pad, jnp.int32)[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(logits, axis=-1) - got, np.float64)[:len(at)].tolist()


def main(argv: list[str]) -> int:
    config_path, sample_path, out_path = argv[:3]
    t_start = time.monotonic()
    with open(config_path) as f:
        doc = json.load(f)
    with open(sample_path) as f:
        groups = json.load(f)["groups"]
    seed = int(doc["served"].get("weights_seed", 0))
    if "--weights-seed" in argv:
        seed = int(argv[argv.index("--weights-seed") + 1])

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):  # the worker's rule: the checkout's own cache
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))

    from chipbench import references

    ref = references.load(doc["reference"])
    params = ref.weights(doc, seed)
    jax.block_until_ready(params)
    t_weights = time.monotonic()
    print(f"parity: {jax.devices()[0].platform}, weights of seed {seed} in {t_weights - t_start:.1f} s", flush=True)
    out = {"platform": jax.devices()[0].platform, "weights_seed": seed, "groups": {}}
    row_tokens = int(doc["served"]["max_model_len"])  # no sequence is longer, and one program compiles
    for label, rows in groups.items():
        gaps, per_row = [], []
        for row in rows:
            t = time.monotonic()
            g = row_gaps(ref, doc, params, row, row_tokens)
            print(f"parity: group {label}: {len(row)} sequences, {sum(len(s['tokens']) for s in row)} tokens, "
                  f"{len(g)} served, in {time.monotonic() - t:.2f} s, widest gap {max(g):.4f}", flush=True)
            gaps += g
            per_row.append({**readings(g), "sequences": len(row)})
        out["groups"][label] = {**readings(gaps), "sequences": sum(len(row) for row in rows), "rows": len(rows),
                                "per_row": per_row}
    out["weights_s"] = t_weights - t_start
    out["seconds"] = time.monotonic() - t_start
    with open(out_path + ".tmp", "w") as f:
        json.dump(out, f)
    os.replace(out_path + ".tmp", out_path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
