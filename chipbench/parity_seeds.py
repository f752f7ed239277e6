#!/usr/bin/env python3
"""Read a cell's parity numbers on many seeds for the price of one set-up.

    chiprun -- python3 chipbench/parity_seeds.py --workload <cell> --seeds 12 --seconds 20
    ... --param 'served.extra_flags=["--kv-quant","int8"]'   the control: the program's own lower precision
    ... --parity-weights-seed 1                              a control: the reference on other weights
    ... --samples 2                                          two disjoint samples from each window

A limit in a configuration's ``parity`` block is set from two readings: the
largest the sound program gives over a dozen seeds or more, and the smallest a
control gives. ``run.py`` pays a minute and a half of set-up for every seed;
this starts the cell's stack once, warms it up once, and then offers the
cell's traffic for ``--seconds`` once per seed (a session cell pre-fills its
histories each time), at the cell's own load, picking each window's sample as
a run does (``--samples N``: N samples of a window, no sequence in two of
them). When the stack has stopped, one child runs the reference over every
sample. One line per sample, then the largest and the smallest of each
number; all of it in ``chiprun_out/parity_<cell>[.<tag>].json``, the samples
themselves beside it before the child starts. Its lines are never results.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import generators, parity, run as R  # noqa: E402
from chipbench.procs import log  # noqa: E402
from chipbench.prove import SEEDS  # noqa: E402


async def windows(run: R.Run, gen, seeds: list[int], n_samples: int) -> dict[str, list]:
    import aiohttp

    samples = {}
    rows, row_tokens = int(run.spec["parity"]["rows"]), int(run.config["served"]["max_model_len"])
    async with aiohttp.ClientSession(connector=aiohttp.TCPConnector(limit=0)) as session:
        await run.wait_listed(session)
        for i, seed in enumerate(seeds):
            plan = gen.generate(run.traffic, seed, run.opts.seconds, run.config["vocab_size"])
            if i == 0:
                await run.warm_up(session, plan)
            run.records = []
            await run.prefill_sessions(session, plan)
            await run.window(session, plan, k=2 * i)
            status = {s: sum(1 for r in run.records if r["status"] == s) for s in ("ok", "failed", "cut")}
            left = parity.sequences(run.records)
            finished = len(left)
            for k in range(n_samples):
                label = str(seed) if n_samples == 1 else f"{seed}.{k}"
                samples[label], left = parity.pick_sample(left, seed + k, rows, row_tokens)
            log(f"seed {seed}: requests {status}, {finished} sequences finished, {len(left)} in no sample")
    return samples


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--skip", type=int, default=0, help="start that far into prove.py's list of seeds")
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--samples", type=int, default=1)
    p.add_argument("--param", action="append", default=[], metavar="KEY=JSON")
    p.add_argument("--tag", default=None, help="names the output beside the cell")
    p.add_argument("--parity-weights-seed", type=int, default=None)
    p.add_argument("--rehearse", action="store_true")
    o = p.parse_args(argv)
    opts = argparse.Namespace(workload=o.workload, seed=0, seconds=o.seconds, trace=0, rehearse=o.rehearse,
                              param=o.param, parity=1, parity_weights_seed=o.parity_weights_seed)
    try:
        spec = R.load_cell(o.workload, o.rehearse)
        run = R.Run(opts, spec)
        gen = generators.load(run.traffic["kind"])
        seeds = SEEDS[o.skip:o.skip + o.seeds]
        try:
            run.start_stack()
            samples = asyncio.run(windows(run, gen, seeds, o.samples))
            run.judge(("", ""))
        finally:
            run.stack.stop()
    except R.NoResult as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return e.code
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    tag = o.workload + (f".{o.tag}" if o.tag else "") + (
        f".w{o.parity_weights_seed}" if o.parity_weights_seed is not None else "")
    with open(os.path.join(ROOT, "chiprun_out", f"parity_{tag}.sample.json"), "w") as f:
        json.dump({"groups": samples}, f)  # before the child: if that is lost, the windows are not
    doc, why = R.parity_child(spec["config_file"], samples, run.out_dir, o.rehearse, o.parity_weights_seed,
                              timeout_s=R.PARITY_CHILD_S + 30.0 * len(samples))
    if os.path.exists(os.path.join(run.out_dir, "parity.log")):
        shutil.copy(os.path.join(run.out_dir, "parity.log"), os.path.join(ROOT, "chiprun_out", f"parity_{tag}.log"))
    if doc is None:
        print(f"chipbench: {why}", file=sys.stderr)
        return 1
    limits = run.spec["parity"]
    rows = []
    for label, read in doc["groups"].items():
        row = {"sample": label, **read, "over": parity.verdict(read, limits)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"cell": o.workload, "config": run.name, "param": o.param, "seconds": o.seconds, "samples": len(rows),
               "notes": run.notes, "child_s": doc["seconds"], "weights_s": doc["weights_s"],
               "weights_seed": doc["weights_seed"]}
    for name in (*parity.JUDGED, "flipped_share"):
        vals = [r[name] for r in rows if name in r]
        if vals:
            summary[name] = {"smallest": min(vals), "largest": max(vals)}
    print(json.dumps(summary), flush=True)
    with open(os.path.join(ROOT, "chiprun_out", f"parity_{tag}.json"), "w") as f:
        json.dump({"summary": summary, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
