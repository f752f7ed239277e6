"""The benchmark's tests that need JAX, on the CPU at ``test-tiny`` size:
the plain reference against the program's own prefill-then-decode through the
paged cache, the seeded weights against the program's initialisers, a served
token altered where it is produced, and a new kind of block brought by files
alone. ``test_chipbench.py`` imports every case, and tier-1 collects them
through ``tests/test_chipbench_spread.py``; JAX is imported inside the cases.
"""

import asyncio
import json
import os
import subprocess
import sys

import pytest

from chipbench import lookup, model_maps, parity, references

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

# Each tolerance with its reason. float32 against float32: the same sums in
# another order, so a served token is the reference's best unless two logits
# tie to a few ulps of 1e-6. bf16 activations and KV against float32: 8 bits of
# mantissa on logits of about 1 leave 0.01-0.03 at the widest over a hundred
# tokens of two layers and under 0.001 in the mean (read while this was
# written: 0.010-0.032 and 0.0001-0.0007); int8 weights are the same numbers
# on both sides and add nothing. Ten times the readings: a wrong token lies
# 1-3 logits under the best, a hundred times further.
TIGHT = {"max_logit_gap": 1e-4, "mean_logit_gap": 1e-5}
BF16 = {"max_logit_gap": 0.3, "mean_logit_gap": 0.005}


def toy(**changes) -> dict:
    with open(os.path.join(BENCH, "configs", "rehearse-tiny.json")) as f:
        doc = json.load(f)
    doc["served"].update({k: v for k, v in changes.items() if k in ("quant", "dtype")})
    doc["assumed"]["qkv_bias"] = changes.get("bias", False)
    return doc


def prompts() -> list[list[int]]:
    """Longer than a prefill chunk of 32, a short one, and the first again,
    which is then prefilled behind its own cached pages."""
    import numpy as np

    rng = np.random.default_rng(11)
    long, short = rng.integers(1, 500, size=70).tolist(), rng.integers(1, 500, size=19).tolist()
    return [long, short, long]


def serve(doc: dict, seed: int = 3, kv_quant: str = "none", new: int = 24) -> list[dict]:
    """The program's engine in this process: chunked prefill, the prefix
    cache and decoding through the paged cache, greedy, as a cell's worker."""
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.runtime.engine import Context

    args = EngineArgs(model=model_maps.model_config(doc), block_size=4, num_kv_blocks=256, max_num_seqs=4,
                      max_model_len=256, max_prefill_tokens=32, quant=doc["served"]["quant"],
                      dtype=doc["served"].get("dtype", "bfloat16"), kv_quant=kv_quant)

    async def go():
        engine = await TpuEngine(args, seed=seed).start()
        try:
            out = []
            for prompt in prompts():
                req = PreprocessedRequest(model="t", token_ids=list(prompt))
                req.sampling.temperature, req.sampling.seed = 0.0, 0
                req.stop.max_tokens, req.stop.ignore_eos = new, True
                answer = []
                async for item in engine.generate(req, Context()):
                    answer += item.get("token_ids", [])
                out.append({"status": "ok", "prompt": prompt, "answer": answer})
            assert engine.pool.hit_blocks > 0  # the third prompt found the first one's pages
            return out
        finally:
            await engine.stop()

    return asyncio.run(go())


def gaps_of(doc: dict, served: list[dict], seed: int = 3) -> list[float]:
    """As ``parity.py``'s child reads them: the sequences packed into rows, one pass a row."""
    ref = references.load(doc["reference"])
    params = ref.weights(doc, seed)
    rows, left = parity.pick_sample(parity.sequences(served), 1, rows=2, row_tokens=512)
    assert not left
    return [g for row in rows for g in parity.row_gaps(ref, doc, params, row, 512)]


CASES = [("none", "float32", False), ("none", "float32", True), ("none", "bfloat16", False),
         ("none", "bfloat16", True), ("int8", "bfloat16", True), ("int8", "float32", False)]


@pytest.mark.parametrize("quant, dtype, bias", CASES)
def test_the_seeded_weights_are_the_programs_own(quant, dtype, bias):
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.quant import random_int8_params_device

    doc = toy(quant=quant, dtype=dtype, bias=bias)
    cfg = model_maps.model_config(doc)
    mine = references.load("dense_gqa").weights(doc, 5)
    theirs = (random_int8_params_device(cfg, 5, dtype) if quant == "int8"
              else M.init_params(cfg, jax.random.PRNGKey(5), jnp.dtype(dtype)))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(mine), jax.tree.leaves(theirs)):
        assert a.dtype == b.dtype and a.shape == b.shape and bool((a == b).all()), path


@pytest.mark.parametrize("quant, dtype, bias", CASES)
def test_prefill_then_decode_through_the_cache_agrees_with_the_reference(quant, dtype, bias):
    doc = toy(quant=quant, dtype=dtype, bias=bias)
    read = parity.readings(gaps_of(doc, serve(doc)))
    assert read["tokens"] == 72
    assert parity.verdict(read, TIGHT if dtype == "float32" else BF16) == [], read


def test_one_token_altered_where_it_is_produced_reads_over_the_limit():
    doc = toy(quant="int8", dtype="bfloat16", bias=True)
    served = serve(doc)
    served[1]["answer"][5] = (served[1]["answer"][5] + 1) % doc["vocab_size"]
    why = parity.verdict(parity.readings(gaps_of(doc, served)), BF16)
    assert why and "max_logit_gap" in why[0], why


def test_the_reference_on_other_weights_reads_over_both_limits():
    doc = toy(quant="int8", dtype="bfloat16", bias=True)
    why = parity.verdict(parity.readings(gaps_of(doc, serve(doc, seed=3), seed=4)), BF16)
    assert len(why) == 2, why


def test_the_programs_own_int8_kv_reads_twice_the_sound_program():
    """The control that a configuration's limits have to fail, at a size a
    test can hold: the program itself with ``--kv-quant int8``, a precision
    under the bf16 KV the configurations state, four seeds pooled (192 tokens
    of two layers a seed swing too far alone; read while this was written:
    mean 0.00106 against 0.00028, flipped 5.1% against 2.0%). On the chip at the
    cells' own sizes the limits lie between the two (PERF.md section 2)."""
    doc = toy(quant="int8", dtype="bfloat16", bias=True)
    sound, control = [], []
    for seed in (3, 4, 5, 6):
        sound += gaps_of(doc, serve(doc, seed=seed, new=64), seed=seed)
        control += gaps_of(doc, serve(doc, seed=seed, kv_quant="int8", new=64), seed=seed)
    sound, control = parity.readings(sound), parity.readings(control)
    assert sound["tokens"] == control["tokens"] == 768
    assert parity.verdict(sound, BF16) == []
    assert control["mean_logit_gap"] > 2 * sound["mean_logit_gap"], (sound, control)
    assert control["flipped_share"] > 1.5 * sound["flipped_share"], (sound, control)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_sequences_packed_into_one_row_read_what_each_reads_alone(dtype):
    """The reference over a row of three sequences end to end against three
    passes of one sequence: no token sees another sequence, and positions
    count from each sequence's own start."""
    import jax.numpy as jnp
    import numpy as np

    doc = toy(quant="none", dtype=dtype, bias=True)
    ref = references.load("dense_gqa")
    params = ref.weights(doc, 2)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(1, 500, size=n).tolist() for n in (37, 150, 64)]
    alone = jnp.concatenate([ref.forward(doc, params, s) for s in seqs])
    packed = ref.forward(doc, params, sum(seqs, []), starts=[0, 37, 187])
    assert packed.shape == alone.shape == (251, 512)
    assert float(jnp.max(jnp.abs(packed - alone))) < 1e-4
    mixed = ref.forward(doc, params, sum(seqs, []))  # as one sequence it reads something else
    assert float(jnp.max(jnp.abs(mixed[40:] - alone[40:]))) > 1e-2


MOE_MAP = '''from chipbench.model_maps import dense_gqa
def fields(doc):
    return {**dense_gqa.fields(doc), "num_experts": doc["num_local_experts"],
            "num_experts_per_token": doc["num_experts_per_tok"]}
'''

MOE_REFERENCE = '''"""Top-k routed experts in the dense block's place (weights renormalised over the picks)."""
import jax, jax.numpy as jnp
from jax import lax
from chipbench.references import dense_gqa as D

def weights(doc, seed):
    p = D.weights(doc, seed)
    L, d, i, E = doc["num_hidden_layers"], doc["hidden_size"], doc["intermediate_size"], doc["num_local_experts"]
    key = jax.random.PRNGKey(seed); keys = jax.random.split(key, 8); dt = p["final_norm"].dtype
    n = lambda k, fan, shape: (jax.random.normal(k, shape, jnp.float32) * fan ** -0.5).astype(dt)
    lay = {k: v for k, v in p["layers"].items() if k not in ("w_gate", "w_up", "w_down")}
    lay.update(w_router=n(jax.random.fold_in(key, 7), d, (L, d, E)), moe_gate=n(keys[5], d, (L, E, d, i)),
               moe_up=n(keys[6], d, (L, E, d, i)), moe_down=n(keys[7], i, (L, E, i, d)))
    return {**p, "layers": lay}

def experts(h, lp):
    f = lambda name: lp[name].astype(jnp.float32)
    probs = jax.nn.softmax(jnp.dot(h, f("w_router"), precision=D.HI), axis=-1)
    top, idx = lax.top_k(probs, 2)
    w = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(top / top.sum(-1, keepdims=True))
    act = jax.nn.silu(jnp.einsum("td,edi->tei", h, f("moe_gate"), precision=D.HI)) * jnp.einsum(
        "td,edi->tei", h, f("moe_up"), precision=D.HI)
    return jnp.einsum("tei,te,eid->td", act, w, f("moe_down"), precision=D.HI)

def forward(doc, params, token_ids, positions=None, starts=(0,)):
    return D.forward(doc, params, token_ids, positions, starts, ffn=experts)
'''


def test_a_new_kind_of_block_is_files_and_entries_only(tmp_path, monkeypatch):
    """A configuration with routed experts (a block the program has), its map
    and its reference in a directory of their own, found through the search
    path: the map's fields reach ``ModelConfig``, the engine serves, and the
    reference's child decides parity; no file of ``chipbench/`` is touched."""
    from chipbench import run

    doc = {**toy(dtype="float32"), "name": "moe-tiny", "num_local_experts": 4, "num_experts_per_tok": 2,
           "model_map": "routed_experts", "reference": "routed_experts",
           "parity": {**TIGHT, "rows": 2, "why": "float32 against float32: the same sums in another order"}}
    doc["served"] = {**doc["served"], "weights_seed": 3}  # the seed serve() gives the engine
    for kind, name, text in (("configs", "moe-tiny.json", json.dumps(doc)),
                             ("model_maps", "routed_experts.py", MOE_MAP),
                             ("references", "routed_experts.py", MOE_REFERENCE)):
        os.makedirs(tmp_path / kind, exist_ok=True)
        (tmp_path / kind / name).write_text(text)
    before = {kind: lookup.names(kind, ".py") for kind in ("model_maps", "references")}
    monkeypatch.setenv("CHIPBENCH_PATH", str(tmp_path))
    assert lookup.names("model_maps", ".py") == sorted(before["model_maps"] + ["routed_experts"])
    cfg = model_maps.model_config(doc)
    assert (cfg.num_experts, cfg.num_experts_per_token, cfg.num_layers) == (4, 2, 2)
    served = serve(doc)
    sample, _ = parity.pick_sample(parity.sequences(served), 1, rows=2, row_tokens=doc["served"]["max_model_len"])
    got, why = run.parity_child(lookup.find("configs", "moe-tiny", ".json"), {"run": sample},
                                str(tmp_path), rehearse=True)
    assert why is None, (why, (tmp_path / "parity.log").read_text()[-2000:])
    read = got["groups"]["run"]
    assert got["platform"] == "cpu" and read["tokens"] == 72
    assert parity.verdict(read, doc["parity"]) == [], read
    # and the dense reference, given the same tokens, does not pass for it
    wrong = {**doc, "reference": "dense_gqa", "model_map": "dense_gqa"}
    assert parity.verdict(parity.readings(gaps_of(wrong, served)), doc["parity"])


BROKEN_MAP = '''"""The dense map, and a worker whose logits are rolled by one: every token it
serves is the neighbour of the one it computed."""
import os, sys
from chipbench.model_maps import dense_gqa

def fields(doc):
    if os.path.basename(sys.argv[0]) == "launch_worker.py":
        import jax.numpy as jnp
        from dynamo_tpu.engine import model as M
        sound = M._logits
        M._logits = lambda cfg, params, x: jnp.roll(sound(cfg, params, x), 1, axis=-1)
    return dense_gqa.fields(doc)
'''


def test_a_run_whose_worker_alters_its_tokens_comes_out_not_correct(tmp_path):
    """The whole of a run but the look for a chip (``--rehearse``: the same
    code path on the CPU), with the timed path broken underneath: the result
    line is printed, ``correct`` is false, and parity says why."""
    toy_doc = {**toy(), "model_map": "rolled_logits"}
    for kind, name, text in (("configs", "rehearse-tiny.json", json.dumps(toy_doc)),
                             ("model_maps", "rolled_logits.py", BROKEN_MAP)):
        os.makedirs(tmp_path / kind, exist_ok=True)
        (tmp_path / kind / name).write_text(text)
    env = {**os.environ, "CHIPBENCH_PATH": str(tmp_path)}
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", "qwen2.5-7b-int8.chat",
                           "--seed", str(2 ** 31 + 29), "--seconds", "6", "--trace", "0", "--rehearse"],
                          env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    read = result["unjudged"]["parity"]
    assert result["correct"] is False and read["ok"] is False and read["tokens"] > 50
    assert read["max_logit_gap"] > read["limits"]["max_logit_gap"]
    assert read["mean_logit_gap"] > read["limits"]["mean_logit_gap"]
    last = proc.stderr.strip().splitlines()[-1]
    assert last.startswith("chipbench: compared: max_logit_gap") and "limit" in last and "rows" in last
    assert "parity: max_logit_gap" in proc.stderr
