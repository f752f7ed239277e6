"""The DeepSeek-V2 block (ISSUE 52) on the CPU at a toy size with seeded weights
(hidden 128, 16 experts in 4 groups of which 2 are kept, 4 a token, 8 heads),
on one device and ``shard_map``ped over four of the CPU's eight: the program
against the benchmark's plain reference, the four chips' parts adding up to the
uncut layer, the router against a hand-worked case, YaRN against its closed
forms, the engine under ``--tp 4``, and what refuses the block."""

import asyncio
import dataclasses
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model_maps, references
from dynamo_tpu.engine import deepseek, longcat
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.parallel.mesh import TP_AXES, ModelSharding, build_mesh
from dynamo_tpu.runtime.engine import Context

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "chipbench", "configs", "rehearse-deepseek-v2-tiny.json")) as f:
    DOC = json.load(f)
CFG = model_maps.model_config(DOC)
REF = references.load("deepseek_v2")
BS = 8
TABLE = jnp.arange(1, 9, dtype=jnp.int32)


def doc_for(dtype: str, **over) -> dict:
    return {**DOC, **over, "served": {**DOC["served"], "dtype": dtype}}


def prompt(n: int, seed: int = 0) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(0, CFG.vocab_size, n)]


def program_params(w: dict) -> dict:
    """The reference's weights (a list of layers, the experts ``[layers, E / C,
    C, ..]`` over its devices) as the program's tree on one device."""
    one = jax.devices()[0]
    w = jax.tree.map(lambda a: jax.device_put(np.asarray(a), one), w)
    L = w["layers"]

    def whole(a):  # [layers, E / C, C, ..] -> [layers, E, ..], expert c E/C + i from [i, c]
        return jnp.moveaxis(a, 2, 1).reshape(a.shape[0], -1, *a.shape[3:])

    return {"embed": w["embed"], "lm_head": w["lm_head"], "final_norm": w["final_norm"], "first": L[0],
            "layers": jax.tree.map(lambda *a: jnp.stack(a), *L[1:]),
            "experts": {k: whole(v) for k, v in w["experts"].items()}}


@functools.cache
def sharding(tp: int):
    return None if tp == 1 else ModelSharding(build_mesh(tp=tp, cfg=CFG), CFG)


def placed(params: dict, tp: int):
    """→ (the parameters as ``--tp`` lays them out, the programs' mesh keyword)."""
    sh = sharding(tp)
    return (params, {}) if sh is None else (sh.shard_params(params), {"mesh": sh.mesh})


def _pad(xs, n):
    return jnp.zeros((n,), jnp.int32).at[:len(xs)].set(jnp.asarray(xs, jnp.int32))


def serve_through_cache(params, dtype, toks, plen: int, mode: str, impl: str, tp: int) -> np.ndarray:
    """Prefill ``toks[:plen]`` (cold, in two chunks, or its second part behind
    pages an earlier prefill cached) and decode the rest teacher-forced through
    the latent pool → float32 logits at positions plen-1 .. len(toks)-1."""
    params, kw = placed(params, tp)
    sh = sharding(tp)
    cache = deepseek.init_kv_cache(CFG, 32, BS, dtype, sharding=sh and sh.cache_sharding)
    kw = {**kw, "attn_impl": impl, "experts": "gmm_interpret" if impl == "pallas_interpret" else "ragged_dot"}
    if mode == "cold":
        logits, cache, _ = deepseek.prefill(CFG, params, cache, _pad(toks[:plen], 48), TABLE, 0, plen, **kw)
    else:
        cut = 16  # whole blocks
        _, cache, _ = deepseek.prefill(CFG, params, cache, _pad(toks[:cut], 16), TABLE, 0, cut, **kw)
        if mode == "cached":  # another dispatch wrote the pages; only the table names them
            cache = jax.tree.map(jnp.copy, cache)
        logits, cache, _ = deepseek.prefill(CFG, params, cache, _pad(toks[cut:plen], 32), TABLE, cut, plen, **kw)
    out = [logits]
    for pos in range(plen, len(toks)):
        step, cache, _ = deepseek.decode_step(
            CFG, params, cache, jnp.asarray([toks[pos], 0], jnp.int32), jnp.asarray([pos, 0], jnp.int32),
            jnp.stack([TABLE, TABLE]), jnp.asarray([True, False]), **kw)
        out.append(step[0])
    return np.asarray(jnp.stack(out), np.float32)


# Tolerances, from these sizes on the CPU (seeds 0-2 read). float32 against the
# float32 reference differs by summation order alone (the program absorbs W_kvb
# and, under --tp 4, adds four chips' partial sums; the reference multiplies W_kvb
# out and sums once): the widest gap must stay under 2e-4 (2e-5 read at --tp 1
# and at --tp 4). bf16 weights are the same numbers on both sides, so bf16 reads
# what rounding activations, latents and (under --tp 4) the chips' partial sums
# costs, and at a toy's sizes that is mostly the model's own discrete choices: 4
# of 16 experts at weights 16 p, so one near-tie in the router exchanges a
# quarter of a layer's routed experts. The mean over the logits is held, the
# widest gap not; the same program on weights rounded to float8_e4m3 must fail.
TOL = {"float32": ("max", 2e-4), "bfloat16": ("mean", 0.2)}


@functools.cache
def reference_run(dtype: str, n: int = 46):
    """→ (the program's tree of the reference's weights of seed 0, the prompt,
    the reference's logits over it): one pass a dtype for the cases below."""
    doc = doc_for(dtype)
    w = REF.weights(doc, 0)
    toks = prompt(n)
    return program_params(w), toks, np.asarray(REF.forward(doc, w, toks), np.float32)


def gap_of(got: np.ndarray, want: np.ndarray, dtype: str) -> float:
    diff = np.abs(got - want[:len(got)])
    return float(diff.max() if TOL[dtype][0] == "max" else diff.mean())


@pytest.mark.parametrize("tp", [1, 4])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("mode", ["cold", "chunked", "cached"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_agrees_with_the_reference_forward(dtype, mode, impl, tp):
    """Logits at every position from the prompt's last on: 40 prompt tokens and
    6 decoded ones, on one device and with every program ``shard_map``ped over four."""
    params, toks, want = reference_run(dtype)
    plen = 40
    want = want[plen - 1:len(toks)]
    got = serve_through_cache(params, jnp.dtype(dtype), toks, plen, mode, impl, tp)
    assert got.shape == want.shape
    assert gap_of(got, want, dtype) < TOL[dtype][1]


@pytest.mark.parametrize("tp", [1, 4])
def test_a_precision_under_bf16_fails_the_bf16_tolerance(tp):
    params, toks, want = reference_run("bfloat16")
    plen = 40
    low = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype) if a.dtype == jnp.bfloat16 else a, params)
    got = serve_through_cache(low, jnp.bfloat16, toks, plen, "cold", "xla", tp)
    assert gap_of(got, want[plen - 1:], "bfloat16") > TOL["bfloat16"][1]


@pytest.mark.parametrize("control", ["roll_groups", "int8_latents"])
def test_the_references_controls_move_its_logits(control, monkeypatch):
    doc = doc_for("float32")
    w = REF.weights(doc, 0)
    toks = prompt(40)
    sound = np.asarray(REF.forward(doc, w, toks))
    monkeypatch.setenv("DEEPSEEK_REF_CONTROL", control)
    moved = np.abs(np.asarray(REF.forward(doc, w, toks)) - sound).mean()
    assert moved > {"roll_groups": 0.3, "int8_latents": 1e-3}[control]


def _same(a, b):
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a), jax.tree.leaves(b)):
        assert x.shape == y.shape and x.dtype == y.dtype, path
        assert np.array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32)), path


def test_the_programs_initialiser_is_the_references():
    _same(deepseek.init_params(CFG, jax.random.PRNGKey(3), jnp.bfloat16),
          program_params(REF.weights(doc_for("bfloat16"), 3)))


def test_a_tp4_engine_draws_the_weights_a_tp1_engine_draws():
    """Every value depends on the key and its place alone: a chip draws the
    experts it holds under their own keys, and the other tensors are cut from
    the same draw, so four chips hold the numbers one holds."""
    sh = sharding(4)
    one = deepseek.init_params(CFG, jax.random.PRNGKey(0), jnp.bfloat16)
    four = sh.born_sharded(functools.partial(deepseek.init_params, CFG, jax.random.PRNGKey(0), jnp.bfloat16, mesh=sh.mesh))
    _same(one, four)
    specs = jax.tree.map(lambda a: a.sharding.spec, four)
    assert specs["experts"]["moe_gate"] == jax.sharding.PartitionSpec(None, TP_AXES)
    assert specs["layers"]["w_uk"] == jax.sharding.PartitionSpec(None, TP_AXES)
    assert four["experts"]["moe_gate"].addressable_shards[0].data.shape[1] == CFG.num_experts // 4
    assert not any(specs["first"]["w_qa"]) and not any(specs["layers"]["w_router"])  # on every chip, whole


def test_the_four_chips_parts_add_up_to_the_uncut_layer():
    """The stream after a dense and an expert layer, read through a head that
    is the identity: what four chips' heads, experts and feed-forward columns
    give, summed by the two ``psum``s a layer, is what the uncut layers give on
    one device. A part counted twice (the shared experts or ``q_a``/``kv_a`` on
    every chip and summed) or left out (an expert no chip holds) shows here."""
    cfg = dataclasses.replace(CFG, num_layers=2, vocab_size=CFG.hidden_size)
    params = deepseek.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    params = {**params, "lm_head": jnp.eye(cfg.hidden_size, dtype=jnp.float32)}
    toks = jnp.asarray(prompt(24, 3), jnp.int32) % cfg.vocab_size
    args = (_pad(toks, 32)[None], TABLE[None], jnp.zeros((1,), jnp.int32), jnp.asarray([24], jnp.int32))
    kw = {"attn_impl": "xla", "experts": "ragged_dot"}
    whole, _, _ = deepseek.prefill_batch(cfg, params, deepseek.init_kv_cache(cfg, 32, BS, jnp.float32), *args, **kw)
    sh = ModelSharding(build_mesh(tp=4, cfg=cfg), cfg)
    on_mesh, _, hist = deepseek.prefill_batch(
        cfg, sh.shard_params(params), deepseek.init_kv_cache(cfg, 32, BS, jnp.float32, sharding=sh.cache_sharding),
        *args, **kw, mesh=sh.mesh)
    np.testing.assert_allclose(np.asarray(on_mesh), np.asarray(whole), atol=2e-5)
    assert np.abs(np.asarray(whole)).mean() > 0.1
    # Every one of the 24 tokens' 4 choices is some chip's (none absent), each chip's touched
    # experts add up to the layer's, and a token's choices span topk_group groups or fewer.
    E, hist = cfg.num_experts, np.asarray(hist)
    assert hist.shape == (1, E + deepseek.hist_extra(4))
    assert hist[0, :E].sum() == 24 * 4 and hist[0, E + 1] == 0 and hist[0, E + 2] == 24
    assert hist[0, E + 5:E + 9].sum() == hist[0, E + 3] and 24 <= hist[0, -1] <= 24 * cfg.topk_group


def test_the_group_limit_changes_the_choice_in_a_hand_worked_case():
    """8 experts in 4 groups of 2, 2 groups kept, 3 a token. Scores (after the
    softmax, so only their order matters): expert 6 is the third best overall,
    but its group's best (6) ranks third among the groups behind group 0 (best
    expert 0) and group 1 (best expert 2): the plain top-3 takes {0, 2, 6}, the
    group-limited one {0, 2, 1}: expert 1 of kept group 0 in 6's place."""
    cfg = dataclasses.replace(CFG, num_experts=8, num_routed_experts=8, n_group=4, topk_group=2,
                              num_experts_per_token=3, hidden_size=8, routed_scaling_factor=16.0)
    logits = jnp.asarray([[4.0, 2.0, 3.5, 0.0, -1.0, -2.0, 3.0, 1.0]])
    lp = {"w_router": jnp.eye(8, dtype=jnp.float32), "router_bias": jnp.zeros((8,))}
    topi, topw = longcat.route(logits, lp, cfg)
    p = np.asarray(jax.nn.softmax(logits[0]))
    assert sorted(np.asarray(topi[0]).tolist()) == [0, 1, 2]
    np.testing.assert_allclose(np.sort(np.asarray(topw[0])), np.sort(16.0 * p[[0, 1, 2]]), rtol=1e-6)
    plain, _ = longcat.route(logits, lp, dataclasses.replace(cfg, topk_method="greedy"))
    assert sorted(np.asarray(plain[0]).tolist()) == [0, 2, 6]
    assert np.asarray(REF.choose(jax.nn.softmax(logits), 3, 4, 2)).tolist() == np.asarray(topi).tolist()


def test_yarn_frequencies_and_softmax_scale_are_the_closed_forms():
    """At the published keys: theta 10000 over 64 lanes, factor 40, L0 4096,
    beta 32 and 1 → the correction range is lanes [10, 23]; below it the lanes
    keep ``f_i``, above it they turn 40 times slower, between a linear ramp; the
    softmax scale is 192^-1/2 (0.1 x 0.707 ln 40 + 1)^2."""
    cfg = dataclasses.replace(CFG, qk_nope_head_dim=128, qk_rope_head_dim=64, yarn_original_max_position=4096)
    f = np.asarray(longcat.yarn_inv_freq(cfg), np.float64)
    base = 10000.0 ** (-np.arange(32) / 32)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000.0)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000.0)))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(f, base / 40 * ramp + base * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m - 1.2608) < 1e-4
    assert abs(deepseek.softmax_scale(cfg) - 192 ** -0.5 * m * m) < 1e-9
    np.testing.assert_allclose(f, REF.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0), rtol=1e-6)
    assert longcat.yarn_inv_freq(dataclasses.replace(cfg, yarn_factor=0.0)) is None  # the other blocks: theta's own


def test_yarn_is_static_and_moves_the_logits():
    """Inside the original 4,096 positions too: the same weights without the
    scaling serve other logits at position 5."""
    w = program_params(REF.weights(doc_for("float32"), 0))
    toks = _pad(prompt(8), 16)

    def last(cfg):
        cache = deepseek.init_kv_cache(cfg, 32, BS, jnp.float32)
        return np.asarray(deepseek.prefill(cfg, w, cache, toks, TABLE, 0, 8, attn_impl="xla", experts="ragged_dot")[0])

    assert np.abs(last(CFG) - last(dataclasses.replace(CFG, yarn_factor=0.0))).max() > 1e-2


def engine_args(tp: int = 1, **over) -> EngineArgs:
    return EngineArgs(model=CFG, block_size=BS, num_kv_blocks=64, max_num_seqs=4, max_model_len=128,
                      max_prefill_tokens=32, decode_steps=4, dtype="float32", tp=tp, **over)


async def _serve(engine: TpuEngine, prompts: list[list[int]], n: int) -> list[list[int]]:
    async def one(p):
        req = PreprocessedRequest(model="t", token_ids=list(p))
        req.sampling.temperature = 0.0
        req.sampling.seed = 0
        req.stop.max_tokens = n
        req.stop.ignore_eos = True
        return [t async for o in engine.generate(req, Context()) for t in o.get("token_ids", [])]

    return await asyncio.gather(*(one(p) for p in prompts))


@pytest.mark.parametrize("tp", [1, 4])
def test_the_engine_serves_the_references_tokens(tp, caplog):
    """Through the scheduler, the block manager and the runner: chunked prefill
    (40-token prompts in 32-token chunks), a prefix hit on the second wave, the
    decode window; greedy tokens are the float32 reference's best at every step,
    and under ``--tp 4`` the start line names four devices and keeps the kernels' path."""
    doc = doc_for("float32")
    w = REF.weights(doc, 0)

    async def run():
        engine = await TpuEngine(engine_args(tp, attn_impl="pallas_interpret")).start()
        try:
            first = await _serve(engine, [prompt(40, 1), prompt(40, 2)], 6)
            again = await _serve(engine, [prompt(40, 1) + first[0][:3]], 5)  # behind cached pages
            return engine, first, again
        finally:
            await engine.stop()

    with caplog.at_level("INFO"):
        engine, first, again = asyncio.run(run())
    for p, served in ((prompt(40, 1), first[0]), (prompt(40, 2), first[1]), (prompt(40, 1) + first[0][:3], again[0])):
        logits = np.asarray(REF.forward(doc, w, p + served))
        assert served == [int(t) for t in logits[len(p) - 1:len(p) + len(served) - 1].argmax(-1)]
    line = engine._runner._start_line("")
    assert f"devices={tp} of" in line and "decode=pallas_interpret" in line and " block=deepseek" in line
    assert (" tp=4" in line) == (tp == 4) and "experts=ragged_dot" in line
    hist = sum(engine.moe_hist.values())
    E = CFG.num_experts
    assert hist.shape == (2, E + deepseek.hist_extra(tp))
    assert hist[:, :E].sum() == hist[:, E + 2].sum() * CFG.num_experts_per_token  # every choice is some chip's
    assert hist[:, E + 2].sum() <= hist[:, -1].sum() <= CFG.topk_group * hist[:, E + 2].sum()
    assert hist[:, E + 5:E + 5 + tp].sum() == hist[:, E + 3].sum()


def test_what_refuses_the_block():
    with pytest.raises(ValueError, match=r"--tp 3 \(it has to divide the 8 heads, the 16 experts, the 512 vocabulary rows"):
        engine_args(3)
    with pytest.raises(ValueError, match="no int8 latent cache.*speculation.*LoRA"):
        engine_args(kv_quant="int8", spec_tokens=2, lora_slots=1)
    with pytest.raises(ValueError, match="group_limited_greedy"):
        EngineArgs(model=dataclasses.replace(CFG, n_group=3))
    with pytest.raises(ValueError, match="brings no placement of its own"):
        ModelSharding(build_mesh(tp=2), ModelConfig.preset("longcat-tiny"))
    with pytest.raises(ValueError, match="block='llama': num_kv_heads=2 not divisible by tp_kv=8"):
        ModelSharding(build_mesh(tp=8), ModelConfig.preset("test-tiny"))
    assert CFG == dataclasses.replace(ModelConfig.preset("deepseek-tiny"), name=CFG.name)
