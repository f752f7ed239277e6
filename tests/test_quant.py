"""Weight-only int8 quantization (engine/quant.py + model._dot_q).

Exactness trick: with power-of-two scales and integer-valued weights,
pre-scaling (float path) and post-scaling (int8 path) are bit-identical,
so the quantized model must reproduce the float model exactly.
"""

from __future__ import annotations

import asyncio

import numpy as np

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.quant import (
    quantize_np,
    quantize_params_np,
    random_int8_params_device,
)

CFG = ModelConfig()  # test-tiny


def test_quantize_np_roundtrip_bound():
    rng = np.random.default_rng(0)
    w = rng.standard_normal((64, 32)).astype(np.float32)
    q, s = quantize_np(w)
    assert q.dtype == np.int8 and s.shape == (32,)
    err = np.abs(w - q.astype(np.float32) * s[None, :])
    assert np.all(err <= s[None, :] / 2 + 1e-7)


def _int8_grid_params(cfg: ModelConfig, seed: int):
    """(float params, quantized params) that are EXACTLY equivalent:
    integer weights times power-of-two scales."""
    rng = np.random.default_rng(seed)
    scale = np.float32(2.0 ** -9)
    d, i, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers

    def grid(shape):
        return rng.integers(-127, 128, size=shape).astype(np.float32)

    shapes = {
        "wq": (L, d, cfg.q_size), "wk": (L, d, cfg.kv_size),
        "wv": (L, d, cfg.kv_size), "wo": (L, cfg.q_size, d),
        "w_gate": (L, d, i), "w_up": (L, d, i), "w_down": (L, i, d),
    }
    layers_f, layers_q = {}, {}
    for name, shape in shapes.items():
        w_int = grid(shape)
        layers_f[name] = w_int * scale
        layers_q[name] = w_int.astype(np.int8)
        layers_q[name + "_scale"] = np.full((L, shape[-1]), scale, np.float32)
    for norm in ("attn_norm", "mlp_norm"):
        layers_f[norm] = layers_q[norm] = np.ones((L, d), np.float32)
    emb_int = grid((cfg.vocab_size, d))
    pf = {"embed": emb_int * scale, "layers": layers_f,
          "final_norm": np.ones((d,), np.float32)}
    pq = {"embed": emb_int.astype(np.int8),
          "embed_scale": np.full((cfg.vocab_size,), scale, np.float32),
          "layers": layers_q, "final_norm": np.ones((d,), np.float32)}
    to_dev = lambda t: jax.tree.map(jnp.asarray, t)
    return to_dev(pf), to_dev(pq)


def test_decode_step_int8_exact_parity():
    pf, pq = _int8_grid_params(CFG, 1)
    rng = np.random.default_rng(2)
    N, bs, B, W = 32, 16, 4, 4
    cache = M.init_kv_cache(CFG, N, bs, jnp.float32)
    tokens = jnp.asarray(rng.integers(1, CFG.vocab_size - 1, B), jnp.int32)
    positions = jnp.asarray([5, 0, 12, 3], jnp.int32)
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    active = jnp.asarray([True] * B)
    ref, _ = M.decode_step_impl(CFG, pf, cache, tokens, positions, tables, active)
    out, _ = M.decode_step_impl(CFG, pq, cache, tokens, positions, tables, active)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_prefill_and_embed_int8_exact_parity():
    pf, pq = _int8_grid_params(CFG, 3)
    rng = np.random.default_rng(4)
    prompt = rng.integers(1, CFG.vocab_size - 1, 12).astype(np.int32)
    cache = M.init_kv_cache(CFG, 16, 4, jnp.float32)
    table = jnp.asarray([1, 2, 3, 4], jnp.int32)
    ref, _ = M.prefill(CFG, pf, cache, jnp.asarray(prompt), table, jnp.int32(0), jnp.int32(12))
    cache2 = M.init_kv_cache(CFG, 16, 4, jnp.float32)
    out, _ = M.prefill(CFG, pq, cache2, jnp.asarray(prompt), table, jnp.int32(0), jnp.int32(12))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))

    e_ref = M.embed(CFG, pf, jnp.asarray(prompt), jnp.int32(12))
    e_out = M.embed(CFG, pq, jnp.asarray(prompt), jnp.int32(12))
    np.testing.assert_array_equal(np.asarray(e_ref), np.asarray(e_out))


def test_quantize_params_np_structure():
    params = jax.tree.map(
        np.asarray, M.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    )
    q = quantize_params_np(params)
    assert q["layers"]["wq"].dtype == np.int8
    assert q["layers"]["wq_scale"].shape == (CFG.num_layers, CFG.q_size)
    assert q["embed"].dtype == np.int8 and q["embed_scale"].shape == (CFG.vocab_size,)


def test_engine_runs_with_int8_quant():
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.runtime.engine import Context

    async def collect(seed):
        eng = await TpuEngine(EngineArgs(
            model=CFG, block_size=4, num_kv_blocks=64, max_num_seqs=4,
            max_model_len=128, dtype="float32", decode_steps=2, quant="int8",
        ), seed=seed).start()
        try:
            req = PreprocessedRequest(model="t", token_ids=[1, 2, 3, 4, 5])
            req.sampling.temperature = 0.0
            req.sampling.seed = 0  # greedy, but unseeded requests draw global RNG (DT004)
            req.stop.max_tokens = 8
            req.stop.ignore_eos = True
            got = []
            async for item in eng.generate(req, Context()):
                got += item.get("token_ids") or []
            return got
        finally:
            await eng.stop()

    a = asyncio.run(collect(5))
    b = asyncio.run(collect(5))
    assert len(a) == 8 and a == b


def test_random_int8_params_shapes():
    p = random_int8_params_device(CFG, 0)
    assert p["layers"]["w_down"].shape == (CFG.num_layers, CFG.intermediate_size, CFG.hidden_size)
    assert p["layers"]["w_down"].dtype == np.int8
    assert p["embed_scale"].shape == (CFG.vocab_size,)


def test_random_int8_params_born_sharded_match_single_device():
    """The tp path builds the tree under jit with out_shardings: no device
    holds the whole model, and the values do not depend on the mesh."""
    from dynamo_tpu.parallel.mesh import ModelSharding, build_mesh

    sh = ModelSharding(build_mesh(tp=2, cfg=CFG), CFG)
    one = random_int8_params_device(CFG, 3)
    two = random_int8_params_device(CFG, 3, sharding=sh)
    wq = two["layers"]["wq"]
    assert len(wq.sharding.device_set) == 2
    assert wq.addressable_shards[0].data.shape[-1] == CFG.q_size // 2
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(two)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
