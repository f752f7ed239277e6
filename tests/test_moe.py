"""Mixture-of-experts FFN + expert parallelism over the ep mesh axis.

Reference analogue: wide-EP deployments the reference reaches only via
engine flags (trtllm_utils.py:140-143, sglang dsr1-wideep docs) — here a
first-class model family (BASELINE config #5 shape: moe-wide preset).
"""

from __future__ import annotations

import asyncio

import numpy as np

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.parallel.mesh import ModelSharding, build_mesh
from dynamo_tpu.runtime.engine import Context

CFG = ModelConfig.preset("moe-tiny")


def moe_reference(x, router, gates, ups, downs, top_k):
    """Per-token loop over selected experts (the obviously-correct path)."""
    T, D = x.shape
    logits = x @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for t in range(T):
        idx = np.argsort(-probs[t])[:top_k]
        w = probs[t, idx] / probs[t, idx].sum()
        for e, wi in zip(idx, w):
            g = x[t] @ gates[e]
            u = x[t] @ ups[e]
            h = (g / (1 + np.exp(-g))) * u  # silu(g) * u
            out[t] += wi * (h @ downs[e])
    return out


def test_moe_matches_loop_reference():
    rng = np.random.default_rng(0)
    D, E, ie, T, k = 16, 4, 32, 6, 2
    x = rng.standard_normal((T, D)).astype(np.float32)
    router = rng.standard_normal((D, E)).astype(np.float32) * 0.3
    gates = rng.standard_normal((E, D, ie)).astype(np.float32) * 0.2
    ups = rng.standard_normal((E, D, ie)).astype(np.float32) * 0.2
    downs = rng.standard_normal((E, ie, D)).astype(np.float32) * 0.2
    cfg = ModelConfig(num_experts=E, num_experts_per_token=k)
    lp = {
        "w_router": jnp.asarray(router), "moe_gate": jnp.asarray(gates),
        "moe_up": jnp.asarray(ups), "moe_down": jnp.asarray(downs),
    }
    out = np.asarray(M._moe(jnp.asarray(x), lp, cfg))
    ref = moe_reference(x, router, gates, ups, downs, k)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_moe_ep_sharded_matches_single_device():
    """ep=4 x tp=2 sharded decode step == unsharded (same params/seed)."""
    cfg = CFG
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(1)
    N, bs, B, W = 32, 8, 4, 4
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size - 1, B), jnp.int32)
    positions = jnp.asarray([3, 0, 9, 5], jnp.int32)
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    active = jnp.asarray([True] * B)
    cache = M.init_kv_cache(cfg, N, bs, jnp.float32)
    ref, _ = M.decode_step_impl(cfg, params, cache, tokens, positions, tables, active)

    mesh = build_mesh(tp=2, ep=4, cfg=cfg)
    sh = ModelSharding(mesh, cfg)
    params_s = sh.shard_params(jax.tree.map(np.asarray, params))
    cache_s = M.init_kv_cache(cfg, N, bs, jnp.float32, sharding=sh.cache_sharding)
    out, _ = M.decode_step(cfg, params_s, cache_s, tokens, positions, tables, active)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-4, rtol=2e-4)


def test_moe_engine_e2e_greedy_deterministic():
    async def collect():
        eng = await TpuEngine(EngineArgs(
            model=CFG, block_size=4, num_kv_blocks=64, max_num_seqs=4,
            max_model_len=128, dtype="float32", decode_steps=2,
        )).start()
        try:
            req = PreprocessedRequest(model="moe", token_ids=[5, 6, 7, 8])
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

    a = asyncio.run(collect())
    b = asyncio.run(collect())
    assert len(a) == 8 and a == b


def test_moe_param_counts():
    assert CFG.param_count() > CFG.active_param_count()
    wide = ModelConfig.preset("moe-wide")
    # top-8 of 64 experts → active params well under total
    assert wide.active_param_count() < 0.4 * wide.param_count()
