"""Parallelism tests on the 8-device virtual CPU platform: TP-sharded
forward must match single-device logits; the sharded engine must produce
identical greedy streams."""

import asyncio
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.parallel.mesh import ModelSharding, build_mesh
from dynamo_tpu.runtime.engine import Context

CFG = ModelConfig()  # test-tiny: 4 heads, 2 kv heads


def test_build_mesh_shapes():
    mesh = build_mesh(tp=2, dp=4)
    assert mesh.shape == {"dp": 4, "ep": 1, "tp_kv": 2, "tp_rep": 1}
    with pytest.raises(ValueError):
        build_mesh(tp=16, dp=1)


def test_build_mesh_splits_tp_beyond_kv_heads():
    # test-tiny: 4 heads / 2 kv heads → tp=4 must replicate kv x2.
    mesh = build_mesh(tp=4, cfg=CFG)
    assert mesh.shape == {"dp": 1, "ep": 1, "tp_kv": 2, "tp_rep": 2}


def test_sharding_divisibility_checks():
    mesh = build_mesh(tp=2)
    ModelSharding(mesh, CFG)  # ok: 4 heads / 2 kv heads / tp=2
    with pytest.raises(ValueError):
        # without cfg the tp axis is not split → kv_heads=2 not divisible
        ModelSharding(build_mesh(tp=4), CFG)
    with pytest.raises(ValueError):
        # 8 devices: tp_rep=4 > G=2 query groups per kv head
        build_mesh(tp=8, cfg=CFG)


def test_tp_beyond_kv_heads_matches_single_device():
    """tp=4 over 2 kv heads (kv replication x2) + vocab-sharded embed
    must reproduce single-device logits."""
    params = M.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    bs = 4
    prompt = list(range(1, 10))
    table = np.zeros((8,), np.int32)
    table[:3] = [1, 2, 3]
    toks = np.zeros((12,), np.int32)
    toks[: len(prompt)] = prompt

    def run(params_in, cache_in):
        logits_p, cache = M.prefill(
            CFG, params_in, cache_in, jnp.asarray(toks), jnp.asarray(table),
            jnp.int32(0), jnp.int32(len(prompt)),
        )
        return np.asarray(logits_p)

    ref = run(params, M.init_kv_cache(CFG, 16, bs, jnp.float32))
    mesh = build_mesh(tp=4, cfg=CFG)
    sh = ModelSharding(mesh, CFG)
    got = run(sh.shard_params(params), M.init_kv_cache(CFG, 16, bs, jnp.float32, sharding=sh.cache_sharding))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_tp_sharded_prefill_and_decode_match_single_device():
    params = M.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    bs = 4
    prompt = list(range(1, 10))
    table = np.zeros((8,), np.int32)
    table[:3] = [1, 2, 3]
    toks = np.zeros((12,), np.int32)
    toks[: len(prompt)] = prompt

    def run(params_in, cache_in):
        logits_p, cache = M.prefill(
            CFG, params_in, cache_in, jnp.asarray(toks), jnp.asarray(table),
            jnp.int32(0), jnp.int32(len(prompt)),
        )
        tables = np.zeros((2, 8), np.int32)
        tables[0, :3] = [1, 2, 3]
        logits_d, cache = M.decode_step(
            CFG, params_in, cache,
            jnp.asarray(np.array([42, 0], np.int32)),
            jnp.asarray(np.array([9, 0], np.int32)),
            jnp.asarray(tables),
            jnp.asarray(np.array([True, False])),
        )
        return np.asarray(logits_p), np.asarray(logits_d)

    ref_p, ref_d = run(params, M.init_kv_cache(CFG, 16, bs, jnp.float32))

    mesh = build_mesh(tp=2, dp=1)
    sh = ModelSharding(mesh, CFG)
    sharded_params = sh.shard_params(params)
    cache = M.init_kv_cache(CFG, 16, bs, jnp.float32, sharding=sh.cache_sharding)
    got_p, got_d = run(sharded_params, cache)

    np.testing.assert_allclose(got_p, ref_p, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got_d, ref_d, rtol=2e-4, atol=2e-4)


def test_split_tp_llama70b_shape():
    from dynamo_tpu.parallel.mesh import split_tp

    cfg70 = ModelConfig.preset("llama-70b")  # 64 heads, 8 kv heads
    assert split_tp(16, cfg70) == (8, 2)
    assert split_tp(8, cfg70) == (8, 1)
    assert split_tp(32, cfg70) == (8, 4)


def test_tp16_70b_shape_runs_on_16_virtual_devices():
    """llama-70b-shaped sharding (8 kv heads, tp=16 → kv replication x2)
    compiles and runs a prefill on 16 virtual CPU devices (subprocess:
    this process is pinned to 8)."""
    import subprocess
    import sys

    script = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import numpy as np
import jax, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.parallel.mesh import ModelSharding, build_mesh
cfg = ModelConfig(name="t70", vocab_size=512, hidden_size=128, intermediate_size=256,
                  num_layers=2, num_heads=16, num_kv_heads=8, head_dim=8)
mesh = build_mesh(tp=16, cfg=cfg)
assert mesh.shape == {"dp": 1, "ep": 1, "tp_kv": 8, "tp_rep": 2}, mesh.shape
sh = ModelSharding(mesh, cfg)
params = sh.shard_params(M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
cache = M.init_kv_cache(cfg, 16, 4, jnp.float32, sharding=sh.cache_sharding)
toks = np.zeros((8,), np.int32); toks[:6] = [3,4,5,6,7,8]
table = np.zeros((4,), np.int32); table[:2] = [1,2]
logits, cache = M.prefill(cfg, params, cache, jnp.asarray(toks), jnp.asarray(table),
                          jnp.int32(0), jnp.int32(6))
assert np.isfinite(np.asarray(logits)).all()
print("TP16_OK")
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), env=env,
        timeout=240,
    )
    assert "TP16_OK" in out.stdout, out.stdout + out.stderr


def test_driver_entry_dryrun_multichip():
    """``__graft_entry__.dryrun_multichip``: the dp x tp and ep x tp meshes,
    cache born sharded, one prefill + decode + sample, on 8 virtual CPU
    devices (subprocess: it pins its whole process to the CPU platform).
    No other test imports that file, so a change to the cache's
    constructor would break the entry point unseen."""
    import subprocess
    import sys

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), env=env,
        timeout=240,
    )
    assert "dryrun_multichip ok" in out.stdout, out.stdout + out.stderr
    assert "MoE decode ran" in out.stdout, out.stdout


def test_sharded_engine_matches_unsharded_greedy():
    args = EngineArgs(
        model=CFG, block_size=4, num_kv_blocks=64, max_num_seqs=4,
        max_model_len=128, max_prefill_tokens=64, dtype="float32", tp=2,
    )

    def req():
        r = PreprocessedRequest(model="t", token_ids=[1, 2, 3, 4, 5])
        r.sampling.temperature = 0.0
        r.sampling.seed = 0  # greedy, but unseeded requests draw global RNG (DT004)
        r.stop.max_tokens = 8
        return r

    async def run_engine(engine_args):
        engine = await TpuEngine(engine_args, seed=0).start()
        try:
            out = []
            async for item in engine.generate(req(), Context()):
                out.extend(item.get("token_ids", []))
            return out
        finally:
            await engine.stop()

    # tp=2 in EngineArgs builds the mesh + shardings internally.
    plain = asyncio.run(run_engine(args.replace(tp=1)))
    sharded = asyncio.run(run_engine(args))
    assert plain == sharded
