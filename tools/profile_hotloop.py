"""Microbenchmark the decode/prefill hot loop at bench shapes on the real
chip: where does the step time go (weights vs KV gather vs dispatch)?

Usage: python tools/profile_hotloop.py [--model llama-1b]
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.compile_cache import configure_compile_cache
from dynamo_tpu.engine.config import EngineArgs, ModelConfig


def timeit(fn, n=10):
    fn()  # compile
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="llama-1b")
    p.add_argument("--bs", type=int, default=16)
    args = p.parse_args()
    configure_compile_cache()

    cfg = ModelConfig.preset(args.model)
    bs = args.bs
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    num_blocks = 128 * 70
    cache = M.init_kv_cache(cfg, num_blocks, bs)
    print(f"model={cfg.name} L={cfg.num_layers} d={cfg.hidden_size} KVH={cfg.num_kv_heads} hd={cfg.head_dim}")
    print(f"params={cfg.param_count()/1e9:.2f}B cache={cache.k.nbytes*2/1e9:.2f}GB blocks={num_blocks}")

    rng = np.random.default_rng(0)

    for B in (8, 32, 128):
        for W in (8, 32, 68):
            tokens = jnp.asarray(rng.integers(1, 100, B), jnp.int32)
            positions = jnp.full((B,), W * bs - 1, jnp.int32)
            tables = jnp.asarray(
                rng.permutation(num_blocks - 1)[: B * W].reshape(B, W) + 0, jnp.int32
            )
            active = jnp.ones((B,), bool)

            def dec(cache=cache):
                logits, c2 = M.decode_step(cfg, params, cache, tokens, positions, tables, active)
                return logits

            # NOTE: decode_step donates the cache; to keep reusing it we time
            # the undonated impl via jit here.
            f = jax.jit(lambda c: M.decode_step_impl(cfg, params, c, tokens, positions, tables, active)[0])
            t = timeit(lambda: f(cache))
            toks = B / t
            print(f"decode  B={B:4d} W={W:3d} ctx={W*bs:5d}: {t*1e3:8.2f} ms/step  {toks:9.0f} tok/s")

    # multi_decode window K=32 greedy
    B, W, K = 128, 68, 32
    tokens = jnp.asarray(rng.integers(1, 100, B), jnp.int32)
    positions = jnp.full((B,), W * bs - K - 1, jnp.int32)
    tables = jnp.asarray(rng.permutation(num_blocks - 1)[: B * W].reshape(B, W), jnp.int32)
    active = jnp.ones((B,), bool)
    temps = jnp.zeros((B,), jnp.float32)
    seeds = jnp.zeros((B,), jnp.uint32)
    steps0 = jnp.zeros((B,), jnp.int32)
    tks = jnp.zeros((B,), jnp.int32)
    tps = jnp.ones((B,), jnp.float32)
    fr = jnp.zeros((B,), jnp.float32)
    pr = jnp.zeros((B,), jnp.float32)
    pen = jnp.full((B, 1), -1, jnp.int32)

    f = jax.jit(lambda c: M.multi_decode_impl(cfg, K, "greedy", 0, params, c, tokens, positions, tables, active, temps, seeds, steps0, tks, tps, fr, pr, pen)[0])
    t = timeit(lambda: f(cache), n=3)
    print(f"multi_decode K={K} B={B} W={W}: {t*1e3:8.2f} ms/window  {K*B/t:9.0f} tok/s  ({t/K*1e3:.2f} ms/step)")

    # prefill
    for T in (128, 512):
        Wp = max(8, T // bs)
        toks = jnp.asarray(rng.integers(1, 100, T), jnp.int32)
        table = jnp.asarray(rng.permutation(num_blocks - 1)[:Wp], jnp.int32)
        f = jax.jit(lambda c: M.prefill_impl(cfg, params, c, toks, table, jnp.int32(0), jnp.int32(T))[0])
        t = timeit(lambda: f(cache))
        print(f"prefill T={T:5d} W={Wp:3d}: {t*1e3:8.2f} ms  {T/t:9.0f} tok/s")

    # roundtrip latency: tiny jitted op + host sync
    g = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,))
    g(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        x = g(x)
        np.asarray(x)
    print(f"host roundtrip (tiny op + sync): {(time.perf_counter()-t0)/10*1e3:.2f} ms")


if __name__ == "__main__":
    main()
