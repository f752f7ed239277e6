"""The wire does not know the pool's layout (PR 46).

The cache keeps a page's K and V side by side in one pool
(``KVCache.kv`` ``[L, N, 2, bs, KVH*hd]``); everything that leaves it
(``extract_pages``, the payloads of ``transfer/``, the host and disk tiers,
migration, a peer that still runs two pools) sees ``(k, v[, k_scale,
v_scale])`` pages ``[L, n, bs, ...]``, byte for byte what two pools gave.
The arrays the pages are compared with are written by hand in the old order.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.engine import kv_transfer
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs, ModelConfig

CFG = ModelConfig()  # test-tiny: 2 layers, 2 KV heads of 32
N, BS = 16, 4
IDS = [5, 1, 9, 14, 2]   # not a power of two: the gather is bucketed and cut
KV_KINDS = ("none", "int8")


def two_pools(kv_quant: str, seed: int = 0) -> tuple[np.ndarray, ...]:
    """(k, v) or (k, v, k_scale, v_scale) as the parent's cache held them:
    each ``[L, N, bs, ...]``, every page different."""
    rng = np.random.default_rng(seed)
    shape = (CFG.num_layers, N, BS, CFG.kv_size)
    if kv_quant == "int8":
        sshape = (CFG.num_layers, N, BS, CFG.num_kv_heads)
        return (rng.integers(-127, 128, shape).astype(np.int8),
                rng.integers(-127, 128, shape).astype(np.int8),
                (np.abs(rng.standard_normal(sshape)) + 1e-3).astype(np.float32),
                (np.abs(rng.standard_normal(sshape)) + 1e-3).astype(np.float32))
    return (rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32))


def filled(kv_quant: str, seed: int = 0) -> tuple[M.KVCache, tuple[np.ndarray, ...]]:
    k, v, *scales = old = two_pools(kv_quant, seed)
    return M.KVCache(M.fuse_kv(jnp.asarray(k), jnp.asarray(v)), *map(jnp.asarray, scales)), old


def same_bytes(got: np.ndarray, want: np.ndarray) -> None:
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.ascontiguousarray(got).tobytes() == np.ascontiguousarray(want).tobytes()


@pytest.mark.parametrize("kv_quant", KV_KINDS)
def test_extract_pages_gives_the_two_pool_bytes(kv_quant):
    cache, old = filled(kv_quant)
    assert cache.kv.shape == (CFG.num_layers, N, 2, BS, CFG.kv_size)
    pages = kv_transfer.extract_pages(cache, IDS)
    assert len(pages) == len(old) == (4 if kv_quant == "int8" else 2)
    for got, pool in zip(pages, old):
        same_bytes(got, pool[:, IDS])
    # ... and so the payload's frames are the parent's frames.
    payload = kv_transfer.KvPagePayload(*pages[:2], len(IDS) * BS, *pages[2:])
    want = kv_transfer.KvPagePayload(*(p[:, IDS] for p in old[:2]), len(IDS) * BS,
                                     *(p[:, IDS] for p in old[2:]))
    assert payload.to_dict() == want.to_dict()
    assert list(payload.to_frames(64)) == list(want.to_frames(64))


@pytest.mark.parametrize("kv_quant", KV_KINDS)
def test_inject_then_extract_round_trips(kv_quant):
    _, old = filled(kv_quant, seed=1)
    pages = tuple(p[:, IDS] for p in old)          # as a two-pool peer would send them
    empty = M.init_kv_cache(CFG, N, BS, jnp.float32, kv_quant=kv_quant)
    into = [3, 7, 11, 4, 8]
    cache = kv_transfer.inject_pages(empty, into, *pages)
    assert cache.kv.shape == (CFG.num_layers, N, 2, BS, CFG.kv_size)
    for got, want in zip(kv_transfer.extract_pages(cache, into), pages):
        same_bytes(got, want)
    # K went to a page's first part and V to its second, nothing anywhere else.
    k, v = (np.asarray(a) for a in M.split_kv(cache.kv))
    same_bytes(k[:, into], pages[0])
    same_bytes(v[:, into], pages[1])
    rest = [b for b in range(N) if b not in into]
    assert not k[:, rest].any() and not v[:, rest].any()


@pytest.mark.parametrize("kv_quant", KV_KINDS)
def test_a_page_is_one_region_of_the_pool_and_the_gauge_says_its_bytes(kv_quant):
    args = EngineArgs(model=CFG, block_size=BS, num_kv_blocks=N, dtype="float32", kv_quant=kv_quant)
    cache = M.init_kv_cache(CFG, N, BS, jnp.float32, kv_quant=kv_quant)
    page = cache.kv[0, 0]
    assert page.shape == (2, BS, CFG.kv_size) and cache.block_size == BS
    assert args.kv_page_bytes() == page.nbytes
    pools = args.pool_bytes_per_block()["kv"] * N
    assert pools == cache.kv.nbytes + sum(s.nbytes for s in (cache.k_scale, cache.v_scale) if s is not None)
