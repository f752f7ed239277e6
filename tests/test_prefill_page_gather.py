"""Prefix pages are read straight out of the stacked KV pool (PR 28).

``gather_kv_pages`` takes the stacked cache ``[L, N, 2, bs, KVH*hd]`` (a page
is K then V, since PR 46) and gathers by (layer, page) at once, each page once. The parent sliced one layer out first
(``lax.dynamic_index_in_dim``), which on the chip copied all N pages of the
layer, 84 MB at 5,120 blocks, twice a layer in every prefill call, to read a
few hundred of them. Two things are pinned here:

- the same bytes: against a plain reference that slices the layer and gathers
  as the parent did, logits and cache come out bit-identical, for a bf16 and
  for an int8 cache, through prefill and through both XLA attention paths;
- no layer of the pool: no equation of the traced programs, at any depth, has
  an output of one layer's shape.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.ops import paged_attention as PA

CFG = ModelConfig()  # test-tiny: 2 layers, 2 KV heads of 32
BS, N = 4, 24        # a pool whose layer shape (24, 4, 64) nothing else has
KVH, HD = CFG.num_kv_heads, CFG.head_dim
KV_KINDS = ("none", "int8")


def gather_as_the_parent_did(kv_cache, k_scale, v_scale, layer_idx, block_tables, KVH, hd, dtype):
    """The plain reference: one layer of the pool sliced out, then its pages
    picked, K's and V's apart (``ops/paged_attention.py`` and
    ``engine/model.py`` at c34b524, over the two parts of today's page)."""
    B, W = block_tables.shape
    layer_cache = lax.dynamic_index_in_dim(kv_cache, layer_idx, 0, keepdims=False)

    def part(i, scale):
        pages = layer_cache[:, i][block_tables].reshape(B, W * BS, KVH, hd)
        if scale is None:
            return pages
        layer_scale = lax.dynamic_index_in_dim(scale, layer_idx, 0, keepdims=False)
        sc = layer_scale[block_tables].reshape(B, W * BS, KVH)
        return (pages.astype(jnp.float32) * sc[..., None]).astype(dtype)

    return part(0, k_scale), part(1, v_scale)


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.PRNGKey(3), jnp.bfloat16)


def fresh(fn, *static):
    """``fn`` under a new identity: JAX caches a trace by the function it was
    given, and a program traced before the reference was put in place would
    be handed back as the reference's."""
    return lambda *args: fn(*static, *args)


def bits(x) -> np.ndarray:
    """An array's bytes, so that equal means bit-identical (bf16 included)."""
    a = np.asarray(x)
    return a.view(np.uint8 if a.dtype.itemsize == 1 else f"u{a.dtype.itemsize}")


def assert_same_bits(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(bits(g), bits(w))


def filled_cache(kv_quant: str, seed: int = 0) -> M.KVCache:
    """A pool with something on every page, so that a wrong page shows."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (CFG.num_layers, N, BS, CFG.kv_size)
    if kv_quant == "int8":
        sshape = (CFG.num_layers, N, BS, KVH)
        return M.KVCache(
            M.fuse_kv(jax.random.randint(k1, shape, -127, 128, jnp.int8),
                      jax.random.randint(k2, shape, -127, 128, jnp.int8)),
            jax.random.uniform(k3, sshape, jnp.float32, 0.001, 0.02),
            jax.random.uniform(k4, sshape, jnp.float32, 0.001, 0.02),
        )
    return M.KVCache(M.fuse_kv(jax.random.normal(k1, shape, jnp.bfloat16),
                               jax.random.normal(k2, shape, jnp.bfloat16)))


# -- prefill over a cached prefix ---------------------------------------------


def prefill_case(rows: int):
    """Two (or one) sequences whose first blocks are cached: tokens of the
    suffix, tables for the whole sequence, where the suffix starts and ends."""
    T, W = 8, 8
    starts = np.array([12, 8][:rows], np.int32)          # 3 and 2 cached blocks
    tlens = np.array([19, 16][:rows], np.int32)
    tables = np.zeros((rows, W), np.int32)
    tables[0, :5] = [3, 17, 5, 9, 21]
    if rows > 1:
        tables[1, :4] = [11, 2, 14, 7]
    toks = ((np.arange(rows * T).reshape(rows, T) * 7 + 5) % 500 + 1).astype(np.int32)
    return toks, tables, starts, tlens


def run_prefill(params, kv_quant: str, rows: int, seed: int = 0):
    toks, tables, starts, tlens = prefill_case(rows)
    cache = filled_cache(kv_quant, seed)
    if rows == 1:
        fn = jax.jit(fresh(M.prefill_impl, CFG))
        return fn(params, cache, toks[0], tables[0], starts[0], tlens[0])
    fn = jax.jit(fresh(M.prefill_batch_impl, CFG))
    return fn(params, cache, toks, tables, starts, tlens)


@pytest.mark.parametrize("rows", [2, 1], ids=["prefill_batch_impl", "prefill_impl"])
@pytest.mark.parametrize("kv_quant", KV_KINDS)
def test_prefill_over_cached_prefix_is_bit_identical(params, kv_quant, rows, monkeypatch):
    logits, cache = run_prefill(params, kv_quant, rows)
    monkeypatch.setattr(PA, "gather_kv_pages", gather_as_the_parent_did)
    want_logits, want_cache = run_prefill(params, kv_quant, rows)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert_same_bits(logits, want_logits)
    assert_same_bits(cache, want_cache)
    # The prefix was read, not skipped: other cached pages, other logits.
    moved, _ = run_prefill(params, kv_quant, rows, seed=1)
    assert not np.array_equal(bits(moved), bits(logits))


# -- the two XLA attention paths ----------------------------------------------


def attention_case(kind: str, kv_quant: str):
    B, W, T, G = 3, 6, 3, CFG.num_heads // KVH
    cache = filled_cache(kv_quant, seed=2)
    tables = (np.arange(B * W).reshape(B, W) * 5 % N).astype(np.int32)
    layer = jnp.int32(1)
    kq = jax.random.PRNGKey(9)
    if kind == "decode":
        q = jax.random.normal(kq, (B, KVH, G, HD), jnp.bfloat16)
        lengths = jnp.asarray([5, 24, 13], jnp.int32)
        return PA.paged_decode_attention_xla, (q, cache.kv, layer, tables, lengths,
                                               cache.k_scale, cache.v_scale)
    q = jax.random.normal(kq, (B, T, KVH, G, HD), jnp.bfloat16)
    if kind == "spec":
        lengths = jnp.asarray([[5, 6, 7], [20, 21, 22], [11, 12, 13]], jnp.int32)
        anc = None
    else:  # tree: node 0 the root, nodes 1 and 2 its children
        lengths = jnp.asarray([[5] * T, [20] * T, [11] * T], jnp.int32)
        anc = jnp.broadcast_to(jnp.asarray([[1, 0, 0], [1, 1, 0], [1, 0, 1]], jnp.int8), (B, T, T))
    return PA.paged_spec_attention_xla, (q, cache.kv, layer, tables, lengths,
                                         cache.k_scale, cache.v_scale, anc)


@pytest.mark.parametrize("kind", ["decode", "spec", "tree"])
@pytest.mark.parametrize("kv_quant", KV_KINDS)
def test_xla_attention_is_bit_identical(kind, kv_quant, monkeypatch):
    fn, args = attention_case(kind, kv_quant)
    got = jax.jit(fresh(fn))(*args)
    monkeypatch.setattr(PA, "gather_kv_pages", gather_as_the_parent_did)
    want = jax.jit(fresh(fn))(*args)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert_same_bits(got, want)


# -- the regression pin: no layer of the pool in any traced program -----------


def output_shapes(jaxpr) -> set[tuple[int, ...]]:
    """Shapes of every equation's outputs, through every nested jaxpr
    (scan bodies, pjit and closed calls, branches)."""
    shapes = set()
    for eqn in jaxpr.eqns:
        shapes.update(tuple(v.aval.shape) for v in eqn.outvars if hasattr(v.aval, "shape"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            shapes |= output_shapes(sub)
    return shapes


def layer_shapes(kv_quant: str) -> set[tuple[int, ...]]:
    """One layer of the pool, of its scales, and both before the squeeze."""
    out = {(N, 2, BS, CFG.kv_size), (1, N, 2, BS, CFG.kv_size), (N, BS, CFG.kv_size)}
    if kv_quant == "int8":
        out |= {(N, BS, KVH), (1, N, BS, KVH)}
    return out


def traced_shapes(program: str, kv_quant: str, params) -> set[tuple[int, ...]]:
    if program == "prefill_batch_impl":
        toks, tables, starts, tlens = prefill_case(2)
        jaxpr = jax.make_jaxpr(fresh(M.prefill_batch_impl, CFG))(
            params, filled_cache(kv_quant), toks, tables, starts, tlens)
    else:
        fn, args = attention_case(program, kv_quant)
        jaxpr = jax.make_jaxpr(fresh(fn))(*args)
    return output_shapes(jaxpr.jaxpr)


@pytest.mark.parametrize("program", ["prefill_batch_impl", "decode", "spec"])
@pytest.mark.parametrize("kv_quant", KV_KINDS)
def test_no_equation_outputs_a_layer_of_the_pool(program, kv_quant, params):
    shapes = traced_shapes(program, kv_quant, params)
    if program == "prefill_batch_impl":  # the walk reached the scan body's scatter into the pool
        assert (CFG.num_layers, N, 2, BS, CFG.kv_size) in shapes
    assert not shapes & layer_shapes(kv_quant)


@pytest.mark.parametrize("program", ["prefill_batch_impl", "decode"])
def test_the_pin_sees_the_parents_slice(program, params, monkeypatch):
    """The walk has teeth: with the parent's way of reading, it finds the layer."""
    monkeypatch.setattr(PA, "gather_kv_pages", gather_as_the_parent_did)
    assert traced_shapes(program, "int8", params) >= {(N, 2, BS, CFG.kv_size), (N, BS, KVH)}
