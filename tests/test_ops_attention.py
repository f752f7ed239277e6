"""Pallas paged-attention kernel vs the XLA gather reference.

The kernel runs in interpreter mode on CPU (tests cannot assume a real
TPU); the compiled path is lowered for v5e in test_ops_tpu_lowering.py
and held against this reference on the chip by chip_smoke.py's kernel phase.
Reference parity target: vLLM's paged-attention kernels vs its reference
torch implementation (the reference delegates both to vLLM; SURVEY §2.4).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.ops.paged_attention import (
    latent_decode_attention,
    latent_decode_attention_xla,
    paged_decode_attention,
    paged_decode_attention_xla,
    paged_prefill_attention,
    paged_prefill_attention_xla,
    paged_spec_attention,
    paged_spec_attention_xla,
    resolve_attn_impl,
    resolve_prefill_impl,
)


def _mk(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _mk_quant_cache(rng, L, N, bs, KVH, hd):
    """An int8 cache + per-position-per-head scales whose dequantized
    values are ordinary unit-scale normals (scales strictly positive so
    every position is exactly representable by its own scale)."""
    kq = jnp.asarray(rng.integers(-127, 128, (L, N, bs, KVH * hd)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (L, N, bs, KVH * hd)), jnp.int8)
    ks = jnp.asarray(np.abs(rng.standard_normal((L, N, bs, KVH))) * 0.02 + 1e-3, jnp.float32)
    vs = jnp.asarray(np.abs(rng.standard_normal((L, N, bs, KVH))) * 0.02 + 1e-3, jnp.float32)
    return kq, vq, ks, vs


def _dequant(cache_q, scales, KVH, hd):
    L, N, bs, D = cache_q.shape
    x = cache_q.astype(jnp.float32).reshape(L, N, bs, KVH, hd)
    return (x * scales[..., None]).reshape(L, N, bs, D)


@pytest.mark.parametrize("lengths", [
    [96, 1, 0, 37, 80],      # mixed, incl. inactive + non-block-aligned
    [16, 16, 16, 16, 16],    # exactly one block each
    [0, 0, 5, 0, 0],         # empty rows on both sides (prefetch skip)
    [0, 96, 0, 0, 33],       # empty rows first and between, the last row live
    [1, 1, 1, 1, 1],         # one token a row
])
def test_kernel_matches_xla(lengths):
    rng = np.random.default_rng(0)
    L, N, bs, KVH, hd = 3, 40, 16, 4, 64
    B, W, G = 5, 6, 2
    k_cache = _mk(rng, (L, N, bs, KVH * hd))
    v_cache = _mk(rng, (L, N, bs, KVH * hd))
    q = _mk(rng, (B, KVH, G, hd))
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    for layer in (0, 2):
        ref = paged_decode_attention_xla(q, M.fuse_kv(k_cache, v_cache), jnp.int32(layer), tables, lens)
        out = paged_decode_attention(
            q, M.fuse_kv(k_cache, v_cache), jnp.int32(layer), tables, lens, interpret=True
        )
        act = np.asarray(lens) > 0
        np.testing.assert_allclose(
            np.asarray(ref)[act], np.asarray(out)[act], atol=2e-5, rtol=2e-5
        )


def test_kernel_single_page_chunks():
    """pages_per_chunk=1 exercises the chunk-boundary pipeline hardest."""
    rng = np.random.default_rng(1)
    L, N, bs, KVH, hd = 1, 16, 8, 2, 64
    B, W, G = 3, 4, 4
    k_cache = _mk(rng, (L, N, bs, KVH * hd))
    v_cache = _mk(rng, (L, N, bs, KVH * hd))
    q = _mk(rng, (B, KVH, G, hd))
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    lens = jnp.asarray([32, 7, 9], jnp.int32)
    ref = paged_decode_attention_xla(q, M.fuse_kv(k_cache, v_cache), jnp.int32(0), tables, lens)
    out = paged_decode_attention(
        q, M.fuse_kv(k_cache, v_cache), jnp.int32(0), tables, lens,
        pages_per_chunk=1, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)


def test_decode_step_pallas_matches_xla():
    """Full decode step (scatter + attention + mlp + logits) end to end."""
    cfg = ModelConfig()  # test-tiny
    rng = np.random.default_rng(2)
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    N, bs, B, W = 32, 16, 4, 4
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size - 1, B), jnp.int32)
    positions = jnp.asarray([17, 3, 40, 0], jnp.int32)
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    active = jnp.asarray([True, True, True, False])

    cache = M.init_kv_cache(cfg, N, bs, jnp.float32)
    cache = M.KVCache(jnp.asarray(rng.standard_normal(cache.kv.shape), jnp.float32))
    ref_logits, ref_cache = M.decode_step_impl(
        cfg, params, cache, tokens, positions, tables, active, attn_impl="xla"
    )
    out_logits, out_cache = M.decode_step_impl(
        cfg, params, cache, tokens, positions, tables, active,
        attn_impl="pallas_interpret",
    )
    act = np.asarray(active)
    np.testing.assert_allclose(
        np.asarray(ref_logits)[act], np.asarray(out_logits)[act], atol=1e-4, rtol=1e-4
    )
    # Block 0 is the garbage sink: inactive rows' hidden states (and hence
    # the garbage they scatter) legitimately diverge between impls.
    np.testing.assert_allclose(
        np.asarray(ref_cache.kv)[:, 1:], np.asarray(out_cache.kv)[:, 1:], atol=1e-4
    )


# ---------------------------------------------------------------------------
# Quantized (int8) variants: the dequantize-in-kernel paths must match
# BOTH the quantized XLA reference (tight bound: same dequantized
# operands, different walk) and the f32 path over the dequantized cache
# (exact-value bound: dequant itself introduces no extra error).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lengths", [
    [96, 1, 0, 37, 80],      # mixed, incl. inactive + non-block-aligned
    [16, 16, 16, 16, 16],    # exactly one block each
])
def test_quantized_kernel_matches_quantized_xla_and_f32(lengths):
    rng = np.random.default_rng(10)
    L, N, bs, KVH, hd = 3, 40, 16, 4, 64
    B, W, G = 5, 6, 2
    kq, vq, ks, vs = _mk_quant_cache(rng, L, N, bs, KVH, hd)
    q = _mk(rng, (B, KVH, G, hd))
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    act = np.asarray(lengths) > 0
    for layer in (0, 2):
        ref_q = paged_decode_attention_xla(
            q, M.fuse_kv(kq, vq), jnp.int32(layer), tables, lens, ks, vs
        )
        out = paged_decode_attention(
            q, M.fuse_kv(kq, vq), jnp.int32(layer), tables, lens, ks, vs, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(ref_q)[act], np.asarray(out)[act], atol=2e-5, rtol=2e-5
        )
        # vs the f32 path over the explicitly dequantized cache: the
        # in-kernel dequant must BE the dequant, not an approximation.
        ref_f = paged_decode_attention_xla(
            q, M.fuse_kv(_dequant(kq, ks, KVH, hd), _dequant(vq, vs, KVH, hd)),
            jnp.int32(layer), tables, lens,
        )
        np.testing.assert_allclose(
            np.asarray(ref_f)[act], np.asarray(out)[act], atol=2e-5, rtol=2e-5
        )


@pytest.mark.parametrize("S", [1, 4, 8])
def test_spec_kernel_matches_xla(S):
    """Fused multi-query gather vs the XLA reference across draft
    lengths: page-boundary crossings (lengths straddle bs multiples),
    partial blocks, a dead row, and dead trailing slots."""
    rng = np.random.default_rng(S)
    L, N, bs, KVH, hd = 2, 48, 8, 2, 64
    B, W, G = 4, 6, 2
    T = S + 1  # [last, d1..dS]
    k_cache = _mk(rng, (L, N, bs, KVH * hd))
    v_cache = _mk(rng, (L, N, bs, KVH * hd))
    q = _mk(rng, (B, T, KVH, G, hd))
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    # Row r's queries attend consecutive prefixes ending at base+t: base
    # chosen to cross a page boundary (bs=8) for row 0, end exactly on
    # one for row 1, sit inside a partial block for row 2; row 3 dead.
    base = np.array([7, 8 - T, 3, 0], np.int32).clip(min=0)
    lengths = np.zeros((B, T), np.int32)
    for b in range(B):
        for t in range(T):
            lengths[b, t] = base[b] + t + 1
    lengths[3, :] = 0                    # dead row
    if T > 2:
        lengths[2, -1] = 0               # dead trailing slot (undrafted)
    lens = jnp.asarray(lengths, jnp.int32)
    for layer in (0, 1):
        ref = paged_spec_attention_xla(
            q, M.fuse_kv(k_cache, v_cache), jnp.int32(layer), tables, lens
        )
        out = paged_spec_attention(
            q, M.fuse_kv(k_cache, v_cache), jnp.int32(layer), tables, lens, interpret=True
        )
        live = np.asarray(lengths) > 0  # dead slots/rows are junk by contract
        np.testing.assert_allclose(
            np.asarray(ref)[live], np.asarray(out)[live], atol=2e-5, rtol=2e-5
        )


@pytest.mark.parametrize("S", [1, 4])
def test_spec_kernel_quantized_matches_xla(S):
    rng = np.random.default_rng(20 + S)
    L, N, bs, KVH, hd = 2, 48, 8, 2, 64
    B, W, G = 3, 6, 2
    T = S + 1
    kq, vq, ks, vs = _mk_quant_cache(rng, L, N, bs, KVH, hd)
    q = _mk(rng, (B, T, KVH, G, hd))
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    lengths = np.zeros((B, T), np.int32)
    for b in range(B):
        for t in range(T):
            lengths[b, t] = 5 + 9 * b + t + 1
    lens = jnp.asarray(lengths, jnp.int32)
    ref = paged_spec_attention_xla(
        q, M.fuse_kv(kq, vq), jnp.int32(1), tables, lens, ks, vs
    )
    out = paged_spec_attention(
        q, M.fuse_kv(kq, vq), jnp.int32(1), tables, lens, ks, vs, interpret=True
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)


def test_spec_kernel_single_page_chunks():
    """pages_per_chunk=1 exercises the multi-query chunk pipeline hardest."""
    rng = np.random.default_rng(3)
    L, N, bs, KVH, hd = 1, 16, 8, 2, 64
    B, W, G, T = 3, 4, 4, 3
    k_cache = _mk(rng, (L, N, bs, KVH * hd))
    v_cache = _mk(rng, (L, N, bs, KVH * hd))
    q = _mk(rng, (B, T, KVH, G, hd))
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    lens = jnp.asarray(
        [[30, 31, 32], [6, 7, 8], [1, 2, 0]], jnp.int32
    )
    ref = paged_spec_attention_xla(q, M.fuse_kv(k_cache, v_cache), jnp.int32(0), tables, lens)
    out = paged_spec_attention(
        q, M.fuse_kv(k_cache, v_cache), jnp.int32(0), tables, lens,
        pages_per_chunk=1, interpret=True,
    )
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(
        np.asarray(ref)[live], np.asarray(out)[live], atol=2e-5, rtol=2e-5
    )


def _tree_anc(parents: list[int], T: int) -> np.ndarray:
    """Ancestor-or-self closure for node parents (node 0 = root)."""
    anc = np.zeros((T, T), np.int8)
    anc[0, 0] = 1
    for j, p in enumerate(parents, start=1):
        anc[j] = anc[p]
        anc[j, j] = 1
    return anc


def test_tree_mask_chain_reduces_to_linear():
    """A lower-triangular topology mask with per-query history horizons
    must reproduce the legacy linear-lengths call exactly (the tree mask
    is a strict generalization of the causal ramp)."""
    rng = np.random.default_rng(30)
    L, N, bs, KVH, hd = 2, 48, 8, 2, 64
    B, W, G, T = 3, 6, 2, 5
    k_cache = _mk(rng, (L, N, bs, KVH * hd))
    v_cache = _mk(rng, (L, N, bs, KVH * hd))
    q = _mk(rng, (B, T, KVH, G, hd))
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    hist = np.array([7, 12, 3], np.int32)
    lens_linear = hist[:, None] + np.arange(1, T + 1, dtype=np.int32)[None, :]
    lens_tree = np.broadcast_to(hist[:, None], (B, T)).copy()
    anc = np.broadcast_to(np.tril(np.ones((T, T), np.int8)), (B, T, T)).copy()
    ref = paged_spec_attention_xla(
        q, M.fuse_kv(k_cache, v_cache), jnp.int32(0), tables, jnp.asarray(lens_linear)
    )
    tree = paged_spec_attention_xla(
        q, M.fuse_kv(k_cache, v_cache), jnp.int32(0), tables, jnp.asarray(lens_tree),
        anc=jnp.asarray(anc),
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(tree), atol=1e-6)
    out = paged_spec_attention(
        q, M.fuse_kv(k_cache, v_cache), jnp.int32(0), tables, jnp.asarray(lens_tree),
        anc=jnp.asarray(anc), interpret=True,
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("hist", [
    [7, 8, 3, 0],    # page-boundary crossing (bs=8), on-boundary, partial, dead
    [15, 1, 9, 5],   # slot window straddles a page boundary for row 0
])
def test_tree_mask_kernel_matches_xla(hist):
    """Topology-masked kernel vs the XLA reference on a real branched
    tree: root with two subtrees, dead row, dead trailing slots."""
    rng = np.random.default_rng(31)
    L, N, bs, KVH, hd = 2, 48, 8, 2, 64
    B, W, G, T = 4, 6, 2, 5
    k_cache = _mk(rng, (L, N, bs, KVH * hd))
    v_cache = _mk(rng, (L, N, bs, KVH * hd))
    q = _mk(rng, (B, T, KVH, G, hd))
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    # parents [-,0,0,1,1]: two children of the root, two of node 1.
    anc1 = _tree_anc([0, 0, 1, 1], T)
    anc = np.broadcast_to(anc1, (B, T, T)).copy()
    anc[3] = 0  # dead row: no live node at all
    anc[2, 4, :] = 0
    anc[2, :, 4] = 0  # row 2: trailing slot undrafted
    h = np.asarray(hist, np.int32)
    lens = np.broadcast_to(h[:, None], (B, T)).copy()
    lens[3, :] = 0
    live = np.asarray(anc.any(axis=2))
    for layer in (0, 1):
        ref = paged_spec_attention_xla(
            q, M.fuse_kv(k_cache, v_cache), jnp.int32(layer), tables, jnp.asarray(lens),
            anc=jnp.asarray(anc),
        )
        out = paged_spec_attention(
            q, M.fuse_kv(k_cache, v_cache), jnp.int32(layer), tables, jnp.asarray(lens),
            anc=jnp.asarray(anc), interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(ref)[live], np.asarray(out)[live], atol=2e-5, rtol=2e-5
        )


def test_tree_mask_kernel_quantized_and_single_page():
    """int8 cache + topology mask, pages_per_chunk=1 (hardest chunk
    pipeline): in-kernel dequant composes with the ancestor bits."""
    rng = np.random.default_rng(32)
    L, N, bs, KVH, hd = 2, 32, 8, 2, 64
    B, W, G, T = 3, 4, 2, 4
    kq, vq, ks, vs = _mk_quant_cache(rng, L, N, bs, KVH, hd)
    q = _mk(rng, (B, T, KVH, G, hd))
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    anc = np.broadcast_to(_tree_anc([0, 0, 2], T), (B, T, T)).copy()
    hist = np.array([9, 16, 2], np.int32)
    lens = np.broadcast_to(hist[:, None], (B, T)).copy()
    ref = paged_spec_attention_xla(
        q, M.fuse_kv(kq, vq), jnp.int32(1), tables, jnp.asarray(lens), ks, vs,
        anc=jnp.asarray(anc),
    )
    out = paged_spec_attention(
        q, M.fuse_kv(kq, vq), jnp.int32(1), tables, jnp.asarray(lens), ks, vs,
        jnp.asarray(anc), pages_per_chunk=1, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)
    # f32 reference over the dequantized cache: the masked in-kernel
    # dequant must BE the dequant.
    ref_f = paged_spec_attention_xla(
        q, M.fuse_kv(_dequant(kq, ks, KVH, hd), _dequant(vq, vs, KVH, hd)),
        jnp.int32(1), tables, jnp.asarray(lens), anc=jnp.asarray(anc),
    )
    np.testing.assert_allclose(np.asarray(ref_f), np.asarray(out), atol=2e-5, rtol=2e-5)


def test_decode_step_int8_cache_logit_error_bound():
    """Full decode step on an int8 cache: sampled logits stay within a
    small bound of the f32-cache step (KV rounding is ~0.4% relative per
    element; at test-tiny scale the end-to-end logit error stays well
    under 0.5), and the two quantized backends agree tightly."""
    cfg = ModelConfig()  # test-tiny
    rng = np.random.default_rng(4)
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    N, bs, B, W = 32, 4, 4, 8
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size - 1, B), jnp.int32)
    positions = jnp.asarray([17, 3, 21, 9], jnp.int32)
    tables = jnp.asarray((np.arange(B * W) + 1).reshape(B, W), jnp.int32)
    active = jnp.asarray([True] * B)

    # Seed both caches through the same prefill so the int8 cache holds a
    # QUANTIZED copy of the f32 cache's history (not unrelated noise).
    cf = M.init_kv_cache(cfg, N, bs, jnp.float32)
    cq = M.init_kv_cache(cfg, N, bs, jnp.float32, kv_quant="int8")
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size - 1, 24), jnp.int32)
    for b in range(B):
        table = jnp.asarray(np.arange(b * W, (b + 1) * W) + 1, jnp.int32)
        _, cf = M.prefill(cfg, params, cf, prompt, table,
                          jnp.int32(0), jnp.int32(positions[b] + 1))
        _, cq = M.prefill(cfg, params, cq, prompt, table,
                          jnp.int32(0), jnp.int32(positions[b] + 1))

    ref, _ = M.decode_step_impl(
        cfg, params, cf, tokens, positions, tables, active, attn_impl="xla"
    )
    out_x, _ = M.decode_step_impl(
        cfg, params, cq, tokens, positions, tables, active, attn_impl="xla"
    )
    out_p, _ = M.decode_step_impl(
        cfg, params, cq, tokens, positions, tables, active,
        attn_impl="pallas_interpret",
    )
    err = float(np.max(np.abs(np.asarray(ref) - np.asarray(out_x))))
    assert err < 0.5, f"int8-KV logit error {err} out of bounds"
    assert err > 0.0, "int8 cache produced bit-identical logits — quantization not applied?"
    # Backend agreement on the SAME quantized cache is tight (both
    # dequantize identical int8+scale operands).
    np.testing.assert_allclose(
        np.asarray(out_x), np.asarray(out_p), atol=1e-4, rtol=1e-4
    )


# ---------------------------------------------------------------------------
# The walk's edges (PR 31): the grid is the rows and a row's chunks are a
# loop inside its step (int8 pages keep the chunk axis on the grid around
# the same body), so what can go wrong is at a row's first and last chunk
# and at the hand-over between rows. Chunks of 2 pages of 8 tokens.
# ---------------------------------------------------------------------------

EDGE_P, EDGE_BS = 2, 8
EDGES = {
    # name: (lengths, table width)
    "exactly_k_chunks": ([32, 16, 48], 6),
    "k_chunks_and_a_token": ([33, 17, 1 + 2 * 16], 6),
    "one_token": ([1, 1, 1], 6),
    "empty_rows_first": ([0, 0, 20, 7], 6),
    "empty_rows_last": ([20, 7, 0, 0], 6),
    "empty_rows_between": ([20, 0, 0, 7, 0, 33], 6),
    "every_row_empty": ([0, 0, 0], 6),
    "table_width_no_multiple_of_P": ([40, 3, 25], 5),
    "one_row": ([37], 6),
}
WALKS = ("decode", "decode-int8", "spec", "spec-int8", "tree", "tree-int8", "latent")


@pytest.mark.parametrize("edge", list(EDGES))
@pytest.mark.parametrize("walk", WALKS)
def test_walk_edges_match_xla(walk, edge):
    lengths, W = EDGES[edge]
    lengths = np.asarray(lengths, np.int32)
    B, bs = len(lengths), EDGE_BS
    rng = np.random.default_rng(40 + len(edge))
    L, N, KVH, hd, G, T = 2, 32, 2, 32, 2, 3
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    kind, _, quant = walk.partition("-")
    kw = dict(pages_per_chunk=EDGE_P, interpret=True)
    if kind == "latent":
        Dk, Dv, H = 96, 64, 4
        pool = _mk(rng, (L, N, bs, Dk))
        q = _mk(rng, (B, H, Dk))
        geo = dict(value_dim=Dv, scale=Dk ** -0.5)
        ref = latent_decode_attention_xla(q, pool, jnp.int32(1), tables, jnp.asarray(lengths), **geo)
        out = latent_decode_attention(q, pool, jnp.int32(1), tables, jnp.asarray(lengths), **geo, **kw)
        live = lengths > 0
    else:
        if quant:
            cache = _mk_quant_cache(rng, L, N, bs, KVH, hd)
            k, v, scales = cache[0], cache[1], cache[2:]
        else:
            k, v, scales = _mk(rng, (L, N, bs, KVH * hd)), _mk(rng, (L, N, bs, KVH * hd)), (None, None)
        if kind == "decode":
            q = _mk(rng, (B, KVH, G, hd))
            lens = jnp.asarray(lengths)
            ref = paged_decode_attention_xla(q, M.fuse_kv(k, v), jnp.int32(1), tables, lens, *scales)
            out = paged_decode_attention(q, M.fuse_kv(k, v), jnp.int32(1), tables, lens, *scales, **kw)
            live = lengths > 0
        else:
            q = _mk(rng, (B, T, KVH, G, hd))
            if kind == "spec":
                # Query t attends [0, length - T + 1 + t): the last query
                # ends where the row does, so the row's walk has the
                # edge's length; short rows leave their first queries dead.
                lens2 = np.maximum(lengths[:, None] - (T - 1) + np.arange(T)[None, :], 0)
                lens2 = np.where(lengths[:, None] > 0, lens2, 0).astype(np.int32)
                anc = None
                live = lens2 > 0
            else:
                # Tree: T in-flight slots on top of the history, so the
                # history is the edge's length less T (a row that short
                # has none); an empty row has no live node.
                hist = np.maximum(lengths - T, 0)
                lens2 = np.repeat(hist[:, None], T, axis=1).astype(np.int32)
                anc1 = _tree_anc([0, 0], T)
                anc_np = np.where(lengths[:, None, None] > 0, anc1[None], 0).astype(np.int8)
                anc = jnp.asarray(anc_np)
                live = np.repeat((lengths > 0)[:, None], T, axis=1)
            ref = paged_spec_attention_xla(
                q, M.fuse_kv(k, v), jnp.int32(1), tables, jnp.asarray(lens2), *scales, anc=anc)
            out = paged_spec_attention(
                q, M.fuse_kv(k, v), jnp.int32(1), tables, jnp.asarray(lens2), *scales, anc, **kw)
    out = np.asarray(out)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(np.asarray(ref)[live], out[live], atol=2e-5, rtol=2e-5)
    # A row that attends nothing comes out as zeros, whatever its buffers held.
    dead_rows = lengths == 0
    assert not out[dead_rows].any()


def test_resolve_attn_impl():
    assert resolve_attn_impl("xla") == "xla"
    assert resolve_attn_impl("pallas") == "pallas"
    # On the CPU test backend, auto → xla.
    assert resolve_attn_impl("auto") == "xla"


# ---------------------------------------------------------------------------
# Prefill: the kernel that attends out of the pages against the XLA form
# ``prefill_batch_impl`` keeps (gather of the table's whole width + the
# chunk's own K and V as computed + one softmax over both).
# ---------------------------------------------------------------------------

# name -> geometry (KVH, G, hd, bs), T, table width, (start_pos, true_len) a
# row, and the kernel's chunk and tile where the case wants several of them.
PREFILL_CASES = {
    # a fresh prompt: every prefix column of the XLA form is dead
    "prefix_0": dict(geom=(4, 7, 32, 16), T=64, W=8, rows=[(0, 64)]),
    # the sessions cell's turn: ~130 new tokens behind a block-aligned 1.7k
    "prefix_1712_block_aligned": dict(geom=(4, 7, 32, 16), T=128, W=128, rows=[(1712, 1840)]),
    # a second chunk of a long prompt (start 2,048, T 2,048, cut to what the
    # CPU bears): four query tiles over sixteen chunks, most of them plain
    "second_chunk": dict(geom=(4, 7, 32, 16), T=256, W=32, rows=[(256, 512)], P=2, tq=64),
    # the row ends inside a page, and inside the tile
    "true_len_inside_a_page": dict(geom=(2, 2, 32, 16), T=64, W=8, rows=[(32, 32 + 21)]),
    # inactive rows beside live ones
    "inactive_row_Bp2": dict(geom=(2, 2, 32, 16), T=32, W=8, rows=[(0, 0), (48, 75)]),
    "inactive_rows_Bp4": dict(geom=(2, 2, 32, 16), T=32, W=8,
                              rows=[(16, 40), (0, 0), (0, 32), (0, 0)], P=1, tq=16),
    # the benchmark's two geometries at their head size
    "qwen_G7_KVH4_bs16": dict(geom=(4, 7, 128, 16), T=32, W=8, rows=[(64, 90)]),
    "mistral_G4_KVH8_bs16": dict(geom=(8, 4, 128, 16), T=32, W=8, rows=[(64, 96), (0, 17)]),
    # the served dtype: bf16 operands and p, float32 statistics
    "bf16": dict(geom=(4, 7, 32, 16), T=64, W=16, rows=[(128, 180)], dtype=jnp.bfloat16, tol=2e-2),
}


def _prefill_case(geom, T, W, rows, dtype=jnp.float32, **_):
    """Random pages and queries for ``rows`` → (q, the chunk's own k and v
    as the pages hold them, caches, layer, tables, start_pos, true_len)."""
    KVH, G, hd, bs = geom
    rng = np.random.default_rng(11)
    B, L = len(rows), 2
    N = B * W + 1
    k_cache = jnp.asarray(rng.standard_normal((L, N, bs, KVH * hd)), dtype)
    v_cache = jnp.asarray(rng.standard_normal((L, N, bs, KVH * hd)), dtype)
    # Each row owns W distinct blocks; block 0 stays the garbage sink.
    tables = rng.permutation(np.arange(1, N))[: B * W].reshape(B, W).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((B, T, KVH, G, hd)), dtype)
    start = np.asarray([r[0] for r in rows], np.int32)
    tlen = np.asarray([r[1] for r in rows], np.int32)
    layer = 1
    pos = start[:, None] + np.arange(T)[None]
    wide = np.concatenate([tables, np.zeros((B, T // bs + 1), np.int32)], axis=1)
    blk = np.take_along_axis(wide, pos // bs, axis=1)
    k = np.asarray(k_cache)[layer, blk, pos % bs].reshape(B, T, KVH, hd)
    v = np.asarray(v_cache)[layer, blk, pos % bs].reshape(B, T, KVH, hd)
    return (q, jnp.asarray(k), jnp.asarray(v), k_cache, v_cache, jnp.int32(layer),
            jnp.asarray(tables), jnp.asarray(start), jnp.asarray(tlen))


@pytest.mark.parametrize("name", list(PREFILL_CASES))
def test_prefill_kernel_matches_the_xla_form(name):
    case = PREFILL_CASES[name]
    q, k, v, k_cache, v_cache, layer, tables, start, tlen = _prefill_case(**case)
    ref = paged_prefill_attention_xla(q, k, v, M.fuse_kv(k_cache, v_cache), layer, tables, start, tlen)
    out = paged_prefill_attention(
        q, M.fuse_kv(k_cache, v_cache), layer, tables, start, tlen,
        pages_per_chunk=case.get("P", 0), q_tile=case.get("tq", 0), interpret=True,
    )
    assert out.dtype == q.dtype and out.shape == q.shape
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(out).all()
    T = q.shape[1]
    # Queries at or past a row's true length are padding: the caller drops them.
    live = np.asarray(start)[:, None] + np.arange(T)[None] < np.asarray(tlen)[:, None]
    tol = case.get("tol", 2e-5)
    np.testing.assert_allclose(ref[live], out[live], atol=tol, rtol=tol)
    # An inactive row does nothing and comes out as zeros.
    assert not out[np.asarray(tlen) == 0].any()


def test_prefill_batch_pallas_matches_xla():
    """The whole packed prefill (scatter + attention + mlp + logits), a
    cached prefix behind one row, a fresh prompt, and an inactive row."""
    cfg = ModelConfig()  # test-tiny
    rng = np.random.default_rng(5)
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    N, bs, Bp, T, W = 40, 4, 3, 16, 12
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size - 1, (Bp, T)), jnp.int32)
    tables = jnp.asarray(rng.permutation(np.arange(1, N))[: Bp * W].reshape(Bp, W), jnp.int32)
    start = jnp.asarray([24, 0, 0], jnp.int32)
    tlen = jnp.asarray([24 + 13, 16, 0], jnp.int32)
    cache = M.init_kv_cache(cfg, N, bs, jnp.float32)
    cache = M.KVCache(jnp.asarray(rng.standard_normal(cache.kv.shape), jnp.float32))
    ref_logits, ref_cache = M.prefill_batch_impl(
        cfg, params, cache, tokens, tables, start, tlen, attn_impl="xla")
    out_logits, out_cache = M.prefill_batch_impl(
        cfg, params, cache, tokens, tables, start, tlen, attn_impl="pallas_interpret")
    np.testing.assert_allclose(
        np.asarray(ref_logits)[:2], np.asarray(out_logits)[:2], atol=1e-4, rtol=1e-4)
    for got, want in zip(M.split_kv(out_cache.kv), M.split_kv(ref_cache.kv)):
        np.testing.assert_allclose(np.asarray(want)[:, 1:], np.asarray(got)[:, 1:], atol=1e-4)


def test_a_prompt_served_through_the_prefill_kernel_emits_the_xla_paths_tokens():
    """Engine level, test-tiny: packed prefill, a chunked long prompt and a
    second turn behind a cached prefix, greedy; the start line and the
    dispatch counter say which path ran."""
    import asyncio

    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    rng = np.random.default_rng(3)
    first = [int(t) for t in rng.integers(1, 500, 21)]
    prompts = [first, [int(t) for t in rng.integers(1, 500, 90)], [7, 8, 9]]

    async def run(attn_impl: str):
        args = EngineArgs(
            model=ModelConfig(), block_size=4, num_kv_blocks=96, max_num_seqs=4,
            max_model_len=160, max_prefill_tokens=32, dtype="float32", attn_impl=attn_impl,
        )
        engine = TpuEngine(args)
        reg = MetricsRegistry()
        engine.bind_metrics(reg)
        await engine.start()

        async def one(prompt):
            req = PreprocessedRequest(model="t", token_ids=list(prompt))
            req.sampling.temperature = 0.0
            req.sampling.seed = 0
            req.stop.max_tokens = 6
            outs = [o async for o in engine.generate(req, Context())]
            return [t for o in outs for t in o.get("token_ids", [])]

        try:
            toks = list(await asyncio.gather(*(one(p) for p in prompts)))
            # a second turn: the first prompt and its answer are cached blocks
            toks.append(await one(first + toks[0] + [11, 12, 13, 14, 15]))
            await engine.run_on_engine_thread(lambda: None)
            await engine.run_on_engine_thread(lambda: None)
            line = engine._runner._start_line("")
        finally:
            await engine.stop()
        return toks, line, reg.render()

    want, xla_line, xla_page = asyncio.run(run("xla"))
    got, line, page = asyncio.run(run("pallas_interpret"))
    assert all(len(t) == 6 for t in want)
    assert got == want
    assert "prefill=pallas_interpret decode=pallas_interpret" in line
    assert "prefill=xla decode=xla" in xla_line
    assert 'engine_prefill_attn_dispatch_total{path="pallas"}' in page
    assert 'engine_prefill_attn_dispatch_total{path="xla"}' not in page
    assert 'engine_prefill_attn_dispatch_total{path="xla"}' in xla_page


def test_resolve_prefill_impl():
    """The XLA form is reached by what the code observes alone: platform
    (through ``resolve_attn_impl``), int8 KV pages, a refused geometry."""
    qwen, tiny = ModelConfig.preset("qwen2-7b"), ModelConfig()
    assert resolve_prefill_impl("auto", qwen, 16, False) == ("xla", "")  # the CPU backend
    assert resolve_prefill_impl("pallas", qwen, 16, False) == ("pallas", "")
    impl, why = resolve_prefill_impl("pallas", qwen, 16, True)
    assert impl == "xla" and "int8" in why
    impl, why = resolve_prefill_impl("pallas", tiny, 16, False)
    assert impl == "xla" and "128" in why
    assert resolve_prefill_impl("pallas", ModelConfig.preset("llama-1b"), 16, False) == ("pallas", "")
    # interpret mode checks none of the compiler's rules
    assert resolve_prefill_impl("pallas_interpret", tiny, 4, False) == ("pallas_interpret", "")
    # latent pages are asked about as latent pages: one 192-lane "KV head" is
    # no geometry of theirs, their row and its value slice are
    latent = ModelConfig(block="longcat", num_kv_heads=1, head_dim=192, num_heads=64,
                         kv_lora_rank=512, qk_rope_head_dim=64, qk_nope_head_dim=128, v_head_dim=128)
    assert latent.kv_size % 128 and resolve_prefill_impl("pallas", latent, 32, False) == ("pallas", "")
    impl, why = resolve_prefill_impl("pallas", ModelConfig.preset("longcat-tiny"), 8, False)
    assert impl == "xla" and "latent page row" in why


# ---------------------------------------------------------------------------
# One page, one descriptor (PR 46): K and V of a block side by side in one
# pool ``[L, N, 2, bs, KVH*hd]``. Every kernel that walks it against its XLA
# form at each served geometry, over the rows a walk can go wrong at: length
# 0, exactly one page, a partial last chunk (chunks of 2 pages), and a table
# wider than the pool has pages (P is cut to the pool).
# ---------------------------------------------------------------------------

SERVED = {
    # name: (KVH, G, hd, bs)
    "qwen_G7_KVH4_bs16": (4, 7, 128, 16),
    "mistral_G4_KVH8_bs16": (8, 4, 128, 16),
    "lfm2_hd64_bs32": (8, 4, 64, 32),
    "sala_KVH2_bs64": (2, 16, 128, 64),
}
FUSED_KINDS = ("decode", "decode-int8", "spec", "spec-int8", "tree", "prefill")


@pytest.mark.parametrize("kind", FUSED_KINDS)
@pytest.mark.parametrize("geometry", list(SERVED))
def test_fused_page_kernels_match_xla_at_served_geometries(geometry, kind):
    KVH, G, hd, bs = SERVED[geometry]
    rng = np.random.default_rng(46)
    L, N, W, P, T = 2, 6, 8, 2, 3          # the table is wider than the pool
    lengths = np.asarray([0, bs, 3 * bs - 5, 1, W * bs], np.int32)
    B = len(lengths)
    tables = jnp.asarray(rng.integers(1, N, size=(B, W)), jnp.int32)
    walk, _, quant = kind.partition("-")
    if quant:
        kq, vq, *scales = _mk_quant_cache(rng, L, N, bs, KVH, hd)
        kv = M.fuse_kv(kq, vq)
    else:
        kv, scales = _mk(rng, (L, N, 2, bs, KVH * hd)), (None, None)
    assert kv.shape == (L, N, 2, bs, KVH * hd)
    layer, kw = jnp.int32(1), dict(pages_per_chunk=P, interpret=True)
    if walk == "decode":
        q = _mk(rng, (B, KVH, G, hd))
        ref = paged_decode_attention_xla(q, kv, layer, tables, jnp.asarray(lengths), *scales)
        out = paged_decode_attention(q, kv, layer, tables, jnp.asarray(lengths), *scales, **kw)
        live = lengths > 0
    elif walk == "prefill":
        # Chunks of T new positions that end where the rows do; the chunk's
        # own K and V are in the pages already (the XLA form takes them too).
        Tq = bs
        start = np.maximum((lengths - 1) // bs * bs, 0).astype(np.int32)
        q = _mk(rng, (B, Tq, KVH, G, hd))
        pos = start[:, None] + np.arange(Tq)[None]
        blk = np.take_along_axis(
            np.concatenate([np.asarray(tables), np.zeros((B, 2), np.int32)], axis=1), pos // bs, axis=1)
        k, v = (jnp.asarray(np.asarray(part)[1, blk, pos % bs].reshape(B, Tq, KVH, hd))
                for part in M.split_kv(kv))
        ref = paged_prefill_attention_xla(q, k, v, kv, layer, tables, jnp.asarray(start), jnp.asarray(lengths))
        out = paged_prefill_attention(q, kv, layer, tables, jnp.asarray(start), jnp.asarray(lengths),
                                      pages_per_chunk=P, interpret=True)
        live = pos < lengths[:, None]
    else:
        if KVH * G * T > 128:  # the spec kernel's 128 query columns: one query position fits
            T = 1
        q = _mk(rng, (B, T, KVH, G, hd))
        if walk == "spec":
            lens2 = np.maximum(lengths[:, None] - (T - 1) + np.arange(T)[None, :], 0)
            lens2 = np.where(lengths[:, None] > 0, lens2, 0).astype(np.int32)
            anc, live = None, lens2 > 0
        else:
            lens2 = np.repeat(np.maximum(lengths - T, 0)[:, None], T, axis=1).astype(np.int32)
            anc1 = _tree_anc([0] * (T - 1), T)
            anc = jnp.asarray(np.where(lengths[:, None, None] > 0, anc1[None], 0).astype(np.int8))
            live = np.repeat((lengths > 0)[:, None], T, axis=1)
        ref = paged_spec_attention_xla(q, kv, layer, tables, jnp.asarray(lens2), *scales, anc=anc)
        out = paged_spec_attention(q, kv, layer, tables, jnp.asarray(lens2), *scales, anc, **kw)
    out = np.asarray(out)
    assert np.isfinite(out[live]).all()
    np.testing.assert_allclose(np.asarray(ref)[live], out[live], atol=3e-5, rtol=3e-5)
    if walk != "prefill":  # a row that attends nothing comes out as zeros
        assert not out[lengths == 0].any()
