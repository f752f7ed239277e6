"""Block-sparse attention over pages (InfLLM-v2 as MiniCPM4 publishes it): the
cache of compressed keys, the per-token choice of blocks, and the attend.

A page is the sparse block (``bs`` tokens). A compressed key is the mean of
``2 * stride`` keys, one every ``stride`` tokens, ``bs // stride = 4`` to a
page; ``kbar`` number j (tokens ``[stride j, stride j + 2 stride)``) is
**stored in the page that holds its last token**, slot ``c`` of block ``b``
holding number ``4b + c - 1`` (slot 0 of block 0 holds nothing), so a sealed
page never depends on what follows it. A query at position t sees the
compressed keys whose last token is at or before t.

The choice (``block_scores``): per query head a softmax over the visible
compressed keys of ``q . kbar / sqrt(d)``, summed over a KV head's G query
heads; a block scores the maximum over the compressed keys whose span
overlaps it (its own four slots and slot 0 of the block after it); the first
``init_blocks`` and the blocks that hold the last ``window`` positions are
forced in; the ``topk`` highest are kept. Per token, per KV head.

- decode (``sparse_select``): the choice becomes a page table of its own a
  row and KV head, ascending so that the row's open block comes last, and
  ``paged_decode_attention`` walks it as it walks any table (no rotary
  embedding in these layers, so a page's place in the walk means nothing).
  A row with at most ``dense_len`` positions keeps its own table.
- prefill (``sparse_prefill_attention``): the same choice for every query of
  the chunk, then attention over all the row's pages under the block mask, a
  tile of queries at a time: the mathematics of the choice, not yet its
  savings (``choice_counts`` counts it so).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dynamo_tpu.ops.paged_attention import gather_kv_pages

NEG = -1e30
_PREFILL_Q_TILE = 32  # float32 scores of 32 heads x 32 queries x 24,576 positions are 101 MB


class SparseSizes(NamedTuple):
    stride: int
    block: int
    topk: int
    init_blocks: int
    window: int
    dense_len: int

    @classmethod
    def of(cls, cfg) -> "SparseSizes":
        return cls(cfg.sparse_kernel_stride, cfg.sparse_block_size, cfg.sparse_topk,
                   cfg.sparse_init_blocks, cfg.sparse_window_size, cfg.sparse_dense_len)

    @property
    def per_block(self) -> int:
        return self.block // self.stride


def compress_keys(k: jax.Array, before: jax.Array, stride: int) -> jax.Array:
    """k [B, T, X] from a block boundary on, ``before`` [B, stride, X] the keys
    just ahead of it → [B, T // stride, X]: entry c is the mean of the
    ``2 * stride`` keys that end with k's token ``stride * c + stride - 1``."""
    B, T, X = k.shape
    halves = jnp.concatenate([before, k], axis=1).astype(jnp.float32)
    halves = halves.reshape(B, T // stride + 1, stride, X).mean(axis=2)
    return (0.5 * (halves[:, :-1] + halves[:, 1:])).astype(k.dtype)


def block_scores(q: jax.Array, ck: jax.Array, t: jax.Array, sp: SparseSizes) -> jax.Array:
    """q [B, Q, KVH, G, hd] at positions ``t`` [B, Q]; ``ck`` [B, W * 4, KVH,
    hd] the compressed keys of the row's table in storage order → block
    scores [B, Q, KVH, W] float32: forced blocks 1e9, blocks past ``t`` NEG."""
    B, Q, KVH, G, hd = q.shape
    J = ck.shape[1]
    W = J // sp.per_block
    slot = jnp.arange(J, dtype=jnp.int32)
    seen = (slot[None, None, :] >= 1) & (sp.stride * slot[None, None, :] + sp.stride - 1 <= t[..., None])
    s = jnp.einsum("bqkgh,bjkh->bqkgj", q, ck, preferred_element_type=jnp.float32) * hd ** -0.5
    s = jnp.where(seen[:, :, None, None, :], s, NEG)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)) * seen[:, :, None, None, :]
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    sj = jnp.sum(p, axis=3).reshape(B, Q, KVH, W, sp.per_block)
    after = jnp.concatenate([sj[..., 1:, 0], jnp.zeros_like(sj[..., :1, 0])], axis=-1)
    score = jnp.maximum(jnp.max(sj, axis=-1), after)                                 # [B, Q, KVH, W]
    b = jnp.arange(W, dtype=jnp.int32)[None, None, :]
    forced = (b < sp.init_blocks) | (b >= (t[..., None] - sp.window + 1) // sp.block)
    score = jnp.where(forced[:, :, None, :], 1e9, score)
    return jnp.where((b <= t[..., None] // sp.block)[:, :, None, :], score, NEG)


def gather_ckeys(ckeys: jax.Array, layer, block_tables: jax.Array, KVH: int) -> jax.Array:
    """The pool ``[L, N, 4, KVH * hd]`` → a table's compressed keys [B, W * 4, KVH, hd]."""
    L, N, per, X = ckeys.shape
    B, W = block_tables.shape
    return ckeys.reshape(L * N, per, X)[layer * N + block_tables].reshape(B, W * per, KVH, X // KVH)


def sparse_select(q, ckeys, layer, block_tables, t, sp: SparseSizes):
    """One decode position a row: q [B, KVH, G, hd] at ``t`` [B] → (page tables
    [B * KVH, Wc], lengths [B * KVH]) for ``paged_decode_attention`` over rows
    of (row, KV head): the chosen pages ascending, the open block last and
    ``lengths`` counting its fill; a row of at most ``dense_len`` positions
    keeps its own table and length."""
    B, KVH, G, hd = q.shape
    W = block_tables.shape[1]
    topk, dense_w = min(sp.topk, W), min(W, sp.dense_len // sp.block)
    Wc = max(topk, dense_w)
    ck = gather_ckeys(ckeys, layer, block_tables, KVH)
    score = block_scores(q[:, None], ck, t[:, None], sp)[:, 0]                       # [B, KVH, W]
    chosen = jnp.sort(lax.top_k(score, topk)[1], axis=-1)                            # [B, KVH, topk]
    pages = jnp.take_along_axis(jnp.broadcast_to(block_tables[:, None], (B, KVH, W)), chosen, axis=-1)
    pages = jnp.pad(pages, ((0, 0), (0, 0), (0, Wc - topk)))
    kept = jnp.minimum(t // sp.block + 1, topk)
    lengths = (kept - 1) * sp.block + t % sp.block + 1
    dense = t + 1 <= sp.dense_len
    own = jnp.pad(block_tables[:, :dense_w], ((0, 0), (0, Wc - dense_w)))
    pages = jnp.where(dense[:, None, None], own[:, None], pages)
    lengths = jnp.where(dense, t + 1, lengths)
    return pages.reshape(B * KVH, Wc), jnp.repeat(lengths, KVH)


def per_kv_head(q: jax.Array) -> jax.Array:
    """q [B, KVH, G, hd] → [B * KVH, KVH, G, hd]: row (b, k) keeps KV head k's
    queries and zeros for the others, whose outputs ``own_kv_head`` drops."""
    B, KVH, G, hd = q.shape
    eye = jnp.eye(KVH, dtype=q.dtype)
    return (q[:, :, None] * eye[None, :, :, None, None]).reshape(B * KVH, KVH, G, hd)


def own_kv_head(o: jax.Array, KVH: int) -> jax.Array:
    """[B * KVH, KVH, G, hd] → [B, KVH, G, hd]: of row (b, k), head k."""
    B = o.shape[0] // KVH
    return jnp.einsum("bkkgh->bkgh", o.reshape(B, KVH, *o.shape[1:]))


def sparse_prefill_attention(q, kv_cache, ckeys, layer, block_tables, start_pos, true_len,
                             sp: SparseSizes) -> jax.Array:
    """q [B, T, KVH, G, hd] at positions ``start_pos + i``, the chunk's K, V and
    compressed keys already in the pools → [B, T, KVH, G, hd]: each query
    attends the positions at or before it of the blocks it chose (every block
    while it sees at most ``dense_len`` positions)."""
    B, T, KVH, G, hd = q.shape
    W = block_tables.shape[1]
    tq = math.gcd(T, _PREFILL_Q_TILE)
    pk, pv = gather_kv_pages(kv_cache, None, None, layer, block_tables, KVH, hd, q.dtype)  # [B, W*bs, KVH, hd] x2
    ck = gather_ckeys(ckeys, layer, block_tables, KVH)
    ctx = jnp.arange(W * sp.block, dtype=jnp.int32)
    topk = min(sp.topk, W)

    def tile(args):
        qt, i0 = args                                                                 # [B, tq, KVH, G, hd]
        t = start_pos[:, None] + i0 + jnp.arange(tq, dtype=jnp.int32)[None, :]         # [B, tq]
        score = block_scores(qt, ck, t, sp)                                            # [B, tq, KVH, W]
        # As decode chooses: lax.top_k's own order, the lower block first among
        # equals (two blocks tie whenever one compressed key across their
        # boundary is the best of both).
        chosen = lax.top_k(score, topk)[1]                                             # [B, tq, KVH, topk]
        keep = (chosen[..., None] == jnp.arange(W, dtype=jnp.int32)).any(axis=-2)
        keep = (keep & (score > NEG)) | (t + 1 <= sp.dense_len)[..., None, None]
        keep = jnp.repeat(keep, sp.block, axis=-1)                                     # [B, tq, KVH, W*bs]
        see = keep & (ctx[None, None, None, :] <= jnp.minimum(t, true_len[:, None] - 1)[..., None, None])
        s = jnp.einsum("bqkgh,bckh->bqkgc", qt, pk, preferred_element_type=jnp.float32) * hd ** -0.5
        s = jnp.where(see[:, :, :, None, :], s, NEG)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bqkgc,bckh->bqkgh", p, pv)

    tiles = jnp.moveaxis(q.reshape(B, T // tq, tq, KVH, G, hd), 1, 0)
    o = lax.map(tile, (tiles, jnp.arange(T // tq, dtype=jnp.int32) * tq))
    return jnp.moveaxis(o, 0, 1).reshape(B, T, KVH, G, hd)


def choice_counts(lengths, sp: SparseSizes, table_blocks: int | None = None) -> tuple[int, int, int]:
    """The counters of one dispatch, from what it was dispatched with: over
    query positions that see ``lengths`` positions each (a numpy array),
    (blocks their attention goes over, blocks visible to them, positions at or
    under ``dense_len``). A decode step (``table_blocks`` None) walks the table
    ``sparse_select`` leaves it: the top-k, or every visible block on the dense
    path. A prefill behind a table ``table_blocks`` wide that has a position
    past ``dense_len`` runs ``sparse_prefill_attention``, which scores every
    page of the table for every query and applies the choice as a mask; any
    other prefill attends its visible blocks."""
    n = np.asarray(lengths, np.int64)
    visible = -(-n // sp.block)
    dense = n <= sp.dense_len
    if table_blocks is None:
        read = np.where(dense, visible, np.minimum(visible, sp.topk))
    elif table_blocks * sp.block > sp.dense_len and not dense.all():
        read = np.full_like(visible, table_blocks)
    else:
        read = visible
    return int(read.sum()), int(visible.sum()), int(dense.sum())
