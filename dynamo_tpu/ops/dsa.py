"""Learned sparse attention over a latent cache (the DeepSeek-V3.2 indexer, as
a ``block="dots3"`` model's full layers run it): a query scores every cached
position against one small key a token, keeps the ``topk`` highest and attends
those rows of the latent pool alone. Where ops/sparse_attention.py chooses
whole pages and hands the page walk a shorter table, the unit chosen here is
one token's row.

    I(t, s) = sum_j w_j(t) * relu(qI_j(t) . kI(s))        s <= t

- ``index_scores`` (the Pallas kernel ``dsa_index_scores`` and its XLA twin):
  a decode row's scores over its cached index keys, straight out of their pages
  (``[full layers, N, bs, index_head_dim]`` under the latent pages' block ids):
  float32 ``[B, W*bs]``, ``NEG_INF`` at and past the row's length. The keys are
  read once, nothing of the table's width is gathered.
- ``select``: the exact ``topk`` of a row (``lax.top_k``: the lower position
  first at a tie), highest first, so a row with fewer visible positions than
  ``topk`` has them all in front.
- A decode row's chosen set reaches the attention in one of two forms, both
  ``latent_sparse_decode_attention`` in a trace, and ``walk_is_cheaper`` says
  which a call's rows take:
  ``sparse_decode_attention``, the gather form: ``select``'s positions, the
  chosen rows gathered out of the latent pool into ``[B, topk, Dk]`` and the
  latent decode kernel over that as a dense table of its own (a sort of the
  table's width and ``topk`` row gathers a bucket row, whatever its length);
  ``masked_decode_attention``, the walk: ``keep_topk``'s mask as one more
  condition of the latent decode kernel's walk over the row's own pages (no
  sort, no gather; what a live row's length costs). Each has its XLA twin
  (the walk's is ``latent_decode_attention_xla(keep=)``).
- ``prefill_keep``: a prefill chunk's choice as a mask ``[B, T, W*bs]`` for
  ``latent_prefill_attention(keep=)``: the scores a head at a time into float32
  ``[T, W*bs]``, each query's ``topk``-th highest by a search over the bits
  (32 counting passes, no sort), ties taken lowest position first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.paged_attention import (
    NEG_INF,
    _gather_pages,
    _page_fetch,
    _paged_attention_mq,
)

# Index-key pages a chunk of the score kernel fetches: 32 pages of 32 tokens are
# 1,024 keys = 256 KiB of bf16, and the chunk's float32 scores [64, 1024] as much.
_INDEX_PAGES_PER_CHUNK = 32


def index_scores_xla(q_idx, w, ikeys, layer_idx, block_tables, lengths):
    """q_idx [B, Hi, di], w [B, Hi] float32, ikeys [Lf, N, bs, di] → [B, W*bs]
    float32 scores, ``NEG_INF`` at and past ``lengths``. Gathers the table's width."""
    B = q_idx.shape[0]
    k = _gather_pages(ikeys, layer_idx, block_tables).reshape(B, -1, ikeys.shape[3])
    s = jnp.einsum("bhd,bcd->bhc", q_idx, k, preferred_element_type=jnp.float32)
    s = jnp.einsum("bh,bhc->bc", w, jnp.maximum(s, 0.0))
    ctx = jnp.arange(k.shape[1], dtype=jnp.int32)
    return jnp.where(ctx[None, :] < lengths[:, None], s, NEG_INF)


def _index_kernel(layer_ref, len_ref, tables_ref, q_ref, w_ref, k_hbm, o_ref, kbuf, slot_ref, sem,
                  *, pages_per_chunk: int):
    """One grid step a row: its index-key pages in chunks of P, double
    buffered; a chunk's scores are one product of the row's heads against the
    landed keys, a relu, and the heads' weighted sum."""
    P = pages_per_chunk
    b = pl.program_id(0)
    bs, di = kbuf.shape[2:]
    CH = P * bs
    length = len_ref[b]
    nchunks = lax.div(length + CH - 1, CH)
    o_ref[...] = jnp.full_like(o_ref, NEG_INF)
    issue, wait = _page_fetch(layer_ref[0], tables_ref, k_hbm, kbuf, sem, P, unroll=False)

    def chunk_pages(c):
        return jnp.minimum(lax.div(length - c * CH + bs - 1, bs), P)

    @pl.when(nchunks > 0)
    def _():
        slot_ref[0] = 0
        issue(b, 0, 0, chunk_pages(0))

        def chunk(c, carry):
            cur = slot_ref[0]
            nxt = 1 - cur
            slot_ref[0] = nxt

            @pl.when(c + 1 < nchunks)
            def _():
                issue(b, c + 1, nxt, chunk_pages(c + 1))

            wait(cur, chunk_pages(c))
            k = kbuf[cur].reshape(CH, di)
            s = lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)           # [Hi, CH]
            s = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0, keepdims=True)  # [1, CH]
            pos = c * CH + lax.broadcasted_iota(jnp.int32, (1, CH), 1)
            # Pages past the row's length were not fetched (garbage, maybe NaN).
            o_ref[0, :, pl.ds(pl.multiple_of(c * CH, CH), CH)] = jnp.where(pos < length, s, NEG_INF)
            return carry

        lax.fori_loop(0, nchunks, chunk, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_scores(q_idx, w, ikeys, layer_idx, block_tables, lengths, *, interpret: bool = False):
    """``index_scores_xla`` as a kernel (``dsa_index_scores`` in a trace): a
    row's keys are read once out of their pages, L x index_head_dim x 2 B, and
    nothing else of the pool."""
    B, Hi, di = q_idx.shape
    bs = ikeys.shape[2]
    W = block_tables.shape[1]
    P = min(_INDEX_PAGES_PER_CHUNK, W, ikeys.shape[1])
    if W % P:
        block_tables = jnp.pad(block_tables, ((0, 0), (0, P - W % P)))
    C = block_tables.shape[1] * bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, Hi, di), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec((1, Hi, 1), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, C), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, P, bs, di), ikeys.dtype), pltpu.SMEM((1,), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        functools.partial(_index_kernel, pages_per_chunk=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, C), jnp.float32),
        interpret=interpret,
        name="dsa_index_scores",
    )(jnp.asarray(layer_idx, jnp.int32).reshape(1), jnp.asarray(lengths, jnp.int32),
      jnp.asarray(block_tables, jnp.int32), q_idx, w.astype(jnp.float32)[..., None], ikeys)
    return out[:, 0, : W * bs]


def select(scores: jax.Array, topk: int) -> jax.Array:
    """[B, C] float32 → the positions of each row's ``topk`` highest scores,
    highest first (exact; the lower position first at a tie), so a row with
    fewer than ``topk`` visible positions has them all in front of the rest.
    ``lax.top_k``: on this chip a stable sort of the whole row, 566 us at
    ``[24, 32768]``; ``keep_topk``'s counting passes (120 us) and a compaction
    of the kept positions by two levels of running counts and row gathers read
    1,296 us together: the sort is the cheaper way to a list of positions
    (PERF.md section 5, step 0, PR 47)."""
    return lax.top_k(scores, topk)[1]


def _chosen_rows(cache, layer_idx, block_tables, chosen):
    """The latent rows at positions ``chosen`` [B, k] of each row's table:
    [B, k, Dk], a row of lanes each with its own (block, slot)."""
    bs = cache.shape[2]
    blk = jnp.take_along_axis(block_tables, chosen // bs, axis=1)
    return cache[layer_idx, blk, chosen % bs]


def sparse_decode_attention_xla(q, cache, layer_idx, block_tables, chosen, counts, *,
                                value_dim: int, scale: float):
    """q [B, H, Dk] over the rows ``chosen`` [B, k] of the latent pool, the
    first ``counts`` [B] of them live → [B, H, value_dim]."""
    rows = _chosen_rows(cache, layer_idx, block_tables, chosen)
    live = jnp.arange(chosen.shape[1], dtype=jnp.int32)[None, :] < counts[:, None]
    s = jnp.einsum("bhd,bcd->bhc", q, rows).astype(jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(live[:, None, :], s, NEG_INF), axis=-1).astype(q.dtype)
    return jnp.einsum("bhc,bcv->bhv", p, rows[..., :value_dim])


@functools.partial(jax.jit, static_argnames=("value_dim", "scale", "interpret"))
def sparse_decode_attention(q, cache, layer_idx, block_tables, chosen, counts, *,
                            value_dim: int, scale: float, interpret: bool = False):
    """``sparse_decode_attention_xla`` with the latent decode kernel over the
    gathered rows (``latent_sparse_decode_attention`` in a trace): the rows
    ``[B, k, Dk]`` are a pool of ``B * k / bs`` pages of their own, a row's
    table its own run of them, and the kernel reads each once."""
    B, k = chosen.shape
    bs, Dk = cache.shape[2:]
    rows = _chosen_rows(cache, layer_idx, block_tables, chosen)
    pages = -(-k // bs)
    rows = jnp.pad(rows, ((0, 0), (0, pages * bs - k), (0, 0))).reshape(1, B * pages, bs, Dk)
    own = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
    o = _paged_attention_mq(
        q[:, None, None], rows, jnp.int32(0), own, jnp.asarray(counts, jnp.int32)[:, None], None, None,
        0, interpret, value_dim=value_dim, scale=scale, name="latent_sparse_decode_attention")
    return o[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("value_dim", "scale", "interpret"))
def masked_decode_attention(q, cache, layer_idx, block_tables, lengths, keep, *,
                            value_dim: int, scale: float, interpret: bool = False):
    """q [B, H, Dk] over the positions of each row's table that ``keep``
    [B, W*bs] marks, of the first ``lengths`` [B] → [B, H, value_dim]: the
    latent decode kernel's walk over the row's own pages
    (``latent_sparse_decode_attention`` in a trace, as the gather form is: one
    call attends a call's chosen sets either way), ``keep`` one more condition
    on a position beside the row's length: a dead row costs a grid step, a live
    one its length, the table's width nothing. Its XLA twin is
    ``latent_decode_attention_xla(keep=)``."""
    o = _paged_attention_mq(
        q[:, None, None], cache, layer_idx, block_tables, jnp.asarray(lengths, jnp.int32)[:, None], None, None,
        0, interpret, value_dim=value_dim, scale=scale, keep=keep.astype(jnp.int32),
        name="latent_sparse_decode_attention")
    return o[:, 0, 0]


# What the two forms cost a full layer's call on a v5e: step 0 of PR 48 (PERF.md
# section 5: one layer in the 32-row bucket, 8, 18, 24 and 32 rows live at 16,384,
# 24,576 and 32,768 tokens, 2,048 chosen of a 32,768-position table; device us a
# call from a profiler trace, twelve shapes). Another device's are not known: it
# takes these.
# The masked walk read 371.9 us at 131,072 live tokens and 2,789.9 at 1,048,576,
# on one line through all twelve: 26 us and 2.64 ns a token a live row holds.
WALK_NS_PER_TOKEN = 2.64
# The row gather and the latent kernel over the gathered rows read 1,726 us at 8
# live rows and 1,860 at 32, for 65,536 gathered rows either way: 25.7 ns a
# chosen row of every row of the bucket, and 5.6 us a live row; 28.4 ns a chosen
# row at a full bucket, which is where the two forms meet.
GATHER_NS_PER_CHOSEN_ROW = 28.4
# ``select``'s sort read 752.7 us over [32, 32768] whatever is live (0.718 ns a
# position of the table a bucket row) and ``keep_topk`` 162.6 (0.155): the
# walk's choice is taken off the gather form's here, so the rule has one term a side.
SORT_NS_PER_POSITION = 0.563


def walk_is_cheaper(lengths, B: int, width: int, topk: int):
    """Whether a call's rows (``lengths`` [B], 0 a dead row of the bucket's
    ``B``; a table ``width`` positions wide) attend their chosen sets cheaper by
    the walk than by the gather. The walk pays for the tokens its live rows
    hold; the gather form pays ``topk`` row gathers and a sort of the table's
    width for every row of the bucket, live or not, and nothing for length.
    Plain arithmetic on ``lengths.sum()`` against a static whole number, so a
    traced int32 array and the host's numpy array of the same lengths give the
    same answer."""
    gather_ns = B * (topk * GATHER_NS_PER_CHOSEN_ROW + width * SORT_NS_PER_POSITION)
    return lengths.sum() < min(int(gather_ns / WALK_NS_PER_TOKEN), 2 ** 31 - 1)


def _sortable(x: jax.Array) -> jax.Array:
    """float32 → uint32 whose order is the floats' (no NaN among them)."""
    i = lax.bitcast_convert_type(x, jnp.int32)
    i = i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))
    return lax.bitcast_convert_type(i, jnp.uint32) ^ jnp.uint32(0x80000000)


def _kth_key(keys: jax.Array, k: int) -> jax.Array:
    """``_sortable`` keys [..., C] → each row's k-th highest [..., 1], exact, by
    a search over the key's 32 bits: a bit stays set where at least k keys reach
    the candidate."""

    def bit(i, got):
        cand = got | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(keys >= cand, axis=-1, keepdims=True, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, got)

    return lax.fori_loop(0, 32, bit, jnp.zeros((*keys.shape[:-1], 1), jnp.uint32))


def keep_topk(scores: jax.Array, k: int) -> jax.Array:
    """[..., C] float32 → bool: a row's k highest scores, ties at the k-th
    taken lowest position first (``lax.top_k``'s set). A row's ``NEG_INF``
    entries may come out kept where fewer than k are above them: the attend's
    causal mask is what hides those."""
    keys = _sortable(scores)
    kth = _kth_key(keys, k)
    above, ties = keys > kth, keys == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    return above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room))


def prefill_scores(q_idx, w, keys, horizon):
    """q_idx [T, Hi, di], w [T, Hi] float32, keys [C, di], horizon [T] (a query
    sees positions under it) → float32 [T, C], a head at a time: never
    ``[Hi, T, C]``."""
    T, Hi, _ = q_idx.shape

    def head(h, acc):
        s = jnp.einsum("td,cd->tc", q_idx[:, h], keys, preferred_element_type=jnp.float32)
        return acc + jnp.maximum(s, 0.0) * w[:, h, None]

    s = lax.fori_loop(0, Hi, head, jnp.zeros((T, keys.shape[0]), jnp.float32))
    return jnp.where(jnp.arange(keys.shape[0], dtype=jnp.int32)[None, :] < horizon[:, None], s, NEG_INF)


def prefill_keep(q_idx, w, ikeys, layer_idx, block_tables, start_pos, true_len, topk: int, dtype):
    """A prefill's choice: q_idx [B, T, Hi, di], w [B, T, Hi] → ``dtype``
    [B, T, W*bs], 1 where query t of row b attends that position of its table
    (the chunk's own keys already in their pages)."""
    B, T = q_idx.shape[:2]
    keys = _gather_pages(ikeys, layer_idx, block_tables).reshape(B, -1, ikeys.shape[3])
    horizon = jnp.minimum(start_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None] + 1, true_len[:, None])
    return lax.map(lambda a: keep_topk(prefill_scores(*a), topk).astype(dtype), (q_idx, w, keys, horizon))
