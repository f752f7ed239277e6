"""Paged attention, decode and prefill: Pallas TPU kernels + XLA references.

The role vLLM's paged-attention CUDA kernels play for the reference
(reference: components/backends/vllm/src/dynamo/vllm/main.py:90 delegates
to vLLM's engine; its CUDA kernels are the analogue of this file).

Why a kernel at all: the XLA formulation gathers the full (bucketed)
block-table width `W*bs` out of the page pool per layer per step —
~3x HBM traffic on padded context (materialize + re-read) regardless of
each sequence's true length. The kernel instead walks each row's actual
pages: ONE DMA a page (a page is a block's keys and then its values,
contiguous ``[2, bs, KVH*hd]`` in the cache layout ``[L, N, 2, bs,
KVH*hd]``; a latent page is ``[bs, Dk]``), online-softmax accumulation,
work proportional to ``sum(lengths)`` rather than ``B*W*bs``.

Design notes (measured on a v5e by PR 31, PERF.md section 6; the kernel
alone at the benchmark cells' call shapes is ``chip_smoke.py``'s kernel
phase):

- The FULL cache ``[L, N, 2, bs, KVH*hd]`` stays in HBM (`pl.ANY`) in its
  native dense layout (a [.., KVH, hd] layout with the heads apart forced a
  whole-cache relayout copy per pallas_call — ~9ms/layer measured on v5e,
  the reason the cache is stored heads-merged; the page's ``(bs, KVH*hd)``
  tiles are what the kernels hold in VMEM, so the part axis ahead of them
  costs no padded sublane and no lane shuffle at head size 64). The layer
  index is a scalar-prefetch operand, so no layer of the pool is ever
  sliced out. The gather path (``gather_kv_pages``: prefill's prefix read
  and the XLA decode and spec-verify below) no longer slices one either:
  it gathers pages from the stacked pool by (layer, page), each page once
  for its K and its V. Until PR 28 it took a ``dynamic_slice`` of the
  layer first, a copy of all N pages of it.
- Grid ``(B,)``: one step a row, and inside it a loop over the row's own
  chunks of P pages. A padding row is one empty step and the table's
  width costs nothing. (Until PR 31 the grid was ``(B, W // P)``: 512
  steps for the 46 live rows of the sessions cell, 321 of them dead at
  0.055 us each.) Software pipelining across the whole walk: every chunk
  starts the DMAs of the next live chunk (double-buffered), which at a
  row's end is chunk 0 of the next non-empty row, so page fetch overlaps
  compute across rows, not just within a row.
- **Block-diagonal q**: per-head lane slices of the KV buffer relayout
  on every access (hd=64 is sub-lane-tile) and measured ~15us/chunk.
  Instead the caller bakes q into a block-diagonal matrix
  ``[KVH*G, KVH*hd]``, a row a query column, so ONE MXU op yields all
  heads' scores ``[KVH*G, P*bs]`` with the context along the lanes
  (``q k^T``, the chunk as transposed right operand, which the MXU takes
  as it is); the online softmax reduces along the lanes, the accumulator
  is ``[KVH*G, KVH*hd]`` (``p v``) and every correction a column
  broadcast; the per-head diagonal is extracted by XLA afterwards. No
  product takes a transposed LEFT operand: the form before PR 31
  (scores ``[P*bs, H]``, accumulator ``v^T p``) made Mosaic transpose
  the whole ``[512, 512]`` chunk of V in every chunk (``tpu.transpose``
  in ``--xla_mosaic_dump_to``'s output; 6.6% of a call), ran its softmax
  over 28 of 128 lanes, and masked V over the whole chunk where only a
  row's last chunk has a tail.
- **What a call costs is its pages' transfer plus the core's own time, and
  nothing hides the second.** With the compute left out a sessions-shape
  call (5.4k scattered pages, 176 MB) takes 240 us, 730 GB/s, with two
  16 KB descriptors a page or with one of 32 KB: that is what the DMA
  engines carry of scattered pages of 16 to 64 KB, not a cost of starting
  them (PR 31 read it as 23 ns a descriptor; PR 46 halved the descriptors
  and it did not move). Beside it stands what the core does itself and
  cannot overlap: a DMA start, about 10 ns (5.4k fewer starts gave back
  53 us), and the compute of a 1 MiB chunk, 0.6 us. A pure delay in place
  of the compute adds its whole length; starts set between the
  sub-products of a chunk, and a third buffer slot, both made a call
  slower. So: a page is ONE descriptor (K and V side by side in one pool
  since PR 46: two pools were two), a full chunk's starts are one
  straight line (no counter, branch or table re-read between them: 64
  ``pl.when``s a chunk cost the old kernel 100 us a call), its wait one
  (a DMA semaphore counts bytes), and only a row's last chunk takes loops.
  A Qwen page (bs=16) as two 16 KB descriptors reached 553 GB/s with the
  compute beside them (67% of 819 GB/s; 318 us a sessions-shape call), as
  one of 32 KB 663 (81%; 266 us); Mistral's 64 KB pages 671 (82%; as two
  of 32 KB: 656, 80%); the 40 KB latent ones 520 (63%: 64 query heads
  make its chunk's compute twice as long). Page bytes = 2 x block_size x
  KVH x hd x 2 (bf16): a larger ``--block-size`` is the remaining lever,
  a configuration's, and worth little now (the kernel is within a tenth
  of its DMA side alone).
- A chunk is about 1 MiB of pages (P from the page's bytes, a power of
  two): 2 MiB chunks are no faster at long context and waste more on a
  row's last chunk, which is computed whole however few pages it holds.
- int8 pages keep a chunk axis on the grid around the same chunk body:
  their scales ride as per-chunk BlockSpec blocks (see
  ``_paged_attention_mq``).
- Prefill (``paged_prefill_attention``, PR 34) is what lies past the 128
  query columns of the kernel above: the query axis tiled, a grid over
  (row, query tile), the same page walk inside, and a KV head's G query
  heads stacked under one another as one left operand. It is bound by the
  MXU and the softmax's vector work, not by descriptors, so each grid step
  fetches its own first chunk and nothing is prefetched across steps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# Most pages a chunk may hold: a full chunk's DMA starts are unrolled, P of
# them, three times over in the kernel's text.
_MAX_PAGES_PER_CHUNK = 64


def resolve_attn_impl(requested: str = "auto") -> str:
    """'auto' → 'pallas' on a TPU, else 'xla'."""
    if requested != "auto":
        return requested
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def kernel_unsupported(cfg, block_size: int) -> str | None:
    """Why the compiled kernel cannot serve ``cfg`` (a ModelConfig), or
    None. These are the limits the Mosaic compiler enforces (found by
    compiling for v5e, tests/test_ops_tpu_lowering.py); interpret mode
    checks none of them, so the engine asks here at start instead of
    meeting a compiler error inside its first decode."""
    if cfg.kv_size % 128:
        return (
            f"a page row is num_kv_heads*head_dim = {cfg.kv_size} lanes; "
            f"page DMAs need a multiple of 128"
        )
    if cfg.num_heads > 128:
        return f"{cfg.num_heads} query heads > 128 lanes; shard heads (tp) first"
    if block_size < 4:
        return f"block_size {block_size} < 4: a page is under one int8 DMA tile"
    return None


def spec_kernel_fits(num_heads: int, positions: int) -> bool:
    """True when ``positions`` query positions per row fit the kernel's
    128 query columns (KVH*T*G = num_heads*T); the fused spec-verify
    takes the XLA gather beyond that."""
    return num_heads * positions <= 128


# ---------------------------------------------------------------------------
# XLA reference implementation (also the CPU / multi-device path)
# ---------------------------------------------------------------------------


def _gather_pages(pool: jax.Array, layer_idx: jax.Array, block_tables: jax.Array) -> jax.Array:
    """A batch's pages of one layer straight out of a stacked pool
    ``[L, N, *page]`` → ``[B, W, *page]``. No array of one layer of the pool
    is formed on the way: slicing the layer out first copied all N pages of
    it (84 MB at 5,120 blocks, 102 us at the HBM roofline) to read a few
    hundred. The pool is viewed as ``[L*N, *page]``, a bitcast of its dense
    layout, with one index a page: ``pool[layer_idx, block_tables]`` is
    copy-free too, but packs a two-component index vector every layer
    (5.27 against 4.83 ms for 28 layers of K and V on v5e, PERF.md, PR 28)."""
    L, N = pool.shape[:2]
    return pool.reshape(L * N, *pool.shape[2:])[layer_idx * N + block_tables]


def _dequant(pages: jax.Array, scale: jax.Array | None, layer_idx, block_tables, dtype):
    """pages [B, W*bs, KVH, hd] as stored → ``dtype``, by the pool of scales
    ``[L, N, bs, KVH]`` where the storage is int8. Both indices at once: the
    chip lays the scales' KVH-wide rows out position-minor, so the flat view
    is no bitcast there (it compiles to a relayout copy of the whole scale
    pool in every layer). Dequantize in f32 and round ONCE into ``dtype``:
    multiplying in bf16 would read the same stored byte back as a different
    value than the Pallas kernel and the host adapters (which also widen to
    f32), breaking cross-path consistency for the same block."""
    if scale is None:
        return pages
    sc = scale[layer_idx, block_tables].reshape(*pages.shape[:3])
    return (pages.astype(jnp.float32) * sc[..., None]).astype(dtype)


def gather_kv_pages(
    kv_cache: jax.Array,        # [L, N, 2, bs, KVH*hd] — a page is K then V
    k_scale: jax.Array | None,  # [L, N, bs, KVH] fp32 | None
    v_scale: jax.Array | None,
    layer_idx: jax.Array,       # scalar int32
    block_tables: jax.Array,    # [B, W] int32
    KVH: int, hd: int, dtype,
):
    """The one way the XLA paths read K and V pages: each page gathered ONCE
    (one index a page moves its K and its V) and split afterwards → (k, v),
    each [B, W*bs, KVH, hd] in ``dtype``; int8 storage dequantizes with the
    per-position-per-head scales in the same fused expression, so the
    int8→float convert rides the gather output and that copy stays half the
    bf16 path's bytes."""
    B, W = block_tables.shape
    pages = _gather_pages(kv_cache, layer_idx, block_tables)      # [B, W, 2, bs, KVH*hd]
    bs = kv_cache.shape[3]
    return tuple(
        _dequant(pages[:, :, i].reshape(B, W * bs, KVH, hd), scale, layer_idx, block_tables, dtype)
        for i, scale in enumerate((k_scale, v_scale))
    )


def paged_decode_attention_xla(
    q: jax.Array,            # [B, KVH, G, hd]
    kv_cache: jax.Array,     # [L, N, 2, bs, KVH*hd]
    layer_idx: jax.Array,    # scalar int32
    block_tables: jax.Array, # [B, W] int32
    lengths: jax.Array,      # [B] int32 — attend positions [0, length)
    k_scale: jax.Array | None = None,  # [L, N, bs, KVH] fp32 — int8 cache only
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Gather-based formulation (the r3 path, hoisted here).  With
    ``k_scale``/``v_scale`` the cache holds int8 pages and the gather
    dequantizes in the same fused expression.  Returns [B, KVH, G, hd]
    in q.dtype."""
    B, KVH, G, hd = q.shape
    pk, pv = gather_kv_pages(kv_cache, k_scale, v_scale, layer_idx, block_tables, KVH, hd, q.dtype)
    scale = hd ** -0.5
    ctx = jnp.arange(pk.shape[1], dtype=jnp.int32)
    mask = jnp.where(ctx[None, :] < lengths[:, None], 0.0, jnp.float32(NEG_INF))
    s = jnp.einsum("bkgh,bckh->bkgc", q, pk).astype(jnp.float32) * scale
    s = s + mask[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgc,bckh->bkgh", p, pv)


def paged_spec_attention_xla(
    q: jax.Array,            # [B, T, KVH, G, hd] — T consecutive query positions
    kv_cache: jax.Array,     # [L, N, 2, bs, KVH*hd]
    layer_idx: jax.Array,    # scalar int32
    block_tables: jax.Array, # [B, W] int32
    lengths: jax.Array,      # [B, T] int32 — query t attends [0, lengths[b, t])
    k_scale: jax.Array | None = None,  # [L, N, bs, KVH] fp32 — int8 cache only
    v_scale: jax.Array | None = None,
    anc: jax.Array | None = None,  # [B, T, T] — tree topology mask (below)
) -> jax.Array:
    """Multi-query generalization of ``paged_decode_attention_xla`` for
    the speculative verify pass: T consecutive positions per row attend
    their own causal prefix out of the SAME gathered pages (one gather
    per layer for all T queries — the single-pass shape that lets a
    verify step score draft_len+1 logit rows in one weight stream).
    T=1 reduces exactly to the decode formulation, so CPU/XLA greedy
    byte-identity between the spec and dense paths holds by construction.
    With scales the gathered pages dequantize in the same expression.

    **Tree mode** (``anc`` given): the T in-flight rows form a draft
    TREE. Node j's KV is written at slot position ``hist + j``, where
    ``hist`` is the row's paged-history horizon — ``lengths[b, t]``
    carries that per-query horizon (the caller passes positions0 for
    every live query, 0 for dead ones).  Query t attends ``[0, hist)``
    paged history PLUS exactly the in-flight slots s with
    ``anc[b, t, s]`` nonzero — its ancestor-or-self set.  The linear draft is the special case
    ``anc[t, s] = (s <= t)`` with ``lengths[b, t] = hist`` (equivalent
    to the non-tree call with ``lengths[b, t] = hist + t + 1``), so the
    tree mask is a strict generalization of the causal ramp.
    Returns [B, T, KVH, G, hd] in q.dtype. (``paged_spec_attention`` is
    the Pallas upgrade: the gather+dequant happen in-register, no
    materialized relayout copy.)"""
    B, T, KVH, G, hd = q.shape
    pk, pv = gather_kv_pages(kv_cache, k_scale, v_scale, layer_idx, block_tables, KVH, hd, q.dtype)
    scale = hd ** -0.5
    ctx = jnp.arange(pk.shape[1], dtype=jnp.int32)
    hist_mask = ctx[None, None, :] < lengths[:, :, None]    # [B, T, W*bs]
    if anc is None:
        attend = hist_mask
    else:
        # Tree: slot s of the in-flight rows lives at paged position
        # hist + s; gather the per-query ancestor bit for positions in
        # the slot window.
        slot = ctx[None, None, :] - lengths[:, :, None]     # [B, T, C]
        in_window = (slot >= 0) & (slot < T)
        anc_g = jnp.take_along_axis(
            (anc != 0), jnp.clip(slot, 0, T - 1), axis=2
        )                                                   # [B, T, C]
        attend = hist_mask | (in_window & anc_g)
    mask = jnp.where(attend, 0.0, jnp.float32(NEG_INF))
    s = jnp.einsum("btkgh,bckh->btkgc", q, pk).astype(jnp.float32) * scale
    s = s + mask[:, :, None, None, :]
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("btkgc,bckh->btkgh", p, pv)


# ---------------------------------------------------------------------------
# Pallas TPU kernel — ONE multi-query kernel for both consumers.
#
# Decode is the T=1 case; the speculative verify pass runs T = S+1 query
# positions per row through the SAME kernel (the "fused gather": the
# [last, d1..dS] rows attend straight out of the page pool — no
# materialized `layer_k[block_tables]` relayout copy, which costs
# ~9ms/layer at 8B geometry, the header's XLA gather tax). With int8
# cache storage the per-page DMAs move HALF the bytes and the dequant
# happens in-register right after the page lands in VMEM, using
# per-position-per-head scales prefetched per row block.
# ---------------------------------------------------------------------------


def _page_fetch(layer, tables_ref, pool, buf, sem, P: int, unroll: bool = True):
    """→ (issue, wait): the page DMAs a kernel walks a row's table with.
    ``pool`` is the stacked pool in HBM ``[L, N, *page]``, a page contiguous
    there (K then V ``[2, bs, lanes]``, or a latent ``[bs, lanes]``), ``buf``
    its double buffer ``[2, P, *page]``, ``sem`` a DMA semaphore a slot. A
    page is ONE descriptor whatever it holds; a chunk is P consecutive
    entries of a row's table. ``unroll`` False keeps every start and wait in
    a loop: some 7 ns a descriptor slower (PERF.md, PR 31), which only a
    kernel bound by its descriptors feels, and a kernel's text without P
    starts a call site."""

    def page_copy(page, slot, p):
        """The DMA descriptor of pool page ``page`` into place p of buffer ``slot``."""
        return pltpu.make_async_copy(pool.at[layer, page], buf.at[slot, p], sem.at[slot])

    def issue(row, chunk, slot, npages=None):
        """Start the copies of ``npages`` pages of (row, chunk) into buffer
        ``slot``. A chunk that holds P pages (``npages`` None: known to
        when traced) starts them in one straight line, with no counter or
        branch between the descriptors; a short one starts what it holds
        in a loop."""
        def start(p, carry=0):
            page_copy(tables_ref[row, chunk * P + p], slot, p).start()
            return carry

        def unrolled():
            for p in range(P):
                start(p)

        def rolled():
            lax.fori_loop(0, npages, start, 0)

        if npages is None:
            unrolled()
        elif not unroll:
            rolled()
        else:
            pl.when(npages == P)(unrolled)
            pl.when(npages < P)(rolled)

    def wait(slot, npages=None):
        """Wait for the copies ``issue`` started. A DMA semaphore counts
        bytes: a full chunk (``npages`` None: known to be) is one wait for
        all P pages' bytes, a short one a page-sized wait a page."""
        def whole():
            pltpu.make_async_copy(pool.at[layer, pl.ds(0, P)], buf.at[slot], sem.at[slot]).wait()

        def paged():
            def one(p, carry):
                page_copy(0, slot, 0).wait()  # any page: its bytes count
                return carry

            lax.fori_loop(0, npages, one, 0)

        if npages is None:
            whole()
        elif not unroll:
            paged()
        else:
            pl.when(npages == P)(whole)
            pl.when(npages < P)(paged)

    return issue, wait


def _mq_kernel(
    # scalar prefetch
    layer_ref,    # [1] int32
    rowlen_ref,   # [B] int32 — max attend length per row (chunk walk bound)
    nextrow_ref,  # [B] int32 — the first non-empty row after b (B if none)
    tables_ref,   # [B, W] int32
    # operands (anc present only in tree mode; keep when ``kept``; kscale/vscale when quantized)
    *refs,
    # static
    pages_per_chunk: int,
    head_dim: int,
    quantized: bool,
    tree_slots: int = 0,
    value_dim: int = 0,
    window: int = 0,
    kept: bool = False,
):
    # One pool either way, and a page of it one DMA descriptor. value_dim 0:
    # a page is K then V, ``[2, bs, KVH*hd]``. value_dim > 0: a latent (MLA)
    # page ``[bs, Dk]``, a row the shared key, whose first ``value_dim`` lanes
    # are also the value.
    refs = list(refs)
    q_ref, lenvec_ref = refs[:2]
    refs = refs[2:]
    anc_ref = keep_ref = kscale_ref = vscale_ref = ml_scr = None
    if tree_slots:
        anc_ref, refs = refs[0], refs[1:]
    if kept:
        keep_ref, refs = refs[0], refs[1:]
    if quantized:
        kscale_ref, vscale_ref, *refs, ml_scr = refs
    kv_hbm, o_ref, kvbuf, acc_scr, slot_ref, started_ref, sem = refs
    # q_ref      VMEM [1, H, KVH*hd] — block-diag q, softmax scale folded in:
    #            one ROW a query column (k, t, g), zero outside head k's lanes
    # lenvec_ref VMEM [1, H, 1] int32 — per query column attend length; in
    #            tree mode the per-column HISTORY horizon (slots ride on top)
    # anc_ref    VMEM [1, H, T] int32 — tree mode: anc[col, s] = query col
    #            may attend in-flight slot s (its ancestor-or-self set)
    # keep_ref   VMEM [1, 1, W*bs] — nonzero: the row's columns attend that
    #            position of its table (a chosen set), read a chunk at a time
    # kscale_ref VMEM [1, P, bs, KVH] f32 — this chunk's per-position-per-head scales
    # kv_hbm     ANY  [L, N, 2, bs, KVH*hd] ([2L, N, bs, Dk] latent)
    # o_ref      VMEM [1, H, Dv] — attention out, every head's lanes a row
    # kvbuf      VMEM [2, P, 2, bs, KVH*hd] (cache dtype; int8 when quantized;
    #            [2, P, bs, Dk] latent)
    # acc        VMEM [H, Dv] f32
    # slot/started SMEM [1] int32; sem DMA sems [2 slots]
    # ml_scr     VMEM [2, H, 1] f32 — int8 only: max and sum between grid steps
    P = pages_per_chunk
    b = pl.program_id(0)
    B = pl.num_programs(0)
    layer = layer_ref[0]
    bs, D = kvbuf.shape[-2:]  # D = KVH*hd
    H = q_ref.shape[1]      # query columns KVH*T*G, padded to whole tiles
    hd = head_dim
    KVH = D // hd
    CH = P * bs             # tokens per chunk

    length = rowlen_ref[b]
    nchunks = lax.div(length + CH - 1, CH)

    @pl.when((b == 0) & (pl.program_id(1) == 0) if quantized else b == 0)
    def _init_globals():
        slot_ref[0] = 0
        started_ref[0] = 0

    def chunk_pages(row, chunk):
        """Pages of (row, chunk) that hold tokens: P but in a row's last."""
        rem = rowlen_ref[row] - chunk * CH
        return jnp.minimum(lax.div(rem + bs - 1, bs), P)

    issue, wait = _page_fetch(layer, tables_ref, kv_hbm, kvbuf, sem, P)
    # Where page p of buffer ``cur`` keeps its value: V's part, or the latent
    # page itself.
    value_at = (lambda cur, p: (cur, p)) if value_dim else (lambda cur, p: (cur, p, 1))

    def successor(row, ch):
        """The live chunk after (row, ch) in the walk; row B: none."""
        more = (ch + 1) * CH < rowlen_ref[row]
        return jnp.where(more, row, nextrow_ref[row]), jnp.where(more, ch + 1, 0)

    def begin_row():
        # Global warmup: the very first live row has no predecessor.
        @pl.when(started_ref[0] == 0)
        def _():
            issue(b, 0, slot_ref[0], chunk_pages(b, 0))
            started_ref[0] = 1

        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(c, cur, m_prev, l_prev):
        """The chunk in buffer ``cur`` into the online softmax."""
        if value_dim:
            k_chunk = kvbuf[cur].reshape(CH, D)
            v_chunk = k_chunk[:, :value_dim]
        else:
            k_chunk = kvbuf[cur, :, 0].reshape(CH, D)
            v_chunk = kvbuf[cur, :, 1].reshape(CH, D)
        if quantized:
            # In-register dequant of the just-landed int8 pages: expand
            # this chunk's [CH, KVH] scales across each head's lanes and
            # multiply — the DMA moved half the bytes, the float page
            # never exists outside VMEM. The scales are flattened to 2D
            # first: Mosaic lowers the [CH, KVH, hd] -> [CH, D] merge at
            # hd=64 and hd=128, but not the 4D form of the same cast.
            def lane_scales(sc_ref):
                sc = sc_ref[0].reshape(CH, KVH)
                return jnp.broadcast_to(
                    sc[..., None], (CH, KVH, hd)
                ).reshape(CH, D)

            k_chunk = (
                k_chunk.astype(jnp.float32) * lane_scales(kscale_ref)
            ).astype(q_ref.dtype)
            v_chunk = (
                v_chunk.astype(jnp.float32) * lane_scales(vscale_ref)
            ).astype(q_ref.dtype)
            held = c * CH + lax.broadcasted_iota(jnp.int32, (CH, 1), 0) < length
            v_chunk = jnp.where(held, v_chunk, 0)

        # All heads' scores in one MXU op via the block-diagonal q, the
        # chunk as the transposed right operand (which the MXU takes as it
        # is): context positions lie along the lanes.
        s = lax.dot_general(
            q_ref[0], k_chunk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [H, CH]
        # Per-COLUMN causal horizon: column (k, t, g) attends positions
        # [0, lengths[b, t]) — for decode (T=1) every column carries the
        # row length and this is exactly the row mask. Tree mode adds the
        # topology bits: in-flight slot s_i sits at paged position
        # hist + s_i and column t attends it only when anc[col, s_i] is
        # set (T compares on the VPU, T is small).
        lenvec = lenvec_ref[0]                             # [H, 1]
        pos = c * CH + lax.broadcasted_iota(jnp.int32, (1, CH), 1)
        att = pos < lenvec
        if window:  # a column attends its last ``window`` positions, its own among them
            att = att & (pos >= lenvec - window)
        if tree_slots:
            anc = anc_ref[0]                               # [H, T]
            for s_i in range(tree_slots):
                att = att | ((pos == lenvec + s_i) & (anc[:, s_i:s_i + 1] != 0))
        if kept:  # of its horizon, a column attends the positions the operand marks
            att = att & (keep_ref[0, :, pl.ds(pl.multiple_of(c * CH, CH), CH)] != 0)
        s = jnp.where(att, s, NEG_INF)

        m_cur = jnp.max(s, axis=1, keepdims=True)          # [H, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)                     # [H, 1]
        p = jnp.exp(s - m_new)                             # [H, CH]
        l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(
            p.astype(v_chunk.dtype), v_chunk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [H, Dv]
        acc_scr[...] = acc_scr[...] * corr + pv
        return m_new, l_new

    def chunk(c, m_prev, l_prev):
        """Chunk c of row b: up to P pages into the online softmax.
        m, l: [H, 1] f32, the running max and sum. Software pipeline: the
        next chunk's pages are started before this one's are waited for."""
        cur = slot_ref[0]
        nxt = 1 - cur
        slot_ref[0] = nxt
        after = length - (c + 1) * CH      # the row's tokens past this chunk

        def steady():
            # A full chunk whose successor is a full chunk of the same row:
            # P starts, one wait and the compute in one straight line.
            issue(b, c + 1, nxt)
            wait(cur)
            return attend(c, cur, m_prev, l_prev)

        def edge():
            # Near a row's end the successor is a short last chunk, or
            # chunk 0 of the next non-empty row, so the fetch overlaps
            # compute across rows too.
            row, ch = successor(b, c)
            @pl.when(row < B)
            def _():
                issue(row, ch, nxt, chunk_pages(row, ch))

            npages = chunk_pages(b, c)
            wait(cur, npages)
            # Unfetched tail pages hold garbage (possibly NaN): k is
            # neutralized by the score mask, v must be zero (0*NaN=NaN).
            # Only a row's last chunk has a tail: clear it in the buffer,
            # whole pages, and in the last fetched page the positions past
            # the row's length. (A latent page is its own value. int8 pages
            # are cleared once they are dequantized: their scales are
            # garbage there too.)
            if not quantized:
                @pl.when(after < 0)
                def _():
                    def clear(p, carry):
                        kvbuf[value_at(cur, p)] = jnp.zeros((bs, D), kvbuf.dtype)
                        return carry

                    lax.fori_loop(npages, P, clear, 0)
                    held = length - c * CH - (npages - 1) * bs  # tokens in the last page
                    row = lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
                    last = value_at(cur, npages - 1)
                    page = kvbuf[last]
                    kvbuf[last] = jnp.where(row < held, page, jnp.zeros_like(page))

            return attend(c, cur, m_prev, l_prev)

        return lax.cond(after >= CH, steady, edge)

    def emit(l):
        # Row done → normalize and emit (XLA takes the per-head diagonal
        # outside).
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    def zero_out():
        # Keep padding rows' output defined (their stale block is otherwise
        # flushed as-is; harmless numerically but keep it clean).
        o_ref[0] = jnp.zeros_like(o_ref[0])

    ml0 = (jnp.full((H, 1), NEG_INF, jnp.float32), jnp.zeros((H, 1), jnp.float32))
    if quantized:
        # int8 pages: the chunk axis stays on the grid, because a chunk's
        # scales ride as a BlockSpec block that the chunk index picks (a
        # manual DMA of the slab is refused, see _paged_attention_mq), and
        # max and sum live in scratch between the steps. Steps past a row's
        # last chunk skip DMA and compute.
        c = pl.program_id(1)

        @pl.when((c == 0) & (nchunks == 0))
        def _():
            zero_out()

        @pl.when(c < nchunks)
        def _():
            @pl.when(c == 0)
            def _():
                begin_row()
                ml_scr[0], ml_scr[1] = ml0

            m, l = chunk(c, ml_scr[0], ml_scr[1])
            ml_scr[0], ml_scr[1] = m, l

            @pl.when(c == nchunks - 1)
            def _():
                emit(l)
    else:
        # One grid step a row, and inside it a loop over the row's OWN
        # chunks. Its bound is read before the loop, so interpret mode can
        # discharge it (a while whose condition reads a ref has no
        # discharge rule).
        pl.when(nchunks == 0)(zero_out)

        @pl.when(nchunks > 0)
        def _():
            begin_row()
            _, l = lax.fori_loop(0, nchunks, lambda c, ml: chunk(c, *ml), ml0)
            emit(l)


def _paged_attention_mq(
    q: jax.Array,            # [B, T, KVH, G, hd]
    kv_cache: jax.Array,     # [L, N, 2, bs, KVH*hd] — dense pages, no per-call
                             #   layout conversion ([2L, N, bs, Dk] latent)
    layer_idx: jax.Array,    # scalar int32
    block_tables: jax.Array, # [B, W] int32
    lengths: jax.Array,      # [B, T] int32
    k_scale: jax.Array | None,  # [L, N, bs, KVH] fp32 | None
    v_scale: jax.Array | None,
    pages_per_chunk: int,
    interpret: bool,
    anc: jax.Array | None = None,  # [B, T, T] — tree topology mask
    *,
    value_dim: int = 0,            # latent pool: V = a row's first lanes
    scale: float | None = None,    # softmax scale (default hd ** -0.5)
    window: int = 0,               # > 0: a query attends its last ``window`` positions only
    keep: jax.Array | None = None, # [B, W*bs]: nonzero where the row's queries attend that position
    name: str | None = None,       # the kernel's name in a trace (default: the caller's jit)
) -> jax.Array:
    """Shared Pallas driver: T query positions per row walk the row's
    true pages once. Returns [B, T, KVH, G, hd] in q.dtype
    ([B, T, 1, G, value_dim] over a latent pool). ``keep`` narrows every
    horizon to the positions it marks (a chosen set): the walk stays that of
    the row's length, a masked position's weight is exactly 0."""
    B, T, KVH, G, hd = q.shape
    page = kv_cache.shape[2:]  # (2, bs, KVH*hd), or a latent (bs, Dk)
    bs = page[-2]
    assert page == ((bs, hd) if value_dim else (2, bs, KVH * hd)), (
        f"cache must be [L, N, 2, bs, KVH*hd] (latent [2L, N, bs, Dk]), not {kv_cache.shape}")
    W = block_tables.shape[1]
    H = KVH * T * G
    if H > 128:
        raise NotImplementedError(
            f"{H} query columns (KVH*T*G) > 128 lanes; shard heads (tp) "
            f"or fall back to the XLA gather path"
        )
    quantized = k_scale is not None
    # A chunk of about 1 MiB of pages, a power of two of them: what a chunk
    # costs beside its bytes (a loop iteration, a softmax update) is small
    # against their transfer, and a row's last chunk, computed whole
    # however few pages it holds, wastes little (PERF.md, PR 31).
    page_bytes = math.prod(page) * kv_cache.dtype.itemsize
    P = pages_per_chunk or min(1 << max(0, ((1 << 20) // page_bytes).bit_length() - 1),
                               _MAX_PAGES_PER_CHUNK)
    P = min(P, W, kv_cache.shape[1])  # no larger than the table, or the pool
    if W % P:  # pad the table so chunks tile it exactly
        block_tables = jnp.pad(block_tables, ((0, 0), (0, P - W % P)))

    # Block-diagonal q with the softmax scale folded in, a row a query
    # column: qbd[b, k*(T*G)+t*G+g, j*hd+h] = q[b,t,k,g,h] * scale * (j==k).
    # Rows are padded to whole (16, 128) tiles; a padding row attends
    # nothing and is cut off again below.
    Hp = -(-H // 16) * 16
    eye = jnp.eye(KVH, dtype=q.dtype)
    qbd = jnp.einsum(
        "btkgh,jk->bktgjh", q * (hd ** -0.5 if scale is None else scale), eye
    )
    qbd = jnp.pad(qbd.reshape(B, H, KVH * hd), ((0, 0), (0, Hp - H), (0, 0)))
    # Per-column attend horizon, same (k, t, g) order as qbd's rows.
    lengths = jnp.asarray(lengths, jnp.int32)
    lenvec = jnp.broadcast_to(
        lengths[:, None, :, None], (B, KVH, T, G)
    ).reshape(B, H, 1)
    lenvec = jnp.pad(lenvec, ((0, 0), (0, Hp - H), (0, 0)))
    rowlen = jnp.max(lengths, axis=1)  # chunk-walk bound per row
    if anc is not None:
        # Tree mode: the walk must also cover the T in-flight slots at
        # positions [hist, hist + T); rows with no live node at all
        # (anc identically zero — padding rows) stay empty so the
        # prefetch skip keeps them ~free.
        live_row = jnp.any(anc != 0, axis=(1, 2))
        rowlen = jnp.where(live_row, rowlen + T, 0)
    # The first non-empty row after each row (B if none): where a row's
    # last chunk prefetches from.
    rows = jnp.arange(B, dtype=jnp.int32)
    live_from = lax.cummin(jnp.where(rowlen > 0, rows, B), reverse=True)
    nextrow = jnp.concatenate([live_from[1:], jnp.full((1,), B, jnp.int32)])

    def row_block(*tail):
        return pl.BlockSpec((1, *tail), lambda b, *_: (b, 0, 0))

    operands = [qbd, lenvec]
    in_specs = [row_block(Hp, KVH * hd), row_block(Hp, 1)]
    if anc is not None:
        # Ancestor bits a query column [B, H, T_slot]: anc_cols[b, col, s]
        # with col = (k*T + t)*G + g — the same (k, t, g) order as qbd's
        # rows.
        anc_b = jnp.asarray(anc != 0, jnp.int32)           # [B, Tq, Ts]
        anc_cols = jnp.broadcast_to(
            anc_b[:, None, :, None, :], (B, KVH, T, G, T)
        ).reshape(B, H, T)
        operands.append(jnp.pad(anc_cols, ((0, 0), (0, Hp - H), (0, 0))))
        in_specs.append(row_block(Hp, T))
    if keep is not None:
        # A row's whole mask rides as one block of its grid step, the table's
        # padded width wide, and a chunk reads its own CH lanes of it.
        C = block_tables.shape[1] * bs
        operands.append(jnp.pad(keep, ((0, 0), (0, C - keep.shape[1])))[:, None])
        in_specs.append(row_block(1, C))
    if quantized:
        # Scales are gathered OUTSIDE the kernel ([B, W, bs, KVH] fp32 is
        # 1/head_dim the page bytes) and ride as per-CHUNK VMEM blocks, so
        # their VMEM footprint does not grow with the context: a whole
        # row's block (KVH padded to 128 lanes) ran out of VMEM from 16k
        # tokens. That block is why this variant's grid keeps a chunk axis.
        # Fetching the slab beside the pages instead, from the gathered
        # array left in HBM, Mosaic refuses: "Slice shape along dimension 3
        # must be aligned to tiling (128), but is 8" (KVH lanes).
        sk = lax.dynamic_index_in_dim(k_scale, layer_idx, 0, keepdims=False)
        sv = lax.dynamic_index_in_dim(v_scale, layer_idx, 0, keepdims=False)
        operands += [sk[block_tables], sv[block_tables]]
        in_specs += [pl.BlockSpec((1, P, bs, KVH), lambda b, c, *_: (b, c, 0, 0))] * 2
    operands.append(kv_cache)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    out_cols = value_dim or KVH * hd
    scratch = [
        pltpu.VMEM((2, P, *page), kv_cache.dtype),
        pltpu.VMEM((Hp, out_cols), jnp.float32),
        pltpu.SMEM((1,), jnp.int32),
        pltpu.SMEM((1,), jnp.int32),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    # The grid is the rows alone: a row's chunks are a loop inside its step,
    # so a padding row is one empty step and the table's width costs nothing.
    grid = (B,)
    if quantized:
        grid = (B, block_tables.shape[1] // P)
        scratch.append(pltpu.VMEM((2, Hp, 1), jnp.float32))

    kernel = functools.partial(
        _mq_kernel, pages_per_chunk=P, head_dim=hd, quantized=quantized,
        tree_slots=T if anc is not None else 0, value_dim=value_dim,
        **({"window": window} if window else {}),
        **({"kept": True} if keep is not None else {}),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=in_specs,
        out_specs=row_block(Hp, out_cols),
        scratch_shapes=scratch,
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, out_cols), q.dtype),
        interpret=interpret,
        **({"name": name} if name else {}),
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        rowlen,
        nextrow,
        jnp.asarray(block_tables, jnp.int32),
        *operands,
    )
    # [B, KVH*T*G, KVH*hd] → per-head diagonal → [B, T, KVH, G, hd].
    o6 = o[:, :H].reshape(B, KVH, T, G, KVH, out_cols // KVH)
    return jnp.einsum("bktgkh->btkgh", o6)


@functools.partial(
    jax.jit,
    static_argnames=("pages_per_chunk", "interpret"),
)
def paged_decode_attention(
    q: jax.Array,            # [B, KVH, G, hd]
    kv_cache: jax.Array,     # [L, N, 2, bs, KVH*hd]
    layer_idx: jax.Array,    # scalar int32
    block_tables: jax.Array, # [B, W] int32
    lengths: jax.Array,      # [B] int32
    k_scale: jax.Array | None = None,  # [L, N, bs, KVH] fp32 — int8 cache only
    v_scale: jax.Array | None = None,
    *,
    pages_per_chunk: int = 0,  # 0 → auto (~512 tokens per chunk)
    interpret: bool = False,
) -> jax.Array:
    B, KVH, G, hd = q.shape
    if KVH * G > 128:
        raise NotImplementedError(
            f"{KVH * G} query heads > 128 lanes; shard heads (tp) first"
        )
    o = _paged_attention_mq(
        q[:, None], kv_cache, layer_idx, block_tables,
        jnp.asarray(lengths, jnp.int32)[:, None], k_scale, v_scale,
        pages_per_chunk, interpret,
    )
    return o[:, 0]


@functools.partial(
    jax.jit,
    static_argnames=("pages_per_chunk", "interpret"),
)
def paged_spec_attention(
    q: jax.Array,            # [B, T, KVH, G, hd]
    kv_cache: jax.Array,     # [L, N, 2, bs, KVH*hd]
    layer_idx: jax.Array,    # scalar int32
    block_tables: jax.Array, # [B, W] int32
    lengths: jax.Array,      # [B, T] int32
    k_scale: jax.Array | None = None,  # [L, N, bs, KVH] fp32 — int8 cache only
    v_scale: jax.Array | None = None,
    anc: jax.Array | None = None,  # [B, T, T] — tree topology mask
    *,
    pages_per_chunk: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Fused spec-verify gather: the [last, d1..dS] multi-query rows
    attend straight out of the page pool in ONE kernel — per-page DMAs,
    in-register dequant when the cache is int8, online softmax — instead
    of the XLA path's materialized (dequantized) relayout copy of the
    whole gathered table (the ~9ms/layer tax in the module header).
    With ``anc`` the rows form a draft TREE: ``lengths`` carries each
    query's paged-history horizon and the [T, T] ancestor mask rides as
    one more per-row prefetched operand (see
    ``paged_spec_attention_xla``) — tree verify is the same
    one-weight-stream gather, just with T extra VPU compares per chunk.
    Requires KVH*T*G ≤ 128 lanes; callers fall back to
    ``paged_spec_attention_xla`` beyond that (model.spec_verify does)."""
    return _paged_attention_mq(
        q, kv_cache, layer_idx, block_tables, lengths,
        k_scale, v_scale, pages_per_chunk, interpret, anc,
    )


# ---------------------------------------------------------------------------
# Prefill: a chunk of T new positions a row, behind whatever the row's pages
# already hold. The chunk's own K and V are in their pages before attention
# runs (model.prefill_batch_impl: ``kv_write`` precedes ``attn``), so the
# kernel attends entirely out of the pages, causal by absolute position.
# ---------------------------------------------------------------------------


def paged_prefill_attention_xla(
    q: jax.Array,            # [B, T, KVH, G, hd] — positions start_pos .. start_pos+T
    k: jax.Array,            # [B, T, KVH, hd] — the chunk's own keys, as computed
    v: jax.Array,
    kv_cache: jax.Array,     # [L, N, 2, bs, KVH*hd]
    layer_idx: jax.Array,    # scalar int32
    block_tables: jax.Array, # [B, W] int32
    start_pos: jax.Array,    # [B] int32 — first position of the chunk (block-aligned)
    true_len: jax.Array,     # [B] int32 — the row's true total length (0: inactive)
    k_scale: jax.Array | None = None,  # [L, N, bs, KVH] fp32 — int8 cache only
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """The gather-based form: the table's whole width gathered dense as the
    prefix (masked past ``start_pos``), the chunk attending its own K and V
    as computed (with int8 pages only later readers see the rounding), one
    softmax over prefix and chunk together. Its scores are float32
    ``[B, T, KVH, G, W*bs + T]`` whatever the prefix is: the CPU, mesh and
    int8-KV path. Returns [B, T, KVH, G, hd] in q.dtype."""
    B, T, KVH, G, hd = q.shape
    W, bs = block_tables.shape[1], kv_cache.shape[3]
    pk, pv = gather_kv_pages(kv_cache, k_scale, v_scale, layer_idx, block_tables, KVH, hd, q.dtype)
    # Masks (fp32 additive). chunk→chunk: causal, and nothing past the row's
    # true length; chunk→prefix: every query sees all of its row's prefix.
    neg = jnp.float32(-1e9)
    sfx = jnp.arange(T, dtype=jnp.int32)
    causal = (sfx[None, :] <= sfx[:, None]).astype(jnp.float32)   # [T, T]
    valid = (start_pos[:, None] + sfx[None, :] < true_len[:, None]).astype(jnp.float32)
    mask_ss = (1.0 - causal[None] * valid[:, None, :]) * neg      # [B, T, T]
    ctx = jnp.arange(W * bs, dtype=jnp.int32)
    mask_sp = jnp.where(ctx[None, :] < start_pos[:, None], 0.0, neg)  # [B, W*bs]
    scale = hd ** -0.5
    s_p = jnp.einsum("btkgh,bckh->btkgc", q, pk).astype(jnp.float32) * scale
    s_s = jnp.einsum("btkgh,bskh->btkgs", q, k).astype(jnp.float32) * scale
    s_p = s_p + mask_sp[:, None, None, None, :]
    s_s = s_s + mask_ss[:, :, None, None, :]
    p = jax.nn.softmax(jnp.concatenate([s_p, s_s], axis=-1), axis=-1).astype(q.dtype)
    p_p, p_s = p[..., : W * bs], p[..., W * bs :]
    return (
        jnp.einsum("btkgc,bckh->btkgh", p_p, pv)
        + jnp.einsum("btkgs,bskh->btkgh", p_s, v)
    )


def resolve_prefill_impl(requested: str, cfg, block_size: int, int8_pages: bool) -> tuple[str, str]:
    """→ (prefill's attention path, why it is the XLA form where the kernel
    was asked for). ``requested`` is an ``attn_impl``; the XLA form serves
    int8 KV pages (there the chunk attends its exact values and only later
    readers see the rounding: attending out of int8 pages would be another
    result) and what the compiler refuses: ``kernel_unsupported``'s limits
    (``latent_kernel_unsupported``'s over latent pages), the page DMAs being
    the decode kernel's (head sizes 64, 128 and 256 and pages of 4 to 64
    tokens compiled for v5e, PR 34; the 640-lane latent row, PR 44)."""
    impl = resolve_attn_impl(requested)
    if impl == "xla":
        return impl, ""
    if int8_pages:
        return "xla", "int8 KV pages: the chunk attends its exact values"
    unsupported = latent_kernel_unsupported if cfg.kv_lora_rank else kernel_unsupported
    limit = unsupported(cfg, block_size) if impl == "pallas" else None
    return ("xla", limit) if limit else (impl, "")


# The prefill kernel's sizes, from the chip (PERF.md section 6, PR 34). Most
# query positions a tile holds: 128 (64 and 256 are both a sixth slower at
# T 2,048). Tokens a chunk: what a chunk costs beside its products is fixed
# a chunk and head (the two lane reductions a row of the softmax, the
# accumulator's rescale), so 1,024 tokens run 1.4-1.7 times faster than 512
# at long context; a prompt of a few hundred tokens pays about 10 us a call
# for the one wide chunk. And the VMEM its scores, statistics and buffers
# may take (Mosaic's default is 16 MiB of the 128 a v5e core has).
_PREFILL_TILE = 128
_PREFILL_CHUNK_TOKENS = 1024
_PREFILL_VMEM_BYTES = 64 << 20
_TABLE_WIDTH = 256


def _prefill_tile(T: int) -> int:
    """The query tile: the largest divisor of T that is a multiple of 16
    (a packed bf16 sublane tile) and no more than ``_PREFILL_TILE``; T
    itself where it has none."""
    fits = [t for t in range(16, min(T, _PREFILL_TILE) + 1, 16) if T % t == 0]
    return fits[-1] if fits else T


def _prefill_kernel(
    # scalar prefetch
    layer_ref,    # [1] int32
    start_ref,    # [B] int32 — the chunk's first position
    len_ref,      # [B] int32 — the row's true length
    tables_ref,   # [B, W] int32
    # operands
    q_ref,        # VMEM [1, tq, H*hd] — a tile of queries, softmax scale folded in
    kv_hbm,       # ANY  [L, N, 2, bs, KVH*hd]
    o_ref,        # VMEM [1, tq, H*hd]
    # scratch
    kvbuf,        # VMEM [2, P, 2, bs, KVH*hd] — pages as they land, K then V
    kh_scr,       # VMEM [KVH, CH, hd] — the chunk in hand, a head at a time
    vh_scr,
    qs_scr,       # VMEM [KVH, G*tq, hd] — the tile regrouped: a KV head's G query
                  # heads stacked under one another (row g*tq + t)
    acc_scr,      # VMEM [KVH, G*tq, hd] f32
    m_scr,        # VMEM [KVH, G*tq, 1] f32 — running max
    l_scr,        # VMEM [KVH, G*tq, 1] f32 — running sum
    hz_scr,       # VMEM [G*tq, 1] int32 — each query row attends [0, horizon)
    slot_ref,     # SMEM [1] int32
    sem,          # DMA semaphores [2 slots]
    *,
    pages_per_chunk: int,
):
    """One grid step a (row, query tile). The tile's G*tq query rows of a KV
    head are ONE left operand, so a page's lanes of that head are read once
    for the group; the walk is the decode kernel's (chunks of P pages,
    double-buffered) and stops at the last position the tile can see:
    ``min(true_len, q0 + tq)``. Chunks wholly at or before the tile's first
    position take no mask; the ones on the diagonal (and a row's last) build
    one from positions. A tile past the row's true length does nothing.

    Every prefill program of the (T, W) lattice traces and compiles one of
    these, so its text is kept short (PERF.md section 6, PR 34). The heads
    are a LOOP: a landed chunk is first copied a head apart (a leading
    index can be dynamic, a lane offset cannot); unrolled over the heads
    the kernel ran 5-7% faster at T 2,048 and took three to four times as
    long to compile. The page DMAs are loops too: 2P unrolled starts a call
    site ran 4% faster and cost 1-2 s of Python tracing a program, 19 s of
    a warm start (counted with two descriptors a page). The G*tq rows stay ONE operand: in blocks of tq rows the
    kernel is 1.6 times slower."""
    P = pages_per_chunk
    b, j = pl.program_id(0), pl.program_id(1)
    tq = q_ref.shape[1]
    bs = kvbuf.shape[3]
    KVH, R, hd = qs_scr.shape
    G, CH = R // tq, P * bs
    layer = layer_ref[0]
    q0 = start_ref[b] + j * tq               # the tile's first position
    true_len = len_ref[b]
    bound = jnp.minimum(true_len, q0 + tq)   # the tile sees context [0, bound)
    nchunks = jnp.where(q0 < true_len, lax.div(bound + CH - 1, CH), 0)
    # Chunks every query of the tile sees whole: all of it at or before q0.
    nplain = jnp.minimum(lax.div(q0 + 1, CH), nchunks)

    issue, wait = _page_fetch(layer, tables_ref, kv_hbm, kvbuf, sem, P, unroll=False)

    def chunk_pages(c):
        return jnp.minimum(lax.div(bound - c * CH + bs - 1, bs), P)

    def head_lanes(h):
        return pl.ds(h * hd, hd)

    @pl.when(nchunks == 0)
    def _():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(nchunks > 0)
    def _():
        slot_ref[0] = 0
        issue(b, 0, 0, chunk_pages(0))
        for k in range(KVH):
            group = q_ref[0, :, pl.ds(k * G * hd, G * hd)]     # [tq, G*hd]
            qs_scr[k] = jnp.concatenate(
                [group[:, g * hd:(g + 1) * hd] for g in range(G)], axis=0)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        # Row r = g*tq + t is query position q0 + t.
        t = lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        for _ in range(1, G):
            t = jnp.where(t >= tq, t - tq, t)
        hz_scr[...] = jnp.minimum(q0 + t + 1, true_len)

        def attend(c, masked: bool):
            """The chunk in hand into every head's online softmax."""
            if masked:
                # Pages past the walk's end were not fetched and hold
                # garbage (possibly NaN): k is neutralized by the score
                # mask, v must be zero (0*NaN=NaN).
                held = c * CH + lax.broadcasted_iota(jnp.int32, (1, CH, 1), 1) < bound
                vh_scr[...] = jnp.where(held, vh_scr[...], jnp.zeros_like(vh_scr))

            def head(k, carry):
                s = lax.dot_general(
                    qs_scr[k], kh_scr[k], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                              # [R, CH]
                if masked:
                    pos = c * CH + lax.broadcasted_iota(jnp.int32, (1, CH), 1)
                    s = jnp.where(pos < hz_scr[...], s, NEG_INF)
                m_prev = m_scr[k]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                corr = jnp.exp(m_prev - m_new)                 # [R, 1]
                p = jnp.exp(s - m_new)                         # [R, CH]
                l_scr[k] = corr * l_scr[k] + jnp.sum(p, axis=1, keepdims=True)
                pv = lax.dot_general(
                    p.astype(vh_scr.dtype), vh_scr[k], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                              # [R, hd]
                acc_scr[k] = acc_scr[k] * corr + pv
                m_scr[k] = m_new
                return carry

            return lax.fori_loop(0, KVH, head, 0)

        def chunk(c, carry):
            # Software pipeline: the next chunk's pages are started before
            # this one's are waited for.
            cur = slot_ref[0]
            nxt = 1 - cur
            slot_ref[0] = nxt

            @pl.when(c + 1 < nchunks)
            def _():
                issue(b, c + 1, nxt, chunk_pages(c + 1))

            wait(cur, chunk_pages(c))
            for k in range(KVH):
                kh_scr[k] = kvbuf[cur, :, 0, :, head_lanes(k)].reshape(CH, hd)
                vh_scr[k] = kvbuf[cur, :, 1, :, head_lanes(k)].reshape(CH, hd)
            return lax.cond(c < nplain, lambda: attend(c, False), lambda: attend(c, True))

        # The bound is read before the loop, so interpret mode can discharge
        # it (see _mq_kernel).
        lax.fori_loop(0, nchunks, chunk, 0)
        for k in range(KVH):
            o = (acc_scr[k] / jnp.maximum(l_scr[k], 1e-30)).astype(o_ref.dtype)
            o_ref[0, :, pl.ds(k * G * hd, G * hd)] = jnp.concatenate(
                [o[g * tq:(g + 1) * tq] for g in range(G)], axis=1)


def paged_prefill_attention(
    q: jax.Array,            # [B, T, KVH, G, hd] — positions start_pos .. start_pos+T
    kv_cache: jax.Array,     # [L, N, 2, bs, KVH*hd] — the chunk's K and V already written
    layer_idx: jax.Array,    # scalar int32
    block_tables: jax.Array, # [B, W] int32
    start_pos: jax.Array,    # [B] int32
    true_len: jax.Array,     # [B] int32 — 0: an inactive row
    *,
    pages_per_chunk: int = 0,  # 0 → _PREFILL_CHUNK_TOKENS a chunk
    q_tile: int = 0,           # 0 → _prefill_tile(T)
    interpret: bool = False,
) -> jax.Array:
    """Prefill attention out of the pages: query ``t`` of row ``b`` sits at
    position ``start_pos[b] + t`` and attends ``[0, min(true_len[b],
    start_pos[b] + t + 1))``. Work follows the context a tile can see: the
    table's padded width costs nothing, a row with ``true_len`` 0 nothing,
    and no score tensor leaves VMEM. bf16 operands, float32 scores, maximum,
    sum and accumulator, ``p`` in the pages' dtype for ``p v``: the XLA
    form's precisions. Returns [B, T, KVH, G, hd] in q.dtype; rows of
    queries at or past ``true_len`` are unspecified (the caller drops them)."""
    B, T, KVH, G, hd = q.shape
    bs = kv_cache.shape[3]
    assert kv_cache.shape[2:] == (2, bs, KVH * hd), "cache must be [L, N, 2, bs, KVH*hd]"
    tq = q_tile or _prefill_tile(T)
    assert T % tq == 0, (T, tq)
    P = pages_per_chunk or min(max(_PREFILL_CHUNK_TOKENS // bs, 1), _MAX_PAGES_PER_CHUNK)
    P = min(P, kv_cache.shape[1])
    return _paged_prefill_call(
        q, kv_cache, layer_idx, _whole_table(block_tables, P), start_pos, true_len,
        P=P, tq=tq, interpret=interpret,
    )


def _whole_table(block_tables: jax.Array, P: int) -> jax.Array:
    """The walk stops at a row's true length, so the table's width is nothing
    to a prefill kernel but a shape: padded here, ahead of the jitted call, to
    whole ``_TABLE_WIDTH``s, the narrow and the wide table bucket trace one
    kernel between them (a trace is 0.3 s of a worker's start, warm or cold)."""
    W = block_tables.shape[1]
    Wp = -(-W // _TABLE_WIDTH) * _TABLE_WIDTH
    Wp = -(-Wp // P) * P  # whole chunks, as the decode kernel's table
    return jnp.pad(block_tables, ((0, 0), (0, Wp - W))) if Wp != W else block_tables


@functools.partial(jax.jit, static_argnames=("P", "tq", "interpret"))
def _paged_prefill_call(q, kv_cache, layer_idx, block_tables, start_pos, true_len,
                        *, P: int, tq: int, interpret: bool):
    B, T, KVH, G, hd = q.shape
    bs = kv_cache.shape[3]
    R, H = G * tq, KVH * G

    tile = pl.BlockSpec((1, tq, H * hd), lambda b, j, *_: (b, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, T // tq),
        in_specs=[tile, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile,
        scratch_shapes=[
            pltpu.VMEM((2, P, 2, bs, KVH * hd), kv_cache.dtype),
            pltpu.VMEM((KVH, P * bs, hd), kv_cache.dtype),
            pltpu.VMEM((KVH, P * bs, hd), kv_cache.dtype),
            pltpu.VMEM((KVH, R, hd), q.dtype),
            pltpu.VMEM((KVH, R, hd), jnp.float32),
            pltpu.VMEM((KVH, R, 1), jnp.float32),
            pltpu.VMEM((KVH, R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    o = pl.pallas_call(
        functools.partial(_prefill_kernel, pages_per_chunk=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, H * hd), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        interpret=interpret,
        name="paged_prefill_attention",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        jnp.asarray(start_pos, jnp.int32),
        jnp.asarray(true_len, jnp.int32),
        jnp.asarray(block_tables, jnp.int32),
        (q * hd ** -0.5).reshape(B, T, H * hd),
        kv_cache,
    )
    return o.reshape(B, T, KVH, G, hd)


# ---------------------------------------------------------------------------
# Latent (MLA) pages: one pool ``[2L, N, bs, Dk]``, a row = the key every
# head shares (the normed latent and the rotated rope key, padded to whole
# lane tiles), its first ``value_dim`` lanes also the value. Decode attends
# in the absorbed form, so the queries arrive already times W_uk.
# ---------------------------------------------------------------------------


def latent_kernel_unsupported(cfg, block_size: int) -> str | None:
    """Why the compiled kernel cannot serve a latent ``cfg``, or None."""
    if cfg.latent_page_width % 128 or cfg.kv_lora_rank % 128:
        return (
            f"latent page row {cfg.latent_page_width} / value {cfg.kv_lora_rank} "
            f"lanes; page DMAs and the value slice need multiples of 128"
        )
    if cfg.num_heads > 128:
        return f"{cfg.num_heads} query heads > 128 lanes"
    if block_size < 8:
        return f"block_size {block_size} < 8: a page is under one bf16 DMA tile"
    return None


def latent_decode_attention_xla(
    q: jax.Array,            # [B, H, Dk] absorbed queries (zero in the padding lanes)
    cache: jax.Array,        # [2L, N, bs, Dk]
    layer_idx: jax.Array,    # scalar int32 — cache layer (2*layer + sub-block)
    block_tables: jax.Array, # [B, W] int32
    lengths: jax.Array,      # [B] int32
    *, value_dim: int, scale: float, window: int = 0, keep: jax.Array | None = None,
) -> jax.Array:
    """Gather-based reference of the latent decode attention → [B, H, value_dim].
    ``window`` > 0: a row attends its last ``window`` positions only; ``keep``
    [B, W*bs]: of its context, the positions it marks (a chosen set)."""
    B, H, Dk = q.shape
    pk = _gather_pages(cache, layer_idx, block_tables).reshape(B, -1, Dk)  # [B, W*bs, Dk]
    ctx = jnp.arange(pk.shape[1], dtype=jnp.int32)
    seen = ctx[None, :] < lengths[:, None]
    if window:
        seen &= ctx[None, :] >= lengths[:, None] - window
    if keep is not None:
        seen &= keep != 0
    mask = jnp.where(seen, 0.0, jnp.float32(NEG_INF))
    s = jnp.einsum("bhd,bcd->bhc", q, pk).astype(jnp.float32) * scale
    p = jax.nn.softmax(s + mask[:, None, :], axis=-1).astype(q.dtype)
    return jnp.einsum("bhc,bcv->bhv", p, pk[..., :value_dim])


@functools.partial(
    jax.jit,
    static_argnames=("value_dim", "scale", "pages_per_chunk", "interpret", "window"),
)
def latent_decode_attention(
    q: jax.Array,            # [B, H, Dk]
    cache: jax.Array,        # [2L, N, bs, Dk]
    layer_idx: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *, value_dim: int, scale: float,
    pages_per_chunk: int = 0,
    interpret: bool = False,
    window: int = 0,
) -> jax.Array:
    """The multi-query kernel at the latent geometry: H query heads against
    one shared key of Dk lanes, value = its first ``value_dim``; each page
    is read once. ``window`` > 0: a row attends its last ``window`` positions
    only (the table then holds the blocks of those positions and ``lengths``
    counts from the table's first block). Returns [B, H, value_dim]."""
    o = _paged_attention_mq(
        q[:, None, None], cache, layer_idx, block_tables,
        jnp.asarray(lengths, jnp.int32)[:, None], None, None,
        pages_per_chunk, interpret, value_dim=value_dim, scale=scale, window=window,
    )
    return o[:, 0, 0]


# Float32 score elements one head group of the XLA form may hold.
_XLA_SCORE_ELEMS = 48 << 20


def latent_prefill_attention_xla(
    q_lat: jax.Array,        # [B, H, T, Dv] absorbed queries at positions start_pos ..: the
    q_rope: jax.Array,       # [B, H, T, Dk - Dv]   latent lanes, and the rope lanes (zero-padded)
    cache: jax.Array,        # [2L, N, bs, Dk] — the chunk's own rows already written
    layer_idx: jax.Array,    # scalar int32 — cache layer (2*layer + sub-block)
    block_tables: jax.Array, # [B, W] int32
    start_pos: jax.Array,    # [B] int32
    true_len: jax.Array,     # [B] int32 — 0: an inactive row
    *, scale: float, window: int = 0, keep: jax.Array | None = None,
) -> jax.Array:
    """The gather-based form of ``latent_prefill_attention``: the table's
    whole width gathered dense, prefix and chunk alike, and float32 scores
    ``[B, h, T, W*bs]`` over it, a group of heads at a time so that they fit
    (``_XLA_SCORE_ELEMS``). The CPU's path, and a refused geometry's.
    Returns [B, H, T, Dv] in q_lat.dtype."""
    B, H, T, Dv = q_lat.shape
    Dk = cache.shape[3]
    pk = _gather_pages(cache, layer_idx, block_tables).reshape(B, -1, Dk)  # [B, W*bs, Dk]
    C = pk.shape[1]
    horizon = jnp.minimum(start_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None] + 1,
                          true_len[:, None])                       # [B, T]
    ctx = jnp.arange(C, dtype=jnp.int32)[None, None]
    seen = ctx < horizon[..., None]
    if window:
        seen &= ctx >= horizon[..., None] - window
    if keep is not None:
        seen &= keep != 0
    mask = jnp.where(seen, 0.0, jnp.float32(NEG_INF))              # [B, T, C]
    g = H
    while g > 1 and B * g * T * C > _XLA_SCORE_ELEMS:
        g //= 2

    def group(qs):  # [B, g, T, Dv], [B, g, T, Dk - Dv]
        s = jnp.einsum("bhtd,bcd->bhtc", qs[0], pk[..., :Dv], preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bhtd,bcd->bhtc", qs[1], pk[..., Dv:], preferred_element_type=jnp.float32)
        p = jax.nn.softmax(s * scale + mask[:, None], axis=-1).astype(q_lat.dtype)
        return jnp.einsum("bhtc,bcv->bhtv", p, pk[..., :Dv])

    def split(a):  # [B, H, T, d] → [H/g, B, g, T, d]
        return jnp.moveaxis(a.reshape(B, H // g, g, T, a.shape[3]), 1, 0)

    o = lax.map(group, (split(q_lat), split(q_rope)))
    return jnp.moveaxis(o, 0, 1).reshape(B, H, T, Dv)


# The latent prefill kernel's sizes, from the chip (PERF.md section 6, PR 44):
# float32 score elements a grid step may hold (rows of the left operand x tokens
# of a chunk), beside which Mosaic keeps the exponentials and their cast: at
# 2 Mi the kernel is 1.5-1.9 times slower at every call shape. And the tokens
# of a chunk: 512 and 1,024 read level at a 256-token turn behind 1.7k, and
# 512 is 10-20% faster at a 64-token turn, in a pack and at T 2,048, because
# a row's last chunk is computed whole however few pages it holds.
_LATENT_SCORE_ELEMS = 1 << 20
_LATENT_CHUNK_TOKENS = 512


def _latent_prefill_tile(T: int, H: int, chunk_tokens: int) -> int:
    """The query tile of the latent kernel, from the shapes: the largest
    divisor of T whose ``H x tq`` rows (every head of a position is a row of
    the one left operand) keep a chunk's float32 scores within
    ``_LATENT_SCORE_ELEMS``; a multiple of 16 where T has one (a packed bf16
    sublane tile: the tile's heads then stack with no relayout), else of 8."""
    most = max(_LATENT_SCORE_ELEMS // (H * chunk_tokens), 1)
    fits = [t for t in range(1, min(T, most) + 1) if T % t == 0]
    for whole in (16, 8, 1):
        aligned = [t for t in fits if t % whole == 0]
        if aligned:
            return aligned[-1]


def _latent_prefill_kernel(
    # scalar prefetch
    layer_ref,    # [1] int32
    start_ref,    # [B] int32 — the chunk's first position
    len_ref,      # [B] int32 — the row's true length
    tables_ref,   # [B, W] int32
    # operands
    ql_ref,       # VMEM [1, H, tq, Dv] — a tile of absorbed queries, head-major: the
    qr_ref,       # VMEM [1, H, tq, Dk - Dv]   latent lanes, and the rope lanes (zero-padded)
    *refs,        # [keep_ref VMEM [1, tq, W*bs] — nonzero: the query attends that position]
    # k_hbm,        ANY  [2L, N, bs, Dk]
    # o_ref,        VMEM [1, H, tq, Dv]
    # scratch
    # q_scr,        VMEM [H*tq, Dk] — the two side by side, row h*tq + t: ONE left operand
    # kbuf,         VMEM [2, P, bs, Dk] — pages as they land
    # acc_scr,      VMEM [H*tq, Dv] f32
    # m_scr,        VMEM [H*tq, 1] f32 — running max
    # l_scr,        VMEM [H*tq, 1] f32 — running sum
    # hz_scr,       VMEM [H*tq, 1] int32 — each query row attends [0, horizon)
    # slot_ref,     SMEM [1] int32
    # sem,          DMA semaphores [2 slots]
    pages_per_chunk: int,
    scale: float,
    window: int = 0,
):
    """``_prefill_kernel``'s walk at the latent geometry: one pool, one key
    row all heads share, its first Dv lanes the value. A tile's ``H x tq``
    query rows are ONE left operand against a chunk (KVH 1, G = H), so there
    is no head loop and no per-head copy of a landed chunk: the page buffer
    is the right operand as it lies. Queries and output are head-major
    ``[H, T, .]``, the layout the absorbing and unabsorbing products (a batch
    of heads) have them in, so XLA transposes nothing around the call. Scores,
    maximum, sum and accumulator are float32 and never leave VMEM; the softmax
    scale multiplies the float32 scores (the published form's ``s * scale``),
    not the bf16 queries. ``window`` > 0: a query attends its last ``window``
    positions only; a ``keep`` operand: those of its causal context that the
    operand marks. Either way every chunk takes the masked form."""
    keep_ref = refs[0] if len(refs) == 11 else None
    k_hbm, o_ref, q_scr, kbuf, acc_scr, m_scr, l_scr, hz_scr, slot_ref, sem = refs[-10:]
    P = pages_per_chunk
    b, j = pl.program_id(0), pl.program_id(1)
    H, tq, Dv = ql_ref.shape[1:]
    R = H * tq
    bs = kbuf.shape[2]
    CH = P * bs
    layer = layer_ref[0]
    q0 = start_ref[b] + j * tq               # the tile's first position
    true_len = len_ref[b]
    bound = jnp.minimum(true_len, q0 + tq)   # the tile sees context [0, bound)
    nchunks = jnp.where(q0 < true_len, lax.div(bound + CH - 1, CH), 0)
    # Chunks every query of the tile sees whole: all of it at or before q0.
    nplain = jnp.minimum(lax.div(q0 + 1, CH), nchunks)
    if window or keep_ref is not None:
        nplain = 0

    issue, wait = _page_fetch(layer, tables_ref, k_hbm, kbuf, sem, P, unroll=False)

    def chunk_pages(c):
        return jnp.minimum(lax.div(bound - c * CH + bs - 1, bs), P)

    @pl.when(nchunks == 0)
    def _():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(nchunks > 0)
    def _():
        slot_ref[0] = 0
        issue(b, 0, 0, chunk_pages(0))
        q_scr[:, :Dv] = ql_ref[0].reshape(R, Dv)
        q_scr[:, Dv:] = qr_ref[0].reshape(R, qr_ref.shape[3])
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        # Row r = h*tq + t is query position q0 + t.
        t = lax.rem(lax.broadcasted_iota(jnp.int32, (R, 1), 0), tq)
        hz_scr[...] = jnp.minimum(q0 + t + 1, true_len)

        def attend(c, cur, masked: bool):
            """The chunk in buffer ``cur`` into the online softmax."""
            k = kbuf[cur].reshape(CH, kbuf.shape[3])
            if masked:
                # Pages past the walk's end were not fetched and hold garbage
                # (possibly NaN): as keys the score mask neutralizes them, as
                # values they must be zero (0*NaN=NaN).
                held = c * CH + lax.broadcasted_iota(jnp.int32, (CH, 1), 0) < bound
                k = jnp.where(held, k, jnp.zeros_like(k))
            s = lax.dot_general(
                q_scr[...], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                          # [R, CH]
            if masked:
                pos = c * CH + lax.broadcasted_iota(jnp.int32, (1, CH), 1)
                seen = pos < hz_scr[...]
                if window:
                    seen = seen & (pos >= hz_scr[...] - window)
                s = jnp.where(seen, s, NEG_INF)
                if keep_ref is not None:
                    kept = keep_ref[0, :, pl.ds(pl.multiple_of(c * CH, CH), CH)] != 0   # [tq, CH]
                    s = jnp.where(kept[None], s.reshape(H, tq, CH), NEG_INF).reshape(R, CH)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)                     # [R, 1]
            p = jnp.exp(s - m_new)                             # [R, CH]
            l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
            pv = lax.dot_general(
                p.astype(k.dtype), k[:, :Dv], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                  # [R, Dv]
            acc_scr[...] = acc_scr[...] * corr + pv
            m_scr[...] = m_new

        def chunk(c, carry):
            # Software pipeline: the next chunk's pages are started before
            # this one's are waited for.
            cur = slot_ref[0]
            nxt = 1 - cur
            slot_ref[0] = nxt

            @pl.when(c + 1 < nchunks)
            def _():
                issue(b, c + 1, nxt, chunk_pages(c + 1))

            wait(cur, chunk_pages(c))
            pl.when(c < nplain)(lambda: attend(c, cur, False))
            pl.when(c >= nplain)(lambda: attend(c, cur, True))
            return carry

        # The bound is read before the loop, so interpret mode can discharge
        # it (see _mq_kernel).
        lax.fori_loop(0, nchunks, chunk, 0)
        o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = o.astype(o_ref.dtype).reshape(H, tq, Dv)


def latent_prefill_attention(
    q_lat: jax.Array,        # [B, H, T, Dv] absorbed queries, head-major: the latent lanes,
    q_rope: jax.Array,       # [B, H, T, Dk - Dv]   and the rope lanes (zero in the padding)
    cache: jax.Array,        # [2L, N, bs, Dk] — the chunk's own rows already written
    layer_idx: jax.Array,    # scalar int32 — cache layer (2*layer + sub-block)
    block_tables: jax.Array, # [B, W] int32
    start_pos: jax.Array,    # [B] int32
    true_len: jax.Array,     # [B] int32 — 0: an inactive row
    *, scale: float,
    pages_per_chunk: int = 0,  # 0 → _LATENT_CHUNK_TOKENS a chunk
    q_tile: int = 0,           # 0 → _latent_prefill_tile
    interpret: bool = False,
    window: int = 0,           # > 0: a query attends its last ``window`` positions only
    keep: jax.Array | None = None,  # [B, T, W*bs]: nonzero where query t attends that position
) -> jax.Array:
    """``paged_prefill_attention`` over latent pages, in the absorbed form
    decode attends in: query ``t`` of row ``b`` sits at ``start_pos[b] + t``
    and attends ``[0, min(true_len[b], start_pos[b] + t + 1))`` straight out
    of the pages, prefix and chunk alike. Work follows the context a tile
    can see; the table's width costs nothing and a row with ``true_len`` 0
    nothing. Operations a context position: 2 x T x H x (Dk + Dv), which at
    this cache's widths is under the expanded form's (a position multiplied
    out by W_kvb once, then 2 x T x H x 320) up to T ~ 157 and 1.6 times it
    at T 256; against the expanded form as XLA ran it (the table's width,
    float32 scores through HBM) it is less work at every T a 4,096-token
    table allows. ``window`` and ``keep`` narrow the causal context (a window
    layer whose table and positions count from the window's first block; a
    layer that attends a chosen set): the walk stays that of the context, the
    mask does the rest. Returns [B, H, T, Dv] in q_lat.dtype; rows of queries at or
    past ``true_len`` are unspecified."""
    B, H, T, Dv = q_lat.shape
    bs = cache.shape[2]
    assert cache.shape[3] == Dv + q_rope.shape[3], "cache must be [2L, N, bs, Dk]"
    P = pages_per_chunk or min(max(_LATENT_CHUNK_TOKENS // bs, 1), _MAX_PAGES_PER_CHUNK)
    P = min(P, cache.shape[1])
    tq = q_tile or _latent_prefill_tile(T, H, P * bs)
    assert T % tq == 0, (T, tq)
    tables = _whole_table(block_tables, P)
    if keep is not None and keep.shape[2] != tables.shape[1] * bs:
        keep = jnp.pad(keep, ((0, 0), (0, 0), (0, tables.shape[1] * bs - keep.shape[2])))
    return _latent_prefill_call(
        q_lat, q_rope, cache, layer_idx, tables, start_pos, true_len, keep,
        scale=scale, P=P, tq=tq, interpret=interpret, window=window,
    )


@functools.partial(jax.jit, static_argnames=("scale", "P", "tq", "interpret", "window"))
def _latent_prefill_call(q_lat, q_rope, cache, layer_idx, block_tables, start_pos, true_len,
                         keep=None, *, scale: float, P: int, tq: int, interpret: bool, window: int = 0):
    B, H, T, Dv = q_lat.shape
    bs, Dk = cache.shape[2:]
    R = H * tq

    def tile(lanes):
        return pl.BlockSpec((1, H, tq, lanes), lambda b, j, *_: (b, 0, j, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, T // tq),
        in_specs=[tile(Dv), tile(Dk - Dv),
                  *([] if keep is None else
                    [pl.BlockSpec((1, tq, keep.shape[2]), lambda b, j, *_: (b, j, 0))]),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile(Dv),
        scratch_shapes=[
            pltpu.VMEM((R, Dk), q_lat.dtype),
            pltpu.VMEM((2, P, bs, Dk), cache.dtype),
            pltpu.VMEM((R, Dv), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_prefill_kernel, pages_per_chunk=P, scale=scale,
                          **({"window": window} if window else {})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, Dv), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        interpret=interpret,
        name="latent_prefill_attention",
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        jnp.asarray(start_pos, jnp.int32),
        jnp.asarray(true_len, jnp.int32),
        jnp.asarray(block_tables, jnp.int32),
        q_lat,
        q_rope,
        *([] if keep is None else [keep]),
        cache,
    )
