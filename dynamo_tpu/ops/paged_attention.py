"""Paged decode attention: Pallas TPU kernel + XLA reference.

The role vLLM's paged-attention CUDA kernels play for the reference
(reference: components/backends/vllm/src/dynamo/vllm/main.py:90 delegates
to vLLM's engine; its CUDA kernels are the analogue of this file).

Why a kernel at all: the XLA formulation gathers the full (bucketed)
block-table width `W*bs` out of the page pool per layer per step —
~3x HBM traffic on padded context (materialize + re-read) regardless of
each sequence's true length. The kernel instead walks each row's actual
pages: one DMA per page (a page is contiguous ``[bs, KVH*hd]`` in the
cache layout), online-softmax accumulation, work proportional to
``sum(lengths)`` rather than ``B*W*bs``.

Design notes (measured on v5e, see tools/profile_decode.py):

- The FULL cache ``[L, N, bs, KVH*hd]`` stays in HBM (`pl.ANY`) in its
  native dense layout (a 5D [.., KVH, hd] layout forced a whole-cache
  relayout copy per pallas_call — ~9ms/layer measured on v5e, the reason
  the cache is stored heads-merged). The layer index is a scalar-prefetch
  operand, so no layer of the pool is ever sliced out. The gather path
  (``gather_dequant_pages``: prefill's prefix read and the XLA decode and
  spec-verify below) no longer slices one either: it gathers pages from
  the stacked pool by (layer, page). Until PR 28 it took a
  ``dynamic_slice`` of the layer first, a copy of all N pages of it.
- Grid ``(B, CMAX)``: chunk c of row b processes up to P pages.
  Cross-step software pipelining: every live step issues the DMAs of the
  *next* live step (double-buffered), so page fetch overlaps compute
  across rows, not just within a row.
- **Block-diagonal q**: per-head lane slices of the KV buffer relayout
  on every access (hd=64 is sub-lane-tile) and measured ~15us/chunk.
  Instead the caller bakes q into a block-diagonal matrix
  ``[KVH*hd, KVH*G]`` so ONE MXU op yields all heads' scores
  ``[P*bs, KVH*G]``; the online softmax is column-wise (axis-0 reduces),
  and the accumulator is kept transposed ``[KVH*hd, KVH*G]`` so every
  correction is a row-vector broadcast. Zero relayouts, zero transposes
  in the kernel; the per-head diagonal is extracted by XLA afterwards.
- Dead steps (chunk beyond the row's length, padding rows) skip DMA and
  compute entirely — padding costs ~grid-iteration overhead only.
- Per-DMA cost measured ~0.6us: pages should be >=32KB to approach
  bandwidth. Page bytes = block_size x KVH x hd x 2 (bf16), so for
  8B-class geometries (KVH*hd = 1024) the default ``block_size=16``
  already gives 32KB pages — r5 bench: decode substeps run AT the int8
  weight-stream roofline (~9 ms vs the 9.8 ms floor) at bs=16, so
  larger blocks buy nothing there. Prefer 64-256 only for SMALL kv
  widths (e.g. KVH*hd <= 256) where bs=16 pages drop under 8KB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# Most DMA semaphores one chunk may hold: 2 slots x (k, v) x P pages must
# fit the chip's semaphore memory (v5e refuses P=128).
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


def gather_dequant_pages(
    cache: jax.Array,         # [L, N, bs, KVH*hd] — the stacked pool
    scale: jax.Array | None,  # [L, N, bs, KVH] fp32 | None
    layer_idx: jax.Array,     # scalar int32
    block_tables: jax.Array,  # [B, W] int32
    KVH: int, hd: int, dtype,
):
    """Gather a batch's pages of one layer straight out of the stacked
    pool and (for int8 storage) dequantize with the per-position-per-head
    scales → [B, W*bs, KVH, hd] in ``dtype``. This is the one way the XLA
    paths read pages, and no array of one layer of the pool is formed on
    the way: slicing the layer out first copied all N pages of it (84 MB
    at 5,120 blocks, 102 us at the HBM roofline) to read a few hundred.
    What is materialized is the gathered ``[B, W, bs, KVH*hd]``, which
    the Pallas kernels avoid too; the int8→float convert rides the
    gather output, so that copy stays half the bf16 path's bytes."""
    B, W = block_tables.shape
    L, N, bs, _ = cache.shape
    # Pages: the pool viewed as [L*N, bs, KVH*hd], a bitcast of its dense
    # layout, and one index a page. ``cache[layer_idx, block_tables]`` is
    # copy-free too, but packs a two-component index vector every layer:
    # 5.27 against 4.83 ms for 28 layers of K and V on v5e (PERF.md, PR 28).
    pages = cache.reshape(L * N, bs, KVH * hd)[layer_idx * N + block_tables]
    pages = pages.reshape(B, W * bs, KVH, hd)
    if scale is None:
        return pages
    # Scales: both indices at once. The chip lays their KVH-wide rows out
    # position-minor, so the flat view is no bitcast there: it compiles to
    # a relayout copy of the whole scale pool in every layer.
    sc = scale[layer_idx, block_tables].reshape(B, W * bs, KVH)
    # Dequantize in f32 and round ONCE into ``dtype`` — multiplying in
    # bf16 would read the same stored byte back as a different value
    # than the Pallas kernel / host adapters (which also widen to f32),
    # breaking cross-path consistency for the same block.
    return (pages.astype(jnp.float32) * sc[..., None]).astype(dtype)


def paged_decode_attention_xla(
    q: jax.Array,            # [B, KVH, G, hd]
    k_cache: jax.Array,      # [L, N, bs, KVH*hd]
    v_cache: jax.Array,
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
    pk = gather_dequant_pages(k_cache, k_scale, layer_idx, block_tables, KVH, hd, q.dtype)
    pv = gather_dequant_pages(v_cache, v_scale, layer_idx, block_tables, KVH, hd, q.dtype)
    scale = hd ** -0.5
    ctx = jnp.arange(pk.shape[1], dtype=jnp.int32)
    mask = jnp.where(ctx[None, :] < lengths[:, None], 0.0, jnp.float32(NEG_INF))
    s = jnp.einsum("bkgh,bckh->bkgc", q, pk).astype(jnp.float32) * scale
    s = s + mask[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgc,bckh->bkgh", p, pv)


def paged_spec_attention_xla(
    q: jax.Array,            # [B, T, KVH, G, hd] — T consecutive query positions
    k_cache: jax.Array,      # [L, N, bs, KVH*hd]
    v_cache: jax.Array,
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
    pk = gather_dequant_pages(k_cache, k_scale, layer_idx, block_tables, KVH, hd, q.dtype)
    pv = gather_dequant_pages(v_cache, v_scale, layer_idx, block_tables, KVH, hd, q.dtype)
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


def _mq_kernel(
    # scalar prefetch
    layer_ref,    # [1] int32
    rowlen_ref,   # [B] int32 — max attend length per row (chunk walk bound)
    tables_ref,   # [B, W] int32
    # operands (anc present only in tree mode; kscale/vscale when quantized)
    *refs,
    # static
    pages_per_chunk: int,
    head_dim: int,
    quantized: bool,
    tree_slots: int = 0,
    value_dim: int = 0,
):
    # value_dim > 0: a latent (MLA) pool. One pool, no V: a page row is the
    # shared key, and its first ``value_dim`` lanes are also the value, so
    # each page is read once.
    refs = list(refs)
    qbd_ref, lenvec_ref = refs[:2]
    refs = refs[2:]
    anc_ref = None
    if tree_slots:
        anc_ref, refs = refs[0], refs[1:]
    if value_dim:
        (k_hbm, o_ref, kbuf, m_scr, l_scr, acc_scr, slot_ref, started_ref,
         sem) = refs
        kscale_ref = vscale_ref = v_hbm = vbuf = None
    elif quantized:
        (kscale_ref, vscale_ref, k_hbm, v_hbm,
         o_ref, kbuf, vbuf, m_scr, l_scr, acc_scr, slot_ref, started_ref,
         sem) = refs
    else:
        (k_hbm, v_hbm,
         o_ref, kbuf, vbuf, m_scr, l_scr, acc_scr, slot_ref, started_ref,
         sem) = refs
        kscale_ref = vscale_ref = None
    # qbd_ref    VMEM [1, KVH*hd, H] — block-diag q, softmax scale folded in
    # lenvec_ref VMEM [1, 1, H] int32 — per query COLUMN attend length; in
    #            tree mode the per-column HISTORY horizon (slots ride on top)
    # anc_ref    VMEM [1, T, H] int8 — tree mode: anc[s, col] = query col
    #            may attend in-flight slot s (its ancestor-or-self set)
    # kscale_ref VMEM [1, P, bs, KVH] f32 — this chunk's per-position-per-head scales
    # k_hbm      ANY  [L, N, bs, KVH*hd]
    # o_ref      VMEM [1, KVH*hd, H] — attention out, transposed
    # kbuf/vbuf  VMEM [2, P, bs, KVH*hd] (cache dtype; int8 when quantized)
    # m/l        VMEM [8, 128] f32 — row 0, first H lanes live
    # acc        VMEM [KVH*hd, H] f32
    # slot/started SMEM [1] int32; sem DMA sems [2, 2, P]
    P = pages_per_chunk
    b = pl.program_id(0)
    c = pl.program_id(1)
    B = pl.num_programs(0)
    layer = layer_ref[0]
    bs = kbuf.shape[2]
    D = kbuf.shape[3]       # KVH*hd
    H = qbd_ref.shape[2]    # KVH*T*G (total query columns)
    hd = head_dim
    KVH = D // hd
    CH = P * bs             # tokens per chunk

    length = rowlen_ref[b]
    nchunks = lax.div(length + CH - 1, CH)
    live = c < nchunks

    @pl.when((b == 0) & (c == 0))
    def _init_globals():
        slot_ref[0] = 0
        started_ref[0] = 0

    def chunk_dmas(row, chunk, slot):
        """DMA descriptors for (row, chunk) into buffer `slot`; page p is
        guarded by the row's true page count."""
        rem = rowlen_ref[row] - chunk * CH
        npages = jnp.minimum(lax.div(rem + bs - 1, bs), P)
        out = []
        for p in range(P):
            page = tables_ref[row, chunk * P + p]
            copies = [pltpu.make_async_copy(
                k_hbm.at[layer, page], kbuf.at[slot, p], sem.at[slot, 0, p])]
            if v_hbm is not None:
                copies.append(pltpu.make_async_copy(
                    v_hbm.at[layer, page], vbuf.at[slot, p], sem.at[slot, 1, p]))
            out.append((p < npages, copies))
        return out

    def issue(row, chunk, slot):
        for ok, copies in chunk_dmas(row, chunk, slot):
            @pl.when(ok)
            def _():
                for dma in copies:
                    dma.start()

    @pl.when(live)
    def _body():
        cur = slot_ref[0]

        # Global warmup: the very first live step has no predecessor.
        @pl.when(started_ref[0] == 0)
        def _():
            issue(b, c, cur)
            started_ref[0] = 1

        # Software pipeline: issue the next live step's pages.
        # Successor is (b, c+1) if this row continues, else chunk 0 of
        # the next non-empty row (scalar search past padding rows).
        nxt = 1 - cur
        row_continues = c + 1 < nchunks

        @pl.when(row_continues)
        def _():
            issue(b, c + 1, nxt)

        @pl.when(~row_continues)
        def _():
            # First non-empty row after b (B if none). A fori_loop, not a
            # while_loop: the scan is O(B) scalar work either way, and a
            # while cond that reads a ref has no interpret-mode discharge
            # rule — this form keeps the kernel CPU-interpret-testable.
            def scan_row(r, best):
                cand = (r > b) & (rowlen_ref[r] > 0) & (r < best)
                return jnp.where(cand, r, best)

            nxt_row = lax.fori_loop(0, B, scan_row, B)

            @pl.when(nxt_row < B)
            def _():
                issue(nxt_row, 0, nxt)

        # Init row accumulators at the row's first chunk.
        @pl.when(c == 0)
        def _():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # Wait for this step's pages.
        for ok, copies in chunk_dmas(b, c, cur):
            @pl.when(ok)
            def _():
                for dma in copies:
                    dma.wait()
        slot_ref[0] = nxt

        # Context-position validity, column orientation [P*bs, 1].
        pos = c * CH + lax.broadcasted_iota(jnp.int32, (P * bs, 1), 0)
        valid = pos < length

        k_chunk = kbuf[cur].reshape(P * bs, D)
        if value_dim:
            v_chunk = k_chunk[:, :value_dim]
        else:
            v_chunk = vbuf[cur].reshape(P * bs, D)
        if quantized:
            # In-register dequant of the just-landed int8 pages: expand
            # this chunk's [P*bs, KVH] scales across each head's lanes and
            # multiply — the DMA moved half the bytes, the float page
            # never exists outside VMEM. The scales are flattened to 2D
            # first: Mosaic lowers the [CH, KVH, hd] -> [CH, D] merge at
            # hd=64 and hd=128, but not the 4D form of the same cast.
            def lane_scales(sc_ref):
                sc = sc_ref[0].reshape(P * bs, KVH)
                return jnp.broadcast_to(
                    sc[..., None], (P * bs, KVH, hd)
                ).reshape(P * bs, D)

            k_chunk = (
                k_chunk.astype(jnp.float32) * lane_scales(kscale_ref)
            ).astype(qbd_ref.dtype)
            v_chunk = (
                v_chunk.astype(jnp.float32) * lane_scales(vscale_ref)
            ).astype(qbd_ref.dtype)
        # Unfetched tail pages hold garbage (possibly NaN): k is
        # neutralized by the score mask, v must be zeroed (0*NaN=NaN).
        v_chunk = jnp.where(valid, v_chunk, 0)

        # All heads' scores in one MXU op via the block-diagonal q.
        s = lax.dot_general(
            k_chunk, qbd_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [P*bs, H]
        # Per-COLUMN causal horizon: column (k, t, g) attends positions
        # [0, lengths[b, t]) — for decode (T=1) every column carries the
        # row length and this is exactly the old row mask. Tree mode
        # adds the topology bits: in-flight slot s_i sits at paged
        # position hist + s_i and column t attends it only when
        # anc[s_i, col] is set (T compares on the VPU, T is small).
        lenvec = lenvec_ref[0]                             # [1, H]
        att = pos < lenvec
        if tree_slots:
            for s_i in range(tree_slots):
                att = att | (
                    (pos == lenvec + s_i)
                    & (anc_ref[0, s_i, :][None, :] != 0)
                )
        s = jnp.where(att, s, NEG_INF)

        m_prev = m_scr[0:1, :H]                            # [1, H]
        l_prev = l_scr[0:1, :H]
        m_cur = jnp.max(s, axis=0, keepdims=True)          # [1, H]
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)                     # [1, H]
        p = jnp.exp(s - m_new)                             # [P*bs, H]
        l_new = corr * l_prev + jnp.sum(p, axis=0, keepdims=True)
        # Transposed accumulator [D, H]: corrections broadcast over rows.
        pv = lax.dot_general(
            v_chunk, p.astype(v_chunk.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [D, H]
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[0:1, :H] = m_new
        l_scr[0:1, :H] = l_new

        # Row done → normalize and emit (still transposed; XLA takes the
        # per-head diagonal outside).
        @pl.when(c == nchunks - 1)
        def _():
            l = jnp.maximum(l_scr[0:1, :H], 1e-30)
            o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)

    # Keep padding rows' output defined (their stale block is otherwise
    # flushed as-is; harmless numerically but keep it clean).
    @pl.when((~live) & (c == 0))
    def _zero():
        o_ref[0] = jnp.zeros_like(o_ref[0])


def _paged_attention_mq(
    q: jax.Array,            # [B, T, KVH, G, hd]
    k_cache: jax.Array,      # [L, N, bs, KVH*hd] — dense pages, no
    v_cache: jax.Array,      #   per-call layout conversion
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
) -> jax.Array:
    """Shared Pallas driver: T query positions per row walk the row's
    true pages once. Returns [B, T, KVH, G, hd] in q.dtype
    ([B, T, 1, G, value_dim] over a latent pool, ``v_cache`` None)."""
    B, T, KVH, G, hd = q.shape
    bs = k_cache.shape[2]
    assert k_cache.shape[3] == KVH * hd, "cache must be [L, N, bs, KVH*hd]"
    W = block_tables.shape[1]
    H = KVH * T * G
    if H > 128:
        raise NotImplementedError(
            f"{H} query columns (KVH*T*G) > 128 lanes; shard heads (tp) "
            f"or fall back to the XLA gather path"
        )
    quantized = k_scale is not None
    P = pages_per_chunk or min(max(1, 512 // bs), _MAX_PAGES_PER_CHUNK)
    P = min(P, W)
    if W % P:  # pad the table so chunks tile it exactly
        pad = P - W % P
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
        W += pad
    chunks_max = W // P

    # Block-diagonal q with the softmax scale folded in:
    # qbd[b, j*hd+h, k*(T*G)+t*G+g] = q[b,t,k,g,h] * scale * (j==k).
    eye = jnp.eye(KVH, dtype=q.dtype)
    qbd = jnp.einsum(
        "btkgh,jk->bjhktg", q * (hd ** -0.5 if scale is None else scale), eye
    )
    qbd = qbd.reshape(B, KVH * hd, H)
    # Per-column attend horizon, same (k, t, g) column order as qbd.
    # Carried [B, 1, H]: Mosaic wants a block's last two dims to be whole
    # (8, 128) tiles or the array's own, and a (1, H) block of [B, H] is
    # neither.
    lengths = jnp.asarray(lengths, jnp.int32)
    lenvec = jnp.broadcast_to(
        lengths[:, None, :, None], (B, KVH, T, G)
    ).reshape(B, 1, H)
    rowlen = jnp.max(lengths, axis=1)  # chunk-walk bound per row
    if anc is not None:
        # Tree mode: the walk must also cover the T in-flight slots at
        # positions [hist, hist + T); rows with no live node at all
        # (anc identically zero — padding rows) stay empty so the
        # prefetch skip keeps them ~free.
        live_row = jnp.any(anc != 0, axis=(1, 2))
        rowlen = jnp.where(live_row, rowlen + T, 0)

    operands = [qbd, lenvec]
    in_specs = [
        pl.BlockSpec((1, KVH * hd, H), lambda b, c, *_: (b, 0, 0)),
        pl.BlockSpec((1, 1, H), lambda b, c, *_: (b, 0, 0)),
    ]
    if anc is not None:
        # Column-order ancestor bits [B, T_slot, H]: anc_cols[b, s, col]
        # with col = (k*T + t)*G + g — the same (k, t, g) layout as
        # lenvec/qbd, prefetched per row block alongside the scales.
        anc_b = jnp.asarray(anc != 0, jnp.int8).transpose(0, 2, 1)  # [B, Ts, Tq]
        anc_cols = jnp.broadcast_to(
            anc_b[:, :, None, :, None], (B, T, KVH, T, G)
        ).reshape(B, T, H)
        operands.append(anc_cols)
        in_specs.append(pl.BlockSpec((1, T, H), lambda b, c, *_: (b, 0, 0)))
    if quantized:
        # Scales are gathered OUTSIDE the kernel ([B, W, bs, KVH] fp32 is
        # 1/head_dim the page bytes) and ride as per-CHUNK VMEM blocks, so
        # their VMEM footprint does not grow with the context: a whole
        # row's block (KVH padded to 128 lanes) ran out of VMEM from 16k
        # tokens.
        sk = lax.dynamic_index_in_dim(k_scale, layer_idx, 0, keepdims=False)
        sv = lax.dynamic_index_in_dim(v_scale, layer_idx, 0, keepdims=False)
        operands += [sk[block_tables], sv[block_tables]]
        in_specs += [
            pl.BlockSpec((1, P, bs, KVH), lambda b, c, *_: (b, c, 0, 0)),
            pl.BlockSpec((1, P, bs, KVH), lambda b, c, *_: (b, c, 0, 0)),
        ]
    pools = [k_cache] if value_dim else [k_cache, v_cache]
    operands += pools
    in_specs += [pl.BlockSpec(memory_space=pl.ANY) for _ in pools]
    out_rows = value_dim or KVH * hd

    kernel = functools.partial(
        _mq_kernel, pages_per_chunk=P, head_dim=hd, quantized=quantized,
        tree_slots=T if anc is not None else 0, value_dim=value_dim,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, chunks_max),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, out_rows, H), lambda b, c, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P, bs, KVH * hd), pool.dtype) for pool in pools
        ] + [
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((out_rows, H), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2, P)),
        ],
    )
    o_t = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, out_rows, H), q.dtype),
        interpret=interpret,
    )(
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        rowlen,
        jnp.asarray(block_tables, jnp.int32),
        *operands,
    )
    # [B, KVH*hd, KVH*T*G] → per-head diagonal → [B, T, KVH, G, hd].
    o6 = o_t.reshape(B, KVH, out_rows // KVH, KVH, T, G)
    return jnp.einsum("bkhktg->btkgh", o6)


@functools.partial(
    jax.jit,
    static_argnames=("pages_per_chunk", "interpret"),
)
def paged_decode_attention(
    q: jax.Array,            # [B, KVH, G, hd]
    k_cache: jax.Array,      # [L, N, bs, KVH*hd]
    v_cache: jax.Array,
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
        q[:, None], k_cache, v_cache, layer_idx, block_tables,
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
    k_cache: jax.Array,      # [L, N, bs, KVH*hd]
    v_cache: jax.Array,
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
        q, k_cache, v_cache, layer_idx, block_tables, lengths,
        k_scale, v_scale, pages_per_chunk, interpret, anc,
    )


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
    *, value_dim: int, scale: float,
) -> jax.Array:
    """Gather-based reference of the latent decode attention → [B, H, value_dim]."""
    B, H, Dk = q.shape
    pk = gather_dequant_pages(cache, None, layer_idx, block_tables, 1, Dk, q.dtype)[:, :, 0]
    ctx = jnp.arange(pk.shape[1], dtype=jnp.int32)
    mask = jnp.where(ctx[None, :] < lengths[:, None], 0.0, jnp.float32(NEG_INF))
    s = jnp.einsum("bhd,bcd->bhc", q, pk).astype(jnp.float32) * scale
    p = jax.nn.softmax(s + mask[:, None, :], axis=-1).astype(q.dtype)
    return jnp.einsum("bhc,bcv->bhv", p, pk[..., :value_dim])


@functools.partial(
    jax.jit,
    static_argnames=("value_dim", "scale", "pages_per_chunk", "interpret"),
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
) -> jax.Array:
    """The multi-query kernel at the latent geometry: H query heads against
    one shared key of Dk lanes, value = its first ``value_dim``; each page
    is read once. Returns [B, H, value_dim]."""
    o = _paged_attention_mq(
        q[:, None, None], cache, None, layer_idx, block_tables,
        jnp.asarray(lengths, jnp.int32)[:, None], None, None,
        pages_per_chunk, interpret, value_dim=value_dim, scale=scale,
    )
    return o[:, 0, 0]
