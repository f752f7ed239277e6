"""Functional Llama-family forward with a paged KV cache, in pure JAX.

TPU-first design notes (this is the part the reference delegates to
vLLM's CUDA kernels; here it is jnp/lax built for XLA:TPU):

- All shapes static: callers pad token runs / batch sizes to buckets
  (config.py) so each (bucket, variant) compiles once.
- ``lax.scan`` over stacked layer parameters → one compiled layer body,
  fast compiles even at 80 layers; the KV cache rides the scan carry and
  is updated with ``dynamic_update_index_in_dim`` so XLA keeps it
  in-place (callers donate it).
- Paged attention has two forms behind one signature an op
  (ops/paged_attention.py): gather-based (KV pages indexed out of the
  cache with a block table and attended densely with masking: the
  canonical XLA-friendly formulation, and the CPU and mesh path), and
  Pallas kernels that walk a row's own pages (decode, spec-verify,
  prefill), chosen once by the runner from what it can observe.
- GQA via reshape (no repeat): q [*, KVH, G, hd] against k [*, KVH, hd].
- bf16 weights/activations; norms, rope, softmax and logits in fp32.

Cache layout: k, v each ``[L, num_blocks, block_size, KVH*head_dim]``
(heads merged into lanes: the page ``[bs, KVH*hd]`` is exactly one dense
VMEM/DMA tile, so the Pallas kernel reads pages with zero layout
conversion — a 5D layout forced a whole-cache relayout copy per
pallas_call, measured ~9ms/layer on v5e). Block 0 is a reserved garbage
sink — padded positions write there.

Reference parity: replaces the engine forward of vLLM workers
(reference: components/backends/vllm/src/dynamo/vllm/main.py:90); block
semantics line up with dynamo_tpu.tokens / the reference's
lib/llm/src/tokens.rs so KV identity is consistent framework-wide.
"""

from __future__ import annotations

import functools
import importlib
import sys
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine.config import ModelConfig

Params = dict[str, Any]


class KVCache(NamedTuple):
    """Paged KV storage. With ``kv_quant="int8"`` the pages hold int8
    and a parallel per-position-per-head fp32 scale array rides along
    (``k_scale``/``v_scale`` are None for full-precision caches) — the
    same symmetric absmax scheme as engine/quant.py, at the granularity
    that keeps writes path-independent: a token's stored bytes depend
    only on its own K/V vector, never on its block's other occupants, so
    speculative-rollback junk and partial blocks cannot perturb already-
    written positions and greedy streams stay byte-stable across
    prefill/decode/spec write orders.

    **A page is one contiguous region of one pool**: ``kv[layer, block]`` is
    ``[2, bs, KVH*hd]``, the block's keys and then its values, so a kernel
    that walks a table moves a page's K and V with ONE DMA descriptor (two
    pools cost two, and a descriptor's start is core time nothing hides:
    PERF.md section 6, PRs 31 and 46). The ``(bs, KVH*hd)`` tiles are what the
    kernels hold in VMEM, and a mesh shards the last axis over KV heads.
    ``block_size`` is the one place the pool's shape is read for it. On the
    wire (engine/kv_transfer.py) a page still travels as ``(k, v[, k_scale,
    v_scale])``: ``split_kv`` and ``fuse_kv`` are that boundary.

    A ``block="longcat"`` model (engine/longcat.py) has no V: ``kv`` is
    ``[2L, N, bs, latent_page_width]`` (cache layer 2*layer + sub-block; a
    row is the normed latent and the rotated rope key every head shares,
    padded to whole lane tiles), a page one region as well.

    A ``block="lfm2"`` model (engine/lfm2.py) has K and V pages for its
    attention layers alone and a third pool under the same block ids,
    ``conv`` ``[conv layers, K, N, hidden]``: the block that holds position
    ``p`` keeps that layer's conv input ``z_p`` in slot ``p % K``.

    A ``block="sala"`` model (engine/sala.py) has K and V pages for its sparse
    layers, their compressed keys under the same block ids (``ckeys``
    ``[sparse layers, N, bs // stride, KVH*hd]``) and the lightning layers'
    matrix states in slots of their own (``state`` ``[lightning layers, S, H,
    d, d]`` in the cache's dtype), which block_manager/pool.py hands out.

    A ``block="dots3"`` model (engine/dots3.py) has latent pages for its full
    layers (``kv`` ``[full layers, N, bs, latent_page_width]``), the indexer's
    keys under the same block ids (``ikeys`` ``[full layers, N, bs,
    index_head_dim]``) and, for its window layers, a pool with block ids and a
    lifetime of its own (``window`` ``[window layers, Nw, bs, swa
    latent_page_width]``), which block_manager/pool.py hands out too."""

    kv: jax.Array  # [L, N, 2, bs, KVH*hd] — a page is K then V
    k_scale: jax.Array | None = None  # [L, N, bs, KVH] fp32 — int8 only
    v_scale: jax.Array | None = None
    conv: jax.Array | None = None     # block="lfm2" only
    ckeys: jax.Array | None = None    # block="sala" only
    state: jax.Array | None = None    # block="sala" only
    ikeys: jax.Array | None = None    # block="dots3" only
    window: jax.Array | None = None   # block="dots3" only

    @property
    def block_size(self) -> int:
        """Tokens a page (the axis ahead of the lanes, in every layout)."""
        return self.kv.shape[-2]


def fuse_kv(k: jax.Array, v: jax.Array) -> jax.Array:
    """k, v ``[..., bs, KVH*hd]`` (pools, or pages off the wire) → the pool's
    layout ``[..., 2, bs, KVH*hd]``."""
    return jnp.stack([k, v], axis=-3)


def split_kv(kv: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The pool's layout ``[..., 2, bs, KVH*hd]`` → (k, v), each
    ``[..., bs, KVH*hd]``: the order the wire and the tiers keep."""
    return kv[..., 0, :, :], kv[..., 1, :, :]


def write_kv_pages(kv: jax.Array, layer_idx, block_ids: jax.Array, k: jax.Array, v: jax.Array):
    """Whole pages of one layer: k, v ``[n, bs, KVH*hd]`` into pages
    ``block_ids`` [n], K then V, in one scatter."""
    return kv.at[layer_idx, block_ids].set(fuse_kv(k, v))


def write_kv_tokens(kv: jax.Array, layer_idx, blk: jax.Array, off: jax.Array, k: jax.Array, v: jax.Array):
    """One token a row: k, v ``[n, KVH*hd]`` at slot ``off`` [n] of page
    ``blk`` [n] of one layer, in ONE scatter of 2n rows of ``KVH*hd`` lanes,
    the K rows then the V rows. Not n windows of ``[2, KVH*hd]``: a window
    that strides over the page's token axis makes the chip's compiler lay
    the whole pool out token-major for the layer loop, behind a copy of the
    pool at every call (4.38 GB of temporaries in the Qwen decode window on
    a described v5e; PERF.md section 6, PR 46)."""
    n = blk.shape[0]
    part = jnp.repeat(jnp.arange(2, dtype=jnp.int32), n)
    return kv.at[layer_idx, jnp.tile(blk, 2), part, jnp.tile(off, 2)].set(jnp.concatenate([k, v]))


def pool_zeros(sharding):
    """→ ``zeros(shape, dtype)``, a pool born under ``sharding``
    (``ModelSharding.cache_sharding`` itself: rank → a jax Sharding over the
    kv-head axis, the last; or None): a pool sized for a mesh never has to fit one
    device first."""
    def zeros(shape, dtype):
        return jnp.zeros(shape, dtype, device=sharding and sharding(len(shape)))

    return zeros


def init_kv_cache(
    cfg: ModelConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
    kv_quant: str = "none", sharding=None,
) -> KVCache:
    """``sharding``: see ``pool_zeros``."""
    shape = (cfg.num_layers, num_blocks, 2, block_size, cfg.num_kv_heads * cfg.head_dim)
    zeros = pool_zeros(sharding)
    if kv_quant == "int8":
        sshape = (cfg.num_layers, num_blocks, block_size, cfg.num_kv_heads)
        return KVCache(
            zeros(shape, jnp.int8), zeros(sshape, jnp.float32), zeros(sshape, jnp.float32),
        )
    return KVCache(zeros(shape, dtype))


def kv_quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric absmax int8 quantization of fresh K/V rows along the
    head dim: x [..., KVH, hd] float → (int8 [..., KVH, hd], fp32 scale
    [..., KVH]). Mirrors quant.py's per-channel scheme (all-zero rows
    get scale 1.0 so dequant is exact zero). Deterministic per written
    vector — the invariant every golden-stability guarantee rests on."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(absmax > 0, absmax, 127.0) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params (benchmarks / tests). Real checkpoints load via
    engine.loader into the same pytree."""
    d, i = cfg.hidden_size, cfg.intermediate_size
    L = cfg.num_layers
    keys = jax.random.split(key, 8)

    def norm_init(k, fan_in, shape):
        return (jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)).astype(dtype)

    layers: dict[str, Any] = {
        "wq": norm_init(keys[1], d, (L, d, cfg.q_size)),
        "wk": norm_init(keys[2], d, (L, d, cfg.kv_size)),
        "wv": norm_init(keys[3], d, (L, d, cfg.kv_size)),
        "wo": norm_init(keys[4], cfg.q_size, (L, cfg.q_size, d)),
        "attn_norm": jnp.ones((L, d), dtype),
        "mlp_norm": jnp.ones((L, d), dtype),
    }
    if cfg.attn_bias:
        bkey = jax.random.fold_in(key, 31)
        layers["bq"] = (jax.random.normal(bkey, (L, cfg.q_size), jnp.float32) * 0.02).astype(dtype)
        layers["bk"] = (jax.random.normal(jax.random.fold_in(bkey, 1), (L, cfg.kv_size), jnp.float32) * 0.02).astype(dtype)
        layers["bv"] = (jax.random.normal(jax.random.fold_in(bkey, 2), (L, cfg.kv_size), jnp.float32) * 0.02).astype(dtype)
    if cfg.num_experts:
        E = cfg.num_experts
        ie = cfg.moe_intermediate_size or i
        layers["w_router"] = norm_init(jax.random.fold_in(key, 7), d, (L, d, E))
        layers["moe_gate"] = norm_init(keys[5], d, (L, E, d, ie))
        layers["moe_up"] = norm_init(keys[6], d, (L, E, d, ie))
        layers["moe_down"] = norm_init(keys[7], ie, (L, E, ie, d))
    else:
        layers["w_gate"] = norm_init(keys[5], d, (L, d, i))
        layers["w_up"] = norm_init(keys[6], d, (L, d, i))
        layers["w_down"] = norm_init(keys[7], i, (L, i, d))
    params: Params = {
        "embed": norm_init(keys[0], d, (cfg.vocab_size, d)),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm_init(jax.random.fold_in(key, 99), d, (d, cfg.vocab_size))
    return params


def _rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    norm = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [..., T, heads, hd] (or [..., heads, hd] with
    positions [...]); positions broadcast against x's leading dims."""
    hd = x.shape[-1]
    half = hd // 2
    freq = jnp.arange(half, dtype=jnp.float32)
    inv_freq = theta ** (-freq / half)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., half]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _dot_q(x: jax.Array, lp: dict, name: str) -> jax.Array:
    """x @ lp[name], dequantizing int8 weights on the fly. The scale is
    applied POST-matmul on the (small) output — XLA fuses the int8→bf16
    convert into the matmul operand read, so weight traffic stays 1
    byte/param (engine/quant.py; measured 2.4x on v5e)."""
    w = lp[name]
    if w.dtype == jnp.int8:
        y = jnp.dot(x, w.astype(x.dtype))
        return y * lp[name + "_scale"].astype(x.dtype)
    return jnp.dot(x, w)


# The blocks there are: this module's own, then those with a module of their name under engine/.
BLOCK_MODULES = ("llama", "longcat", "lfm2", "sala", "dots3", "deepseek")


def block_module(cfg: ModelConfig):
    """The module that runs ``cfg.block``, chosen once (engine/runner.py):
    this one or the ``BLOCK_MODULES`` entry's under engine/. Each has ``init_params``,
    ``init_kv_cache`` and the jitted ``prefill``, ``prefill_batch``,
    ``decode_step`` and ``multi_decode`` under these names; a block that
    routes (``routed_layers(cfg)``) returns a routing histogram after what
    these return, one with a second cache names its kind (``side_cache``,
    engine/side.py), and ``UNCARRIED`` is what KV transfer's refusal and live
    migration's say they cannot carry of its cache."""
    if cfg.block not in BLOCK_MODULES:
        raise ValueError(f"no module runs block={cfg.block!r} ({', '.join(BLOCK_MODULES)})")
    if cfg.block != BLOCK_MODULES[0]:
        return importlib.import_module(f"dynamo_tpu.engine.{cfg.block}")
    return sys.modules[__name__]


def refuse_block(cfg: ModelConfig, mechanism: str) -> None:
    """The mechanisms of this module that assume per-head K and V pages and
    the attention-then-FFN layer say so by name for any other block
    (EngineArgs refuses the flag-driven ones at construction)."""
    if cfg.block != "llama":
        raise ValueError(
            f"{mechanism} cannot run a block={cfg.block!r} model: it assumes "
            f"per-head K and V pages and the attention-then-FFN layer"
        )


def _embed_rows(params: Params, tokens: jax.Array, dtype) -> jax.Array:
    e = params["embed"][tokens]
    if e.dtype == jnp.int8:
        scale = params["embed_scale"][tokens].astype(dtype)
        return e.astype(dtype) * scale[..., None]
    return e


def _qkv(h: jax.Array, lp: dict, cfg: ModelConfig):
    """Fused-layout q/k/v projections with optional Qwen2-style bias
    (o_proj is bias-free in that family). Shapes: h [..., D] →
    ([..., q_size], [..., kv_size], [..., kv_size])."""
    q = _dot_q(h, lp, "wq")
    k = _dot_q(h, lp, "wk")
    v = _dot_q(h, lp, "wv")
    if cfg.attn_bias:
        q = q + lp["bq"].astype(q.dtype)
        k = k + lp["bk"].astype(k.dtype)
        v = v + lp["bv"].astype(v.dtype)
    return q, k, v


def _lora_apply(y: jax.Array, h: jax.Array, A: jax.Array, B_: jax.Array,
                slots: jax.Array) -> jax.Array:
    """Batched gathered LoRA matmul (Punica's BGMV shape): per batch row
    b, ``y[b] += (h[b] @ A[slots[b]]) @ B[slots[b]]``. ``A`` [S, in, r]
    and ``B_`` [S, r, out] are one layer's slice of the device adapter
    bank; ``slots`` [B] int32 names each row's resident adapter slot,
    -1 = base. The whole mixed batch rides two skinny einsums — no
    per-adapter sub-batching, which is what keeps multi-tenant batches
    at ~base throughput.

    Base rows take a ``where`` on the ORIGINAL projection values, never
    an add-of-zero (bf16 ``-0.0 + 0.0`` would flip the sign bit), so a
    base row in an adapter-mixed batch is bit-identical to the same row
    on a no-LoRA engine — the byte-identity contract the golden suite
    pins. Per-adapter alpha/rank scaling is folded into B at upload
    (engine/lora.py), so no scalar operand rides here."""
    idx = jnp.maximum(slots, 0)
    Ag = jnp.take(A, idx, axis=0)   # [B, in, r]
    Bg = jnp.take(B_, idx, axis=0)  # [B, r, out]
    if h.ndim == 2:                  # decode: h [B, in]
        t = jnp.einsum("bd,bdr->br", h, Ag)
        delta = jnp.einsum("br,bro->bo", t, Bg)
        mask = (slots >= 0)[:, None]
    else:                            # prefill / spec-verify: h [B, T, in]
        t = jnp.einsum("btd,bdr->btr", h, Ag)
        delta = jnp.einsum("btr,bro->bto", t, Bg)
        mask = (slots >= 0)[:, None, None]
    return jnp.where(mask, y + delta.astype(y.dtype), y)


def _qkv_lora(h: jax.Array, lp: dict, cfg: ModelConfig,
              ll: dict | None, slots: jax.Array | None):
    """_qkv plus the per-row adapter deltas when an adapter bank layer
    slice ``ll`` rides the dispatch (None = the exact base path)."""
    q, k, v = _qkv(h, lp, cfg)
    if ll is not None:
        q = _lora_apply(q, h, ll["qa"], ll["qb"], slots)
        k = _lora_apply(k, h, ll["ka"], ll["kb"], slots)
        v = _lora_apply(v, h, ll["va"], ll["vb"], slots)
    return q, k, v


def _wo_lora(o: jax.Array, lp: dict, ll: dict | None,
             slots: jax.Array | None) -> jax.Array:
    """o-projection with the optional per-row adapter delta."""
    y = _dot_q(o, lp, "wo")
    if ll is not None:
        y = _lora_apply(y, o, ll["oa"], ll["ob"], slots)
    return y


def _mlp(x, lp):
    g = _dot_q(x, lp, "w_gate")
    u = _dot_q(x, lp, "w_up")
    return _dot_q(jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u, lp, "w_down")


def _moe(x: jax.Array, lp: dict, cfg: ModelConfig) -> jax.Array:
    """Top-k routed mixture of experts over the FFN. x: [..., D].

    Expert-parallel formulation: every expert's FFN is computed for every
    token as sharded einsums over the expert axis — with experts sharded
    over the ``ep`` mesh axis each device computes only ITS experts for
    all tokens and the weighted combine is a psum XLA inserts (SPMD
    wide-EP; reference reaches this only through engine flags,
    trtllm_utils.py:140-143). Dense-over-local-experts trades FLOPs for
    perfectly regular MXU work — the standard XLA MoE shape (token-
    dropping/segment-matmul sparsity is a later Pallas upgrade)."""
    orig_shape = x.shape
    D = orig_shape[-1]
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    logits = jnp.dot(xt, lp["w_router"]).astype(jnp.float32)     # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(probs, cfg.num_experts_per_token)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)          # mixtral renorm
    weights = jnp.zeros_like(probs)
    weights = weights.at[jnp.arange(T)[:, None], topi].set(topv)  # [T, E] sparse
    g = jnp.einsum("td,edi->tei", xt, lp["moe_gate"])
    u = jnp.einsum("td,edi->tei", xt, lp["moe_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u   # [T, E, ie]
    y = jnp.einsum("tei,te,eid->td", h, weights.astype(xt.dtype), lp["moe_down"])
    return y.reshape(orig_shape)


def _ffn(x: jax.Array, lp: dict, cfg: ModelConfig) -> jax.Array:
    return _moe(x, lp, cfg) if cfg.num_experts else _mlp(x, lp)


def _logits(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    x = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    if cfg.tie_embeddings:
        emb = params["embed"]
        if emb.dtype == jnp.int8:
            y = jnp.dot(x, emb.astype(x.dtype).T).astype(jnp.float32)
            return y * params["embed_scale"][None, :] if y.ndim == 2 else y * params["embed_scale"]
        return jnp.dot(x, emb.T).astype(jnp.float32)
    head = params["lm_head"]
    if head.dtype == jnp.int8:
        y = jnp.dot(x, head.astype(x.dtype)).astype(jnp.float32)
        return y * params["lm_head_scale"][None, :] if y.ndim == 2 else y * params["lm_head_scale"]
    return jnp.dot(x, head).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Prefill: one (possibly prefix-cached) sequence, padded to a length bucket
# ---------------------------------------------------------------------------


def prefill_batch_impl(
    cfg: ModelConfig,
    params: Params,
    cache: KVCache,
    tokens: jax.Array,        # [Bp, T_pad] suffix token ids per row
    block_tables: jax.Array,  # [Bp, W] int32 — blocks for each FULL sequence
    start_pos: jax.Array,     # [Bp] int32 — first suffix position (block-aligned)
    true_len: jax.Array,      # [Bp] int32 — true total length (0 = inactive row)
    lora: dict | None = None,         # adapter bank {qa..ob: [L, S, ...]}
    adapter_slots: jax.Array | None = None,  # [Bp] int32, -1 = base row
    *,
    attn_impl: str = "auto",  # static: "auto" | "xla" | "pallas" | "pallas_interpret"
) -> tuple[jax.Array, KVCache]:
    """Packed prefill: run Bp sequences' suffixes through the model in ONE
    dispatch, each attending to its own cached prefix pages. Returns
    last-token logits [Bp, V] and the updated cache.

    Attention (ops/paged_attention.py): the suffix's K and V are written
    to their pages first, and the Pallas kernel attends out of the pages,
    walking only the context a chunk can see; the XLA form gathers the
    table's whole width and is the CPU, mesh and int8-KV path
    (``resolve_prefill_impl``).

    One-at-a-time prefill was the r3 TTFT killer (VERDICT r3 weak #2):
    each admission paid its own dispatch and ran tiny matmuls alone.
    Packing an admission wave batches the MXU work and collapses the
    dispatch count. Rows are padded to a shared (T, W) bucket; inactive
    rows (true_len=0) write only to garbage block 0.

    Prefix caching contract per row: positions [0, start_pos) are already
    present in the blocks named by ``block_tables`` (whole blocks only);
    suffix positions [start_pos, true_len) are computed here."""
    Bp, T = tokens.shape
    bs = cache.block_size
    KVH, hd = cfg.num_kv_heads, cfg.head_dim
    sfx = jnp.arange(T, dtype=jnp.int32)
    suffix_positions = start_pos[:, None] + sfx[None, :]          # [Bp, T]

    compute_dtype = params["layers"]["attn_norm"].dtype
    with jax.named_scope("embed"):
        x = _embed_rows(params, tokens, compute_dtype)  # [Bp, T, D]

    # Suffix block scatter targets per row: suffix-local block j lands in
    # table slot start_pos//bs + j (start_pos is block-aligned).
    nb = T // bs
    slot = start_pos[:, None] // bs + jnp.arange(nb, dtype=jnp.int32)[None, :]
    padded_tables = jnp.concatenate(
        [block_tables, jnp.zeros((Bp, nb), jnp.int32)], axis=1
    )
    sfx_block_ids = jnp.take_along_axis(padded_tables, slot, axis=1)  # [Bp, nb]
    # Padded suffix blocks (beyond true_len) → garbage block 0.
    blk_start = start_pos[:, None] + jnp.arange(nb, dtype=jnp.int32)[None, :] * bs
    sfx_block_ids = jnp.where(blk_start < true_len[:, None], sfx_block_ids, 0)
    flat_ids = sfx_block_ids.reshape(Bp * nb)

    G = cfg.num_heads // KVH

    from dynamo_tpu.ops.paged_attention import (
        paged_prefill_attention,
        paged_prefill_attention_xla,
        resolve_prefill_impl,
    )

    impl, _ = resolve_prefill_impl(attn_impl, cfg, bs, cache.k_scale is not None)

    def layer(carry, xs):
        x, kv_cache, k_scale, v_scale = carry
        if lora is not None:
            lp, ll, layer_idx = xs
        else:
            (lp, layer_idx), ll = xs, None
        with jax.named_scope("attn_qkv"):
            h = _rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _qkv_lora(h, lp, cfg, ll, adapter_slots)
            q = q.reshape(Bp, T, cfg.num_heads, hd)
            k = k.reshape(Bp, T, KVH, hd)
            v = v.reshape(Bp, T, KVH, hd)
            q = _rope(q, suffix_positions, cfg.rope_theta)
            k = _rope(k, suffix_positions, cfg.rope_theta)

        with jax.named_scope("kv_write"):
            # Write all rows' suffix pages, K and V, in one scatter (rows own
            # disjoint blocks; duplicates only at garbage block 0).
            # int8 storage: quantize at page-write time, scales ride a
            # parallel scatter; the suffix still self-attends its exact
            # register values below (only LATER readers see the rounding).
            k_w, v_w = k, v
            if k_scale is not None:
                (k_w, ksc), (v_w, vsc) = kv_quantize(k), kv_quantize(v)
                k_scale = k_scale.at[layer_idx, flat_ids].set(
                    ksc.reshape(Bp * nb, bs, KVH)
                )
                v_scale = v_scale.at[layer_idx, flat_ids].set(
                    vsc.reshape(Bp * nb, bs, KVH)
                )
            kv_cache = write_kv_pages(
                kv_cache, layer_idx, flat_ids,
                k_w.reshape(Bp * nb, bs, KVH * hd), v_w.reshape(Bp * nb, bs, KVH * hd),
            )

        with jax.named_scope("attn"):
            qg = q.reshape(Bp, T, KVH, G, hd)
            if impl == "xla":
                o = paged_prefill_attention_xla(
                    qg, k, v, kv_cache, layer_idx, block_tables,
                    start_pos, true_len, k_scale, v_scale,
                )
            else:
                o = paged_prefill_attention(
                    qg, kv_cache, layer_idx, block_tables, start_pos, true_len,
                    interpret=(impl == "pallas_interpret"),
                )
            o = o.reshape(Bp, T, cfg.q_size)
        with jax.named_scope("attn_out"):
            x = x + _wo_lora(o, lp, ll, adapter_slots)
        with jax.named_scope("ffn"):
            h = _rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            x = x + _ffn(h, lp, cfg)
        return (x, kv_cache, k_scale, v_scale), None

    layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    xs_in = (
        (params["layers"], lora, layer_ids) if lora is not None
        else (params["layers"], layer_ids)
    )
    (x, kv_cache, k_scale, v_scale), _ = lax.scan(
        layer, (x, cache.kv, cache.k_scale, cache.v_scale), xs_in,
    )

    last = jnp.clip(true_len - start_pos - 1, 0, T - 1)      # [Bp]
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]  # [Bp, D]
    with jax.named_scope("logits"):
        logits = _logits(cfg, params, x_last)
    return logits, KVCache(kv_cache, k_scale, v_scale)


def prefill_impl(
    cfg: ModelConfig,
    params: Params,
    cache: KVCache,
    tokens: jax.Array,       # [T_pad] suffix token ids (prompt minus cached prefix)
    block_table: jax.Array,  # [W] int32 — blocks for the FULL sequence
    start_pos: jax.Array,    # scalar int32 — first suffix position (block-aligned)
    true_len: jax.Array,     # scalar int32 — true total length (prefix + suffix)
    lora: dict | None = None,
    adapter_slot: jax.Array | None = None,  # scalar int32, -1 = base
    *,
    attn_impl: str = "auto",
) -> tuple[jax.Array, KVCache]:
    """Single-sequence prefill: the Bp=1 case of ``prefill_batch_impl``
    (kept as the chunked-prefill / compatibility entry point)."""
    logits, cache = prefill_batch_impl(
        cfg, params, cache,
        tokens[None, :], block_table[None, :],
        jnp.asarray(start_pos, jnp.int32).reshape(1),
        jnp.asarray(true_len, jnp.int32).reshape(1),
        lora,
        None if adapter_slot is None
        else jnp.asarray(adapter_slot, jnp.int32).reshape(1),
        attn_impl=attn_impl,
    )
    return logits[0], cache


# ---------------------------------------------------------------------------
# Decode: one token for each of B sequences, padded to a batch bucket
# ---------------------------------------------------------------------------


def decode_step_impl(
    cfg: ModelConfig,
    params: Params,
    cache: KVCache,
    tokens: jax.Array,        # [B] int32 — current token per sequence
    positions: jax.Array,     # [B] int32 — position of that token (seq_len-1)
    block_tables: jax.Array,  # [B, W] int32
    active: jax.Array,        # [B] bool — padding rows are False
    lora: dict | None = None,         # adapter bank {qa..ob: [L, S, ...]}
    adapter_slots: jax.Array | None = None,  # [B] int32, -1 = base row
    *,
    attn_impl: str = "auto",  # static: "auto" | "xla" | "pallas" | "pallas_interpret"
) -> tuple[jax.Array, KVCache]:
    """One decode step for a batch. Writes each sequence's new KV at its
    position, attends over its pages, returns logits [B, V] (fp32).

    Attention backend (ops/paged_attention.py): the Pallas kernel walks
    each row's true pages (work ∝ sum(lengths)); the XLA path gathers the
    padded table width (work ∝ B*W*bs) and is the CPU/multi-device
    fallback."""
    from dynamo_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_xla,
        resolve_attn_impl,
    )

    impl = resolve_attn_impl(attn_impl)
    B = tokens.shape[0]
    W = block_tables.shape[1]
    bs = cache.block_size

    compute_dtype = params["layers"]["attn_norm"].dtype
    with jax.named_scope("embed"):
        x = _embed_rows(params, tokens, compute_dtype)  # [B, D]

    blk = jnp.where(active, block_tables[jnp.arange(B), positions // bs], 0)
    off = jnp.where(active, positions % bs, 0)
    # token at `positions` attends [0, positions]; inactive rows attend nothing
    lengths = jnp.where(active, positions + 1, 0)

    G = cfg.num_heads // cfg.num_kv_heads

    def layer(carry, xs):
        x, kv_cache, k_scale, v_scale = carry
        if lora is not None:
            lp, ll, layer_idx = xs
        else:
            (lp, layer_idx), ll = xs, None
        with jax.named_scope("attn_qkv"):
            h = _rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _qkv_lora(h, lp, cfg, ll, adapter_slots)
            q = q.reshape(B, cfg.num_heads, cfg.head_dim)
            k = k.reshape(B, cfg.num_kv_heads, cfg.head_dim)
            v = v.reshape(B, cfg.num_kv_heads, cfg.head_dim)
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
            qg = q.reshape(B, cfg.num_kv_heads, G, cfg.head_dim)

        with jax.named_scope("kv_write"):
            # In-place scatter of the new token's KV (inactive rows → garbage
            # block 0), then paged attention over [0, positions]. int8
            # storage quantizes the fresh row at write time, so this step's
            # OWN token is read back dequantized — exactly what any later
            # step would see, keeping the math write-order-independent.
            k_w, v_w = k, v
            if k_scale is not None:
                (k_w, ksc), (v_w, vsc) = kv_quantize(k), kv_quantize(v)
                k_scale = k_scale.at[layer_idx, blk, off].set(ksc)
                v_scale = v_scale.at[layer_idx, blk, off].set(vsc)
            kv_cache = write_kv_tokens(
                kv_cache, layer_idx, blk, off,
                k_w.reshape(B, cfg.kv_size), v_w.reshape(B, cfg.kv_size))
        with jax.named_scope("attn"):
            if impl == "xla":
                o = paged_decode_attention_xla(
                    qg, kv_cache, layer_idx, block_tables, lengths,
                    k_scale, v_scale,
                )
            else:
                o = paged_decode_attention(
                    qg, kv_cache, layer_idx, block_tables, lengths,
                    k_scale, v_scale,
                    interpret=(impl == "pallas_interpret"),
                )
            o = o.reshape(B, cfg.q_size)
        with jax.named_scope("attn_out"):
            x = x + _wo_lora(o, lp, ll, adapter_slots)
        with jax.named_scope("ffn"):
            h = _rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            x = x + _ffn(h, lp, cfg)
        return (x, kv_cache, k_scale, v_scale), None

    layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    xs_in = (
        (params["layers"], lora, layer_ids) if lora is not None
        else (params["layers"], layer_ids)
    )
    (x, kv_cache, k_scale, v_scale), _ = lax.scan(
        layer, (x, cache.kv, cache.k_scale, cache.v_scale), xs_in,
    )

    with jax.named_scope("logits"):
        logits = _logits(cfg, params, x)  # [B, V]
    return logits, KVCache(kv_cache, k_scale, v_scale)


def multi_decode_impl(
    cfg: ModelConfig,
    num_steps: int,           # static — fused substep count
    mode: str,                # static — "greedy" | "simple" | "full"
    top_n: int,               # static — top-n alternative logprobs (0 = off)
    params: Params,
    cache: KVCache,
    tokens: jax.Array,        # [B] int32 — current token per sequence
    positions: jax.Array,     # [B] int32 — position of that token
    block_tables: jax.Array,  # [B, W] int32 (must cover positions+num_steps)
    active: jax.Array,        # [B] bool
    temperature: jax.Array,   # [B] fp32 (<=0 → greedy)
    seeds: jax.Array,         # [B] uint32 per-row sample seed
    steps0: jax.Array,        # [B] int32 per-row emission index of first substep
    top_k: jax.Array,         # [B] int32 (mode="full"; 0 = off)
    top_p: jax.Array,         # [B] fp32 (mode="full"; 1.0 = off)
    freq_penalty: jax.Array,  # [B] fp32 (mode="full")
    pres_penalty: jax.Array,  # [B] fp32 (mode="full")
    penalty_tokens: jax.Array,  # [B, L] int32 generated-so-far ids, -1 pad (mode="full")
    chain_mask: jax.Array | None = None,  # [B] bool — row chains from last_toks
    chain_src: jax.Array | None = None,   # [B] int32 — SLOT in last_toks
    last_toks: jax.Array | None = None,   # [slots+1] int32 — per-slot latest
                                          # sampled token (device). Fed by every
                                          # window's fold and admission samples,
                                          # in dispatch order, so a chained row
                                          # reads the newest on-device token for
                                          # its slot even with several windows
                                          # in flight (pipeline_depth > 1).
    lora: dict | None = None,             # adapter bank {qa..ob: [L, S, ...]}
    adapter_slots: jax.Array | None = None,  # [B] int32, -1 = base row
    *,
    attn_impl: str = "auto",
) -> tuple[jax.Array, jax.Array, KVCache]:
    """``num_steps`` fused decode+sample steps: sampled tokens feed back on
    device, so the host fetches once per num_steps×B tokens instead of
    per token — and with the engine's window pipeline, consecutive
    windows chain through ``last_toks`` so the device never waits for a
    host fetch either. It saves a host sync (a device round trip) and a
    dispatch per token; the same trick as vLLM's multi-step scheduling,
    expressed as lax.scan.

    Sampler modes (static → three compiled variants per shape):
    - "greedy": every row argmax; no RNG at all.
    - "simple": temperature via gumbel-max; no sort.
    - "full": frequency/presence penalties + exact top-k/top-p. Penalty
      counts start from ``penalty_tokens`` and are updated ON DEVICE with
      each sampled token, so the whole window stays fused — one request
      with sampler knobs no longer collapses the batch to per-step decode
      (VERDICT r2 weak #5).

    Rows that hit a stop condition mid-window keep generating; the host
    truncates after the sync (wasted work is bounded by num_steps).

    Returns (tokens [num_steps, B], logprobs [num_steps, B] fp32,
    top_vals [num_steps, B, top_n], top_ids [num_steps, B, top_n], cache):
    logprobs are the chosen-token log-softmax values (pre-penalty, raw
    model distribution — OpenAI reports model logprobs, not sampler-
    modified ones); top_* are the raw-distribution ranked alternatives
    (zero-sized when top_n == 0)."""
    def step(cache, tok, pos):
        logits, cache = decode_step_impl(
            cfg, params, cache, tok, pos, block_tables, active,
            lora, adapter_slots, attn_impl=attn_impl,
        )
        return logits, cache, None

    return decode_window(
        step, None, cfg.vocab_size, num_steps, mode, top_n, cache, tokens,
        positions, temperature, seeds, steps0, top_k, top_p, freq_penalty,
        pres_penalty, penalty_tokens, chain_mask, chain_src, last_toks,
    )[:5]


def decode_window(
    step, aux0, V: int, num_steps: int, mode: str, top_n: int,
    cache: KVCache, tokens, positions, temperature, seeds, steps0, top_k,
    top_p, freq_penalty, pres_penalty, penalty_tokens,
    chain_mask=None, chain_src=None, last_toks=None,
):
    """The fused window ``multi_decode_impl`` describes, over any block's
    decode step: ``step(cache, tokens, positions) -> (logits, cache, aux)``.
    ``aux`` is a pytree summed over the substeps from ``aux0`` (None for a
    block that has nothing to count; engine/longcat.py sums its routing
    histogram) and returned after the cache."""
    from dynamo_tpu.engine.sampler import (
        apply_penalties,
        sample_step,
        token_counts,
        token_logprobs,
        top_k_logprobs,
    )

    B = tokens.shape[0]
    if chain_mask is not None:
        # Window pipeline: chained rows take their input token from the
        # previous window's on-device output — composed INSIDE the jit so
        # the variant count stays fixed (an eager scatter with
        # data-dependent index counts compiled per distinct count).
        tokens = jnp.where(chain_mask, last_toks[chain_src], tokens)
    counts0 = (
        token_counts(penalty_tokens, V) if mode == "full"
        else jnp.zeros((B, 1), jnp.float32)  # unused placeholder carry
    )

    def row_gumbel(i):
        def noise(s, e):
            key = jax.random.fold_in(jax.random.PRNGKey(s), e)
            return jax.random.gumbel(key, (V,), jnp.float32)

        return jax.vmap(noise)(seeds, steps0 + i)

    def substep(carry, i):
        cache, tok, pos, counts, aux = carry
        logits, cache, more = step(cache, tok, pos)
        aux = jax.tree.map(jnp.add, aux, more)
        with jax.named_scope("sample"):
            if mode == "greedy":
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            elif mode == "simple":
                greedy = temperature < 1e-5
                temp = jnp.where(greedy, 1.0, temperature)
                scaled = logits / temp[:, None]
                noisy = jnp.where(greedy[:, None], logits, scaled + row_gumbel(i))
                nxt = jnp.argmax(noisy, axis=-1).astype(jnp.int32)
            else:
                penalized = apply_penalties(logits, counts, freq_penalty, pres_penalty)
                nxt = sample_step(penalized, temperature, top_k, top_p, row_gumbel(i))
                counts = counts.at[jnp.arange(B), nxt].add(1.0)
            logp = token_logprobs(logits, nxt)
            if top_n > 0:
                tvals, tids = top_k_logprobs(logits, top_n)
            else:
                tvals = jnp.zeros((B, 0), jnp.float32)
                tids = jnp.zeros((B, 0), jnp.int32)
        return (cache, nxt, pos + 1, counts, aux), (nxt, logp, tvals, tids)

    (cache, _, _, _, aux), (toks, logps, top_vals, top_ids) = lax.scan(
        substep, (cache, tokens, positions, counts0, aux0),
        jnp.arange(num_steps, dtype=jnp.int32),
    )
    return toks, logps, top_vals, top_ids, cache, aux  # [num_steps, B(, top_n)]


def spec_verify_impl(
    cfg: ModelConfig,
    S1: int,                  # static — draft slots + 1 ([last, d1..dS])
    mode: str,                # static — "greedy" | "simple"
    top_n: int,               # static — top-n alternative logprobs (0 = off)
    params: Params,
    cache: KVCache,
    tokens: jax.Array,        # [B, S1] int32 — [last_token, draft_1..draft_S]
    positions0: jax.Array,    # [B] int32 — position of last_token
    draft_len: jax.Array,     # [B] int32 — true draft length per row (≤ S1-1)
    block_tables: jax.Array,  # [B, W] int32 (must cover positions0+draft_len)
    active: jax.Array,        # [B] bool
    temperature: jax.Array,   # [B] fp32 (<=0 → greedy row)
    seeds: jax.Array,         # [B] uint32 per-row sample seed
    steps0: jax.Array,        # [B] int32 per-row emission index of the first token
    tree_parents: jax.Array | None = None,  # [B, S1] int32 — tree mode (below)
    tree_anc: jax.Array | None = None,      # [B, S1, S1] int8 ancestor-or-self
    tree_depth: jax.Array | None = None,    # [B, S1] int32 per-node depth
    mask_bits: jax.Array | None = None,     # [B, S1, W32] uint32 per-node grammar masks
    lora: dict | None = None,               # adapter bank {qa..ob: [L, S, ...]}
    adapter_slots: jax.Array | None = None,  # [B] int32, -1 = base row
    *,
    fused: bool = True,       # static — single-pass forward vs stepwise scan
    attn_impl: str = "auto",  # attention backend: stepwise decode steps AND
                              # the fused path's gather (Pallas fused-gather
                              # kernel on TPU, XLA gather otherwise)
) -> tuple[jax.Array, ...]:
    """Speculative verify: score S1 consecutive positions per row in one
    dispatch. Input j writes its KV at positions0+j and position j's
    logits score the token FOLLOWING input j, exactly as
    ``decode_step_impl`` would have on the j-th sequential step.

    Two forward shapes behind the same contract:

    - ``fused=True`` (default): ONE forward over all S1 positions — the
      single weight stream that makes speculation a bandwidth win
      (tokens-per-weight-pass > 1). Mathematically identical to the
      stepwise path; floating-point reduction order in the batched
      matmuls can differ from the dense step's at the last ulp on some
      backends (greedy token streams match in practice, reported logprob
      VALUES may differ by ~1e-7).
    - ``fused=False``: a teacher-forced ``lax.scan`` of the SAME
      ``decode_step_impl`` the dense path runs — bitwise identical to
      dense decode on every backend by construction. Weights stream S1
      times, so this keeps only the dispatch/fetch saving (one host
      roundtrip per S1 tokens); it is the parity/debug mode and the
      golden suite's byte-identity anchor.

    Per-position validity: slot j of a row is live when j <= draft_len
    (slot 0, the last real token, always is). Dead slots and inactive
    rows scatter their KV to garbage block 0, and causal masking keeps
    live queries from ever seeing them. KV written for drafts BEYOND the
    accepted run is junk by construction — the engine rolls
    ``next_write_pos`` back to the acceptance boundary and the very next
    dispatch rewrites those positions (block lookahead already covers
    them), so nothing downstream observes it.

    **Tree mode** (``tree_parents`` given): the S1 slots form a draft
    TREE (SpecInfer) instead of a chain. Node j writes its KV at SLOT
    position positions0+j (slots are distinct even when depths collide),
    RoPE-rotates at its true sequence position positions0+depth[j], and
    attends paged history plus exactly its ancestor-or-self slots via
    the [S1, S1] topology mask (ops.paged_spec_attention ``anc``).
    Acceptance walks the longest accepted root path
    (sampler.spec_tree_acceptance — argmax chain for greedy rows,
    multi-round rejection sampling for sampled ones), and the accepted
    path's KV is then COMPACTED on device into contiguous positions
    positions0+1..positions0+a (non-accepted branches' writes are
    redirected to garbage block 0) — so the engine's rollback contract
    is identical to the linear path's. Tree mode always runs the fused
    forward: a branched topology has no stepwise decode-step equivalent
    (``fused=False`` is the linear parity anchor only).

    Returns (out [B, S1] emitted tokens, n_emit [B] = accepted+1,
    logps [B, S1] raw chosen-token logprobs, cand [B, S1] per-node
    argmax predictions — free Jacobi-pool food for the drafter,
    top_vals [B, S1, top_n], top_ids [B, S1, top_n], last_tok [B] =
    out[b, n_emit-1] for the chain-buffer fold, cache)."""
    refuse_block(cfg, "speculation (spec_verify_impl)")
    from dynamo_tpu.engine.sampler import (
        spec_acceptance,
        spec_tree_acceptance,
        top_k_logprobs,
    )
    from dynamo_tpu.ops.paged_attention import (
        paged_spec_attention,
        paged_spec_attention_xla,
        resolve_attn_impl,
        spec_kernel_fits,
    )

    B, T = tokens.shape
    bs = cache.block_size
    KVH, hd = cfg.num_kv_heads, cfg.head_dim
    tree = tree_parents is not None
    slot = jnp.arange(T, dtype=jnp.int32)[None, :]
    use = active[:, None] & (slot <= draft_len[:, None])                 # [B, T]
    # Write position per slot (always slot-ordered: distinct cache slots
    # regardless of tree shape) and RoPE position per node (its true
    # sequence depth — equal to the slot index for a chain).
    wpos = positions0[:, None] + slot                                    # [B, T]
    pos = wpos if not tree else positions0[:, None] + tree_depth

    if fused or tree:
        compute_dtype = params["layers"]["attn_norm"].dtype
        x = _embed_rows(params, tokens, compute_dtype)  # [B, T, D]

        blk = jnp.where(
            use, jnp.take_along_axis(block_tables, wpos // bs, axis=1), 0
        )
        off = jnp.where(use, wpos % bs, 0)
        if tree:
            # Per-query paged-history horizon; the slot window rides on
            # top of it under the topology mask (dead queries/slots are
            # masked out of the anc bits entirely).
            lengths = jnp.where(use, positions0[:, None], 0)
            anc = (
                (tree_anc != 0) & use[:, :, None] & use[:, None, :]
            ).astype(jnp.int8)
        else:
            lengths = jnp.where(use, pos + 1, 0)  # query j attends [0, pos_j]
            anc = None

        G = cfg.num_heads // KVH
        # Fused spec-verify gather (ops.paged_spec_attention): one Pallas
        # kernel walks each row's true pages for all T queries and
        # dequantizes in-register — no materialized relayout copy of the
        # gathered table (the ~9ms/layer XLA tax). Takes the XLA gather
        # when the query columns exceed the 128-lane budget (the runner's
        # start line names the T values that will) or off the TPU.
        impl = resolve_attn_impl(attn_impl)
        use_kernel = (
            impl in ("pallas", "pallas_interpret")
            and spec_kernel_fits(cfg.num_heads, T)
        )

        def layer(carry, xs):
            x, kv_cache, k_scale, v_scale = carry
            if lora is not None:
                lp, ll, layer_idx = xs
            else:
                (lp, layer_idx), ll = xs, None
            h = _rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _qkv_lora(h, lp, cfg, ll, adapter_slots)
            q = q.reshape(B, T, cfg.num_heads, hd)
            k = k.reshape(B, T, KVH, hd)
            v = v.reshape(B, T, KVH, hd)
            q = _rope(q, pos, cfg.rope_theta)
            k = _rope(k, pos, cfg.rope_theta)
            qg = q.reshape(B, T, KVH, G, hd)

            # Scatter all T new KV entries, then gather-attend: in-chunk
            # keys come back out of the pages, so query j sees inputs
            # 0..j through the same path the dense step does
            # (write-then-attend) — including the same quantization
            # rounding when the cache is int8.
            k_w, v_w = k, v
            if k_scale is not None:
                (k_w, ksc), (v_w, vsc) = kv_quantize(k), kv_quantize(v)
                k_scale = k_scale.at[layer_idx, blk.reshape(-1), off.reshape(-1)].set(
                    ksc.reshape(B * T, KVH)
                )
                v_scale = v_scale.at[layer_idx, blk.reshape(-1), off.reshape(-1)].set(
                    vsc.reshape(B * T, KVH)
                )
            kv_cache = write_kv_tokens(
                kv_cache, layer_idx, blk.reshape(-1), off.reshape(-1),
                k_w.reshape(B * T, cfg.kv_size), v_w.reshape(B * T, cfg.kv_size))
            if use_kernel:
                o = paged_spec_attention(
                    qg, kv_cache, layer_idx, block_tables, lengths,
                    k_scale, v_scale, anc,
                    interpret=(impl == "pallas_interpret"),
                )
            else:
                o = paged_spec_attention_xla(
                    qg, kv_cache, layer_idx, block_tables, lengths,
                    k_scale, v_scale, anc=anc,
                )
            o = o.reshape(B, T, cfg.q_size)
            x = x + _wo_lora(o, lp, ll, adapter_slots)

            h = _rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            x = x + _ffn(h, lp, cfg)
            return (x, kv_cache, k_scale, v_scale), None

        layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        xs_in = (
            (params["layers"], lora, layer_ids) if lora is not None
            else (params["layers"], layer_ids)
        )
        (x, kv_cache, k_scale, v_scale), _ = lax.scan(
            layer, (x, cache.kv, cache.k_scale, cache.v_scale), xs_in,
        )
        logits = _logits(cfg, params, x)  # [B, T, V] fp32
        cache = KVCache(kv_cache, k_scale, v_scale)
    else:
        def substep(c, xs):
            tok_j, pos_j, use_j = xs
            lg, c = decode_step_impl(
                cfg, params, c, tok_j, pos_j, block_tables, use_j,
                lora, adapter_slots, attn_impl=attn_impl,
            )
            return c, lg

        cache, logits_t = lax.scan(
            substep, cache,
            (tokens.T, pos.T, use.T),
        )
        logits = jnp.transpose(logits_t, (1, 0, 2))  # [B, T, V] fp32

    if tree:
        # Grammar masks ride the tree path only: every constrained batch
        # dispatches as a tree (chains are trees), so the linear op below
        # never sees a mask. Acceptance + correction/bonus sampling then
        # renormalize over each node's LEGAL vocabulary
        # (sampler.spec_tree_acceptance) while the reported logprobs stay
        # raw-model values (OpenAI semantics), masked or not.
        out, n_emit, path, cand = spec_tree_acceptance(
            logits, tokens, tree_parents, draft_len, temperature, seeds,
            steps0, mode, mask_bits,
        )
        # Everything downstream reads PATH-ALIGNED logits: emitted token
        # k came from node path[k]'s distribution (path is clamped to
        # the stopping node past n_emit, so the gathers stay in-bounds).
        logits_out = jnp.take_along_axis(logits, path[:, :, None], axis=1)
        # KV compaction: relocate the accepted path's KV from its tree
        # slots to the contiguous positions the engine's rollback
        # contract expects (positions0+k holds the depth-k accepted
        # node); depths beyond the accepted run redirect to garbage
        # block 0. Gather-before-scatter, so aliasing (path[k] == k on
        # chain prefixes) is value-identical, and the moved bytes are
        # ~the KV the pass just wrote — noise next to the weight stream.
        kdepth = jnp.arange(1, T, dtype=jnp.int32)[None, :]       # [1, S]
        src_pos = positions0[:, None] + path[:, 1:]
        dst_pos = positions0[:, None] + kdepth
        keep = active[:, None] & (kdepth < n_emit[:, None])
        src_blk = jnp.take_along_axis(block_tables, src_pos // bs, axis=1)
        src_off = src_pos % bs
        dst_blk = jnp.where(
            keep, jnp.take_along_axis(block_tables, dst_pos // bs, axis=1), 0
        )
        dst_off = jnp.where(keep, dst_pos % bs, 0)
        k_scale, v_scale = cache.k_scale, cache.v_scale
        # A token's K and V of every layer move as rows of KVH*hd lanes, each
        # by its own (layer, block, part, slot) index: a window over layers
        # or parts would have the chip's compiler re-lay the pool out behind
        # a copy of it (``write_kv_tokens``).
        layers = jnp.arange(cache.kv.shape[0], dtype=jnp.int32)[:, None, None, None]
        part = jnp.arange(2, dtype=jnp.int32)[None, :, None, None]
        kv_cache = cache.kv.at[layers, dst_blk[None, None], part, dst_off[None, None]].set(
            cache.kv[layers, src_blk[None, None], part, src_off[None, None]])
        if k_scale is not None:
            k_scale = k_scale.at[:, dst_blk, dst_off].set(
                k_scale[:, src_blk, src_off]
            )
            v_scale = v_scale.at[:, dst_blk, dst_off].set(
                v_scale[:, src_blk, src_off]
            )
        cache = KVCache(kv_cache, k_scale, v_scale)
    else:
        drafts = tokens[:, 1:]
        out, n_emit = spec_acceptance(
            logits, drafts, draft_len, temperature, seeds, steps0, mode
        )
        cand = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits_out = logits
    # Raw-distribution logprobs of the EMITTED tokens (dense parity:
    # OpenAI reports model logprobs, not sampler-modified ones).
    logz = jax.nn.logsumexp(logits_out, axis=-1)
    logps = (
        jnp.take_along_axis(logits_out, out[:, :, None], axis=-1)[..., 0] - logz
    )                                                      # [B, T]
    if top_n > 0:
        flat_vals, flat_ids = top_k_logprobs(logits_out.reshape(B * T, -1), top_n)
        top_vals = flat_vals.reshape(B, T, top_n)
        top_ids = flat_ids.reshape(B, T, top_n)
    else:
        top_vals = jnp.zeros((B, T, 0), jnp.float32)
        top_ids = jnp.zeros((B, T, 0), jnp.int32)
    last_tok = jnp.take_along_axis(out, (n_emit - 1)[:, None], axis=1)[:, 0]
    return out, n_emit, logps, cand, top_vals, top_ids, last_tok, cache


def embed_impl(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,    # [T_pad] int32
    true_len: jax.Array,  # scalar int32
) -> jax.Array:
    """Mean-pooled final-norm hidden state over the true tokens → [D]
    fp32. Cache-free causal forward (serves /v1/embeddings; reference:
    lib/llm/src/http/service/openai.rs:302)."""
    refuse_block(cfg, "embeddings (embed_impl)")
    T = tokens.shape[0]
    compute_dtype = params["layers"]["attn_norm"].dtype
    x = _embed_rows(params, tokens, compute_dtype)  # [T, D]
    pos = jnp.arange(T, dtype=jnp.int32)
    neg = jnp.float32(-1e9)
    causal = (pos[None, :] <= pos[:, None])
    valid = pos[None, :] < true_len
    mask = jnp.where(causal & valid, 0.0, neg)  # [T, T]
    scale = cfg.head_dim ** -0.5
    G = cfg.num_heads // cfg.num_kv_heads

    def layer(x, lp):
        h = _rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(h, lp, cfg)
        q = q.reshape(T, cfg.num_heads, cfg.head_dim)
        k = k.reshape(T, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(T, cfg.num_kv_heads, cfg.head_dim)
        q = _rope(q, pos, cfg.rope_theta)
        k = _rope(k, pos, cfg.rope_theta)
        qg = q.reshape(T, cfg.num_kv_heads, G, cfg.head_dim)
        s = jnp.einsum("tkgh,skh->tkgs", qg, k).astype(jnp.float32) * scale
        s = s + mask[:, None, None, :]
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o = jnp.einsum("tkgs,skh->tkgh", p, v).reshape(T, cfg.q_size)
        x = x + _dot_q(o, lp, "wo")
        h = _rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        x = x + _ffn(h, lp, cfg)
        return x, None

    x, _ = lax.scan(layer, x, params["layers"])
    x = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps).astype(jnp.float32)
    w = (pos < true_len).astype(jnp.float32)[:, None]
    return jnp.sum(x * w, axis=0) / jnp.maximum(true_len.astype(jnp.float32), 1.0)


# Jitted entry points (static model config / step count, donated cache).
prefill = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=("attn_impl",), donate_argnums=(2,)
)(prefill_impl)
prefill_batch = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=("attn_impl",), donate_argnums=(2,)
)(prefill_batch_impl)
decode_step = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=("attn_impl",), donate_argnums=(2,)
)(decode_step_impl)
multi_decode = functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3), static_argnames=("attn_impl",), donate_argnums=(5,)
)(multi_decode_impl)
spec_verify = functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3),
    static_argnames=("fused", "attn_impl"), donate_argnums=(5,)
)(spec_verify_impl)
embed = functools.partial(jax.jit, static_argnums=(0,))(embed_impl)
