"""The MiniCPM-SALA block (``ModelConfig.block == "sala"``): block-sparse
attention layers among lightning (linear-attention) layers, one layer
definition of each kind used by the packed prefill, the single prefill and
the decode window. ``model.block_module(cfg)`` is this module for such a
model: ``init_params``, ``init_kv_cache`` and the jitted ``prefill``,
``prefill_batch``, ``decode_step`` and ``multi_decode`` under engine/model.py's
names, each taking one operand more, ``state_slots`` (below), and returning
None where a routing block returns its histogram.

The stream: ``x0 = scale_emb * embed(token)``; a layer is ``h = x + r *
Mix_i(rms(x))``, ``out = h + r * FF(rms(h))`` with ``r = scale_depth /
sqrt(layers)`` and a dense SwiGLU; logits ``= W_head (rms(x_L) / (hidden /
dim_model_base))``.

- ``Mix`` **"lightning-attn"** (ops/lightning.py): per head q and k RMS-normed,
  rotated (rotate-half, whole head); ``S_t = lambda_h S_{t-1} + k_t^T v_t``,
  ``o_t = (q_t / sqrt(d)) S_t``; the heads' outputs concatenated, RMS-normed,
  times ``sigmoid(W_ogate u)``, then ``W_o``.
- ``Mix`` **"minicpm4"** (ops/sparse_attention.py): GQA without rotary
  embedding, an RMS norm over each query and key head, dense causal attention
  while a query sees at most ``sparse_dense_len`` positions and the top-k
  blocks chosen on compressed keys past it, the output times ``sigmoid(W_ogate
  u)`` before ``W_o``.

**The cache has three pools** (``KVCache``): ``kv`` ``[sparse layers, N, 2,
bs, KVH*hd]`` with the page the sparse block, its K and then its V; ``ckeys`` ``[sparse layers, N,
bs // stride, KVH*hd]``, the compressed keys under the same block ids, each in
the page that holds its last token; and ``state`` ``[lightning layers, S, H, d,
d]`` in the cache's dtype, S slots that the block manager hands out (block_manager/pool.py
has the policy; this module only reads and writes the slots it is told).
``state_slots`` says which: for prefill ``[Bp, 6]`` = (the slot a row's state
is read from at ``start_pos`` (ignored at position 0), the slot it is left in
after the row's last token, and twice a slot for a snapshot (0, the sink, for
none) with the tokens of the chunk after which it is taken, a multiple of the
block size: the chunk's last block boundary, and where the row's cached pages
ended); for decode ``[B, 3]``, the row's pair
and the last position whose state anyone will want (the steps a finished
sequence's window runs past it leave the pair alone): the state after position
p rests in ``pair[(p // bs) % 2]``, so the step that opens a block reads the
other slot and leaves it behind as the snapshot of the block before.

**Compile cost does not grow with depth.** The layers are one ``lax.scan``
over the sparse layers, each followed by a ``fori_loop`` over the lightning
layers up to the next sparse one (8, 6, 0, 4, 6, 0, 0, 0 of them as
published): each kind's body is traced once, its weights stacked
(``params["sparse"]`` ``[8, ...]``, ``params["lightning"]`` ``[24, ...]``).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.model import (
    KVCache, _dot_q, _embed_rows, _logits, _mlp, _rms_norm, _rope, decode_window, pool_zeros,
    write_kv_pages, write_kv_tokens,
)
from dynamo_tpu.engine.quant import SALA_LAYER_WEIGHTS
from dynamo_tpu.engine.side import StateSlots as side_cache  # noqa: F401 — this block's second cache (engine/side.py)
from dynamo_tpu.ops import sparse_attention as sparse
from dynamo_tpu.ops.lightning import PREFILL_CHUNK, lightning_decode, lightning_decode_xla, lightning_prefill
from dynamo_tpu.ops.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_xla,
    paged_prefill_attention,
    resolve_attn_impl,
    resolve_prefill_impl,
)

Params = dict[str, Any]

START_LINE = " block=sala"  # what the engine's start line says of this block
UNCARRIED = ("state pool", "the state pool")



def layer_shapes(cfg: ModelConfig, kind: str) -> dict[str, tuple[tuple[int, int], int]]:
    """A layer's matmul weights in the key schedule's order
    (``quant.SALA_LAYER_WEIGHTS``): name -> ((in, out), fan_in)."""
    D, I = cfg.hidden_size, cfg.intermediate_size
    q, kv = (cfg.q_size, cfg.kv_size) if kind == "sparse" else (cfg.lightning_size, cfg.lightning_size)
    shapes = {"wq": ((D, q), D), "wk": ((D, kv), D), "wv": ((D, kv), D), "wo": ((q, D), q),
              "w_ogate": ((D, q), D), "w_gate": ((D, I), D), "w_up": ((D, I), D), "w_down": ((I, D), I)}
    return {name: shapes[name] for name in SALA_LAYER_WEIGHTS}


def segments(cfg: ModelConfig) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """For each sparse layer, (the lightning layers before it, how many follow
    it before the next sparse layer), counted among the lightning layers."""
    first, count, seen = [], [], 0
    for kind in cfg.mixer_types:
        if kind == "minicpm4":
            first.append(seen)
            count.append(0)
        else:
            seen += 1
            count[-1] += 1
    return tuple(first), tuple(count)


# -- the seeded initialiser (chipbench/references/minicpm_sala.py keeps a copy) ----


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1,))
def _draw_int8(key, shape):
    """[n, a, b] uniform int8, a slice at a time under its own key: the chip's
    compiler takes half a minute over one draw of more than 2**27 elements and
    three seconds over a loop of smaller ones."""
    return lax.map(lambda i: jax.random.randint(jax.random.fold_in(key, i), shape[1:], -127, 128, jnp.int8),
                   jnp.arange(shape[0]))


def _vocab_pieces(V: int) -> int:
    return math.gcd(V, 8)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16, quant: str = "none") -> Params:
    """Random-init params, a stacked tensor at a time (one jitted draw each).
    ``quant="int8"``: weight-only int8 drawn as ``quant.random_int8_params_device``
    draws the dense block's (uniform int8, one scale ``fan_in ** -0.5 / 64`` an
    output channel), so 9.5B parameters are born int8 on a 16 GB chip."""
    D, V = cfg.hidden_size, cfg.vocab_size
    int8 = quant == "int8"

    def stack(kind: str, n: int, base: int) -> dict:
        out = {}
        for idx, (name, ((fin, fout), fan)) in enumerate(layer_shapes(cfg, kind).items()):
            k = jax.random.fold_in(key, base + idx)
            if int8:
                out[name] = _draw_int8(k, (n, fin, fout))
                out[name + "_scale"] = jnp.full((n, fout), (fan ** -0.5) / 64.0, jnp.float32)
            else:
                out[name] = _draw(k, (n, fin, fout), fan ** -0.5, dtype)
        out["attn_norm"] = jnp.ones((n, D), dtype)
        out["mlp_norm"] = jnp.ones((n, D), dtype)
        hd = cfg.head_dim if kind == "sparse" else cfg.lightning_head_dim
        out["q_norm"] = jnp.ones((n, hd), dtype)
        out["k_norm"] = jnp.ones((n, hd), dtype)
        if kind == "lightning":
            out["o_norm"] = jnp.ones((n, cfg.lightning_size), dtype)
        return out

    params: Params = {
        "sparse": stack("sparse", len(cfg.sparse_layers), 100),
        "lightning": stack("lightning", len(cfg.lightning_layers), 200),
        "final_norm": jnp.ones((D,), dtype),
    }
    if int8:
        p = _vocab_pieces(V)  # both tables drawn as p pieces of V / p rows
        params["embed"] = _draw_int8(jax.random.fold_in(key, 90), (p, V // p, D)).reshape(V, D)
        params["embed_scale"] = jnp.full((V,), (D ** -0.5) / 64.0, jnp.float32)
        params["lm_head"] = _draw_int8(jax.random.fold_in(key, 91), (p, V // p, D)).reshape(V, D).T
        params["lm_head_scale"] = jnp.full((V,), (D ** -0.5) / 64.0, jnp.float32)
    else:
        params["embed"] = _draw(jax.random.fold_in(key, 90), (V, D), D ** -0.5, dtype)
        params["lm_head"] = _draw(jax.random.fold_in(key, 91), (D, V), D ** -0.5, dtype)
    return params


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
                  sharding=None, kv_quant: str = "none", state_slots: int = 2) -> KVCache:
    """K and V pages of the sparse layers, their compressed keys under the
    same block ids (``KVCache.ckeys``) and the lightning layers' state pool
    (``KVCache.state``), all in ``dtype``: a state rests as the pages do and is
    worked on in float32 (ops/lightning.py)."""
    if kv_quant != "none":
        raise ValueError("the state pool has no int8 form (kv_quant)")
    if block_size != cfg.sparse_block_size:
        raise ValueError(f"block_size {block_size} is not the sparse block of {cfg.sparse_block_size} tokens")
    zeros = pool_zeros(sharding)
    n = len(cfg.sparse_layers)
    kv = (n, num_blocks, 2, block_size, cfg.kv_size)
    ck = (n, num_blocks, block_size // cfg.sparse_kernel_stride, cfg.kv_size)
    state = (len(cfg.lightning_layers), state_slots, cfg.lightning_heads,
             cfg.lightning_head_dim, cfg.lightning_head_dim)
    return KVCache(zeros(kv, dtype), ckeys=zeros(ck, dtype),
                   state=zeros(state, dtype))


# -- pieces ----------------------------------------------------------------------


def _gate(y: jax.Array, u: jax.Array, lp: dict) -> jax.Array:
    """The mixer's output times ``sigmoid(W_ogate u)``, then ``W_o``."""
    with jax.named_scope("mix_out"):
        g = jax.nn.sigmoid(_dot_q(u, lp, "w_ogate").astype(jnp.float32)).astype(y.dtype)
        return _dot_q(y * g, lp, "wo")


def lightning_qkv(u: jax.Array, lp: dict, cfg: ModelConfig, positions: jax.Array):
    """u [..., D] at ``positions`` [...] → q (times ``d ** -0.5``), k, v [..., H, d]."""
    H, d = cfg.lightning_heads, cfg.lightning_head_dim
    with jax.named_scope("lightning_qkv"):
        q = _dot_q(u, lp, "wq").reshape(*u.shape[:-1], H, d)
        k = _dot_q(u, lp, "wk").reshape(*u.shape[:-1], H, d)
        v = _dot_q(u, lp, "wv").reshape(*u.shape[:-1], H, d)
        q = _rope(_rms_norm(q, lp["q_norm"], cfg.rms_norm_eps), positions, cfg.rope_theta)
        k = _rope(_rms_norm(k, lp["k_norm"], cfg.rms_norm_eps), positions, cfg.rope_theta)
        return (q.astype(jnp.float32) * d ** -0.5).astype(q.dtype), k, v


def lightning_out(o: jax.Array, u: jax.Array, lp: dict, cfg: ModelConfig) -> jax.Array:
    y = _rms_norm(o.reshape(*o.shape[:-2], cfg.lightning_size).astype(u.dtype), lp["o_norm"], cfg.rms_norm_eps)
    return _gate(y, u, lp)


def sparse_qkv(u: jax.Array, lp: dict, cfg: ModelConfig):
    """u [..., D] → q [..., KVH, G, hd], k and v [..., KVH * hd]: no bias, no
    rotary embedding, an RMS norm over each query and key head."""
    KVH, hd, G = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads // cfg.num_kv_heads
    with jax.named_scope("attn_qkv"):
        q = _dot_q(u, lp, "wq").reshape(*u.shape[:-1], KVH, G, hd)
        k = _dot_q(u, lp, "wk").reshape(*u.shape[:-1], KVH, hd)
        v = _dot_q(u, lp, "wv")
        q = _rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = _rms_norm(k, lp["k_norm"], cfg.rms_norm_eps).reshape(*u.shape[:-1], KVH * hd)
    return q, k, v


def _layers(cfg: ModelConfig, params: Params, x, cache: KVCache, sparse_op, lightning_op):
    """Every layer over ``x`` [..., D]. ``sparse_op(si, lp, cache, u)`` and
    ``lightning_op(li, lp, cache, u)`` → (Mix(u), cache) are what prefill and
    decode do differently; ``si`` and ``li`` count the layers of a kind (traced),
    which is where their pages and states are in the pools."""
    r = jnp.asarray(cfg.scale_depth / cfg.num_layers ** 0.5, x.dtype)
    first, count = segments(cfg)

    def layer(x, cache, lp, mix):
        y, cache = mix(lp, cache, _rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps))
        h = x + r * y
        with jax.named_scope("ffn_dense"):
            return h + r * _mlp(_rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps), lp), cache

    def lightning_layer(li, carry):
        lp = jax.tree.map(lambda a: a[li], params["lightning"])
        return layer(*carry, lp, functools.partial(lightning_op, li))

    def segment(carry, xs):
        si, lp, lo, n = xs
        carry = layer(*carry, lp, functools.partial(sparse_op, si))
        return lax.fori_loop(lo, lo + n, lightning_layer, carry), None

    n_sparse = len(first)
    (x, cache), _ = lax.scan(segment, (x, cache), (
        jnp.arange(n_sparse, dtype=jnp.int32), params["sparse"],
        jnp.asarray(first, jnp.int32), jnp.asarray(count, jnp.int32)))
    return x, cache


def _head(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    """``W_head (rms(x) / (hidden / dim_model_base))``: the division rides the norm's gain."""
    width = cfg.hidden_size / (cfg.dim_model_base or cfg.hidden_size)
    gain = (params["final_norm"].astype(jnp.float32) / width).astype(params["final_norm"].dtype)
    return _logits(cfg, {**params, "final_norm": gain}, x)


def _embed(cfg: ModelConfig, params: Params, tokens: jax.Array, dtype) -> jax.Array:
    with jax.named_scope("embed"):
        return (_embed_rows(params, tokens, dtype).astype(jnp.float32) * cfg.scale_emb).astype(dtype)


def _no_lora(lora) -> None:
    if lora is not None:
        raise ValueError("LoRA banks cannot run a block='sala' model")


# -- the programs ----------------------------------------------------------------


def prefill_batch_impl(cfg, params, cache, tokens, block_tables, start_pos, true_len,
                       lora=None, adapter_slots=None, *, attn_impl: str = "auto", state_slots=None):
    """``model.prefill_batch_impl`` for this block: same arguments and contract
    (positions before the block-aligned ``start_pos`` are cached: K, V and
    compressed keys in the row's pages, the lightning state in slot
    ``state_slots[:, 0]``; the suffix is computed here)."""
    _no_lora(lora)
    Bp, T = tokens.shape
    bs, sp = cache.block_size, sparse.SparseSizes.of(cfg)
    W = block_tables.shape[1]
    KVH, hd, G = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads // cfg.num_kv_heads
    dtype = cache.kv.dtype
    sfx = jnp.arange(T, dtype=jnp.int32)
    positions = start_pos[:, None] + sfx[None, :]                 # [Bp, T]
    x = _embed(cfg, params, tokens, dtype)
    impl, _ = resolve_prefill_impl(attn_impl, cfg, bs, False)
    src, dst = state_slots[:, 0], state_slots[:, 1]
    snaps, snap_at = state_slots[:, 2::2], state_slots[:, 3::2]                       # [Bp, 2] each
    resumes = start_pos > 0
    n_valid = jnp.maximum(true_len - start_pos, 0)

    # Suffix pages' targets, as model.prefill_batch_impl derives them.
    nb = T // bs
    blocks = jnp.arange(nb, dtype=jnp.int32)
    padded_tables = jnp.concatenate([block_tables, jnp.zeros((Bp, nb), jnp.int32)], axis=1)
    ids = jnp.take_along_axis(padded_tables, start_pos[:, None] // bs + blocks[None, :], axis=1)
    blk_start = start_pos[:, None] + blocks[None, :] * bs
    flat_ids = jnp.where(blk_start < true_len[:, None], ids, 0).reshape(Bp * nb)
    # The block before the chunk: its last ``stride`` keys open the chunk's
    # first compressed key (nothing does at position 0).
    prev_blk = jnp.take_along_axis(block_tables, jnp.maximum(start_pos // bs - 1, 0)[:, None], axis=1)[:, 0]
    tail = jnp.arange(bs - sp.stride, bs, dtype=jnp.int32)
    every_row_dense = W * bs <= sp.dense_len  # static: the table cannot hold a sparse row

    def dense_attend(q, kv_cache, si):
        if impl == "xla":
            return sparse.sparse_prefill_attention(  # every block kept under dense_len: plain causal attention
                q, kv_cache, cache.ckeys, si, block_tables, start_pos, true_len,
                sp._replace(dense_len=W * bs))
        return paged_prefill_attention(q, kv_cache, si, block_tables, start_pos, true_len,
                                       interpret=(impl == "pallas_interpret"))

    def sparse_op(si, lp, cache, u):
        q, k, v = sparse_qkv(u, lp, cfg)                           # [Bp, T, KVH, G, hd], [Bp, T, KVH*hd] x2
        with jax.named_scope("kv_write"):
            kv_cache = write_kv_pages(cache.kv, si, flat_ids, k.reshape(Bp * nb, bs, KVH * hd),
                                      v.reshape(Bp * nb, bs, KVH * hd))
            # K's tail, [Bp, stride, KVH*hd], as rows of lanes each with its own slot: a
            # window over part of the page's token axis (``[..., bs - stride:]``) made the
            # chip's compiler copy the whole pool token-major wherever Bp > 1.
            before = cache.kv[si, prev_blk[:, None], 0, tail[None, :]]
            ck = sparse.compress_keys(k, before, sp.stride)
            ckeys = cache.ckeys.at[si, flat_ids].set(ck.reshape(Bp * nb, sp.per_block, KVH * hd))
        if every_row_dense:
            with jax.named_scope("attn"):
                o = dense_attend(q, kv_cache, si)
        else:
            with jax.named_scope("sparse_attn"):
                o = lax.cond(
                    jnp.max(true_len) <= sp.dense_len,
                    lambda: dense_attend(q, kv_cache, si),
                    lambda: sparse.sparse_prefill_attention(
                        q, kv_cache, ckeys, si, block_tables, start_pos, true_len, sp))
        y = _gate(o.reshape(Bp, T, cfg.q_size), u, lp)
        return y, cache._replace(kv=kv_cache, ckeys=ckeys)

    def lightning_op(li, lp, cache, u):
        q, k, v = lightning_qkv(u, lp, cfg, positions)
        with jax.named_scope("lightning_scan"):
            s0 = jnp.where(resumes[:, None, None, None], cache.state[li, src].astype(jnp.float32), 0.0)
            o, s, kept = lightning_prefill(q, k, v, s0, n_valid, snap_at, math.gcd(bs, PREFILL_CHUNK))
            pool = cache.state.at[li, dst].set(s.astype(cache.state.dtype))
            for j in range(snaps.shape[1]):
                pool = pool.at[li, snaps[:, j]].set(kept[:, j].astype(cache.state.dtype))
        return lightning_out(o, u, lp, cfg), cache._replace(state=pool)

    x, cache = _layers(cfg, params, x, cache, sparse_op, lightning_op)
    last_tok = jnp.clip(true_len - start_pos - 1, 0, T - 1)
    x_last = jnp.take_along_axis(x, last_tok[:, None, None], axis=1)[:, 0]
    with jax.named_scope("logits"):
        logits = _head(cfg, params, x_last)
    return logits, cache, None


def prefill_impl(cfg, params, cache, tokens, block_table, start_pos, true_len,
                 lora=None, adapter_slot=None, *, attn_impl: str = "auto", state_slots=None):
    """Single-sequence prefill: the Bp=1 case of ``prefill_batch_impl``."""
    logits, cache, _ = prefill_batch_impl(
        cfg, params, cache, tokens[None, :], block_table[None, :],
        jnp.asarray(start_pos, jnp.int32).reshape(1),
        jnp.asarray(true_len, jnp.int32).reshape(1), lora,
        attn_impl=attn_impl, state_slots=state_slots.reshape(1, 6),
    )
    return logits[0], cache, None


def decode_step_impl(cfg, params, cache, tokens, positions, block_tables, active,
                     lora=None, adapter_slots=None, *, attn_impl: str = "auto", state_slots=None):
    """``model.decode_step_impl`` for this block: a position writes its K and V
    (and the compressed key it completes), chooses its blocks and attends them
    in the sparse layers, and steps its state in the lightning layers."""
    _no_lora(lora)
    impl = resolve_attn_impl(attn_impl)
    B = tokens.shape[0]
    bs, sp = cache.block_size, sparse.SparseSizes.of(cfg)
    W = block_tables.shape[1]
    KVH, hd = cfg.num_kv_heads, cfg.head_dim
    x = _embed(cfg, params, tokens, cache.kv.dtype)
    rows = jnp.arange(B)
    at = positions // bs
    blk = jnp.where(active, block_tables[rows, at], 0)
    off = jnp.where(active, positions % bs, 0)
    lengths = jnp.where(active, positions + 1, 0)
    # The state after position p rests in pair[(p // bs) % 2]: read where
    # p - 1 left it, write where p belongs (the sink for a padding row).
    wanted = active & (positions <= state_slots[:, 2])
    read = jnp.where(wanted, state_slots[rows, (jnp.maximum(positions - 1, 0) // bs) % 2], 0)
    write = jnp.where(wanted, state_slots[rows, at % 2], 0)
    # The compressed key this position completes: the mean of the 2 * stride
    # keys that end here, in slot (p % bs) // stride of its block.
    completes = active & (positions % sp.stride == sp.stride - 1) & (positions >= 2 * sp.stride - 1)
    ck_blk = jnp.where(completes, blk, 0)
    span = positions[:, None] - jnp.arange(2 * sp.stride - 1, -1, -1, dtype=jnp.int32)[None, :]
    span = jnp.maximum(span, 0)                                                      # [B, 2 * stride]
    span_blk = jnp.where(active[:, None], jnp.take_along_axis(block_tables, span // bs, axis=1), 0)
    every_row_dense = W * bs <= sp.dense_len  # static

    def attend(q, kv_cache, si, tables, lens):
        if impl == "xla":
            return paged_decode_attention_xla(q, kv_cache, si, tables, lens)
        return paged_decode_attention(q, kv_cache, si, tables, lens,
                                      interpret=(impl == "pallas_interpret"))

    def sparse_op(si, lp, cache, u):
        q, k, v = sparse_qkv(u, lp, cfg)                            # [B, KVH, G, hd], [B, KVH*hd] x2
        with jax.named_scope("kv_write"):
            kv_cache = write_kv_tokens(cache.kv, si, blk, off, k, v)
            mean = kv_cache[si, span_blk, 0, span % bs].astype(jnp.float32).mean(axis=1)
            ckeys = cache.ckeys.at[si, ck_blk, off // sp.stride].set(mean.astype(k.dtype))
        if every_row_dense:
            with jax.named_scope("attn"):
                o = attend(q, kv_cache, si, block_tables, lengths)
        else:
            with jax.named_scope("sparse_select"):
                tables, lens = sparse.sparse_select(q, ckeys, si, block_tables, positions, sp)
                lens = jnp.where(jnp.repeat(active, KVH), lens, 0)
            with jax.named_scope("sparse_attn"):
                o = sparse.own_kv_head(
                    attend(sparse.per_kv_head(q), kv_cache, si, tables, lens), KVH)
        y = _gate(o.reshape(B, cfg.q_size), u, lp)
        return y, cache._replace(kv=kv_cache, ckeys=ckeys)

    def lightning_op(li, lp, cache, u):
        q, k, v = lightning_qkv(u, lp, cfg, positions)
        with jax.named_scope("lightning_step"):
            if impl == "xla":
                o, pool = lightning_decode_xla(q, k, v, cache.state, li, read, write)
            else:
                o, pool = lightning_decode(q, k, v, cache.state, li, read, write,
                                           interpret=(impl == "pallas_interpret"))
        return lightning_out(o, u, lp, cfg), cache._replace(state=pool)

    x, cache = _layers(cfg, params, x, cache, sparse_op, lightning_op)
    with jax.named_scope("logits"):
        logits = _head(cfg, params, x)
    return logits, cache, None


def multi_decode_impl(cfg, num_steps, mode, top_n, params, cache, tokens, positions,
                      block_tables, active, temperature, seeds, steps0, top_k, top_p,
                      freq_penalty, pres_penalty, penalty_tokens, chain_mask=None,
                      chain_src=None, last_toks=None, lora=None, adapter_slots=None,
                      *, attn_impl: str = "auto", state_slots=None):
    """``model.multi_decode_impl`` for this block: the same fused window
    (``model.decode_window``) over this block's step."""
    def step(cache, tok, pos):
        return decode_step_impl(cfg, params, cache, tok, pos, block_tables, active,
                                lora, adapter_slots, attn_impl=attn_impl, state_slots=state_slots)

    return decode_window(
        step, None, cfg.vocab_size, num_steps, mode, top_n, cache, tokens, positions,
        temperature, seeds, steps0, top_k, top_p, freq_penalty, pres_penalty,
        penalty_tokens, chain_mask, chain_src, last_toks,
    )


# The jitted programs, under engine/model.py's names and with its donation.
_STATIC = ("attn_impl",)
prefill = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=_STATIC, donate_argnums=(2,))(prefill_impl)
prefill_batch = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=_STATIC, donate_argnums=(2,))(prefill_batch_impl)
decode_step = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=_STATIC, donate_argnums=(2,))(decode_step_impl)
multi_decode = functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3), static_argnames=_STATIC, donate_argnums=(5,)
)(multi_decode_impl)
