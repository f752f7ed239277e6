"""The LFM2-MoE block (``ModelConfig.block == "lfm2"``): one layer definition,
used by the packed prefill, the single prefill and the decode window.
``model.block_module(cfg)`` is this module for such a model: it has
``init_params``, ``init_kv_cache`` and the jitted ``prefill``,
``prefill_batch``, ``decode_step`` and ``multi_decode`` under the names
engine/model.py has them (``jit_prefill_batch_impl``, ``jit_prefill_impl``,
``jit_multi_decode_impl`` in a trace), each returning the expert layers'
routing histogram ``[expert layers, E + 5]`` after what the dense block's
returns.

A layer is an operator and a feed-forward, each behind its own norm:

    h   = x + Op_i(rms(x; operator_norm))
    out = h + FF_i(rms(h; ffn_norm))

- ``Op`` **"conv"** (``cfg.layer_types[i]``): ``[B, C, X] = split3(W_in u)``;
  ``z = B * X``; ``y_t = sum_j w[:, j] * z_{t-K+j}`` over the ``K + 1 =
  conv_L_cache`` taps (depthwise, causal, no bias, zeros before a sequence's
  first position); ``Op(u) = W_out (C * y)``. What a sequence carries from
  one position to the next is ``z`` at its last K positions.
- ``Op`` **"full_attention"**: GQA with an RMS norm over each query head and
  each key head before the rotary embedding (rotate-half, the whole head),
  the dense block's pages and kernels (ops/paged_attention.py).
- ``FF``: a dense SwiGLU for the first ``num_dense_layers`` layers, then the
  expert layer of engine/longcat.py (``moe``) under the second router
  arithmetic (``route``: sigmoid, choice on ``s + bias``, renormalised), all
  experts held, none zero-compute.

**The conv state lives where the pages live.** Beside K and V
(``[attention layers, N, bs, KVH*hd]`` each) the cache has a third pool,
``conv [conv layers, K, N, D]``, indexed by the same block ids: the block that
holds position ``p`` keeps ``z_p`` in slot ``p % K`` (the block size is a
multiple of K). A finished block therefore holds the state at its end, and a
position finds ``z_{p-1} .. z_{p-K}`` in its own block or in the one before
it in its table. A prefix hit at block ``n``, the second chunk of a chunked
prefill, a packed row at its own ``start_pos``, a decode step across a block
boundary and a preempted sequence's return all resume from the block table
alone: no per-slot state, no copy at admission, nothing the block manager
has to know. A shared sealed block is never written again, because a
position writes the block that holds it, and that is the sequence's own open
block. (Slots ahead of the block axis, not behind it: a ``[.., N, K, D]``
pool has K = 2 rows in its second-minor dimension, which the TPU's tiling
pads eightfold.)

Layers differ in kind and in shape, so they are a Python loop over
per-layer parameter dicts (``params["layers"][i]``), not a scan over stacked
tensors: each expert layer's stacks are leaves of their own and reach the
grouped product without a slice.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import longcat
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.longcat import HIST_EXTRA, expert_impl  # noqa: F401 - the runner's start line asks expert_impl
from dynamo_tpu.engine.model import (
    KVCache, _logits, _rms_norm, _rope, decode_window, pool_zeros, write_kv_pages, write_kv_tokens,
)
from dynamo_tpu.ops.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_xla,
    paged_prefill_attention,
    paged_prefill_attention_xla,
    resolve_attn_impl,
    resolve_prefill_impl,
)

Params = dict[str, Any]

START_LINE = " block=lfm2"  # what the engine's start line says of this block
UNCARRIED = ("conv-state pool", "the conv-state pool")

# The seeded router's logits have this deviation (a normed stream times
# w_router), and the expert bias is drawn at this scale: sigmoid scores then
# spread over 0.1-0.9, the four chosen of 64 differ in weight, and a choice
# made on the wrong scores (without the bias) picks other experts.
ROUTER_LOGIT_STD = 2.0
EXPERT_BIAS_STD = 0.1


# -- the seeded initialiser (chipbench/references/lfm2_moe.py keeps a copy) -------


def _tensors(cfg: ModelConfig, i: int) -> dict[str, tuple[tuple[int, ...], float]]:
    """Layer ``i``'s drawn tensors: name -> (shape, deviation)."""
    D, I, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    ie = cfg.moe_intermediate_size or I
    if cfg.layer_types[i] == "conv":
        op = {"conv_in": ((D, 3 * D), D ** -0.5), "conv_w": ((D, cfg.conv_L_cache), cfg.conv_L_cache ** -0.5),
              "conv_out": ((D, D), D ** -0.5)}
    else:
        op = {"wq": ((D, cfg.q_size), D ** -0.5), "wk": ((D, cfg.kv_size), D ** -0.5),
              "wv": ((D, cfg.kv_size), D ** -0.5), "wo": ((cfg.q_size, D), cfg.q_size ** -0.5)}
    if i < cfg.num_dense_layers:
        ff = {"w_gate": ((D, I), D ** -0.5), "w_up": ((D, I), D ** -0.5), "w_down": ((I, D), I ** -0.5)}
    else:
        ff = {"w_router": ((D, E), ROUTER_LOGIT_STD * D ** -0.5),
              "moe_gate": ((E, D, ie), D ** -0.5), "moe_up": ((E, D, ie), D ** -0.5),
              "moe_down": ((E, ie, D), ie ** -0.5)}
    return {**op, **ff}


# Every drawn tensor's place in the key schedule, whatever the layer's kind.
_NAMES = ("conv_in", "conv_w", "conv_out", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
          "w_router", "moe_gate", "moe_up", "moe_down", "router_bias")


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params, a tensor of a layer at a time (one jitted draw
    each): 5.3B parameters are made on a 16 GB chip with no float32 copy of
    more than one tensor. ``params["layers"]`` is a list of per-layer dicts.
    The head is tied to the embedding."""
    D = cfg.hidden_size
    draw = longcat._draw

    def layer_key(name: str, i: int):
        return jax.random.fold_in(jax.random.fold_in(key, 100 + _NAMES.index(name)), i)

    layers = []
    for i, kind in enumerate(cfg.layer_types):
        lp = {name: draw(layer_key(name, i), shape, std, dtype)
              for name, (shape, std) in _tensors(cfg, i).items()}
        lp["operator_norm"] = jnp.ones((D,), dtype)
        lp["ffn_norm"] = jnp.ones((D,), dtype)
        if kind == "full_attention":
            lp["q_layernorm"] = jnp.ones((cfg.head_dim,), dtype)
            lp["k_layernorm"] = jnp.ones((cfg.head_dim,), dtype)
        if "w_router" in lp:
            lp["router_bias"] = draw(layer_key("router_bias", i), (cfg.num_experts,),
                                     EXPERT_BIAS_STD, jnp.float32)
        layers.append(lp)
    return {
        "embed": draw(jax.random.fold_in(key, 1), (cfg.vocab_size, D), D ** -0.5, dtype),
        "layers": layers,
        "final_norm": jnp.ones((D,), dtype),  # embedding_norm, under the name model._logits reads
    }


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
                  sharding=None, kv_quant: str = "none") -> KVCache:
    """K and V pages of the attention layers, and the conv layers' state
    under the same block ids (``KVCache.conv``)."""
    if kv_quant != "none":
        raise ValueError("the conv-state pool has no int8 form (kv_quant)")
    if block_size % cfg.conv_state_slots:
        raise ValueError(f"block_size {block_size} is no multiple of the {cfg.conv_state_slots} conv-state slots")
    zeros = pool_zeros(sharding)
    kv = (len(cfg.attn_layers), num_blocks, 2, block_size, cfg.kv_size)
    conv = (len(cfg.conv_layers), cfg.conv_state_slots, num_blocks, cfg.hidden_size)
    return KVCache(zeros(kv, dtype), conv=zeros(conv, dtype))


def routed_layers(cfg: ModelConfig) -> tuple[int, ...]:
    """The layers that route, in the order of the histogram's rows."""
    return cfg.expert_layers


# -- pieces ----------------------------------------------------------------------


def _mlp(x, lp):
    g, u = jnp.dot(x, lp["w_gate"]), jnp.dot(x, lp["w_up"])
    return jnp.dot(jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u, lp["w_down"])


def conv_gates(u: jax.Array, lp: dict):
    """u [..., D] → (z = B * X, C), each [..., D]."""
    with jax.named_scope("conv_in"):
        b, c, x = jnp.split(jnp.dot(u, lp["conv_in"]), 3, axis=-1)
        return b * x, c


def conv_mix(z_taps: list[jax.Array], c: jax.Array, lp: dict) -> jax.Array:
    """``z_taps[j]`` is z at the position K - j before each output position
    (the last is the position itself) → W_out (C * y)."""
    with jax.named_scope("conv_mix"):
        w = lp["conv_w"].astype(jnp.float32)
        y = sum(w[:, j] * z.astype(jnp.float32) for j, z in enumerate(z_taps))
        gated = c * y.astype(c.dtype)
    with jax.named_scope("conv_out"):
        return jnp.dot(gated, lp["conv_out"])


def qkv_heads(h: jax.Array, lp: dict, cfg: ModelConfig, positions: jax.Array):
    """h [..., D] at ``positions`` [...] → q [..., H, hd], k and v [..., KVH, hd]:
    projections without bias, the per-head norms, then the rotary embedding."""
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope("attn_qkv"):
        q = jnp.dot(h, lp["wq"]).reshape(*h.shape[:-1], H, hd)
        k = jnp.dot(h, lp["wk"]).reshape(*h.shape[:-1], KVH, hd)
        v = jnp.dot(h, lp["wv"]).reshape(*h.shape[:-1], KVH, hd)
    with jax.named_scope("attn_qk_norm"):
        q = _rms_norm(q, lp["q_layernorm"], cfg.rms_norm_eps)
        k = _rms_norm(k, lp["k_layernorm"], cfg.rms_norm_eps)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    return q, k, v


def _layers(cfg: ModelConfig, params: Params, x, cache: KVCache, valid, conv_op, attn_op,
            moe_impl: str):
    """Every layer over ``x`` [..., D]. ``conv_op(ci, lp, cache, u)`` and
    ``attn_op(ai, lp, cache, u)`` → (Op(u), cache) are the two things prefill
    and decode do differently; ``ci`` and ``ai`` count the layers of a kind,
    which is where their pages are in the pools."""
    hists, ci, ai = [], 0, 0
    for i, kind in enumerate(cfg.layer_types):
        lp = params["layers"][i]
        u = _rms_norm(x, lp["operator_norm"], cfg.rms_norm_eps)
        if kind == "conv":
            y, cache = conv_op(ci, lp, cache, u)
            ci += 1
        else:
            y, cache = attn_op(ai, lp, cache, u)
            ai += 1
        x = x + y
        h = _rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps)
        if i < cfg.num_dense_layers:
            with jax.named_scope("ffn_dense"):
                x = x + _mlp(h, lp)
        else:
            m, hist = longcat.moe(h, valid, {**lp, "moe_layer": 0}, cfg, moe_impl)
            x = x + m
            hists.append(hist)
    if not hists:  # a cut that keeps the leading dense layers alone
        return x, cache, jnp.zeros((0, cfg.num_experts + HIST_EXTRA), jnp.int32)
    return x, cache, jnp.stack(hists)  # [expert layers, E + 5]


def _no_lora(lora) -> None:
    if lora is not None:
        raise ValueError("LoRA banks cannot run a block='lfm2' model")


# -- the programs ----------------------------------------------------------------


def prefill_batch_impl(cfg, params, cache, tokens, block_tables, start_pos, true_len,
                       lora=None, adapter_slots=None, *, attn_impl: str = "auto",
                       experts: str | None = None):
    """``model.prefill_batch_impl`` for this block: same arguments and contract
    (positions before the block-aligned ``start_pos`` are cached, K and V and
    the conv state at ``start_pos`` alike; the suffix is computed here), and a
    third result, the routing histogram."""
    _no_lora(lora)
    Bp, T = tokens.shape
    bs, K = cache.block_size, cfg.conv_state_slots
    KVH, hd, G = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads // cfg.num_kv_heads
    sfx = jnp.arange(T, dtype=jnp.int32)
    positions = start_pos[:, None] + sfx[None, :]                 # [Bp, T]
    valid = positions < true_len[:, None]
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    impl, _ = resolve_prefill_impl(attn_impl, cfg, bs, False)

    # Suffix pages' targets, as model.prefill_batch_impl derives them.
    nb = T // bs
    blocks = jnp.arange(nb, dtype=jnp.int32)
    padded_tables = jnp.concatenate([block_tables, jnp.zeros((Bp, nb), jnp.int32)], axis=1)
    ids = jnp.take_along_axis(padded_tables, start_pos[:, None] // bs + blocks[None, :], axis=1)
    blk_start = start_pos[:, None] + blocks[None, :] * bs
    flat_ids = jnp.where(blk_start < true_len[:, None], ids, 0).reshape(Bp * nb)

    # The conv state a row resumes from: the block before its first, whole
    # (slot s of a finished block is z at its position K - s from the end);
    # zeros at position 0.
    resumes = start_pos > 0
    prev_blk = jnp.take_along_axis(
        block_tables, jnp.maximum(start_pos // bs - 1, 0)[:, None], axis=1)[:, 0]
    # What each suffix block holds when the chunk is done: per slot, z at the
    # block's last valid position of that slot's turn (local index in T).
    last = jnp.minimum((blocks[None, :] + 1) * bs, (true_len - start_pos)[:, None]) - 1   # [Bp, nb]
    slots = jnp.arange(K, dtype=jnp.int32)
    keep = jnp.maximum(last[..., None] - (last[..., None] - slots) % K, 0)                 # [Bp, nb, K]

    def conv_op(ci, lp, cache, u):
        z, c = conv_gates(u, lp)                                   # [Bp, T, D]
        with jax.named_scope("conv_state"):
            state = cache.conv[ci, slots[None, :], prev_blk[:, None]]                      # [Bp, K, D]
            state = jnp.where(resumes[:, None, None], state, jnp.zeros_like(state))
            kept = jnp.take_along_axis(z, keep.reshape(Bp, nb * K, 1), axis=1).reshape(Bp * nb, K, -1)
            pool = cache.conv
            for s in range(K):
                pool = pool.at[ci, s, flat_ids].set(kept[:, s])
        zs = jnp.concatenate([state, z], axis=1)                   # [Bp, K + T, D]
        y = conv_mix([zs[:, j:j + T] for j in range(K + 1)], c, lp)
        return y, cache._replace(conv=pool)

    def attn_op(ai, lp, cache, u):
        q, k, v = qkv_heads(u, lp, cfg, positions)
        with jax.named_scope("kv_write"):
            kv_cache = write_kv_pages(cache.kv, ai, flat_ids, k.reshape(Bp * nb, bs, KVH * hd),
                                      v.reshape(Bp * nb, bs, KVH * hd))
        with jax.named_scope("attn"):
            qg = q.reshape(Bp, T, KVH, G, hd)
            if impl == "xla":
                o = paged_prefill_attention_xla(
                    qg, k, v, kv_cache, ai, block_tables, start_pos, true_len)
            else:
                o = paged_prefill_attention(
                    qg, kv_cache, ai, block_tables, start_pos, true_len,
                    interpret=(impl == "pallas_interpret"))
        with jax.named_scope("attn_out"):
            y = jnp.dot(o.reshape(Bp, T, cfg.q_size), lp["wo"])
        return y, cache._replace(kv=kv_cache)

    x, cache, hist = _layers(cfg, params, x, cache, valid, conv_op, attn_op,
                             experts or expert_impl())
    last_tok = jnp.clip(true_len - start_pos - 1, 0, T - 1)
    x_last = jnp.take_along_axis(x, last_tok[:, None, None], axis=1)[:, 0]
    with jax.named_scope("logits"):
        logits = _logits(cfg, params, x_last)
    return logits, cache, hist


def prefill_impl(cfg, params, cache, tokens, block_table, start_pos, true_len,
                 lora=None, adapter_slot=None, *, attn_impl: str = "auto",
                 experts: str | None = None):
    """Single-sequence prefill: the Bp=1 case of ``prefill_batch_impl``."""
    logits, cache, hist = prefill_batch_impl(
        cfg, params, cache, tokens[None, :], block_table[None, :],
        jnp.asarray(start_pos, jnp.int32).reshape(1),
        jnp.asarray(true_len, jnp.int32).reshape(1), lora,
        attn_impl=attn_impl, experts=experts,
    )
    return logits[0], cache, hist


def decode_step_impl(cfg, params, cache, tokens, positions, block_tables, active,
                     lora=None, adapter_slots=None, *, attn_impl: str = "auto",
                     experts: str | None = None):
    """``model.decode_step_impl`` for this block: a position reads the conv
    state behind it out of its own block or the one before, writes its own
    ``z`` through, and attends over the K and V pages."""
    _no_lora(lora)
    impl = resolve_attn_impl(attn_impl)
    B = tokens.shape[0]
    bs, K = cache.block_size, cfg.conv_state_slots
    KVH, hd, G = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads // cfg.num_kv_heads
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    rows = jnp.arange(B)
    at = positions // bs
    blk = jnp.where(active, block_tables[rows, at], 0)
    off = jnp.where(active, positions % bs, 0)
    lengths = jnp.where(active, positions + 1, 0)
    # z at the K positions behind this one, nearest last: position p - j is in
    # this block while p % bs >= j, else in the block before; slot (p - j) % K.
    back = jnp.arange(K, 0, -1, dtype=jnp.int32)[None, :]            # [1, K]: K .. 1
    behind = positions[:, None] - back                               # [B, K]
    before = jnp.where(active, block_tables[rows, jnp.maximum(at - 1, 0)], 0)
    src_blk = jnp.where((positions % bs)[:, None] >= back, blk[:, None], before[:, None])
    src_slot = jnp.maximum(behind, 0) % K
    have = (behind >= 0) & active[:, None]
    own_slot = jnp.where(active, positions % K, 0)

    def conv_op(ci, lp, cache, u):
        z, c = conv_gates(u, lp)                                    # [B, D]
        with jax.named_scope("conv_state"):
            prev = cache.conv[ci, src_slot, src_blk]                # [B, K, D]
            prev = jnp.where(have[..., None], prev, jnp.zeros_like(prev))
            pool = cache.conv.at[ci, own_slot, blk].set(z)
        y = conv_mix([prev[:, j] for j in range(K)] + [z], c, lp)
        return y, cache._replace(conv=pool)

    def attn_op(ai, lp, cache, u):
        q, k, v = qkv_heads(u, lp, cfg, positions)
        with jax.named_scope("kv_write"):
            kv_cache = write_kv_tokens(cache.kv, ai, blk, off, k.reshape(B, cfg.kv_size),
                                       v.reshape(B, cfg.kv_size))
        with jax.named_scope("attn"):
            qg = q.reshape(B, KVH, G, hd)
            if impl == "xla":
                o = paged_decode_attention_xla(qg, kv_cache, ai, block_tables, lengths)
            else:
                o = paged_decode_attention(qg, kv_cache, ai, block_tables, lengths,
                                           interpret=(impl == "pallas_interpret"))
        with jax.named_scope("attn_out"):
            y = jnp.dot(o.reshape(B, cfg.q_size), lp["wo"])
        return y, cache._replace(kv=kv_cache)

    x, cache, hist = _layers(cfg, params, x, cache, active, conv_op, attn_op,
                             experts or expert_impl())
    with jax.named_scope("logits"):
        logits = _logits(cfg, params, x)
    return logits, cache, hist


def multi_decode_impl(cfg, num_steps, mode, top_n, params, cache, tokens, positions,
                      block_tables, active, temperature, seeds, steps0, top_k, top_p,
                      freq_penalty, pres_penalty, penalty_tokens, chain_mask=None,
                      chain_src=None, last_toks=None, lora=None, adapter_slots=None,
                      *, attn_impl: str = "auto", experts: str | None = None):
    """``model.multi_decode_impl`` for this block: the same fused window
    (``model.decode_window``) over this block's step; the window's routing
    histogram, summed over the substeps, comes after the cache."""
    def step(cache, tok, pos):
        return decode_step_impl(cfg, params, cache, tok, pos, block_tables, active,
                                lora, adapter_slots, attn_impl=attn_impl, experts=experts)

    hist0 = jnp.zeros((len(routed_layers(cfg)), cfg.num_experts + HIST_EXTRA), jnp.int32)
    return decode_window(
        step, hist0, cfg.vocab_size, num_steps, mode, top_n, cache, tokens, positions,
        temperature, seeds, steps0, top_k, top_p, freq_penalty, pres_penalty,
        penalty_tokens, chain_mask, chain_src, last_toks,
    )


# The jitted programs, under engine/model.py's names and with its donation.
_STATIC = ("attn_impl", "experts")
prefill = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=_STATIC, donate_argnums=(2,))(prefill_impl)
prefill_batch = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=_STATIC, donate_argnums=(2,))(prefill_batch_impl)
decode_step = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=_STATIC, donate_argnums=(2,))(decode_step_impl)
multi_decode = functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3), static_argnames=_STATIC, donate_argnums=(5,)
)(multi_decode_impl)
