"""The LongCat-Flash block (``ModelConfig.block == "longcat"``): one layer
definition, used by the packed prefill, the single prefill and the decode
window. ``model.block_module(cfg)`` is this module for such a model: it has
``init_params``, ``init_kv_cache`` and the jitted ``prefill``,
``prefill_batch``, ``decode_step`` and ``multi_decode`` under the names
engine/model.py has them (the programs are ``jit_prefill_batch_impl``,
``jit_prefill_impl``, ``jit_multi_decode_impl`` in a trace, as the dense
block's are), each returning the layers' routing histogram ``[L, E + 5]``
after what the dense block's returns.

A layer is no longer "attention, then FFN":

    a1 = x  + MLA_0(norm(x));  h1 = norm(a1);  m = MoE(h1)
    b1 = a1 + FFN_0(h1)
    a2 = b1 + MLA_1(norm(b1)); h2 = norm(a2)
    out = a2 + FFN_1(h2) + m            (the shortcut: m joins at the end)

- **MLA** (latent attention, low-rank query): the cache holds, per token
  and sub-block, the normed-and-scaled latent ``c_kv`` (``kv_lora_rank``)
  and the rotated rope key all heads share (``qk_rope_head_dim``): one row
  of ``latent_dim`` values, padded to whole lane tiles, in ONE pool of
  ``2L`` cache layers (cache layer ``2*layer + sub_block``). Prefill and
  decode both absorb ``W_kvb`` into the query and the output and attend
  straight over the latent pages (ops/paged_attention.py
  ``latent_prefill_attention``, ``latent_decode_attention``): a chunk's
  latents are in their pages before its attention runs. The sums are those
  of the published, expanded form (tests/test_longcat.py keeps it as the
  reference).
- **MoE**: a router over the PUBLISHED width (routed + zero-compute
  experts), float32 softmax, top-k chosen on ``p + bias`` with weights
  ``scaling * p`` (the bias moves the choice, not the weights; nothing is
  renormalised). Tokens assigned to the experts HELD here
  (``[expert_offset, expert_offset + num_experts)``) are grouped by a sort
  and go through a grouped matrix product over the held stacks; a
  zero-compute expert adds ``w * h`` and costs no weights; what an absent
  expert would add is left out. Shapes are static: the grouped product
  has room for every assignment landing here, so no token is dropped
  whatever the imbalance, and tiles past the real rows are skipped.

The rope on the 64 rope lanes pairs neighbours ``(2i, 2i+1)`` as published
(DeepSeek-V3 convention) and leaves the result in half-split order on both
the query and the key, which leaves every dot product as it is.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.model import KVCache, _logits, decode_window, pool_zeros
from dynamo_tpu.ops.paged_attention import (
    latent_decode_attention,
    latent_decode_attention_xla,
    latent_prefill_attention,
    latent_prefill_attention_xla,
    resolve_attn_impl,
    resolve_prefill_impl,
)

Params = dict[str, Any]

UNCARRIED = ("latent pages", "latent (MLA) pages")
# Assignment rows the grouped product is given at a time (tokens x min(top-k,
# experts held)): 512 tokens of this block's twelve choices, so its 2,048-token
# chunk is routed in four parts; a block of four choices routes 1,536 tokens
# at once, which covers any pack of prefill rows the engine forms
# (runner.pack_limit), so a pack streams a layer's experts once.
MOE_CHUNK_ROWS = 6144
# The grouped product's tiles come from its operands (``gmm_tiling``). Rows:
# 128 a tile, which ``_moe_tokens`` pads its assignment rows to; at one K tile
# 64 read within a point of it in a decode call and 2-3 points under in a
# pack, 256 3-5 points under in a decode call (PERF.md section 6, PR 41).
_GMM_ROW_TILE = 128
# Bytes the kernel's buffers may take by ``gmm_tiling``'s count: the weight
# tile and the activation tile twice each (the pipeline fetches the next beside
# the one in use), the float32 output tile twice and the accumulator. The v5e
# compiler gives a kernel 16.00 MiB of scoped VMEM and wants about 1.2 MiB of
# its own beside these (a set counted at 15.75 MiB was refused at "16.95M and
# limit 16.00M": tests/test_ops_tpu_lowering.py compiles without a chip), so
# 12 MiB leaves it three times that.
_GMM_VMEM_BUDGET = 12 << 20
# The least bytes of a weight row a tile narrower than the row may take: a
# tile's rows are fetched apart, ``tn x itemsize`` bytes each, and 512 B rows
# ([6144, 256] of bf16 rows 4 KiB apart) read 3-5% under whole rows inside the
# layer where 1 KiB and 1.5 KiB rows read level with them (PERF.md, PR 41).
_GMM_MIN_FETCH_BYTES = 1024
# The seeded router's logits have this deviation (a normed stream times
# w_router): at 1 the softmax over 768 outputs is flat, twelve choices hold
# 8% of the mass and the whole expert block moves 4% of the residual stream,
# so nothing downstream can tell a wrong expert from a right one. At 3 the
# twelve hold about half, as a trained router's do (weights 6p of 0.05 to
# 0.9), and a held expert that fires weighs what a dense FFN does.
ROUTER_LOGIT_STD = 3.0


# -- the seeded initialiser (chipbench/references/longcat_scmoe.py keeps a copy) --


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params. Every tensor is drawn a layer at a time (one
    jitted draw each, then stacked) so that no float32 copy of more than
    one layer of one tensor ever exists: 5.2B parameters are made on a
    16 GB chip. ``router_bias`` is drawn too, at the scale of a router
    probability, so that selection on the wrong scores shows."""
    L, D, I, H = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size, cfg.num_heads
    E, ie, R = cfg.num_experts, cfg.moe_intermediate_size or I, cfg.router_width
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    # Each attention sub-block's tensors are leaves of their own
    # (``<name>_0``, ``<name>_1``), stacked over layers like the dense
    # model's: a leaf [L, 2, ..] made the layer scan copy both sub-blocks'
    # weights out of the stack before every use. W_qb's nope and rope
    # columns, and W_kvb's key and value halves, are held apart and head-
    # major (w_qn, w_qr, w_uk [H, dn, rkv], w_uv [H, rkv, dv]): what the
    # absorbed form multiplies by, with no relayout of a weight in a step.
    sub = {  # name: (shape of one layer, fan_in)
        "w_qa": ((D, rq), D), "w_qn": ((rq, H * dn), rq), "w_qr": ((rq, H * dr), rq),
        "w_kva": ((D, rkv + dr), D), "w_uk": ((H, dn, rkv), rkv), "w_uv": ((H, rkv, dv), rkv),
        "wo": ((H * dv, D), H * dv),
        "w_gate": ((D, I), D), "w_up": ((D, I), D), "w_down": ((I, D), I),
    }
    shapes = {f"{name}_{j}": v for j in (0, 1) for name, v in sub.items()}
    shapes.update({
        "w_router": ((D, R), D),
        "moe_gate": ((E, D, ie), D), "moe_up": ((E, D, ie), D), "moe_down": ((E, ie, D), ie),
    })
    layers: dict[str, Any] = {}
    for n, (name, (shape, fan_in)) in enumerate(shapes.items()):
        k = jax.random.fold_in(key, 100 + n)
        std = fan_in ** -0.5 * (ROUTER_LOGIT_STD if name == "w_router" else 1.0)
        layers[name] = jnp.stack([
            _draw(jax.random.fold_in(k, l), shape, std, dtype) for l in range(L)
        ])
    layers["router_bias"] = jnp.stack([
        _draw(jax.random.fold_in(jax.random.fold_in(key, 99), l), (R,), 1.0 / R, jnp.float32)
        for l in range(L)
    ])
    # The two latent norms' gains undo the published scale factors
    # (mla_scale_q_lora, mla_scale_kv_lora), as training would: with gains of
    # 1 a random model's attention scores have a deviation of 6 and softmax is
    # an argmax, so a rounding in bf16 doubles from layer to layer (0.03 of a
    # logit after one layer, 0.4 after four: my chip runs, PR 30) and no
    # comparison with a float32 reference can tell a fault from a rounding.
    q_gain = (rq / D) ** 0.5 if cfg.mla_scale_q_lora else 1.0
    kv_gain = (rkv / D) ** 0.5 if cfg.mla_scale_kv_lora else 1.0
    for j in (0, 1):
        layers[f"attn_norm_{j}"] = jnp.ones((L, D), dtype)
        layers[f"mlp_norm_{j}"] = jnp.ones((L, D), dtype)
        layers[f"q_norm_{j}"] = jnp.full((L, rq), q_gain, dtype)
        layers[f"kv_norm_{j}"] = jnp.full((L, rkv), kv_gain, dtype)
    return {
        "embed": _draw(jax.random.fold_in(key, 1), (cfg.vocab_size, D), D ** -0.5, dtype),
        "lm_head": _draw(jax.random.fold_in(key, 2), (D, cfg.vocab_size), D ** -0.5, dtype),
        "layers": layers,
        "final_norm": jnp.ones((D,), dtype),
    }


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
                  sharding=None, kv_quant: str = "none") -> KVCache:
    """One pool of ``2L`` cache layers of latent rows, no V (``KVCache``)."""
    if kv_quant != "none":
        raise ValueError("a latent (MLA) cache has no int8 form (kv_quant)")
    shape = (cfg.cache_layers, num_blocks, block_size, cfg.latent_page_width)
    return KVCache(pool_zeros(sharding)(shape, dtype))


_SUB_KEYS = ("attn_norm", "mlp_norm", "q_norm", "kv_norm", "w_qa", "w_qn", "w_qr", "w_kva",
             "w_uk", "w_uv", "wo", "w_gate", "w_up", "w_down")


# -- pieces ----------------------------------------------------------------------


def _rms(x: jax.Array, w: jax.Array, eps: float, scale: float = 1.0) -> jax.Array:
    xf = x.astype(jnp.float32)
    norm = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * w.astype(jnp.float32) * scale).astype(x.dtype)


def yarn_inv_freq(cfg: ModelConfig):
    """The rope lanes' frequencies under static YaRN scaling (``cfg.yarn_factor``),
    or None where the model has none: ``f_i = theta^(-2i/dr)`` as they are
    above the correction range, ``f_i / factor`` below it, a linear ramp
    between. The range: ``dr ln(L0 / (beta 2 pi)) / (2 ln theta)`` at
    ``beta_fast`` (floor) and ``beta_slow`` (ceil), clipped to the lanes."""
    if not cfg.yarn_factor:
        return None
    dr, theta, L0 = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn_original_max_position

    def correction(beta: float) -> float:
        return dr * math.log(L0 / (beta * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.yarn_beta_slow)), dr - 1)
    f = theta ** (-np.arange(dr // 2, dtype=np.float64) * 2 / dr)
    ramp = np.clip((np.arange(dr // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return jnp.asarray(f / cfg.yarn_factor * ramp + f * (1.0 - ramp), jnp.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction ``0.1 mscale ln(factor) + 1`` (1 unscaled)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_pairs(x: jax.Array, positions: jax.Array, theta: float, inv_freq=None, mscale: float = 1.0) -> jax.Array:
    """Rotary embedding over neighbouring pairs. x [..., heads, hd] with
    positions [...]; returns the rotated pairs in half-split order.
    ``inv_freq`` [hd / 2]: scaled frequencies in the place of ``theta``'s
    (``yarn_inv_freq``), cos and sin times ``mscale`` where that is not 1."""
    half = x.shape[-1] // 2
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _yarn(cfg: ModelConfig) -> tuple:
    """What ``_rope_pairs`` takes after the base: nothing for a model without
    YaRN, else its frequencies and the ratio of its two magnitude corrections."""
    if not cfg.yarn_factor:
        return ()
    return (yarn_inv_freq(cfg), yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
            / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))


def mla_query_latent(h: jax.Array, sub: dict, cfg: ModelConfig) -> jax.Array:
    """h [..., D] → the normed low-rank query latent ``c_q`` [..., q_lora_rank]."""
    with jax.named_scope("mla_q"):
        return _rms(jnp.dot(h, sub["w_qa"]), sub["q_norm"], cfg.rms_norm_eps)


def mla_project(h: jax.Array, sub: dict, cfg: ModelConfig, positions: jax.Array, c_q=None):
    """h [..., D] at ``positions`` [...] → (q_n [..., H, dn], q_r [..., H, dr],
    latent [..., latent_dim]): the low-rank query, and the row the cache holds.
    ``c_q``: ``mla_query_latent(h, ..)`` where the caller has it already."""
    D, H = cfg.hidden_size, cfg.num_heads
    dn, dr, rkv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    if c_q is None:
        c_q = mla_query_latent(h, sub, cfg)
    with jax.named_scope("mla_q"):
        q_scale = jnp.asarray((D / cfg.q_lora_rank) ** 0.5 if cfg.mla_scale_q_lora else 1.0, h.dtype)
        q_n = (jnp.dot(c_q, sub["w_qn"]) * q_scale).reshape(*h.shape[:-1], H, dn)
        q_r = (jnp.dot(c_q, sub["w_qr"]) * q_scale).reshape(*h.shape[:-1], H, dr)
        q_r = _rope_pairs(q_r, positions, cfg.rope_theta, *_yarn(cfg))
    with jax.named_scope("mla_kv_write"):
        kv = jnp.dot(h, sub["w_kva"])
        c_kv = _rms(kv[..., :rkv], sub["kv_norm"], cfg.rms_norm_eps,
                    (D / rkv) ** 0.5 if cfg.mla_scale_kv_lora else 1.0)
        k_r = _rope_pairs(kv[..., None, rkv:], positions, cfg.rope_theta, *_yarn(cfg))[..., 0, :]
        latent = jnp.concatenate([c_kv, k_r], axis=-1)
    return q_n, q_r, latent


def _pad_row(x: jax.Array, cfg: ModelConfig, width: int = 0) -> jax.Array:
    """Zero lanes after the last axis, to ``width`` (a cache row's by default)."""
    pad = (width or cfg.latent_page_width) - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def absorb_query(q_n, q_r, sub: dict, cfg: ModelConfig) -> jax.Array:
    """[.., H, dn], [.., H, dr] → the query against a cache row, [.., H,
    latent_page_width]: ``q_n W_uk^T`` beside ``q_r``, zero in the padding."""
    q_abs = jnp.einsum("...hn,hnc->...hc", q_n, sub["w_uk"])
    return _pad_row(jnp.concatenate([q_abs, q_r], axis=-1), cfg)


def unabsorb_output(o_lat, sub: dict, cfg: ModelConfig) -> jax.Array:
    """Σ p·c_kv [.., H, kv_lora_rank] → [.., H, dv] through W_uv."""
    return jnp.einsum("...hc,hcv->...hv", o_lat, sub["w_uv"])


def _mlp(x, sub):
    g, u = jnp.dot(x, sub["w_gate"]), jnp.dot(x, sub["w_up"])
    return jnp.dot(jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u, sub["w_down"])


# -- the expert layer ------------------------------------------------------------


def expert_impl() -> str:
    """The grouped product's path, from the platform: the megablox ``gmm``
    kernel on a TPU, ``lax.ragged_dot`` elsewhere. The engine's start line
    says which (``experts=``)."""
    return "gmm" if jax.default_backend() == "tpu" else "ragged_dot"


def _tile_sizes(d: int) -> list[int]:
    """The tiles that leave no remainder in a dimension of ``d``, widest
    first: ``d`` itself, then the multiples of 128 lanes that divide it."""
    return [d] + [t for t in range(d // 128 * 128, 0, -128) if d % t == 0 and t != d]


def _gmm_buffer_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """What ``_GMM_VMEM_BUDGET`` counts: the weight and activation tiles twice
    each, the float32 output tile twice and the accumulator."""
    return 2 * tk * tn * itemsize + 2 * tm * tk * itemsize + 3 * tm * tn * 4


def gmm_tiling(k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """The megablox kernel's (row, K, N) tiles for a product of ``[M, k]`` rows
    by ``[k, n]`` groups of ``itemsize``-byte elements (M a multiple of the
    row tile: ``_moe_tokens`` pads to it). The kernel's grid is (N tiles, the
    row tiles that hold a real row once a group in them, K tiles) with K
    innermost, and its pipeline skips a fetch whose block is the one it holds.

    **One K tile** wherever a ``[k, tn]`` weight tile of rows worth fetching
    (``_GMM_MIN_FETCH_BYTES``, or whole rows) fits ``_GMM_VMEM_BUDGET`` beside
    the rest: then a row tile's activations are fetched once and not once a K
    step, a group's weights once and not once a row tile it straddles (with K
    steps between two visits of a group the block cycles and is read again),
    no K step is a remainder the kernel has to mask in float32, and the grid
    has a K-th of the steps. ``tn`` is the widest multiple of 128 that divides
    ``n`` and fits, so no N tile is part padding either. **Elsewhere** (a K so
    deep that only a sliver of N fits beside it): the widest such ``tn`` some
    K tile fits beside, whole rows first, then the deepest ``tk`` that divides
    ``k``: with K tiled the activations' re-read costs ``row tile / tn`` of
    the weights' bytes, and a tile of whole rows is one contiguous fetch. On
    the chip, inside the layer (PERF.md section 6, PR 41): (128, 2048, 768) and
    (128, 1536, 1024) took 10.5% off a decode call's three products at
    ``K, N`` 2048, 1536 and 17.5% off a pack's against (128, 1024, 1024);
    (128, 1024, 2048) 1.5-2% at 6144, 2048, where (128, 6144, 256) added 3%."""
    tm = _GMM_ROW_TILE

    def fits(tk: int, tn: int) -> bool:
        return _gmm_buffer_bytes(tm, tk, tn, itemsize) <= _GMM_VMEM_BUDGET

    depths, widths = _tile_sizes(k), _tile_sizes(n)
    one_k = [(k, tn) for tn in widths if tn == n or tn * itemsize >= _GMM_MIN_FETCH_BYTES]
    shapes = one_k + [(tk, tn) for tn in widths for tk in depths]
    tk, tn = next((s for s in shapes if fits(*s)), (depths[-1], widths[-1]))
    return tm, tk, tn


@functools.partial(jax.jit, static_argnames=("impl",))
def grouped_expert_matmul(x, w, group_sizes, *, impl: str = "ragged_dot"):
    """x [M, K] sorted by group, w [E, K, N], ``group_sizes`` [E] → [M, N]
    float32: rows of group e times w[e]. Rows past the groups' total are not
    computed (and hold nothing to rely on). ``impl`` "gmm" (``expert_impl``
    on the chip) is the megablox grouped product: only tiles that hold a
    real row run, so an expert no token chose has its weights left unread,
    and under ``gmm_tiling``'s tiles an expert some token chose has them read
    once; "gmm_interpret" runs that kernel in interpret mode (tests)."""
    if impl == "ragged_dot":
        return lax.ragged_dot(x, w, group_sizes, preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(x, w, group_sizes, preferred_element_type=jnp.float32,
               tiling=gmm_tiling(*w.shape[1:], w.dtype.itemsize), interpret=(impl == "gmm_interpret"))


def route(xt: jax.Array, lp: dict, cfg: ModelConfig):
    """xt [N, D] → (expert ids [N, k] over the published router width,
    weights [N, k] float32). Selection on ``p + bias``; weights
    ``scaling * p``, not renormalised. ``cfg.router_scoring == "sigmoid"``
    (engine/lfm2.py) is the second arithmetic: ``s = sigmoid(logits)``,
    selection on ``s + bias`` where ``use_expert_bias``, weights ``s`` there,
    over their sum where ``norm_topk_prob``, times the scaling.
    ``cfg.topk_method == "group_limited_greedy"`` (engine/deepseek.py) is the
    third: softmax scores, the experts in ``n_group`` consecutive groups, a
    group's score its best expert's, the ``topk_group`` best groups kept and
    the top-k taken among their experts alone; weights ``scaling * p``."""
    logits = jnp.dot(xt, lp["w_router"], preferred_element_type=jnp.float32)
    if cfg.topk_method == "group_limited_greedy":
        probs = jax.nn.softmax(logits, axis=-1)
        N, E = probs.shape
        best = jnp.max(probs.reshape(N, cfg.n_group, E // cfg.n_group), axis=-1)
        _, groups = lax.top_k(best, cfg.topk_group)                          # [N, topk_group]
        kept = jnp.any(groups[:, :, None] == jnp.arange(cfg.n_group)[None, None, :], axis=1)  # [N, n_group]
        allowed = jnp.repeat(kept, E // cfg.n_group, axis=-1)
        _, topi = lax.top_k(jnp.where(allowed, probs, 0.0), cfg.num_experts_per_token)
        topw = jnp.take_along_axis(probs, topi, axis=-1) * cfg.routed_scaling_factor
        return topi, topw
    if cfg.router_scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
        chosen_on = s + lp["router_bias"][None, :] if cfg.use_expert_bias else s
        _, topi = lax.top_k(chosen_on, cfg.num_experts_per_token)
        topw = jnp.take_along_axis(s, topi, axis=-1)
        if cfg.norm_topk_prob:
            topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-6)
        return topi, topw * cfg.routed_scaling_factor
    probs = jax.nn.softmax(logits, axis=-1)
    _, topi = lax.top_k(probs + lp["router_bias"][None, :], cfg.num_experts_per_token)
    topw = jnp.take_along_axis(probs, topi, axis=-1) * cfg.routed_scaling_factor
    return topi, topw


def _moe_tokens(xt, valid, lp: dict, cfg: ModelConfig, impl: str):
    """``lp["moe_gate"|"moe_up"|"moe_down"]`` are every layer's stacks
    [L*E, ..] (``stacked_experts``) and ``lp["moe_layer"]`` says which layer's
    experts are meant (one layer's own stacks with layer 0 do as well);
    ``lp["expert_offset"]``, where given, is the first expert held in the
    place of the static ``cfg.expert_offset``."""
    N, D = xt.shape
    E, k = cfg.num_experts, cfg.num_experts_per_token
    n_routed = cfg.num_routed_experts
    with jax.named_scope("moe_route"):
        topi, topw = route(xt, lp, cfg)
        # Under a mesh the first expert held is the chip's own (engine/deepseek.py
        # puts ``lax.axis_index`` times its share there); else the static offset.
        local = topi - lp.get("expert_offset", cfg.expert_offset)
        live = valid[:, None]
        held = (local >= 0) & (local < E) & (topi < n_routed) & live
        zero = (topi >= n_routed) & live
    with jax.named_scope("moe_zero"):
        y = jnp.sum(jnp.where(zero, topw, 0.0), axis=-1, keepdims=True) * xt.astype(jnp.float32)
    with jax.named_scope("moe_experts"):
        # Group the assignments that land here: a stable sort by local expert
        # (everything else sorts last under the sentinel E); the first
        # ``rows`` places have room for every one of them.
        flat = jnp.where(held, local, E).reshape(N * k)
        order = jnp.argsort(flat, stable=True)
        place = jnp.argsort(order).reshape(N, k)        # where each assignment went
        sizes = jnp.sum(flat[:, None] == jnp.arange(E, dtype=flat.dtype)[None, :],
                        axis=0, dtype=jnp.int32)
        rows = -(-N * min(k, E) // _GMM_ROW_TILE) * _GMM_ROW_TILE
        tok = jnp.pad(order, (0, max(0, rows - N * k)))[:rows] // k
        xs = xt[tok]                                     # [rows, D]
        # This layer's groups among every layer's, the rest empty.
        groups = lax.dynamic_update_slice(
            jnp.zeros((lp["moe_gate"].shape[0],), jnp.int32), sizes, (lp["moe_layer"] * E,))
        g = grouped_expert_matmul(xs, lp["moe_gate"], groups, impl=impl)
        u = grouped_expert_matmul(xs, lp["moe_up"], groups, impl=impl)
        a = (jax.nn.silu(g) * u).astype(xt.dtype)
        d = grouped_expert_matmul(a, lp["moe_down"], groups, impl=impl)  # [rows, D] f32
        back = d[jnp.minimum(place, rows - 1)]           # [N, k, D]
        y = y + jnp.sum(jnp.where(held[..., None], topw[..., None] * back, 0.0), axis=1)
    n_zero = jnp.sum(zero, dtype=jnp.int32)
    n_all = jnp.sum(valid, dtype=jnp.int32)
    hist = jnp.concatenate([
        sizes, jnp.stack([n_zero, n_all * k - jnp.sum(sizes) - n_zero, n_all,
                          jnp.sum(sizes > 0, dtype=jnp.int32), jnp.int32(1)])
    ])
    return y.astype(xt.dtype), hist


# After the experts held: zero-compute, absent, tokens routed, experts
# touched (held experts with at least one token in a call of the grouped
# product: whose weights that call had to read), and those calls.
HIST_EXTRA = 5


def routed_layers(cfg: ModelConfig) -> tuple[int, ...]:
    """The layers that route, in the order of the histogram's rows (the
    engine sizes and labels its ``moe_*`` counters by this)."""
    return tuple(range(cfg.num_layers))


def moe(h: jax.Array, valid: jax.Array, lp: dict, cfg: ModelConfig, impl: str):
    """h [..., D], valid [...] bool → (MoE(h) [..., D], hist [E + 5] int32:
    assignments to each expert held, to zero-compute experts, to absent
    experts, the tokens routed, the experts touched and the calls (1);
    padding counts nowhere)."""
    D = h.shape[-1]
    xt, vt = h.reshape(-1, D), valid.reshape(-1)
    N = xt.shape[0]
    per = MOE_CHUNK_ROWS // min(cfg.num_experts_per_token, cfg.num_experts)
    parts = -(-N // per)
    while N % parts:  # the fewest equal parts of no more than ``per`` tokens
        parts += 1
    if parts > 1:
        y, hist = lax.map(
            lambda a: _moe_tokens(a[0], a[1], lp, cfg, impl),
            (xt.reshape(parts, N // parts, D), vt.reshape(parts, N // parts)),
        )
        y, hist = y.reshape(N, D), jnp.sum(hist, axis=0)
    else:
        y, hist = _moe_tokens(xt, vt, lp, cfg, impl)
    return y.reshape(h.shape), hist


# -- the layer -------------------------------------------------------------------


def layer(cfg: ModelConfig, lp: dict, x, cache, positions, valid, attend, moe_impl: str):
    """One layer over ``x`` [..., D]. ``attend(j, sub, cache, q_n, q_r,
    latent) -> (o [..., H, dv], cache)`` writes sub-block j's latents and
    attends: the one thing prefill and decode do differently."""
    m = hist = None
    for j in (0, 1):
        sub = {name: lp[f"{name}_{j}"] for name in _SUB_KEYS}
        h = _rms(x, sub["attn_norm"], cfg.rms_norm_eps)
        q_n, q_r, latent = mla_project(h, sub, cfg, positions)
        o, cache = attend(j, sub, cache, q_n, q_r, latent)
        with jax.named_scope("mla_out"):
            x = x + jnp.dot(o.reshape(*x.shape[:-1], cfg.num_heads * cfg.v_head_dim), sub["wo"])
        h = _rms(x, sub["mlp_norm"], cfg.rms_norm_eps)
        if j == 0:
            m, hist = moe(h, valid, lp, cfg, moe_impl)
        with jax.named_scope("ffn_dense"):
            x = x + _mlp(h, sub)
    return x + m, cache, hist


_EXPERT_KEYS = ("moe_gate", "moe_up", "moe_down")


def stacked_experts(layers: dict) -> dict:
    """Every layer's expert stacks as one [L*E, ..] stack each (a bitcast).
    The layer scan closes over these instead of slicing a layer out: a
    slice that feeds a kernel is a copy of all of a layer's experts, every
    layer of every step, touched or not. The grouped product is given
    every layer's groups, all empty but this layer's."""
    return {k: layers[k].reshape(-1, *layers[k].shape[2:]) for k in _EXPERT_KEYS}


def _scan_layers(cfg, params, x, pool, positions, valid, attend_for, moe_impl):
    experts = stacked_experts(params["layers"])
    scanned = {k: v for k, v in params["layers"].items() if k not in _EXPERT_KEYS}

    def body(carry, xs):
        x, pool = carry
        lp, layer_idx = xs
        lp = {**lp, **experts, "moe_layer": layer_idx}
        x, pool, hist = layer(cfg, lp, x, pool, positions, valid, attend_for(layer_idx), moe_impl)
        return (x, pool), hist

    (x, pool), hist = lax.scan(
        body, (x, pool), (scanned, jnp.arange(cfg.num_layers, dtype=jnp.int32))
    )
    return x, pool, hist  # hist [L, E + 5]


def prefill_batch_impl(cfg, params, cache, tokens, block_tables, start_pos, true_len,
                       lora=None, adapter_slots=None, *, attn_impl: str = "auto",
                       experts: str | None = None):
    """``model.prefill_batch_impl`` for this block: same arguments and contract
    (prefix pages cached in whole blocks, suffix computed here), and a third
    result, the layers' routing histogram [L, E + 5]. ``experts`` names the
    grouped product's path (None: ``expert_impl()``, by platform)."""
    if lora is not None:
        raise ValueError("LoRA banks cannot run a block='longcat' model")
    Bp, T = tokens.shape
    bs, Wd = cache.block_size, cache.kv.shape[3]
    positions = start_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]   # [Bp, T]
    valid = positions < true_len[:, None]
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    impl, _ = resolve_prefill_impl(attn_impl, cfg, bs, False)
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if impl == "xla":
        attention = functools.partial(latent_prefill_attention_xla, scale=scale)
    else:
        attention = functools.partial(
            latent_prefill_attention, scale=scale, interpret=(impl == "pallas_interpret"))

    # Suffix pages' targets, as model.prefill_batch_impl derives them.
    nb = T // bs
    slot = start_pos[:, None] // bs + jnp.arange(nb, dtype=jnp.int32)[None, :]
    padded_tables = jnp.concatenate([block_tables, jnp.zeros((Bp, nb), jnp.int32)], axis=1)
    ids = jnp.take_along_axis(padded_tables, slot, axis=1)
    blk_start = start_pos[:, None] + jnp.arange(nb, dtype=jnp.int32)[None, :] * bs
    flat_ids = jnp.where(blk_start < true_len[:, None], ids, 0).reshape(Bp * nb)

    def attend_for(layer_idx):
        def attend(j, sub, pool, q_n, q_r, latent):
            ci = 2 * layer_idx + j
            with jax.named_scope("mla_kv_write"):
                pool = pool.at[ci, flat_ids].set(_pad_row(latent, cfg).reshape(Bp * nb, bs, Wd))
            with jax.named_scope("mla_attn"):
                # Out of the pages, prefix and chunk alike, in decode's form,
                # head-major: a batch of heads is what both products make and
                # take, so nothing is transposed around the attention.
                q_lat = jnp.einsum("bthn,hnc->bhtc", q_n, sub["w_uk"])
                q_rope = _pad_row(jnp.moveaxis(q_r, 2, 1), cfg, Wd - cfg.kv_lora_rank)
                o = attention(q_lat, q_rope, pool, ci, block_tables, start_pos, true_len)
                return jnp.einsum("bhtc,hcv->bthv", o, sub["w_uv"]), pool
        return attend

    x, pool, hist = _scan_layers(cfg, params, x, cache.kv, positions, valid, attend_for,
                                 experts or expert_impl())
    last = jnp.clip(true_len - start_pos - 1, 0, T - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    with jax.named_scope("logits"):
        logits = _logits(cfg, params, x_last)
    return logits, KVCache(pool), hist


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
    """``model.decode_step_impl`` for this block (absorbed attention over the
    latent pages), with the routing histogram as a third result."""
    if lora is not None:
        raise ValueError("LoRA banks cannot run a block='longcat' model")
    impl = resolve_attn_impl(attn_impl)
    B = tokens.shape[0]
    bs = cache.block_size
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    blk = jnp.where(active, block_tables[jnp.arange(B), positions // bs], 0)
    off = jnp.where(active, positions % bs, 0)
    lengths = jnp.where(active, positions + 1, 0)
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5

    def attend_for(layer_idx):
        def attend(j, sub, pool, q_n, q_r, latent):
            ci = 2 * layer_idx + j
            with jax.named_scope("mla_kv_write"):
                pool = pool.at[ci, blk, off].set(_pad_row(latent, cfg))
            with jax.named_scope("mla_attn"):
                q = absorb_query(q_n, q_r, sub, cfg)
                if impl == "xla":
                    o = latent_decode_attention_xla(
                        q, pool, ci, block_tables, lengths,
                        value_dim=cfg.kv_lora_rank, scale=scale)
                else:
                    o = latent_decode_attention(
                        q, pool, ci, block_tables, lengths,
                        value_dim=cfg.kv_lora_rank, scale=scale,
                        interpret=(impl == "pallas_interpret"))
                return unabsorb_output(o, sub, cfg), pool
        return attend

    x, pool, hist = _scan_layers(cfg, params, x, cache.kv, positions, active, attend_for,
                                 experts or expert_impl())
    with jax.named_scope("logits"):
        logits = _logits(cfg, params, x)
    return logits, KVCache(pool), hist


def multi_decode_impl(cfg, num_steps, mode, top_n, params, cache, tokens, positions,
                      block_tables, active, temperature, seeds, steps0, top_k, top_p,
                      freq_penalty, pres_penalty, penalty_tokens, chain_mask=None,
                      chain_src=None, last_toks=None, lora=None, adapter_slots=None,
                      *, attn_impl: str = "auto", experts: str | None = None):
    """``model.multi_decode_impl`` for this block: the same fused window
    (``model.decode_window``) over this block's step, returning the window's
    routing histogram, summed over the substeps, after the cache: it rides
    the fetch that carries the tokens."""
    def step(cache, tok, pos):
        return decode_step_impl(cfg, params, cache, tok, pos, block_tables, active,
                                lora, adapter_slots, attn_impl=attn_impl, experts=experts)

    hist0 = jnp.zeros((cfg.num_layers, cfg.num_experts + HIST_EXTRA), jnp.int32)
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
