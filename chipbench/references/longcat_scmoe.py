"""The LongCat-Flash language model as published, written down plainly: latent
(MLA) attention with a low-rank query, a shortcut-connected double layer, and an
expert layer with zero-compute experts. The benchmark's yardstick for
``correct`` in the LongCat cells.

    weights(doc, seed)                                           the seeded weights the cell serves
    forward(doc, params, token_ids, positions=None, starts=(0,)) float32 logits [T or len(positions), V]

Whole sequences at once, ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST``, dense causal attention in the EXPANDED form, no cache,
no kernel, no grouping of tokens. It imports nothing of the program.

The block, from the published ``config.json`` (what it does not state is under
``assumed`` in the configuration's file). With ``D`` hidden, ``H`` heads:

- ``MLA(x, pos)``: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` as ``[H, dn + dr]``,
  times ``sqrt(D / q_lora_rank)`` (``mla_scale_q_lora``), split ``q_n | q_r``;
  ``[c_kv | k_r] = x W_kva`` split ``kv_lora_rank | dr``;
  ``c_kv = RMSNorm(c_kv) sqrt(D / kv_lora_rank)`` (``mla_scale_kv_lora``); rotary
  embedding over neighbouring pairs ``(2i, 2i+1)`` on ``q_r`` and on the one
  ``k_r`` all heads share; ``[k_n | v] = c_kv W_kvb`` as ``[H, dn + dv]``;
  ``score_h(t, s) = (q_n,h(t) k_n,h(s) + q_r,h(t) k_r(s)) / sqrt(dn + dr)``, causal
  softmax, ``o_h = sum p v_h``, output ``concat_h(o_h) W_o``.
- ``MoE(h)``: ``p = softmax(h W_r)`` over all routed + zero-compute experts;
  ``S = top_k(p + b)`` with ``b`` the ``e_score_correction_bias`` (selection only);
  ``w_e = routed_scaling_factor * p_e`` for ``e in S``, not renormalised;
  ``MoE(h) = sum_{e in S, routed} w_e (silu(h G_e) * (h U_e)) D_e + sum_{e in S, zero} w_e h``.
- layer: ``a1 = x + MLA_0(norm(x))``; ``h1 = norm(a1)``; ``m = MoE(h1)``;
  ``b1 = a1 + FFN_0(h1)``; ``a2 = b1 + MLA_1(norm(b1))``; ``h2 = norm(a2)``;
  ``out = a2 + FFN_1(h2) + m``. Final norm, untied head.

Departures from the published description, each for a reason:
- **The share.** The configuration's file states what one chip of the deployment
  holds: ``n_routed_experts`` experts from index ``first_expert_held`` of the
  published ``published_n_routed_experts``, and ``vocab_size`` rows of the
  vocabulary. The router keeps its published width; a chosen routed expert that
  is not held adds nothing here, as it adds nothing on this chip (its owner adds
  it after the exchange). The program is given the same share.
- The weights are the cell's *served* weights (bf16): the reference multiplies
  them out to float32 a tensor, or an expert, at a time, since 5.2B parameters
  in float32 are 20.7 GB.
- ``W_qb``'s nope and rope columns, and ``W_kvb``'s key and value halves, are
  drawn as tensors of their own (``w_qn``, ``w_qr``, ``w_uk``, ``w_uv``), as the
  seeded initialiser makes them: the products are the published ones, split.
- The held experts are a plain loop: every expert over every token, times the
  weight the router gave it there (0 where it was not chosen).
- ``token_ids`` may hold several sequences end to end (``starts``), each
  attending to itself alone with positions from its own start; the row is
  padded to a multiple of 512; attention runs a few heads at a time;
  ``positions`` picks the rows wanted before the head (as ``dense_gqa.py``).

Controls (``parity_seeds.py`` only; a run never sets them): the environment's
``LONGCAT_REF_CONTROL`` = ``roll_experts`` rolls the router's choice by one
expert index, ``int8_latents`` rounds every cached latent to int8 with one
scale a token, the precision under the bf16 cache the configuration states,
which the program cannot run. Either must come out as not correct.

``weights`` is a copy of the program's seeded initialiser
(``engine/longcat.py:init_params``): each tensor a layer at a time, one jitted
draw each. A program that changes its own stops agreeing with it.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
PAD = 512
HEADS_AT_A_TIME = 4  # float32 scores of 4 heads over 4,096 x 4,096 positions are 268 MB
ROUTER_LOGIT_STD = 3.0  # the seeded router's logits: twelve choices hold about half the mass, as a trained router's


def sizes(doc: dict) -> tuple:
    return (doc["num_layers"], doc["hidden_size"], doc["ffn_hidden_size"], doc["num_attention_heads"],
            doc["q_lora_rank"], doc["kv_lora_rank"], doc["qk_nope_head_dim"], doc["qk_rope_head_dim"],
            doc["v_head_dim"], doc["n_routed_experts"], doc["published_n_routed_experts"],
            doc["first_expert_held"], doc["zero_expert_num"], doc["moe_topk"],
            doc["expert_ffn_hidden_size"], doc["vocab_size"], bool(doc["mla_scale_q_lora"]),
            bool(doc["mla_scale_kv_lora"]), float(doc["routed_scaling_factor"]),
            float(doc["rope_theta"]), float(doc["rms_norm_eps"]),
            os.environ.get("LONGCAT_REF_CONTROL", ""))


# -- the seeded weights ----------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def weights(doc: dict, seed: int) -> dict:
    (L, D, I, H, rq, rkv, dn, dr, dv, E, _, _, Z, _, ie, V, *_rest) = sizes(doc)
    R = doc["published_n_routed_experts"] + Z
    dtype = jnp.dtype(doc["served"].get("dtype", "bfloat16"))
    if doc["served"]["quant"] != "none":
        raise ValueError("the LongCat block is served in its published bf16 only")
    key = jax.random.PRNGKey(seed)
    sub = {"w_qa": ((D, rq), D), "w_qn": ((rq, H * dn), rq), "w_qr": ((rq, H * dr), rq),
           "w_kva": ((D, rkv + dr), D), "w_uk": ((H, dn, rkv), rkv), "w_uv": ((H, rkv, dv), rkv),
           "wo": ((H * dv, D), H * dv),
           "w_gate": ((D, I), D), "w_up": ((D, I), D), "w_down": ((I, D), I)}
    shapes = {f"{name}_{j}": v for j in (0, 1) for name, v in sub.items()}
    shapes.update({"w_router": ((D, R), D), "moe_gate": ((E, D, ie), D), "moe_up": ((E, D, ie), D),
                   "moe_down": ((E, ie, D), ie)})
    layers = {}
    for n, (name, (shape, fan_in)) in enumerate(shapes.items()):
        k = jax.random.fold_in(key, 100 + n)
        std = fan_in ** -0.5 * (ROUTER_LOGIT_STD if name == "w_router" else 1.0)
        layers[name] = jnp.stack([_draw(jax.random.fold_in(k, l), shape, std, dtype) for l in range(L)])
    layers["router_bias"] = jnp.stack([
        _draw(jax.random.fold_in(jax.random.fold_in(key, 99), l), (R,), 1.0 / R, jnp.float32)
        for l in range(L)])
    # the latent norms' gains undo the published scale factors (engine/longcat.py says why)
    q_gain = (rq / D) ** 0.5 if doc["mla_scale_q_lora"] else 1.0
    kv_gain = (rkv / D) ** 0.5 if doc["mla_scale_kv_lora"] else 1.0
    for j in (0, 1):
        layers[f"attn_norm_{j}"] = jnp.ones((L, D), dtype)
        layers[f"mlp_norm_{j}"] = jnp.ones((L, D), dtype)
        layers[f"q_norm_{j}"] = jnp.full((L, rq), q_gain, dtype)
        layers[f"kv_norm_{j}"] = jnp.full((L, rkv), kv_gain, dtype)
    return {"embed": _draw(jax.random.fold_in(key, 1), (V, D), D ** -0.5, dtype),
            "lm_head": _draw(jax.random.fold_in(key, 2), (D, V), D ** -0.5, dtype),
            "layers": layers, "final_norm": jnp.ones((D,), dtype)}


# -- the forward pass ------------------------------------------------------------


def _f32(w: jax.Array) -> jax.Array:
    return w.astype(jnp.float32)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _rope_pairs(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x [T, heads, hd] at positions ``pos`` [T]; lane 2i pairs with lane 2i+1."""
    T, heads, hd = x.shape
    inv_freq = theta ** (-jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2))
    angles = pos.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    pairs = x.reshape(T, heads, hd // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(T, heads, hd)


def _dot(a, b):
    return jnp.dot(a, b, precision=HI)


def _swiglu(h, gate, up, down):
    return _dot(jax.nn.silu(_dot(h, _f32(gate))) * _dot(h, _f32(up)), _f32(down))


def _mla(h, seq, pos, lp, j, dims):
    (_, D, _, H, rq, rkv, dn, dr, dv, *_r) = dims
    scale_q, scale_kv, theta, eps, control = dims[16], dims[17], dims[19], dims[20], dims[21]
    T = h.shape[0]
    w = lambda name: lp[f"{name}_{j}"]  # noqa: E731
    c_q = _rms(_dot(h, _f32(w("w_qa"))), w("q_norm"), eps)
    q_mul = (D / rq) ** 0.5 if scale_q else 1.0
    q_n = (_dot(c_q, _f32(w("w_qn"))) * q_mul).reshape(T, H, dn)
    q_r = _rope_pairs((_dot(c_q, _f32(w("w_qr"))) * q_mul).reshape(T, H, dr), pos, theta)
    kv = _dot(h, _f32(w("w_kva")))
    c_kv = _rms(kv[:, :rkv], w("kv_norm"), eps) * ((D / rkv) ** 0.5 if scale_kv else 1.0)
    k_r = _rope_pairs(kv[:, None, rkv:], pos, theta)[:, 0]
    if control == "int8_latents":  # the cache row in int8, one scale a token
        row = jnp.concatenate([c_kv, k_r], axis=-1)
        s = jnp.max(jnp.abs(row), axis=-1, keepdims=True) / 127.0
        row = jnp.clip(jnp.round(row / s), -127, 127) * s
        c_kv, k_r = row[:, :rkv], row[:, rkv:]
    causal = (jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]) & (seq[:, None] == seq[None, :])

    def heads(args):  # a few heads at a time
        qn, qr, uk, uv = args  # [g, T, dn], [g, T, dr], [g, dn, rkv], [g, rkv, dv]
        k_n = jnp.einsum("sl,gnl->gsn", c_kv, _f32(uk), precision=HI)
        v = jnp.einsum("sl,glv->gsv", c_kv, _f32(uv), precision=HI)
        s = (jnp.einsum("gtn,gsn->gts", qn, k_n, precision=HI)
             + jnp.einsum("gtr,sr->gts", qr, k_r, precision=HI)) * (dn + dr) ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,gsv->gtv", p, v, precision=HI)

    g = min(HEADS_AT_A_TIME, H)
    grouped = lambda a: a.reshape(H // g, g, *a.shape[1:])  # noqa: E731
    o = lax.map(heads, (grouped(q_n.transpose(1, 0, 2)), grouped(q_r.transpose(1, 0, 2)),
                        grouped(w("w_uk")), grouped(w("w_uv"))))
    o = o.reshape(H, T, dv).transpose(1, 0, 2).reshape(T, H * dv)
    return _dot(o, _f32(w("wo")))


def _moe(h, lp, dims):
    E, n_routed, first, Z, k = dims[9], dims[10], dims[11], dims[12], dims[13]
    scaling, control = dims[18], dims[21]
    p = jax.nn.softmax(_dot(h, _f32(lp["w_router"])), axis=-1)
    _, chosen = lax.top_k(p + lp["router_bias"][None, :], k)
    w = jnp.take_along_axis(p, chosen, axis=-1) * scaling
    if control == "roll_experts":
        chosen = (chosen + 1) % (n_routed + Z)
    y = jnp.sum(jnp.where(chosen >= n_routed, w, 0.0), axis=-1, keepdims=True) * h  # zero-compute: w * h

    def expert(y, args):  # one held expert over every token, times its weight there
        e, gate, up, down = args
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1, keepdims=True)
        return y + w_e * _swiglu(h, gate, up, down), None

    return lax.scan(expert, y, (jnp.arange(E), lp["moe_gate"], lp["moe_up"], lp["moe_down"]))[0]


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer(x, seq, pos, layers, l, dims):
    eps = dims[20]
    lp = {k: lax.dynamic_index_in_dim(v, l, 0, keepdims=False) for k, v in layers.items()}
    a1 = x + _mla(_rms(x, lp["attn_norm_0"], eps), seq, pos, lp, 0, dims)
    h1 = _rms(a1, lp["mlp_norm_0"], eps)
    m = _moe(h1, lp, dims)
    b1 = a1 + _swiglu(h1, lp["w_gate_0"], lp["w_up_0"], lp["w_down_0"])
    a2 = b1 + _mla(_rms(b1, lp["attn_norm_1"], eps), seq, pos, lp, 1, dims)
    h2 = _rms(a2, lp["mlp_norm_1"], eps)
    return a2 + _swiglu(h2, lp["w_gate_1"], lp["w_up_1"], lp["w_down_1"]) + m


@jax.jit
def _embed(params: dict, tokens: jax.Array) -> jax.Array:
    return params["embed"][tokens].astype(jnp.float32)


@jax.jit
def _head(x: jax.Array, w: jax.Array) -> jax.Array:
    return _dot(x, _f32(w))


def forward(doc: dict, params: dict, token_ids: list[int], positions=None, starts=(0,)) -> jax.Array:
    dims = sizes(doc)
    L, eps = dims[0], dims[20]
    T = len(token_ids)
    padded = -(-T // PAD) * PAD
    tokens = jnp.asarray(list(token_ids) + [0] * (padded - T), jnp.int32)
    first = jnp.asarray(sorted(starts), jnp.int32)
    seq = jnp.searchsorted(first, jnp.arange(padded, dtype=jnp.int32), side="right") - 1
    pos = jnp.arange(padded, dtype=jnp.int32) - first[seq]
    x = _embed(params, tokens)
    for l in range(L):
        x = _layer(x, seq, pos, params["layers"], jnp.int32(l), dims)
    rows = jnp.arange(T) if positions is None else jnp.asarray(positions, jnp.int32)
    return _head(_rms(x[rows], params["final_norm"], eps), params["lm_head"])
