"""The LFM2-MoE language model (HF ``model_type: lfm2_moe``) written down plainly:
gated short-convolution layers among full-attention layers with a norm on every
query and key head, a dense SwiGLU in the leading layers and routed experts
after. The benchmark's yardstick for ``correct`` in the LFM2 cells.

    weights(doc, seed)                                           the seeded weights the cell serves
    forward(doc, params, token_ids, positions=None, starts=(0,)) float32 logits [T or len(positions), V]

Whole sequences at once, ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST``, dense causal attention, the convolution as shifted
products, a plain loop over the experts, no cache, no kernel, no grouping of
tokens. It imports nothing of the program.

The layer, from the published ``config.json`` (what it does not state is under
``assumed`` in the configuration's file). With ``rms(x; w) = x * rsqrt(mean(x^2)
+ norm_eps) * w``:

- layer ``i``: ``h = x + Op_i(rms(x; operator_norm))``;
  ``out = h + FF_i(rms(h; ffn_norm))``. After the last layer
  ``rms(.; embedding_norm)``, then the head, tied to the embedding.
- ``Op`` "conv" (``layer_types[i]``): ``[B, C, X] = split3(u W_in)``, three parts of
  ``hidden_size`` in that order; ``z = B * X``;
  ``y_t = sum_{j=0..L-1} w[:, j] * z_{t-(L-1)+j}`` with ``L = conv_L_cache`` taps,
  depthwise, causal, no bias (``conv_bias`` false), zeros before a sequence's
  first position; ``Op(u) = (C * y) W_out``.
- ``Op`` "full_attention": ``q, k, v = u W_q, u W_k, u W_v`` (no bias); an RMS norm
  over the ``head_dim`` values of each query head and each key head (gains
  ``q_layernorm``, ``k_layernorm``); rotary embedding over the whole head,
  rotate-half pairs ``(i, i + head_dim/2)``, ``rope_theta``; causal softmax of
  ``q k^T / sqrt(head_dim)``, ``num_attention_heads`` query heads over
  ``num_key_value_heads`` KV heads; ``W_o``.
- ``FF``, layers before ``num_dense_layers``: ``(silu(x W_1) * (x W_3)) W_2`` of
  width ``intermediate_size``. From there on: ``s = sigmoid(x W_g)`` in float32;
  the ``num_experts_per_tok`` experts are the top of ``s + expert_bias``
  (``use_expert_bias``); their weights are ``s`` there, divided by their sum
  + 1e-6 (``norm_topk_prob``), times ``routed_scaling_factor``; each expert a
  SwiGLU of ``moe_intermediate_size``.

Departures, each for a reason:
- The weights are the cell's *served* weights (bf16): the reference multiplies
  them out to float32 a tensor, or an expert, at a time.
- The experts are a plain loop: every expert over every token, times the weight
  the router gave it there (0 where it was not chosen).
- ``token_ids`` may hold several sequences end to end (``starts``), each attending
  to itself alone and convolving over itself alone, with positions from its own
  start; the row is padded to a multiple of 512; attention runs a few heads at a
  time; ``positions`` picks the rows wanted before the head (as ``dense_gqa.py``).

Controls (``parity_seeds.py`` only; a run never sets them): the environment's
``LFM2_REF_CONTROL`` = ``roll_experts`` rolls the router's choice by one expert
index; ``int8_cache`` rounds what the cache holds to int8, the precision under
the bf16 the configuration states, which the program cannot run: every key and
value with one scale a token and head, every conv input ``z`` a later position
reads back with one scale a token; ``zero_conv_at_resume`` zeroes the conv state where a served turn's
prefill resumed from the cache: at the last whole 32-token block of the turn
before it (the end of the run of ``positions`` before this run), and for a
sequence's first served run at the last whole block of the first half of its
prompt (where that resume was is the cache's to know, not the sample's).
Either must be told from a sound run, by ``correct`` or by the CPU test the
configuration's file names.

``weights`` is a copy of the program's seeded initialiser
(``engine/lfm2.py:init_params``): a tensor of a layer at a time, one jitted
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
HEADS_AT_A_TIME = 4   # float32 scores of 4 heads over 4,096 x 4,096 positions are 268 MB
ROUTER_LOGIT_STD = 2.0  # the seeded router's logits; sigmoid scores then spread over 0.1-0.9
EXPERT_BIAS_STD = 0.1   # drawn, not zeros: a zero bias hides a choice made on the wrong scores
RESUME_BLOCK = 32       # the control's block size (the cell's served.block_size)

_NAMES = ("conv_in", "conv_w", "conv_out", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
          "w_router", "moe_gate", "moe_up", "moe_down", "router_bias")


def sizes(doc: dict) -> tuple:
    return (int(doc["num_hidden_layers"]), tuple(doc["layer_types"][:doc["num_hidden_layers"]]),
            int(doc["num_dense_layers"]), int(doc["hidden_size"]), int(doc["intermediate_size"]),
            int(doc["moe_intermediate_size"]), int(doc["num_attention_heads"]),
            int(doc["num_key_value_heads"]), int(doc["hidden_size"]) // int(doc["num_attention_heads"]),
            int(doc["num_experts"]), int(doc["num_experts_per_tok"]), int(doc["conv_L_cache"]),
            int(doc["vocab_size"]), bool(doc["use_expert_bias"]), bool(doc["norm_topk_prob"]),
            float(doc["routed_scaling_factor"]), float(doc["rope_parameters"]["rope_theta"]),
            float(doc["norm_eps"]), os.environ.get("LFM2_REF_CONTROL", ""))


# -- the seeded weights ----------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def weights(doc: dict, seed: int) -> dict:
    (L, kinds, n_dense, D, I, ie, H, KVH, hd, E, _, taps, V, *_rest) = sizes(doc)
    if doc.get("conv_bias"):
        raise ValueError("the lfm2_moe reference has no conv bias (conv_bias false as published)")
    if doc["served"]["quant"] != "none":
        raise ValueError("the LFM2 block is served in its published bf16 only")
    dtype = jnp.dtype(doc["served"].get("dtype", "bfloat16"))
    key = jax.random.PRNGKey(seed)

    def layer_key(name: str, i: int):
        return jax.random.fold_in(jax.random.fold_in(key, 100 + _NAMES.index(name)), i)

    layers = []
    for i, kind in enumerate(kinds):
        if kind == "conv":
            shapes = {"conv_in": ((D, 3 * D), D ** -0.5), "conv_w": ((D, taps), taps ** -0.5),
                      "conv_out": ((D, D), D ** -0.5)}
        else:
            shapes = {"wq": ((D, H * hd), D ** -0.5), "wk": ((D, KVH * hd), D ** -0.5),
                      "wv": ((D, KVH * hd), D ** -0.5), "wo": ((H * hd, D), (H * hd) ** -0.5)}
        if i < n_dense:
            shapes.update({"w_gate": ((D, I), D ** -0.5), "w_up": ((D, I), D ** -0.5),
                           "w_down": ((I, D), I ** -0.5)})
        else:
            shapes.update({"w_router": ((D, E), ROUTER_LOGIT_STD * D ** -0.5),
                           "moe_gate": ((E, D, ie), D ** -0.5), "moe_up": ((E, D, ie), D ** -0.5),
                           "moe_down": ((E, ie, D), ie ** -0.5)})
        lp = {name: _draw(layer_key(name, i), shape, std, dtype) for name, (shape, std) in shapes.items()}
        lp["operator_norm"] = jnp.ones((D,), dtype)
        lp["ffn_norm"] = jnp.ones((D,), dtype)
        if kind != "conv":
            lp["q_layernorm"] = jnp.ones((hd,), dtype)
            lp["k_layernorm"] = jnp.ones((hd,), dtype)
        if i >= n_dense:
            lp["router_bias"] = _draw(layer_key("router_bias", i), (E,), EXPERT_BIAS_STD, jnp.float32)
        layers.append(lp)
    return {"embed": _draw(jax.random.fold_in(key, 1), (V, D), D ** -0.5, dtype),
            "layers": layers, "final_norm": jnp.ones((D,), dtype)}


# -- the forward pass ------------------------------------------------------------


def _f32(w: jax.Array) -> jax.Array:
    return w.astype(jnp.float32)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x [T, heads, hd] at positions ``pos`` [T]; lane i pairs with lane i + hd/2."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = pos.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _dot(a, b):
    return jnp.dot(a, b, precision=HI)


def _swiglu(h, gate, up, down):
    return _dot(jax.nn.silu(_dot(h, _f32(gate))) * _dot(h, _f32(up)), _f32(down))


def _int8(x: jax.Array) -> jax.Array:
    """The ``int8_cache`` control: symmetric absmax rounding along the last axis."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _conv(u, cseg, lp, dims):
    """The gated short convolution as shifted products: tap j reaches back
    L - 1 - j positions, and only inside the position's own stretch."""
    D, taps = dims[3], dims[11]
    T = u.shape[0]
    b, c, x = jnp.split(_dot(u, _f32(lp["conv_in"])), 3, axis=-1)
    z = b * x
    w = _f32(lp["conv_w"])
    y = jnp.zeros_like(z)
    for j in range(taps):
        d = taps - 1 - j
        back = _int8(z) if dims[18] == "int8_cache" and d else z   # what a later position reads from the cache
        shifted = jnp.pad(back, ((d, 0), (0, 0)))[:T]
        same = jnp.pad(cseg, (d, 0), constant_values=-1)[:T] == cseg
        y = y + w[:, j] * jnp.where(same[:, None], shifted, 0.0)
    return _dot(c * y, _f32(lp["conv_out"]))


def _attention(u, seq, pos, lp, dims):
    H, KVH, hd, theta, eps = dims[6], dims[7], dims[8], dims[16], dims[17]
    T, G = u.shape[0], dims[6] // dims[7]
    q = _rms(_dot(u, _f32(lp["wq"])).reshape(T, H, hd), lp["q_layernorm"], eps)
    k = _rms(_dot(u, _f32(lp["wk"])).reshape(T, KVH, hd), lp["k_layernorm"], eps)
    v = _dot(u, _f32(lp["wv"])).reshape(T, KVH, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    if dims[18] == "int8_cache":
        k, v = _int8(k), _int8(v)
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)      # a KV head under each of its query heads
    causal = (jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]) & (seq[:, None] == seq[None, :])

    def heads(args):  # a few heads at a time
        qg, kg, vg = args  # [g, T, hd]
        s = jnp.einsum("gtd,gsd->gts", qg, kg, precision=HI) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,gsd->gtd", p, vg, precision=HI)

    g = min(HEADS_AT_A_TIME, H)
    grouped = lambda a: a.transpose(1, 0, 2).reshape(H // g, g, T, hd)  # noqa: E731
    o = lax.map(heads, (grouped(q), grouped(k), grouped(v)))
    return _dot(o.reshape(H, T, hd).transpose(1, 0, 2).reshape(T, H * hd), _f32(lp["wo"]))


def _moe(h, lp, dims):
    E, k, use_bias, renorm, scaling, control = dims[9], dims[10], dims[13], dims[14], dims[15], dims[18]
    s = jax.nn.sigmoid(_dot(h, _f32(lp["w_router"])))
    _, chosen = lax.top_k(s + lp["router_bias"][None, :] if use_bias else s, k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if renorm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * scaling
    if control == "roll_experts":
        chosen = (chosen + 1) % E

    def expert(y, args):  # one expert over every token, times its weight there
        e, gate, up, down = args
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)
        return y + w_e * _swiglu(h, gate, up, down), None

    return lax.scan(expert, jnp.zeros_like(h), (jnp.arange(E), lp["moe_gate"], lp["moe_up"], lp["moe_down"]))[0]


@functools.partial(jax.jit, static_argnames=("kind", "dense", "dims"))
def _layer(x, seq, cseg, pos, lp, kind, dense, dims):
    eps = dims[17]
    u = _rms(x, lp["operator_norm"], eps)
    h = x + (_conv(u, cseg, lp, dims) if kind == "conv" else _attention(u, seq, pos, lp, dims))
    f = _rms(h, lp["ffn_norm"], eps)
    return h + (_swiglu(f, lp["w_gate"], lp["w_up"], lp["w_down"]) if dense else _moe(f, lp, dims))


@jax.jit
def _embed(params: dict, tokens: jax.Array) -> jax.Array:
    return params["embed"][tokens].astype(jnp.float32)


@jax.jit
def _head(x: jax.Array, w: jax.Array) -> jax.Array:
    return _dot(x, _f32(w).T)


def resume_points(starts: list[int], positions: list[int]) -> list[int]:
    """The ``zero_conv_at_resume`` control's cuts: for each run of served
    positions, where that turn's prefill is taken to have resumed."""
    first = sorted(starts)
    cuts, prev_end = [], {}
    runs = [p for i, p in enumerate(positions) if i == 0 or p != positions[i - 1] + 1]
    ends = [p for i, p in enumerate(positions) if i + 1 == len(positions) or positions[i + 1] != p + 1]
    for a, b in zip(runs, ends):
        s0 = max(s for s in first if s <= a)
        upto = prev_end.get(s0, s0 + (a - s0) // 2)   # the turn before, else half the first prompt
        cuts.append(s0 + (upto - s0) // RESUME_BLOCK * RESUME_BLOCK)
        prev_end[s0] = b + 1
    return [c for c in cuts if c not in first]


def forward(doc: dict, params: dict, token_ids: list[int], positions=None, starts=(0,)) -> jax.Array:
    dims = sizes(doc)
    kinds, n_dense, eps, control = dims[1], dims[2], dims[17], dims[18]
    T = len(token_ids)
    padded = -(-T // PAD) * PAD
    tokens = jnp.asarray(list(token_ids) + [0] * (padded - T), jnp.int32)
    where = jnp.arange(padded, dtype=jnp.int32)
    first = jnp.asarray(sorted(starts), jnp.int32)
    seq = jnp.searchsorted(first, where, side="right") - 1
    pos = where - first[seq]
    cuts = sorted(starts)
    if control == "zero_conv_at_resume" and positions is not None:
        cuts = sorted(set(cuts) | set(resume_points(list(starts), [int(p) for p in positions])))
    cseg = jnp.searchsorted(jnp.asarray(cuts, jnp.int32), where, side="right") - 1
    x = _embed(params, tokens)
    for i, kind in enumerate(kinds):
        x = _layer(x, seq, cseg, pos, params["layers"][i], kind, i < n_dense, dims)
    rows = jnp.arange(T) if positions is None else jnp.asarray(positions, jnp.int32)
    return _head(_rms(x[rows], params["final_norm"], eps), params["embed"])
