"""The dense grouped-query-attention transformer as published (Llama, Qwen2,
Mistral), written down plainly: the benchmark's yardstick for ``correct``.

    weights(doc, seed)                                           the seeded weights the cell serves
    forward(doc, params, token_ids, positions=None, starts=(0,)) float32 logits [T or len(positions), V]

Whole sequences at once, ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST`` (on a TPU a float32 product otherwise runs in bf16
passes), dense causal attention, no cache, no kernel. It imports nothing of
the program and is given nothing the program made.

Per layer, with ``h = RMSNorm(x)``: ``q, k, v = h Wq + bq, h Wk + bk, h Wv + bv``
(the biases only where ``assumed.qkv_bias``; none on the output projection);
rotary embedding on q and k in the half-split layout (``rotate_half``);
grouped-query causal softmax attention with scale ``head_dim ** -0.5``;
``x += o Wo``; ``x += (silu(h Wg) * (h Wu)) Wd`` on the second norm (``swiglu``). Final
norm, then the head (the embedding transposed where ``tie_word_embeddings``).

Departures from the published description, each for a reason:
- The weights are the configuration's *served* weights: weight-only int8 with
  one float32 scale per output channel (per row for the embedding) where
  ``served.quant`` is ``int8``. The reference multiplies them out to float32,
  a layer at a time, so a 7.6 GB model fits beside one float32 layer. The
  served precision the configuration states (bf16 activations and KV) is what
  the comparison measures; the int8 values are the same numbers on both sides.
- ``token_ids`` may hold several sequences end to end, ``starts`` saying where
  each begins: a token attends to the earlier tokens of its own sequence only,
  and its rotary position counts from its sequence's start, so each sequence
  reads what it would alone. ``parity.py`` packs a run's sample into rows of one
  fixed length: one program compiles, and no position is spent on padding.
- The row is padded to a multiple of 512 so that few shapes compile; attention
  is causal, so the padding changes no earlier position.
- Attention runs one KV head's group of query heads at a time, and the head
  in blocks of the vocabulary, so that the scores of 4096 tokens and a
  152k-row head fit; the sums are the same.
- ``positions`` picks the rows whose logits are wanted before the head.

``weights`` is a copy of the program's seeded initialisers
(``engine/quant.py:random_int8_params_device``, ``engine/model.py:init_params``):
the cell's weights are a function of the seed, and the benchmark keeps that
function. A program that changes its own stops agreeing with it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
PAD = 512  # one layer program compiles in a quarter of a minute for the chip: few lengths


def sizes(doc: dict) -> tuple:
    """(L, D, I, H, KVH, hd, V, bias, tied, theta, eps), from the published keys."""
    assumed = doc.get("assumed", {})
    hd = doc.get("head_dim") or assumed.get("head_dim") or doc["hidden_size"] // doc["num_attention_heads"]
    return (doc["num_hidden_layers"], doc["hidden_size"], doc["intermediate_size"],
            doc["num_attention_heads"], doc["num_key_value_heads"], hd, doc["vocab_size"],
            bool(assumed.get("qkv_bias", False)), bool(doc["tie_word_embeddings"]),
            float(doc["rope_theta"]), float(doc["rms_norm_eps"]))


# -- the seeded weights ----------------------------------------------------------


def weights(doc: dict, seed: int) -> dict:
    L, D, I, H, KVH, hd, V, bias, tied, _, _ = sizes(doc)
    dtype = jnp.dtype(doc["served"].get("dtype", "bfloat16"))
    q_size, kv_size = H * hd, KVH * hd
    shapes = {"wq": ((L, D, q_size), D), "wk": ((L, D, kv_size), D), "wv": ((L, D, kv_size), D),
              "wo": ((L, q_size, D), q_size), "w_gate": ((L, D, I), D), "w_up": ((L, D, I), D),
              "w_down": ((L, I, D), I)}

    def biases(key):
        bkey = jax.random.fold_in(key, 31)
        return {"bq": (jax.random.normal(bkey, (L, q_size), jnp.float32) * 0.02).astype(dtype),
                "bk": (jax.random.normal(jax.random.fold_in(bkey, 1), (L, kv_size), jnp.float32) * 0.02).astype(dtype),
                "bv": (jax.random.normal(jax.random.fold_in(bkey, 2), (L, kv_size), jnp.float32) * 0.02).astype(dtype)}

    def int8():
        key = jax.random.PRNGKey(seed)

        def q(idx, shape, fan_in):
            w = jax.random.randint(jax.random.fold_in(key, idx), shape, -127, 128, jnp.int8)
            return w, jnp.full((L, shape[-1]), (fan_in ** -0.5) / 64.0, jnp.float32)

        layers = {}
        for idx, (name, (shape, fan)) in enumerate(shapes.items()):
            layers[name], layers[name + "_scale"] = q(idx, shape, fan)
        layers["attn_norm"] = jnp.ones((L, D), dtype)
        layers["mlp_norm"] = jnp.ones((L, D), dtype)
        if bias:
            layers.update(biases(key))
        params = {"embed": jax.random.randint(jax.random.fold_in(key, 90), (V, D), -127, 128, jnp.int8),
                  "embed_scale": jnp.full((V,), (D ** -0.5) / 64.0, jnp.float32),
                  "layers": layers, "final_norm": jnp.ones((D,), dtype)}
        if not tied:
            w, s = q(91, (D, V), D)
            params["lm_head"], params["lm_head_scale"] = w, s[0]
        return params

    def plain():
        key = jax.random.PRNGKey(seed)
        keys = jax.random.split(key, 8)

        def normal(k, fan_in, shape):
            return (jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)).astype(dtype)

        order = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
        layers = {name: normal(keys[1 + n], shapes[name][1], shapes[name][0])
                  for n, name in enumerate(order)}
        layers["attn_norm"] = jnp.ones((L, D), dtype)
        layers["mlp_norm"] = jnp.ones((L, D), dtype)
        if bias:
            layers.update(biases(key))
        params = {"embed": normal(keys[0], D, (V, D)), "layers": layers,
                  "final_norm": jnp.ones((D,), dtype)}
        if not tied:
            params["lm_head"] = normal(jax.random.fold_in(key, 99), D, (D, V))
        return params

    quant = doc["served"]["quant"]
    if quant not in ("int8", "none"):
        raise ValueError(f"no seeded weights for served.quant {quant!r}")
    # As the program runs them (engine/runner.py): the int8 tree in one jitted
    # call, the plain one eagerly; jitted, a fused multiply-and-round differs
    # from the eager one by a last bit of bf16 here and there.
    return jax.jit(int8)() if quant == "int8" else plain()


# -- the forward pass ------------------------------------------------------------


def _full(tree: dict, name: str) -> jax.Array:
    """A weight in float32: int8 times its per-output-channel scale."""
    w = tree[name].astype(jnp.float32)
    return w * tree[name + "_scale"].astype(jnp.float32) if name + "_scale" in tree else w


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x [T, heads, hd] at positions ``pos`` [T]; the first half of a head pairs with the second."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = pos.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def swiglu(h: jax.Array, lp: dict) -> jax.Array:
    gate = jax.nn.silu(jnp.dot(h, _full(lp, "w_gate"), precision=HI))
    return jnp.dot(gate * jnp.dot(h, _full(lp, "w_up"), precision=HI), _full(lp, "w_down"), precision=HI)


@functools.partial(jax.jit, static_argnames=("dims", "ffn"))
def _layer(x: jax.Array, seq: jax.Array, pos: jax.Array, layers: dict, l: jax.Array, dims: tuple,
           ffn=swiglu) -> jax.Array:
    """One layer over a row: token ``t`` belongs to sequence ``seq[t]`` and stands at ``pos[t]`` in it."""
    _, D, _, H, KVH, hd, _, bias, _, theta, eps = dims
    T = x.shape[0]
    lp = {k: lax.dynamic_index_in_dim(v, l, 0, keepdims=False) for k, v in layers.items()}
    h = _rms(x, lp["attn_norm"], eps)
    q, k, v = (jnp.dot(h, _full(lp, n), precision=HI) for n in ("wq", "wk", "wv"))
    if bias:
        q, k, v = (a + lp[n].astype(jnp.float32) for a, n in ((q, "bq"), (k, "bk"), (v, "bv")))
    q = _rope(q.reshape(T, H, hd), pos, theta).reshape(T, KVH, H // KVH, hd)
    k = _rope(k.reshape(T, KVH, hd), pos, theta)
    v = v.reshape(T, KVH, hd)
    causal = (jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]) & (seq[:, None] == seq[None, :])

    def group(qkv):  # one KV head and the query heads that share it
        qg, kg, vg = qkv  # [T, G, hd], [T, hd], [T, hd]
        s = jnp.einsum("tgh,sh->gts", qg, kg, precision=HI) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sh->tgh", p, vg, precision=HI)

    o = lax.map(group, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    x = x + jnp.dot(o.transpose(1, 0, 2, 3).reshape(T, H * hd), _full(lp, "wo"), precision=HI)
    return x + ffn(_rms(x, lp["mlp_norm"], eps), lp)


@jax.jit
def _embed(params: dict, tokens: jax.Array) -> jax.Array:
    e = params["embed"][tokens].astype(jnp.float32)
    return e * params["embed_scale"][tokens][:, None] if "embed_scale" in params else e


@functools.partial(jax.jit, static_argnames=("tied",))
def _head_block(x: jax.Array, w: jax.Array, scale, tied: bool) -> jax.Array:
    w = w.astype(jnp.float32)
    y = jnp.dot(x, w.T if tied else w, precision=HI)
    return y if scale is None else y * scale[None, :]


def forward(doc: dict, params: dict, token_ids: list[int], positions=None, starts=(0,),
            ffn=swiglu) -> jax.Array:
    """``starts`` are the indices at which the sequences in ``token_ids`` begin
    (one sequence from 0 by default). ``ffn(h, layer's weights) -> y`` is the
    block after the second norm: a reference for another feed-forward (routed
    experts) passes its own."""
    dims = sizes(doc)
    L, _, _, _, _, _, V, _, tied, _, eps = dims
    T = len(token_ids)
    padded = -(-T // PAD) * PAD
    tokens = jnp.asarray(list(token_ids) + [0] * (padded - T), jnp.int32)
    first = jnp.asarray(sorted(starts), jnp.int32)
    seq = jnp.searchsorted(first, jnp.arange(padded, dtype=jnp.int32), side="right") - 1
    pos = jnp.arange(padded, dtype=jnp.int32) - first[seq]
    x = _embed(params, tokens)
    for l in range(L):
        x = _layer(x, seq, pos, params["layers"], jnp.int32(l), dims, ffn)
    rows = jnp.arange(T) if positions is None else jnp.asarray(positions, jnp.int32)
    x = _rms(x[rows], params["final_norm"], eps)
    w, scale = (params["embed"], params.get("embed_scale")) if tied else (
        params["lm_head"], params.get("lm_head_scale"))
    block = -(-V // 8 // 128) * 128 if V > 32768 else V
    out = []
    for a in range(0, V, block):
        b = min(V, a + block)
        out.append(_head_block(x, w[a:b] if tied else w[:, a:b],
                               None if scale is None else scale[a:b], tied))
    return jnp.concatenate(out, axis=-1)
