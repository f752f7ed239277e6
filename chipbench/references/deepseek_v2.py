"""The DeepSeek-V2 language model as published, written down plainly: latent
(MLA) attention with a low-rank query and static YaRN on the rope lanes, a
dense first layer, then expert layers under group-limited softmax routing
beside shared experts. The benchmark's yardstick for ``correct`` in the
DeepSeek cell.

    weights(doc, seed)                                           the seeded weights the cell serves
    forward(doc, params, token_ids, positions=None, starts=(0,)) float32 logits [T or len(positions), V]

Whole sequences at once, ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST``, dense causal attention in the EXPANDED form, no cache,
no kernel, no grouping of tokens. It imports nothing of the program.

The block, from the published ``config.json`` (what it does not state is under
``assumed`` in the configuration's file). With ``D`` hidden, ``H`` heads:

- ``MLA(x, pos)``: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` as ``[H, dn + dr]``,
  split ``q_n | q_r``; ``[c_kv | k_r] = x W_kva`` split ``kv_lora_rank | dr``;
  ``c_kv = RMSNorm(c_kv)``; rotary embedding over neighbouring pairs ``(2i, 2i+1)``
  on ``q_r`` and on the one ``k_r`` all heads share, at YaRN's frequencies
  (``yarn_inv_freq``: ``f_i = theta^(-2i/dr)``; ``low, high`` = floor, ceil of
  ``dr ln(L0 / (beta 2 pi)) / (2 ln theta)`` at ``beta_fast`` and ``beta_slow``,
  clipped to the lanes; ``ramp_i = clip((i - low) / (high - low), 0, 1)``;
  ``f_i / factor`` where the ramp is 1, ``f_i`` where it is 0), cos and sin times
  ``m(mscale) / m(mscale_all_dim)`` with ``m(s) = 0.1 s ln(factor) + 1``;
  ``[k_n | v] = c_kv W_kvb`` as ``[H, dn + dv]``;
  ``score_h(t, s) = (q_n,h(t) k_n,h(s) + q_r,h(t) k_r(s)) (dn + dr)^-1/2 m(mscale_all_dim)^2``,
  causal softmax, ``o_h = sum p v_h``, output ``concat_h(o_h) W_o``. YaRN is
  static: the same at every length.
- ``MoE(h)``: ``s = softmax(h W_r)`` over the ``n_routed_experts`` in float32; a
  group's score is the largest ``s`` among its ``E / n_group`` consecutive
  experts; the ``topk_group`` best groups are kept; among their experts the
  ``num_experts_per_tok`` largest ``s`` are chosen; ``w_e = routed_scaling_factor s_e``,
  not renormalised; ``MoE(h) = sum w_e SwiGLU_e(h) + SwiGLU_shared(h)`` (the
  ``n_shared_experts`` as one of their summed width).
- layer: ``a = x + MLA(norm(x))``; ``out = a + FF(norm(a))``, ``FF`` the dense
  SwiGLU in the first ``first_k_dense_replace`` layers and ``MoE`` after. Final
  norm, untied head.

Departures from the published description, each for a reason:
- The weights are the cell's *served* weights (bf16): the reference multiplies
  them out to float32 a tensor, or an expert a device, at a time (21.2B
  parameters in float32 are 85 GB).
- **Placement only.** 42.5 GB of bf16 values fit no one chip: the child sees the
  host's four, and ``weights`` lays the expert stacks out ``[layers, E / C, C, ..]``
  with the ``C`` axis over ``C`` devices (expert ``c E/C + i`` at ``[i, c]``; ``C`` is 1
  where the process sees fewer than four devices) and the other tensors cut along
  their first axis. The forward pass is the same ``jax.numpy`` whatever ``C``: the
  experts' loop takes ``C`` experts a step and every expert still runs over every
  token, times the weight the router gave it there (0 where it was not chosen).
- ``W_qb``'s nope and rope columns, and ``W_kvb``'s key and value halves, are
  drawn as tensors of their own (``w_qn``, ``w_qr``, ``w_uk``, ``w_uv``), as the
  seeded initialiser makes them: the products are the published ones, split.
- ``token_ids`` may hold several sequences end to end (``starts``), each
  attending to itself alone with positions from its own start; the row is
  padded to a multiple of 512; attention runs a few heads at a time;
  ``positions`` picks the rows wanted before the head (as ``dense_gqa.py``).

Controls (``parity_seeds.py`` only; a run never sets them): the environment's
``DEEPSEEK_REF_CONTROL`` = ``roll_groups`` rolls the kept groups by one group
index, ``int8_latents`` rounds every cached latent to int8 with one scale a
token, the precision under the bf16 cache the configuration states, which the
program cannot run. Either must come out as not correct.

``weights`` is a copy of the program's seeded initialiser
(``engine/deepseek.py:init_params``): a tensor in pieces of its leading axis,
an expert under a key of its own. A program that changes its own stops
agreeing with it.
"""

from __future__ import annotations

import functools
import math
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HI = lax.Precision.HIGHEST
PAD = 512
HEADS_AT_A_TIME = 4  # float32 scores of 4 heads over 4,096 x 4,096 positions are 268 MB
ROUTER_LOGIT_STD = 3.0  # the seeded router's logits: six choices hold about half the mass, as a trained router's
DRAW_PIECE = 1 << 23    # elements a piece of a drawn tensor may hold (the program's)
PLACED_OVER = 4         # devices the expert stacks lie over, where the process sees as many


def sizes(doc: dict) -> tuple:
    y = doc["rope_scaling"]
    return (doc["num_hidden_layers"], doc["hidden_size"], doc["intermediate_size"], doc["num_attention_heads"],
            doc["q_lora_rank"], doc["kv_lora_rank"], doc["qk_nope_head_dim"], doc["qk_rope_head_dim"],
            doc["v_head_dim"], doc["n_routed_experts"], doc["num_experts_per_tok"], doc["n_shared_experts"],
            doc["moe_intermediate_size"], doc["first_k_dense_replace"], doc["n_group"], doc["topk_group"],
            doc["vocab_size"], float(doc["routed_scaling_factor"]), float(doc["rope_theta"]),
            float(doc["rms_norm_eps"]),
            (float(y["factor"]), int(y["original_max_position_embeddings"]), float(y["beta_fast"]),
             float(y["beta_slow"]), float(y["mscale"]), float(y["mscale_all_dim"])),
            os.environ.get("DEEPSEEK_REF_CONTROL", ""))


# -- YaRN's closed forms -----------------------------------------------------------


def yarn_inv_freq(dr: int, theta: float, factor: float, L0: int, beta_fast: float, beta_slow: float) -> np.ndarray:
    def correction(beta: float) -> float:
        return dr * math.log(L0 / (beta * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dr - 1)
    i = np.arange(dr // 2, dtype=np.float64)
    f = theta ** (-2 * i / dr)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def yarn_m(factor: float, s: float) -> float:
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0


# -- the seeded weights ----------------------------------------------------------


def _pieces(shape: tuple[int, ...]) -> int:
    n = math.prod(shape)
    return next((p for p in range(1, shape[0] + 1) if shape[0] % p == 0 and n // p <= DRAW_PIECE), shape[0])


def _draw(key, shape, std, dtype):
    """Normal(0, std) of ``shape``, a piece of the leading axis at a time under
    its own key (``fold_in(key, piece)``)."""
    p = _pieces(shape)
    part = (shape[0] // p, *shape[1:])
    pieces = lax.map(lambda i: (jax.random.normal(jax.random.fold_in(key, i), part, jnp.float32) * std).astype(dtype),
                     jnp.arange(p))
    return pieces.reshape(shape)


def _placement() -> Mesh:
    devices = jax.devices()
    return Mesh(np.array(devices[:PLACED_OVER if len(devices) >= PLACED_OVER else 1]), ("x",))


def weights(doc: dict, seed: int) -> dict:
    (L, D, I, H, rq, rkv, dn, dr, dv, E, _, n_shared, ie, n_dense, *_r) = sizes(doc)
    V = doc["vocab_size"]
    dtype = jnp.dtype(doc["served"].get("dtype", "bfloat16"))
    if doc["served"]["quant"] != "none":
        raise ValueError("the DeepSeek block is served in its published bf16 only")
    mesh = _placement()
    C = mesh.size if E % mesh.size == 0 else 1
    key = jax.random.PRNGKey(seed)
    layer_key = functools.partial(jax.random.fold_in, jax.random.fold_in(key, 100))

    def cut(a: jax.ShapeDtypeStruct) -> NamedSharding:  # along the first axis, where the devices divide it
        return NamedSharding(mesh, P("x") if a.ndim > 1 and a.shape[0] % mesh.size == 0 else P())

    def placed(build):
        """``build(key)`` as one compiled program whatever the key, its results cut over the devices."""
        return jax.jit(build, out_shardings=jax.tree.map(cut, jax.eval_shape(build, key)))

    def layer(dense: bool, k) -> dict:
        width = I if dense else n_shared * ie
        shapes = {"w_qa": ((D, rq), D), "w_qn": ((rq, H * dn), rq), "w_qr": ((rq, H * dr), rq),
                  "w_kva": ((D, rkv + dr), D), "w_uk": ((H, dn, rkv), rkv), "w_uv": ((H, rkv, dv), rkv),
                  "wo": ((H * dv, D), H * dv),
                  "w_gate": ((D, width), D), "w_up": ((D, width), D), "w_down": ((width, D), width)}
        if not dense:
            shapes["w_router"] = ((D, E), D)
        out = {}
        for n, (name, (shape, fan_in)) in enumerate(shapes.items()):
            std = fan_in ** -0.5 * (ROUTER_LOGIT_STD if name == "w_router" else 1.0)
            out[name] = _draw(jax.random.fold_in(k, n), shape, std, dtype)
        out.update({"attn_norm": jnp.ones((D,), dtype), "mlp_norm": jnp.ones((D,), dtype),
                    "q_norm": jnp.ones((rq,), dtype), "kv_norm": jnp.ones((rkv,), dtype)})
        return out

    def experts(shape, fan_in):
        """→ ``draw(k)``: ``[expert layers, E / C, C, *shape]``, the ``C`` axis over
        the devices: expert ``e`` of expert layer ``l`` under ``fold_in(fold_in(k, l), e)``."""
        def mine(k):
            first = lax.axis_index("x") * (E // C) if C > 1 else 0

            def one(l, e):
                kk = jax.random.fold_in(jax.random.fold_in(k, l), e)
                return (jax.random.normal(kk, shape, jnp.float32) * fan_in ** -0.5).astype(dtype)

            ids = first + jnp.arange(E // C, dtype=jnp.int32)
            return lax.map(lambda l: lax.map(lambda e: one(l, e), ids), jnp.arange(L - n_dense, dtype=jnp.int32))[:, :, None]

        if C == 1:
            return jax.jit(mine)
        return jax.jit(jax.shard_map(mine, mesh=mesh, in_specs=P(), out_specs=P(None, None, "x"), check_vma=False))

    wide, deep = experts((D, ie), D), experts((ie, D), ie)  # one compiled draw a shape
    stacks = {name: draw(jax.random.fold_in(key, 200 + n))
              for n, (name, draw) in enumerate((("moe_gate", wide), ("moe_up", wide), ("moe_down", deep)))}
    rows = placed(lambda k: _draw(k, (V, D), D ** -0.5, dtype))
    cols = placed(lambda k: _draw(k, (D, V), D ** -0.5, dtype))
    draw_layer = {dense: placed(functools.partial(layer, dense)) for dense in (True, False)}
    return {"embed": rows(jax.random.fold_in(key, 1)), "lm_head": cols(jax.random.fold_in(key, 2)),
            "final_norm": jnp.ones((D,), dtype),
            "layers": [draw_layer[l < n_dense](layer_key(l)) for l in range(L)],
            "experts": stacks}


# -- the forward pass ------------------------------------------------------------


def _f32(w: jax.Array) -> jax.Array:
    return w.astype(jnp.float32)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _rope_pairs(x: jax.Array, pos: jax.Array, inv_freq: np.ndarray, mscale: float) -> jax.Array:
    """x [T, heads, hd] at positions ``pos`` [T]; lane 2i pairs with lane 2i+1."""
    T, heads, hd = x.shape
    angles = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles)[:, None, :] * mscale, jnp.sin(angles)[:, None, :] * mscale
    pairs = x.reshape(T, heads, hd // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(T, heads, hd)


def _dot(a, b):
    return jnp.dot(a, b, precision=HI)


def _swiglu(h, gate, up, down):
    return _dot(jax.nn.silu(_dot(h, _f32(gate))) * _dot(h, _f32(up)), _f32(down))


def _mla(h, seq, pos, lp, dims):
    (_, _, _, H, rq, rkv, dn, dr, dv, *_r) = dims
    theta, eps, (factor, L0, beta_fast, beta_slow, mscale, mscale_all), control = dims[18:22]
    T = h.shape[0]
    inv_freq = yarn_inv_freq(dr, theta, factor, L0, beta_fast, beta_slow)
    rot = yarn_m(factor, mscale) / yarn_m(factor, mscale_all)
    scale = (dn + dr) ** -0.5 * yarn_m(factor, mscale_all) ** 2
    c_q = _rms(_dot(h, _f32(lp["w_qa"])), lp["q_norm"], eps)
    q_n = _dot(c_q, _f32(lp["w_qn"])).reshape(T, H, dn)
    q_r = _rope_pairs(_dot(c_q, _f32(lp["w_qr"])).reshape(T, H, dr), pos, inv_freq, rot)
    kv = _dot(h, _f32(lp["w_kva"]))
    c_kv = _rms(kv[:, :rkv], lp["kv_norm"], eps)
    k_r = _rope_pairs(kv[:, None, rkv:], pos, inv_freq, rot)[:, 0]
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
             + jnp.einsum("gtr,sr->gts", qr, k_r, precision=HI)) * scale
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,gsv->gtv", p, v, precision=HI)

    g = min(HEADS_AT_A_TIME, H)
    grouped = lambda a: a.reshape(H // g, g, *a.shape[1:])  # noqa: E731
    o = lax.map(heads, (grouped(q_n.transpose(1, 0, 2)), grouped(q_r.transpose(1, 0, 2)),
                        grouped(lp["w_uk"]), grouped(lp["w_uv"])))
    o = o.reshape(H, T, dv).transpose(1, 0, 2).reshape(T, H * dv)
    return _dot(o, _f32(lp["wo"]))


def choose(s: jax.Array, k: int, n_group: int, topk_group: int, control: str = "") -> jax.Array:
    """s [T, E] router scores → the chosen experts [T, k]: the ``topk_group``
    groups of the largest best score, then the ``k`` largest among their experts."""
    T, E = s.shape
    best = jnp.max(s.reshape(T, n_group, E // n_group), axis=-1)
    _, groups = lax.top_k(best, topk_group)
    if control == "roll_groups":
        groups = (groups + 1) % n_group
    kept = jnp.zeros((T, n_group), bool).at[jnp.arange(T)[:, None], groups].set(True)
    allowed = jnp.repeat(kept, E // n_group, axis=-1)
    return lax.top_k(jnp.where(allowed, s, 0.0), k)[1]


def _moe(h, lp, stacks, dims):
    E, k, n_group, topk_group, scaling, control = dims[9], dims[10], dims[14], dims[15], dims[17], dims[21]
    s = jax.nn.softmax(_dot(h, _f32(lp["w_router"])), axis=-1)
    chosen = choose(s, k, n_group, topk_group, control)
    w = jnp.take_along_axis(s, chosen, axis=-1) * scaling
    gate, up, down = stacks  # [E / C, C, ..]: C experts a step, each on the device that holds it
    C = gate.shape[1]

    def step(y, args):  # every expert over every token, times its weight there
        i, g, u, d = args
        e = jnp.arange(C) * (E // C) + i
        w_e = jnp.sum(jnp.where(chosen[None] == e[:, None, None], w[None], 0.0), axis=-1)   # [C, T]
        a = jax.nn.silu(jnp.einsum("td,cdf->ctf", h, _f32(g), precision=HI)) * jnp.einsum(
            "td,cdf->ctf", h, _f32(u), precision=HI)
        return y + w_e[:, :, None] * jnp.einsum("ctf,cfd->ctd", a, _f32(d), precision=HI), None

    y0 = jnp.zeros((C, *h.shape), jnp.float32)
    y = lax.scan(step, y0, (jnp.arange(E // C), gate, up, down))[0]
    return jnp.sum(y, axis=0) + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


# A layer is two compiled programs, its attention and its feed-forward: the dense layer and the expert layers
# share the first, and the child's 120 s hold one compilation of attention instead of two.


@functools.partial(jax.jit, static_argnames=("dims",))
def _attend(x, seq, pos, lp, dims):
    return x + _mla(_rms(x, lp["attn_norm"], dims[19]), seq, pos, lp, dims)


@functools.partial(jax.jit, static_argnames=("dims",))
def _feed_forward(a, lp, experts, l, dims):
    """``experts``: every expert layer's stacks and ``l`` which of them is this
    layer's (one compiled program for them all); None for a dense layer."""
    h = _rms(a, lp["mlp_norm"], dims[19])
    if experts is None:
        return a + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    stacks = tuple(lax.dynamic_index_in_dim(experts[k], l, 0, keepdims=False) for k in ("moe_gate", "moe_up", "moe_down"))
    return a + _moe(h, lp, stacks, dims)


@jax.jit
def _embed(params: dict, tokens: jax.Array) -> jax.Array:
    return params["embed"][tokens].astype(jnp.float32)


@jax.jit
def _head(x: jax.Array, w: jax.Array) -> jax.Array:
    return _dot(x, _f32(w))


def forward(doc: dict, params: dict, token_ids: list[int], positions=None, starts=(0,)) -> jax.Array:
    dims = sizes(doc)
    L, n_dense, eps = dims[0], dims[13], dims[19]
    T = len(token_ids)
    padded = -(-T // PAD) * PAD
    tokens = jnp.asarray(list(token_ids) + [0] * (padded - T), jnp.int32)
    first = jnp.asarray(sorted(starts), jnp.int32)
    seq = jnp.searchsorted(first, jnp.arange(padded, dtype=jnp.int32), side="right") - 1
    pos = jnp.arange(padded, dtype=jnp.int32) - first[seq]
    x = _embed(params, tokens)
    for l in range(L):
        dense = l < n_dense
        lp = params["layers"][l]
        attn = {k: v for k, v in lp.items() if k not in ("w_gate", "w_up", "w_down", "w_router", "mlp_norm")}
        ff = {k: lp[k] for k in ("w_gate", "w_up", "w_down", "w_router", "mlp_norm") if k in lp}
        x = _feed_forward(_attend(x, seq, pos, attn, dims), ff, None if dense else params["experts"],
                          None if dense else jnp.int32(l - n_dense), dims)
    rows = jnp.arange(T) if positions is None else jnp.asarray(positions, jnp.int32)
    return _head(_rms(x[rows], params["final_norm"], eps), params["lm_head"])
