"""The dots3-note language model, written down plainly: latent (MLA) attention
in two geometries, a learned indexer that picks the tokens a full layer attends,
window layers, and the DeepSeek-V3 expert layer. The benchmark's yardstick for
``correct`` in the dots3 cells.

    weights(doc, seed)                                           the seeded weights the cell serves
    forward(doc, params, token_ids, positions=None, starts=(0,)) float32 logits [T or len(positions), V]

Whole sequences at once, ``jax.numpy`` in float32 (products at
``Precision.HIGH``, the two discrete choices at ``HIGHEST``: below), attention in the EXPANDED form (``W_kvb`` multiplied out,
nothing absorbed), no cache, no kernel, no grouping of tokens. It imports
nothing of the program.

The model, from the published ``config.json`` (what it does not state is under
``assumed`` in the configuration's file). ``D`` hidden, ``u = rms(x)``:

- stream: ``x0 = embed(token)``; ``h = x + Attn_i(rms(x))``; ``out = h + FF_i(rms(h))``;
  logits ``= W_head rms(x_L)``. ``FF_0`` is SwiGLU at ``intermediate_size``; every
  later layer's is the expert layer.
- full layer: ``c_q = s_q rms(W_qa u)``; ``q = W_qb c_q`` as heads of ``nope | rope``;
  ``[c_kv | k_r] = W_kva u``; ``c = s_kv rms(c_kv)``; ``k_r`` rotated, shared by the
  heads; ``[k_n | v] = W_kvb c`` a head; ``score = (q_n k_n + q_r k_r) / sqrt(nope +
  rope)``; softmax over the CHOSEN set; ``o_h = sum p v``; ``o_h *= sigmoid(W_g u)_h``;
  ``W_o``. ``s_q = sqrt(D / q_rank)``, ``s_kv = sqrt(D / kv_rank)``
  (``apply_mla_qkv_lora_rescale``).
- the indexer: ``qI = W_qI c_q`` as ``index_n_heads`` heads of ``index_head_dim``;
  ``kI = LayerNorm(W_kI u)`` (eps 1e-6), one a token; the first ``rope`` lanes of
  both rotated; ``w = W_w u * n_heads^-0.5 * head_dim^-0.5``; ``I(t, s) = sum_j
  w_j(t) relu(qI_j(t) . kI(s))``; the chosen set of t: every visible s when there
  are at most ``index_topk``, else the ``index_topk`` highest ``I`` (exact, by
  ``jnp.argsort``: the lower position first at a tie).
- window layer: the same attention at the ``swa_*`` sizes over positions
  ``t - (sliding_window_size - 1) .. t``. No indexer.
- expert layer: ``s = sigmoid(W_r h)``; choice = top-k of ``s + b``; weights
  ``s[choice] / sum s[choice]`` times ``routed_scaling_factor``; ``out = shared(h) +
  sum`` over the chosen experts HELD here of ``w_e SwiGLU_e(h)``.

Departures from the published description, each for a reason:
- **The share.** The file states what one chip holds: ``n_routed_experts`` experts
  from ``first_expert_held`` of ``published_n_routed_experts``, ``vocab_size`` rows
  of the vocabulary. The router keeps its published width; a chosen expert that
  is not held adds nothing here, as on this chip. The program has the same share.
- The weights are the cell's served bf16 weights, multiplied out to float32 a
  tensor (or an expert) at a time.
- ``W_qb`` and ``W_kvb`` are drawn split (``w_qn``, ``w_qr``, ``w_uk``, ``w_uv``), as the
  seeded initialiser makes them: the products are the published ones.
- DeepSeek-V3.2's FP8 cast and Hadamard rotation of ``qI`` and ``kI`` are an
  implementation's shortcut and are not taken: scores are float32.
- The held experts are a plain loop: every expert over every token, times the
  weight the router gave it there (0 where it was not chosen); in a row of more
  than 16,384 tokens an expert runs over the tokens that chose it alone
  (gathered, a quarter of the row's room; over every token where more chose it):
  16 experts over 32,768 tokens each are 25 TFLOP a layer of which 3% is used,
  and the child that runs this has 120 s.
- Products run at ``Precision.HIGH`` (three bf16 passes, relative error 2^-16
  against bf16's 2^-8: the same verdicts at half the time), but where a choice
  is made: the indexer's scores and the router's logits are ``HIGHEST``.
- ``token_ids`` may hold several sequences end to end (``starts``), each attending
  itself alone with positions from its own start; the row is padded to a multiple
  of 512; a full layer's choice is made 512 queries at a time and kept as a mask;
  attention runs a few heads and 512 queries at a time, a window layer against
  the query block and the one before it (which hold its window); the
  feed-forwards run 4,096 rows at a time. ``positions`` picks the rows wanted
  before the head.

Controls (a run never sets them): the environment's ``DOTS3_REF_CONTROL`` =
``int8_latents`` (both kinds of latent row and the index keys rounded to int8,
one scale a token: the precision under the bf16 the configuration states, in
explicit int8 arithmetic), ``roll_choice`` (the chosen set taken from the scores
rolled by one position), ``window_short`` (the window two blocks of 32 shorter),
``roll_experts`` (the router's choice rolled by one expert index). Each must come
out as not correct.

``weights`` is a copy of the program's seeded initialiser
(``engine/dots3.py:init_params``). A program that changes its own stops agreeing.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGH        # three bf16 passes: relative error 2^-16, against bf16's 2^-8
EXACT = lax.Precision.HIGHEST   # six passes, where a choice is made: the indexer's scores, the router's
PAD = 512            # a row is padded to a multiple of this, and attention takes this many queries at a time
HEADS_AT_A_TIME = 4    # float32 scores of 4 heads over 512 x 32,768 positions are 268 MB, and their softmax as much
SPANS = 4              # a long row's queries in this many spans, each against the positions up to its own end
FF_ROWS = 4096
ROUTER_LOGIT_STD = 3.0
EXPERT_BIAS_STD = 0.1
INDEX_NORM_EPS = 1e-6


class Geo(NamedTuple):
    heads: int
    rq: int
    rkv: int
    dn: int
    dr: int
    dv: int
    theta: float


class Sizes(NamedTuple):
    layer_types: tuple
    D: int
    I: int
    ie: int
    full: Geo
    swa: Geo
    Hi: int
    di: int
    topk: int
    window: int
    E: int
    R: int
    first: int
    k: int
    shared: int
    scaling: float
    V: int
    eps: float
    rescale: bool
    control: str


def sizes(doc: dict) -> Sizes:
    control = os.environ.get("DOTS3_REF_CONTROL", "")
    full = Geo(doc["num_attention_heads"], doc["q_lora_rank"], doc["kv_lora_rank"], doc["qk_nope_head_dim"],
               doc["qk_rope_head_dim"], doc["v_head_dim"], float(doc["rope_theta"]))
    swa = Geo(doc["swa_num_attention_heads"], doc["swa_q_lora_rank"], doc["swa_kv_lora_rank"],
              doc["swa_qk_nope_head_dim"], doc["swa_qk_rope_head_dim"], doc["swa_v_head_dim"],
              float(doc["swa_rope_theta"]))
    window = int(doc["sliding_window_size"]) - (64 if control == "window_short" else 0)
    return Sizes(tuple(doc["layer_types"]), doc["hidden_size"], doc["intermediate_size"],
                 doc["moe_intermediate_size"], full, swa, doc["index_n_heads"], doc["index_head_dim"],
                 doc["index_topk"], window, doc["n_routed_experts"], doc["published_n_routed_experts"],
                 doc["first_expert_held"], doc["num_experts_per_tok"], doc["n_shared_experts"],
                 float(doc["routed_scaling_factor"]), doc["vocab_size"], float(doc["rms_norm_eps"]),
                 bool(doc["apply_mla_qkv_lora_rescale"]), control)


# -- the seeded weights ----------------------------------------------------------


DRAW_PIECE = 1 << 23  # elements a piece of a drawn tensor may hold (engine/dots3.py says why)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    """Normal(0, std), in the fewest equal pieces of the leading axis of no
    more than ``DRAW_PIECE`` elements, each under ``fold_in(key, piece)``."""
    n = 1
    for d in shape:
        n *= d
    p = next((p for p in range(1, shape[0] + 1) if shape[0] % p == 0 and n // p <= DRAW_PIECE), shape[0])
    part = (shape[0] // p, *shape[1:])
    pieces = lax.map(lambda i: (jax.random.normal(jax.random.fold_in(key, i), part, jnp.float32) * std).astype(dtype),
                     jnp.arange(p))
    return pieces.reshape(shape)


def _layer_weights(z: Sizes, g: Geo, key, dtype, indexer: bool, dense: bool) -> dict:
    D, H = z.D, g.heads
    shapes = {"w_qa": ((D, g.rq), D), "w_qn": ((g.rq, H * g.dn), g.rq), "w_qr": ((g.rq, H * g.dr), g.rq),
              "w_kva": ((D, g.rkv + g.dr), D), "w_uk": ((H, g.dn, g.rkv), g.rkv), "w_uv": ((H, g.rkv, g.dv), g.rkv),
              "wo": ((H * g.dv, D), H * g.dv), "w_og": ((D, H), D)}
    if indexer:
        shapes.update({"wi_q": ((g.rq, z.Hi * z.di), g.rq), "wi_k": ((D, z.di), D), "wi_w": ((D, z.Hi), D)})
    I = z.I if dense else z.shared * z.ie
    shapes.update({"w_gate": ((D, I), D), "w_up": ((D, I), D), "w_down": ((I, D), I)})
    if not dense:
        shapes["w_router"] = ((D, z.R), D)
    out = {}
    for n, (name, (shape, fan_in)) in enumerate(shapes.items()):
        std = fan_in ** -0.5 * (ROUTER_LOGIT_STD if name == "w_router" else 1.0)
        out[name] = _draw(jax.random.fold_in(key, n), shape, std, dtype)
    if not dense:
        out["router_bias"] = _draw(jax.random.fold_in(key, 90), (z.R,), EXPERT_BIAS_STD, jnp.float32)
    if indexer:
        out["ik_norm_w"] = jnp.ones((z.di,), dtype)
        out["ik_norm_b"] = jnp.zeros((z.di,), dtype)
    out["attn_norm"] = jnp.ones((D,), dtype)
    out["mlp_norm"] = jnp.ones((D,), dtype)
    # the latent norms' gains undo the rescale (engine/longcat.py says why)
    out["q_norm"] = jnp.full((g.rq,), (g.rq / D) ** 0.5 if z.rescale else 1.0, dtype)
    out["kv_norm"] = jnp.full((g.rkv,), (g.rkv / D) ** 0.5 if z.rescale else 1.0, dtype)
    return out


def weights(doc: dict, seed: int) -> dict:
    """A list of layers (each its own tensors; an expert layer's held experts
    under ``moe_gate`` / ``moe_up`` / ``moe_down`` ``[E, ..]``), the embedding,
    the head and the final norm: the program's draws, key for key."""
    z = sizes(doc)
    dtype = jnp.dtype(doc["served"].get("dtype", "bfloat16"))
    if doc["served"]["quant"] != "none":
        raise ValueError("the dots3 block is served in its published bf16 only")
    key = jax.random.PRNGKey(seed)
    layer_key = functools.partial(jax.random.fold_in, jax.random.fold_in(key, 100))
    layers = []
    for i, kind in enumerate(z.layer_types):
        full = kind == "full_attention"
        layers.append(_layer_weights(z, z.full if full else z.swa, layer_key(i), dtype, indexer=full, dense=i == 0))
    for n, (name, shape, fan_in) in enumerate((("moe_gate", (z.E, z.D, z.ie), z.D), ("moe_up", (z.E, z.D, z.ie), z.D),
                                                ("moe_down", (z.E, z.ie, z.D), z.ie))):
        k = jax.random.fold_in(key, 200 + n)
        for i in range(1, len(layers)):
            layers[i][name] = _draw(jax.random.fold_in(k, i), shape, fan_in ** -0.5, dtype)
    return {"embed": _draw(jax.random.fold_in(key, 1), (z.V, z.D), z.D ** -0.5, dtype),
            "lm_head": _draw(jax.random.fold_in(key, 2), (z.D, z.V), z.D ** -0.5, dtype),
            "final_norm": jnp.ones((z.D,), dtype), "layers": layers}


# -- the forward pass ------------------------------------------------------------


def _f32(w):
    return w.astype(jnp.float32)


def _dot(a, b):
    return jnp.dot(a, b, precision=HI)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _rope_pairs(x, pos, theta):
    """x [T, heads, hd] at positions ``pos`` [T]; lane 2i pairs with lane 2i+1."""
    T, heads, hd = x.shape
    inv_freq = theta ** (-jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2))
    angles = pos.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    pairs = x.reshape(T, heads, hd // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(T, heads, hd)


def _int8(x):
    """Rounded to int8 and back, one scale a row (the last axis), in explicit
    int8 arithmetic (a cast pair keeps its excess precision on the chip)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8).astype(jnp.float32) * s


def _rows(fn, x, rows: int):
    """``fn`` over ``x`` [T, ..] ``rows`` rows at a time."""
    T = x.shape[0]
    if T <= rows or T % rows:
        return fn(x)
    return lax.map(fn, x.reshape(T // rows, rows, *x.shape[1:])).reshape(T, -1)


def _swiglu(h, gate, up, down):
    gate, up, down = _f32(gate), _f32(up), _f32(down)
    return _rows(lambda a: _dot(jax.nn.silu(_dot(a, gate)) * _dot(a, up), down), h, FF_ROWS)


def _spans(nb: int) -> list[tuple[int, int]]:
    """Blocks [lo, hi) of a row of ``nb`` query blocks, ``SPANS`` equal spans
    where the row divides: a span's queries are causal, so they need no position
    past the span's end (three eighths of a long row's score products saved)."""
    n = SPANS if nb % SPANS == 0 else 1
    return [(i * nb // n, (i + 1) * nb // n) for i in range(n)]


def _chosen(u, c_q, seq, pos, lp, z: Sizes):
    """The full layer's choice → bool [T, T]: query t attends position s."""
    T = u.shape[0]
    g, dr = z.full, z.full.dr
    q = jnp.dot(c_q, _f32(lp["wi_q"]), precision=EXACT).reshape(T, z.Hi, z.di)
    q = jnp.concatenate([_rope_pairs(q[..., :dr], pos, g.theta), q[..., dr:]], axis=-1)
    k = jnp.dot(u, _f32(lp["wi_k"]), precision=EXACT)
    k = (k - jnp.mean(k, axis=-1, keepdims=True)) * lax.rsqrt(jnp.var(k, axis=-1, keepdims=True) + INDEX_NORM_EPS)
    k = k * _f32(lp["ik_norm_w"]) + _f32(lp["ik_norm_b"])
    k = jnp.concatenate([_rope_pairs(k[:, None, :dr], pos, g.theta)[:, 0], k[:, dr:]], axis=-1)
    if z.control == "int8_latents":
        k = _int8(k)
    w = jnp.dot(u, _f32(lp["wi_w"]), precision=EXACT) * (z.Hi ** -0.5 * z.di ** -0.5)
    cols, k_all, seq_all = jnp.arange(T), k, seq

    def block(args):  # PAD queries against the positions of their span (``k``, ``seq_k``: set below)
        qb, wb, sb, tb = args  # [PAD, Hi, di], [PAD, Hi], [PAD], [PAD]
        n = k.shape[0]

        def head(j, acc):
            return acc + wb[:, j, None] * jnp.maximum(jnp.einsum("td,sd->ts", qb[:, j], k, precision=EXACT), 0.0)

        score = lax.fori_loop(0, z.Hi, head, jnp.zeros((qb.shape[0], n), jnp.float32))
        seen = (tb[:, None] >= cols[None, :n]) & (sb[:, None] == seq_k[None, :])
        score = jnp.where(seen, score, -jnp.inf)
        if z.control == "roll_choice":
            score = jnp.where(seen, jnp.roll(score, 1, axis=1), -jnp.inf)
        top = jnp.argsort(-score, axis=1, stable=True)[:, :min(z.topk, n)]
        kept = jnp.zeros((qb.shape[0], n), bool).at[jnp.arange(qb.shape[0])[:, None], top].set(True)
        return kept & seen

    nb = T // PAD
    kept = []
    for lo, hi in _spans(nb):  # queries of blocks lo..hi see no position past block hi's end
        n = hi * PAD
        part = lambda a: a[lo * PAD:n].reshape(hi - lo, PAD, *a.shape[1:])  # noqa: E731
        k, seq_k = k_all[:n], seq_all[:n]
        kept.append(jnp.pad(lax.map(block, (part(q), part(w), part(seq_all), part(cols))).reshape(n - lo * PAD, n),
                            ((0, 0), (0, T - n))))
    return jnp.concatenate(kept)


def _attention(u, seq, pos, lp, g: Geo, z: Sizes, allowed=None, window: int = 0):
    """MLA at geometry ``g``, expanded. ``allowed`` [T, T] (a full layer: the
    chosen set, causal and of the query's own sequence); ``window`` > 0: a
    window layer, each block of PAD queries against itself and the block before."""
    T, H = u.shape[0], g.heads
    s_q, s_kv = ((z.D / g.rq) ** 0.5, (z.D / g.rkv) ** 0.5) if z.rescale else (1.0, 1.0)
    c_q = _rms(_dot(u, _f32(lp["w_qa"])), lp["q_norm"], z.eps) * s_q
    kv = _dot(u, _f32(lp["w_kva"]))
    c_kv = _rms(kv[:, :g.rkv], lp["kv_norm"], z.eps) * s_kv
    k_r = _rope_pairs(kv[:, None, g.rkv:], pos, g.theta)[:, 0]
    if z.control == "int8_latents":  # the cache row in int8, one scale a token
        row = _int8(jnp.concatenate([c_kv, k_r], axis=-1))
        c_kv, k_r = row[:, :g.rkv], row[:, g.rkv:]
    if allowed is None and not window:
        allowed = _chosen(u, c_q, seq, pos, lp, z)
    scale = (g.dn + g.dr) ** -0.5
    nb = T // PAD
    cols = jnp.arange(T)
    gate = jax.nn.sigmoid(_dot(u, _f32(lp["w_og"])))                    # [T, H]: the head-wise gate

    def attend(qn_b, qr_b, k_n_b, k_r_b, v_b, ok):
        s = (jnp.einsum("htn,hsn->hts", qn_b, k_n_b, precision=HI)
             + jnp.einsum("htr,sr->hts", qr_b, k_r_b, precision=HI)) * scale
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,hsv->htv", jnp.where(ok[None], p, 0.0), v_b, precision=HI)

    def heads(out, args):
        """A few heads at a time, from their own columns of the weights to their
        own rows of ``W_o``: no tensor here has every head's queries or outputs
        (at 128 heads and 32,768 tokens each of those is 2.1 GB in float32)."""
        w_qn, w_qr, uk, uv, wo, gt = args  # [rq, h*dn], [rq, h*dr], [h, dn, rkv], [h, rkv, dv], [h*dv, D], [T, h]
        h = uk.shape[0]
        qn = _dot(c_q, _f32(w_qn)).reshape(T, h, g.dn).transpose(1, 0, 2)
        qr = _rope_pairs(_dot(c_q, _f32(w_qr)).reshape(T, h, g.dr), pos, g.theta).transpose(1, 0, 2)
        k_n = jnp.einsum("sl,hnl->hsn", c_kv, _f32(uk), precision=HI)
        v = jnp.einsum("sl,hlv->hsv", c_kv, _f32(uv), precision=HI)

        def full_blocks():  # a span of query blocks at a time, against the positions up to the span's end
            out = []
            for lo, hi in _spans(nb):
                n = hi * PAD

                def full_block(i, n=n):
                    sl = lambda a, ax: lax.dynamic_slice_in_dim(a, i * PAD, PAD, ax)  # noqa: E731
                    return attend(sl(qn, 1), sl(qr, 1), k_n[:, :n], k_r[:n], v[:, :n], sl(allowed, 0)[:, :n])

                out.append(lax.map(full_block, jnp.arange(lo, hi)))
            return jnp.concatenate(out)

        def window_block(i):  # keys: the block before (nothing before block 0) and this one
            lo = jnp.maximum(i - 1, 0) * PAD
            sl = lambda a, ax: lax.dynamic_slice_in_dim(a, i * PAD, PAD, ax)  # noqa: E731
            two = lambda a, ax: lax.dynamic_slice_in_dim(a, lo, 2 * PAD, ax) if nb > 1 else a  # noqa: E731
            t, s = sl(cols, 0), two(cols, 0)
            ok = ((t[:, None] >= s[None, :]) & (t[:, None] - s[None, :] < window)
                  & (sl(seq, 0)[:, None] == two(seq, 0)[None, :]))
            return attend(sl(qn, 1), sl(qr, 1), two(k_n, 1), two(k_r, 0), two(v, 1), ok)

        o = lax.map(window_block, jnp.arange(nb)) if window else full_blocks()   # [nb, h, PAD, dv]
        o = jnp.moveaxis(o, 0, 1).reshape(h, T, g.dv) * gt.T[..., None]
        return out + _dot(o.transpose(1, 0, 2).reshape(T, h * g.dv), _f32(wo)), None

    h = min(HEADS_AT_A_TIME, H)
    cols_of = lambda w, d: w.reshape(w.shape[0], H // h, h * d).transpose(1, 0, 2)  # noqa: E731
    grouped = lambda a: a.reshape(H // h, h, *a.shape[1:])  # noqa: E731
    xs = (cols_of(lp["w_qn"], g.dn), cols_of(lp["w_qr"], g.dr), grouped(lp["w_uk"]), grouped(lp["w_uv"]),
          lp["wo"].reshape(H // h, h * g.dv, z.D), gate.reshape(T, H // h, h).transpose(1, 0, 2))
    return lax.scan(heads, jnp.zeros((T, z.D), jnp.float32), xs)[0]


def _experts(h, lp, z: Sizes):
    s = jax.nn.sigmoid(jnp.dot(h, _f32(lp["w_router"]), precision=EXACT))
    _, chosen = lax.top_k(s + lp["router_bias"][None, :], z.k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * z.scaling
    if z.control == "roll_experts":
        chosen = (chosen + 1) % z.R

    T = h.shape[0]
    room = T if T <= 4 * FF_ROWS else T // 4  # a long row: an expert runs over the tokens that chose it

    def expert(y, args):  # one held expert, times the weight the router gave it at each token
        e, gate, up, down = args
        w_e = jnp.sum(jnp.where(chosen == z.first + e, w, 0.0), axis=-1, keepdims=True)
        if room == T:
            return y + w_e * _swiglu(h, gate, up, down), None
        took = jnp.any(chosen == z.first + e, axis=-1)
        rows = jnp.nonzero(took, size=room, fill_value=0)[0]           # the tokens that chose it, then padding
        live = (jnp.arange(room) < jnp.sum(took))[:, None]
        add = jnp.where(live, w_e[rows] * _swiglu(h[rows], gate, up, down), 0.0)
        # more than a quarter of the row chose it (no seeded router does): over every token instead
        return lax.cond(jnp.sum(took) > room, lambda: y + w_e * _swiglu(h, gate, up, down),
                        lambda: y.at[rows].add(add)), None

    shared = _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    return lax.scan(expert, shared, (jnp.arange(z.E), lp["moe_gate"], lp["moe_up"], lp["moe_down"]))[0]


_FF_KEYS = ("mlp_norm", "w_gate", "w_up", "w_down", "w_router", "router_bias", "moe_gate", "moe_up", "moe_down")


@functools.partial(jax.jit, static_argnames=("z", "kind"), donate_argnums=(0,))
def _mix(x, seq, pos, lp, z: Sizes, kind: str):
    """``x + Attn(rms(x))``: one compiled program a kind of attention (layer 0's
    and the expert layers' full attention are the same program: the child that
    runs this compiles inside its 120 s)."""
    u = _rms(x, lp["attn_norm"], z.eps)
    if kind == "full_attention":
        return x + _attention(u, seq, pos, lp, z.full, z)
    return x + _attention(u, seq, pos, lp, z.swa, z, window=z.window)


@functools.partial(jax.jit, static_argnames=("z",), donate_argnums=(0,))
def _ff(h, lp, z: Sizes):
    """``h + FF(rms(h))``: the dense feed-forward, or the expert layer."""
    f = _rms(h, lp["mlp_norm"], z.eps)
    if "w_router" in lp:
        return h + _experts(f, lp, z)
    return h + _swiglu(f, lp["w_gate"], lp["w_up"], lp["w_down"])


def _layer(x, seq, pos, lp, z: Sizes, kind: str):
    ff = {k: v for k, v in lp.items() if k in _FF_KEYS}
    return _ff(_mix(x, seq, pos, {k: v for k, v in lp.items() if k not in ff}, z, kind), ff, z)


@jax.jit
def _embed(params: dict, tokens: jax.Array) -> jax.Array:
    return params["embed"][tokens].astype(jnp.float32)


@jax.jit
def _head(x: jax.Array, w: jax.Array) -> jax.Array:
    return _dot(x, _f32(w))


def forward(doc: dict, params: dict, token_ids: list[int], positions=None, starts=(0,)) -> jax.Array:
    z = sizes(doc)
    if z.window - 1 > PAD:
        raise ValueError(f"a window of {z.window} positions does not fit the block of {PAD} queries and the one before")
    T = len(token_ids)
    padded = -(-T // PAD) * PAD
    tokens = jnp.asarray(list(token_ids) + [0] * (padded - T), jnp.int32)
    first = jnp.asarray(sorted(starts), jnp.int32)
    seq = jnp.searchsorted(first, jnp.arange(padded, dtype=jnp.int32), side="right") - 1
    pos = jnp.arange(padded, dtype=jnp.int32) - first[seq]
    x = _embed(params, tokens)
    for lp, kind in zip(params["layers"], z.layer_types):
        x = _layer(x, seq, pos, lp, z, kind)
    rows = jnp.arange(T) if positions is None else jnp.asarray(positions, jnp.int32)
    return _head(_rms(x[rows], params["final_norm"], z.eps), params["lm_head"])
