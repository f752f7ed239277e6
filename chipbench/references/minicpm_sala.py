"""MiniCPM-SALA (block-sparse attention layers among lightning linear-attention
layers) written down plainly: the benchmark's yardstick for ``correct`` in the
MiniCPM-SALA cells.

    weights(doc, seed)                                           the seeded weights the cell serves
    forward(doc, params, token_ids, positions=None, starts=(0,)) float32 logits [T or len(positions), V]

Whole sequences at once, ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST``, dense score matrices for the lightning layers, for the
sparse layers' choice and for their attention; no cache, no state, no kernel,
no chunking. It imports nothing of the program.

The layers, from the published ``config.json`` and, for what it does not state,
the configuration file's ``assumed``. ``rms(x; w) = x * rsqrt(mean(x^2) +
rms_norm_eps) * w``; ``r = scale_depth / sqrt(num_hidden_layers)``:

- stream: ``x0 = scale_emb * embed(token)``; layer i: ``h = x + r * Mix_i(rms(x))``,
  ``out = h + r * (silu(f W_1) * (f W_3)) W_2`` on ``f = rms(h)``; logits
  ``= (rms(x_L) / (hidden_size / dim_model_base)) W_head`` (untied).
- ``Mix`` "lightning-attn", per head h of ``lightning_nh``, d = ``lightning_head_dim``:
  q and k RMS-normed over d, then rotary (rotate-half over the whole head);
  ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t = (q_t / sqrt(d)) S_t`` with
  ``lambda_h = exp(-2^(-8 (h + 1) / H))``. **Written here without the state**:
  unrolled, ``o_t = sum_{j<=t} lambda_h^(t-j) (q_t . k_j / sqrt(d)) v_j``, a causal
  score matrix times a decay, which is the recurrence's definition and shares
  nothing with the program's chunked scan or its one-step update. The heads'
  outputs concatenated, RMS-normed, times ``sigmoid(u W_ogate)``, then ``W_o``.
- ``Mix`` "minicpm4" (InfLLM-v2 as MiniCPM4 publishes it): GQA, no rotary
  embedding, an RMS norm over each query and key head, the output times
  ``sigmoid(u W_ogate)`` before ``W_o``. A query at position t that sees at most
  ``dense_len`` positions attends all of them. Past it: compressed keys
  ``kbar_j = mean(k[stride j : stride j + kernel])`` (visible when its last token
  is at or before t); per query head ``p = softmax_j(q . kbar_j / sqrt(d))`` over
  the visible ones; per KV head ``s_j = sum`` of p over its query heads; block b
  (``block_size`` tokens) scores the maximum of ``s_j`` over the compressed keys
  whose span overlaps it (0 where none does); the first ``init_blocks`` blocks
  and the blocks that hold the last ``window_size`` positions are forced in; the
  ``topk`` highest-scoring blocks, forced ones among them, are kept (among equal
  scores the earlier block first); softmax attention over the kept blocks'
  positions at or before t. Per token.

Departures, each for a reason:
- The weights are the cell's *served* weights: weight-only int8 with one float32
  scale an output channel where ``served.quant`` is ``int8``, multiplied out to
  float32 a layer at a time (the model in float32 is 38 GB).
- ``token_ids`` may hold several sequences end to end (``starts``): positions,
  blocks, compressed keys, decay and attention all count from a sequence's own
  start and stop at its end. Rows of queries are computed a few hundred at a time
  and a group of heads at a time, so that the scores of a 24,576-token row fit;
  the sums are the same. ``positions`` picks the rows wanted before the head.
- A compressed key is formed at every token as the mean of the ``kernel`` keys
  that end there; the tokens that end one (local position ``stride j + kernel -
  1``) are then picked out of the row, at most one every ``stride`` tokens: the
  same means, whatever the sequences' places in the row. A block's score and a
  token's block are read through 0/1 matrices (a product with one 1 a column
  copies a value), which the chip does far faster than a gather.

Controls (a run never sets them): the environment's ``SALA_REF_CONTROL`` =
``lower_cache`` computes everything a later position reads back in the nearest
precision under the one the configuration states: K, V and the compressed keys
rounded to int8 with one scale a token and head (the pages are bf16), and the
lightning layers run as the recurrence, token by token, with the state rounded
to int8 after every token, one scale a head and row of it (the state pool is
bfloat16); ``int8_state`` does the second alone. Both must come out ``correct:
false`` and do, by ten times the limits (the cell's limits file has the
readings). ``bf16_state`` rounds the state to bfloat16 after every token, which
is what the configuration states and more often than the program does (it
rounds a prefill's state once, where it comes to rest): it reads as the sound
reference does, which is why the pool is no wider. ``roll_blocks`` rolls the
sparse layers' choice by one block (a gross control), which must come out
``correct: false``. ``tests/test_sala.py`` tells each from the float32 program
on the CPU.

``weights`` is a copy of the program's seeded initialiser
(``engine/sala.py:init_params``). A program that changes its own stops agreeing
with it.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
PAD = 512
QUERY_ROWS = 256     # float32 scores of 16 heads x 256 queries x 24,576 positions are 403 MB
FFN_ROWS = 512      # the padding unit: every row length is a multiple
MAX_SEQUENCES = 16   # most sequences a row may hold: bounds the row's count of blocks
NEG = -1e30
WEIGHTS = ("wq", "wk", "wv", "wo", "w_ogate", "w_gate", "w_up", "w_down")


def sizes(doc: dict) -> dict:
    sc = doc["assumed"]["sparse_config"]
    kinds = tuple(doc["mixer_types"])
    assert len(kinds) == doc["num_hidden_layers"] and set(kinds) <= {"minicpm4", "lightning-attn"}, kinds
    return dict(
        kinds=kinds, D=int(doc["hidden_size"]), I=int(doc["intermediate_size"]), V=int(doc["vocab_size"]),
        H=int(doc["num_attention_heads"]), KVH=int(doc["num_key_value_heads"]), hd=int(doc["head_dim"]),
        LH=int(doc["lightning_nh"]), ld=int(doc["lightning_head_dim"]),
        eps=float(doc["rms_norm_eps"]), theta=float(doc["rope_theta"]),
        scale_emb=float(doc["scale_emb"]), r=float(doc["scale_depth"]) / int(doc["num_hidden_layers"]) ** 0.5,
        width=int(doc["hidden_size"]) / int(doc["dim_model_base"]),
        kernel=int(sc["kernel_size"]), stride=int(sc["kernel_stride"]), block=int(sc["block_size"]),
        topk=int(sc["topk"]), init=int(sc["init_blocks"]), window=int(sc["window_size"]),
        dense_len=int(sc["dense_len"]), control=os.environ.get("SALA_REF_CONTROL", ""))


def _static(z: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in z.items() if k != "kinds"))


# -- the seeded weights ----------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1,))
def _draw_int8(key, shape):
    """[n, a, b] uniform int8, a slice at a time under its own key (the program's own draw)."""
    return lax.map(lambda i: jax.random.randint(jax.random.fold_in(key, i), shape[1:], -127, 128, jnp.int8),
                   jnp.arange(shape[0]))


def weights(doc: dict, seed: int) -> dict:
    z = sizes(doc)
    D, I, V = z["D"], z["I"], z["V"]
    dtype = jnp.dtype(doc["served"].get("dtype", "bfloat16"))
    int8 = doc["served"]["quant"] == "int8"
    key = jax.random.PRNGKey(seed)

    def stack(kind: str, n: int, base: int) -> dict:
        q, kv, hd = (z["H"] * z["hd"], z["KVH"] * z["hd"], z["hd"]) if kind == "sparse" else \
            (z["LH"] * z["ld"], z["LH"] * z["ld"], z["ld"])
        shapes = {"wq": ((D, q), D), "wk": ((D, kv), D), "wv": ((D, kv), D), "wo": ((q, D), q),
                  "w_ogate": ((D, q), D), "w_gate": ((D, I), D), "w_up": ((D, I), D), "w_down": ((I, D), I)}
        out = {}
        for idx, name in enumerate(WEIGHTS):
            (fin, fout), fan = shapes[name]
            k = jax.random.fold_in(key, base + idx)
            if int8:
                out[name] = _draw_int8(k, (n, fin, fout))
                out[name + "_scale"] = jnp.full((n, fout), (fan ** -0.5) / 64.0, jnp.float32)
            else:
                out[name] = _draw(k, (n, fin, fout), fan ** -0.5, dtype)
        out["attn_norm"], out["mlp_norm"] = jnp.ones((n, D), dtype), jnp.ones((n, D), dtype)
        out["q_norm"], out["k_norm"] = jnp.ones((n, hd), dtype), jnp.ones((n, hd), dtype)
        if kind == "lightning":
            out["o_norm"] = jnp.ones((n, q), dtype)
        return out

    kinds = z["kinds"]
    params = {"sparse": stack("sparse", kinds.count("minicpm4"), 100),
              "lightning": stack("lightning", kinds.count("lightning-attn"), 200),
              "final_norm": jnp.ones((D,), dtype)}
    if int8:
        p = math.gcd(V, 8)  # both tables drawn as p pieces of V / p rows
        params["embed"] = _draw_int8(jax.random.fold_in(key, 90), (p, V // p, D)).reshape(V, D)
        params["embed_scale"] = jnp.full((V,), (D ** -0.5) / 64.0, jnp.float32)
        params["lm_head"] = _draw_int8(jax.random.fold_in(key, 91), (p, V // p, D)).reshape(V, D).T
        params["lm_head_scale"] = jnp.full((V,), (D ** -0.5) / 64.0, jnp.float32)
    else:
        params["embed"] = _draw(jax.random.fold_in(key, 90), (V, D), D ** -0.5, dtype)
        params["lm_head"] = _draw(jax.random.fold_in(key, 91), (D, V), D ** -0.5, dtype)
    return params


# -- the forward pass ------------------------------------------------------------


def _w(lp: dict, name: str) -> jax.Array:
    """A layer's weight multiplied out to float32."""
    w = lp[name].astype(jnp.float32)
    return w * lp[name + "_scale"][None, :] if name + "_scale" in lp else w


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _dot(a, b):
    return jnp.dot(a, b, precision=HI)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x [T, heads, d] at positions ``pos`` [T]; lane i pairs with lane i + d/2."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = pos.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _row_blocks(fn, n: int, rows: int):
    """``fn(first row)`` over the rows in blocks of ``rows``, stacked back."""
    firsts = jnp.arange(0, n, rows, dtype=jnp.int32)
    out = lax.map(fn, firsts)
    return out.reshape(n, *out.shape[2:])


def _ffn(f: jax.Array, lp: dict) -> jax.Array:
    w1, w3, w2 = _w(lp, "w_gate"), _w(lp, "w_up"), _w(lp, "w_down")
    rows = min(FFN_ROWS, f.shape[0])

    def block(i0):
        fb = lax.dynamic_slice_in_dim(f, i0, rows)
        return _dot(jax.nn.silu(_dot(fb, w1)) * _dot(fb, w3), w2)

    return _row_blocks(block, f.shape[0], rows)


def _int8(x: jax.Array) -> jax.Array:
    """The ``lower_cache`` and ``int8_state`` controls: symmetric absmax rounding along the last axis."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _lightning_dense(q, k, v, seq, pos, z) -> jax.Array:
    """``o_t = sum_{j<=t, same sequence} lambda^(t-j) (q_t . k_j) v_j``; q, k, v [T, H, d]."""
    T, H, d = q.shape
    rows, hg = min(QUERY_ROWS, T), min(8, H)
    log_lam = -jnp.exp2(-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H)
    qh, kh, vh = (a.transpose(1, 0, 2).reshape(H // hg, hg, T, d) for a in (q, k, v))

    def heads(args):
        qg, kg, vg, lg = args

        def block(i0):
            qb = lax.dynamic_slice_in_dim(qg, i0, rows, axis=1)                       # [hg, rows, d]
            sb, pb = lax.dynamic_slice_in_dim(seq, i0, rows), lax.dynamic_slice_in_dim(pos, i0, rows)
            gap = pb[:, None] - pos[None, :]                                          # [rows, T]
            see = (sb[:, None] == seq[None, :]) & (gap >= 0)
            decay = jnp.where(see[None], jnp.exp(lg[:, None, None] * jnp.maximum(gap, 0)[None]), 0.0)
            s = jnp.einsum("hqd,hcd->hqc", qb, kg, precision=HI) * decay
            return jnp.einsum("hqc,hcd->hqd", s, vg, precision=HI)

        out = lax.map(block, jnp.arange(0, T, rows, dtype=jnp.int32))                 # [T/rows, hg, rows, d]
        return out.transpose(1, 0, 2, 3).reshape(hg, T, d)

    o = lax.map(heads, (qh, kh, vh, log_lam.reshape(H // hg, hg)))
    return o.reshape(H, T, d).transpose(1, 0, 2)


_STATE_RESTS = {  # what a lightning state is rounded to after every token, by control
    # Not ``astype`` there and back: the chip's compiler may keep the excess precision of such a pair.
    "bf16_state": lambda s: lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7),
    "int8_state": lambda s: _int8(s),
    "lower_cache": lambda s: _int8(s),
}


def _lightning_scan(q, k, v, seq, z) -> jax.Array:
    """The controls that round the state (``_STATE_RESTS``): the recurrence a
    token at a time, the state rounded after every token."""
    H = q.shape[1]
    lam = jnp.exp(-jnp.exp2(-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H))
    first = jnp.concatenate([jnp.ones((1,), bool), seq[1:] != seq[:-1]])

    rest = _STATE_RESTS[z["control"]]

    def step(s, xs):
        qt, kt, vt, new = xs
        s = rest(jnp.where(new, 0.0, lam[:, None, None] * s) + kt[:, :, None] * vt[:, None, :])
        return s, jnp.einsum("hd,hde->he", qt, s, precision=HI)

    return lax.scan(step, jnp.zeros((H, q.shape[2], q.shape[2]), jnp.float32), (q, k, v, first))[1]


def _lightning(u, seq, pos, lp, z) -> jax.Array:
    T, H, d = u.shape[0], z["LH"], z["ld"]
    q = _rope(_rms(_dot(u, _w(lp, "wq")).reshape(T, H, d), lp["q_norm"], z["eps"]), pos, z["theta"])
    k = _rope(_rms(_dot(u, _w(lp, "wk")).reshape(T, H, d), lp["k_norm"], z["eps"]), pos, z["theta"])
    v = _dot(u, _w(lp, "wv")).reshape(T, H, d)
    q = q * d ** -0.5
    scan = z["control"] in _STATE_RESTS
    o = _lightning_scan(q, k, v, seq, z) if scan else _lightning_dense(q, k, v, seq, pos, z)
    y = _rms(o.reshape(T, H * d), lp["o_norm"], z["eps"])
    return _dot(y * jax.nn.sigmoid(_dot(u, _w(lp, "w_ogate"))), _w(lp, "wo"))


def _sparse(u, seq, pos, lp, z) -> jax.Array:
    T, H, KVH, hd = u.shape[0], z["H"], z["KVH"], z["hd"]
    G, bs, kernel, stride = H // KVH, z["block"], z["kernel"], z["stride"]
    q = _rms(_dot(u, _w(lp, "wq")).reshape(T, KVH, G, hd), lp["q_norm"], z["eps"])
    k = _rms(_dot(u, _w(lp, "wk")).reshape(T, KVH, hd), lp["k_norm"], z["eps"])
    v = _dot(u, _w(lp, "wv")).reshape(T, KVH, hd)
    # A compressed key at every token: the mean of the ``kernel`` keys that end
    # there; those that count are where a token ends one, picked out of the row.
    kbar = sum(jnp.pad(k, ((d, 0), (0, 0), (0, 0)))[:T] for d in range(kernel)) / kernel
    if z["control"] == "lower_cache":  # what the pages would hold
        k, v, kbar = _int8(k), _int8(v), _int8(kbar)
    ends = (pos >= kernel - 1) & ((pos - (kernel - 1)) % stride == 0)
    NC = T // stride
    cand = jnp.nonzero(ends, size=NC, fill_value=0)[0]                                # [NC] the tokens that end one
    cand_ok = jnp.arange(NC) < jnp.sum(ends)
    # Blocks, numbered along the row: a new one wherever a local position opens one.
    where = jnp.arange(T, dtype=jnp.int32)
    gb = jnp.cumsum(pos % bs == 0) - 1                                                # [T] the token's block
    NB = T // bs + MAX_SEQUENCES
    blk_local = jnp.zeros((NB,), jnp.int32).at[gb].set(pos // bs)                     # a block's index in its sequence
    blk_seq = jnp.full((NB,), -1, jnp.int32).at[gb].set(seq)
    blk_first = jnp.full((NB,), T, jnp.int32).at[gb].min(where)                       # the row's token that opens it
    in_block = (gb[None, :] == jnp.arange(NB)[:, None]).astype(jnp.float32)           # [NB, T] 0/1
    # The compressed keys whose span overlaps a block end at its tokens
    # kernel - 1, kernel - 1 + stride, ... up to kernel - 2 past its last.
    reach = jnp.asarray([o for o in range(bs + kernel - 1) if (o - (kernel - 1)) % stride == 0], jnp.int32)
    over = blk_first[:, None] + reach[None, :]                                        # [NB, R] tokens
    over_ok = (over < T) & (seq[jnp.minimum(over, T - 1)] == blk_seq[:, None]) & ends[jnp.minimum(over, T - 1)]
    picks = ((cand[:, None, None] == over[None]) & over_ok[None] & cand_ok[:, None, None])   # [NC, NB, R] 0/1
    picks = picks.reshape(NC, NB * reach.shape[0]).astype(jnp.float32)
    rows = min(QUERY_ROWS, T)

    def per_kv_head(args):
        qk, kk, vk, kb = args                                                         # [T, G, hd], [T, hd] x3
        kc = kb[cand]                                                                 # [NC, hd]

        def block(i0):
            qb = lax.dynamic_slice_in_dim(qk, i0, rows)                               # [rows, G, hd]
            sb, pb = lax.dynamic_slice_in_dim(seq, i0, rows), lax.dynamic_slice_in_dim(pos, i0, rows)
            # the choice
            seen = cand_ok[None, :] & (sb[:, None] == seq[cand][None, :]) & (pos[cand][None, :] <= pb[:, None])
            sc = jnp.einsum("qgd,cd->qgc", qb, kc, precision=HI) * hd ** -0.5
            sc = jnp.where(seen[:, None, :], sc, NEG)
            p = jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True)) * seen[:, None, :]
            p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
            sj = jnp.sum(p, axis=1)                                                   # [rows, NC]
            score = jnp.max(_dot(sj, picks).reshape(rows, NB, -1), axis=-1)           # [rows, NB]
            mine = blk_seq[None, :] == sb[:, None]
            forced = (blk_local[None, :] < z["init"]) | (
                blk_local[None, :] >= (pb[:, None] - z["window"] + 1) // bs)
            score = jnp.where(forced, 1e9, score)
            score = jnp.where(mine & (blk_local[None, :] <= pb[:, None] // bs), score, NEG)
            # lax.top_k's own order among equals, the lower block first: two
            # blocks tie whenever one compressed key across their boundary is
            # the best of both.
            chosen = lax.top_k(score, min(z["topk"], NB))[1]                          # [rows, topk]
            keep = (chosen[:, :, None] == jnp.arange(NB)[None, None, :]).any(axis=1) & (score > NEG)
            if z["control"] == "roll_blocks":
                keep = jnp.roll(keep, 1, axis=1)
            keep = jnp.dot(keep.astype(jnp.float32), in_block) > 0.5                  # [rows, T]: the token's block's
            causal = (sb[:, None] == seq[None, :]) & (pos[None, :] <= pb[:, None])
            see = causal & (keep | (pb[:, None] + 1 <= z["dense_len"]))
            # the attention
            s = jnp.einsum("qgd,cd->qgc", qb, kk, precision=HI) * hd ** -0.5
            a = jax.nn.softmax(jnp.where(see[:, None, :], s, NEG), axis=-1)
            return jnp.einsum("qgc,cd->qgd", a, vk, precision=HI)

        return _row_blocks(block, T, rows)                                            # [T, G, hd]

    heads = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    o = lax.map(per_kv_head, (heads(q), heads(k), heads(v), heads(kbar)))             # [KVH, T, G, hd]
    o = jnp.moveaxis(o, 0, 1).reshape(T, H * hd)
    return _dot(o * jax.nn.sigmoid(_dot(u, _w(lp, "w_ogate"))), _w(lp, "wo"))


@functools.partial(jax.jit, static_argnames=("kind", "static"))
def _layer(x, seq, pos, stack, idx, kind, static):
    z = dict(static)
    lp = jax.tree.map(lambda a: a[idx], stack)
    u = _rms(x, lp["attn_norm"], z["eps"])
    h = x + z["r"] * (_sparse if kind == "minicpm4" else _lightning)(u, seq, pos, lp, z)
    return h + z["r"] * _ffn(_rms(h, lp["mlp_norm"], z["eps"]), lp)


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(params: dict, tokens: jax.Array, scale: float) -> jax.Array:
    e = params["embed"][tokens].astype(jnp.float32)
    if "embed_scale" in params:
        e = e * params["embed_scale"][tokens][:, None]
    return e * scale


@functools.partial(jax.jit, static_argnames=("eps", "width"))
def _head(x, params, eps, width):
    h = _rms(x, params["final_norm"], eps) / width
    w = params["lm_head"].astype(jnp.float32)
    y = _dot(h, w)
    return y * params["lm_head_scale"][None, :] if "lm_head_scale" in params else y


def forward(doc: dict, params: dict, token_ids: list[int], positions=None, starts=(0,)) -> jax.Array:
    z = sizes(doc)
    assert len(starts) <= MAX_SEQUENCES, f"{len(starts)} sequences in a row; MAX_SEQUENCES is {MAX_SEQUENCES}"
    T = len(token_ids)
    padded = -(-T // PAD) * PAD
    tokens = jnp.asarray(list(token_ids) + [0] * (padded - T), jnp.int32)
    where = jnp.arange(padded, dtype=jnp.int32)
    first = jnp.asarray(sorted(starts), jnp.int32)
    seq = (jnp.searchsorted(first, where, side="right") - 1).astype(jnp.int32)
    pos = where - first[seq]
    x = _embed(params, tokens, z["scale_emb"])
    static, n_sparse, n_light = _static(z), 0, 0
    for kind in z["kinds"]:
        if kind == "minicpm4":
            x, n_sparse = _layer(x, seq, pos, params["sparse"], n_sparse, kind, static), n_sparse + 1
        else:
            x, n_light = _layer(x, seq, pos, params["lightning"], n_light, kind, static), n_light + 1
    rows = jnp.arange(T) if positions is None else jnp.asarray(positions, jnp.int32)
    return _head(x[rows], {k: v for k, v in params.items() if k in ("final_norm", "lm_head", "lm_head_scale")},
                 z["eps"], z["width"])
