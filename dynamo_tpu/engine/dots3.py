"""The dots3-note block (``ModelConfig.block == "dots3"``): latent (MLA)
attention in two geometries, one layer definition used by the packed prefill,
the single prefill and the decode window. ``model.block_module(cfg)`` is this
module for such a model: ``init_params``, ``init_kv_cache`` and the jitted
``prefill``, ``prefill_batch``, ``decode_step`` and ``multi_decode`` under
engine/model.py's names, each taking one operand more, the rows' window tables
(``state_slots``, below), and returning the expert layers' routing histogram
after what the dense block's returns, as engine/longcat.py's do.

The stream: ``x0 = embed(token)``; a layer is ``h = x + Attn(rms(x))``,
``out = h + FF(rms(h))``; logits ``= W_head rms(x_L)``. Layer 0's ``FF`` is a
dense SwiGLU, every later layer's one shared expert beside the routed ones
(engine/longcat.py's expert path under the sigmoid router: choice on ``s +
bias``, weights renormalised; the experts held here, the rest left out).

- **A full layer** (``layer_types[i] == "full_attention"``): MLA as
  engine/longcat.py has it (low-rank query, one latent row a token, ``W_kvb``
  absorbed both ways), over a **chosen set**: an indexer (ops/dsa.py) scores
  every cached position against one ``index_head_dim`` key a token and the
  query attends the ``index_topk`` highest; a query that sees no more than that
  attends them all. The choice is made anew at every token, in prefill (a mask
  over the latent prefill kernel's walk) as in decode (a mask over the decode
  kernel's walk, or the chosen rows gathered and attended alone, whichever
  ``dsa.walk_is_cheaper`` of the call's lengths). Then a head-wise gate
  ``o_h * sigmoid(W_g u)_h`` and ``W_o``.
- **A window layer** (``"sliding_attention"``): the same equations at the
  ``swa_*`` sizes (``cfg.swa``), a query attending its last ``sliding_window``
  positions, its own among them. No indexer.

**The cache has three pools** (``KVCache``): ``kv`` ``[full layers, N, bs,
latent_page_width]`` and ``ikeys`` ``[full layers, N, bs, index_head_dim]``
under the block table's ids, and ``window`` ``[window layers, Nw, bs, swa
latent_page_width]`` under ids of its own, which block_manager/pool.py hands
out and takes back behind a sequence as it runs. ``state_slots`` carries a
row's window table: column 0 the index, in the sequence, of the block its
first entry holds, then the entries (prefill ``[Bp, 1 + window_prefill_width]``,
decode ``[B, 1 + window_table_width]``); positions in a window layer count from
that block, so the latent kernels walk the window table as they walk any other.

**Compile cost does not grow with depth.** Layer 0 alone, then one
``lax.scan`` over the periods of one ``[full, window, ...]`` body whose window
layers are an inner scan: each kind's layer is traced once a program (the full
layer twice: with the dense feed-forward and with the experts). The inner scan
runs over the layer's index and reads ``params["swa"]`` ``[P, n_win, ..]``
whole: the stack is an operand of neither scan.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.longcat import (
    HIST_EXTRA, ROUTER_LOGIT_STD, _mlp, _pad_row, _rms, _rope_pairs, absorb_query, expert_impl,
    mla_project, mla_query_latent, moe, unabsorb_output,
)
from dynamo_tpu.engine.model import KVCache, _logits, decode_window, pool_zeros
from dynamo_tpu.engine.side import WindowBlocks as side_cache  # noqa: F401 — this block's second cache (engine/side.py)
from dynamo_tpu.ops import dsa
from dynamo_tpu.ops.paged_attention import (
    latent_decode_attention,
    latent_decode_attention_xla,
    latent_prefill_attention,
    latent_prefill_attention_xla,
    resolve_attn_impl,
    resolve_prefill_impl,
)

Params = dict[str, Any]

START_LINE = " block=dots3"  # what the engine's start line says of this block
UNCARRIED = ("latent pages, index keys and window pool", "latent pages, index keys and the window pool")
# The expert bias is drawn at this scale (engine/lfm2.py's, for the same
# router): a choice made on the scores without it picks other experts.
EXPERT_BIAS_STD = 0.1
# Query positions the full layers' prefill chooses and attends at a time: the
# indexer's float32 scores, their sortable keys and the mask are [this, table
# width x block size] each (67 MB at 32,768 positions), whatever the chunk.
CHOICE_QUERIES = 512
INDEX_NORM_EPS = 1e-6


def periods(cfg: ModelConfig) -> tuple[int, int]:
    """(periods after layer 0, window layers a period)."""
    n_win = len(cfg.window_layers) // (len(cfg.full_layers) - 1)
    return len(cfg.full_layers) - 1, n_win


def routed_layers(cfg: ModelConfig) -> tuple[int, ...]:
    """The layers that route, in the order of the histogram's rows."""
    return tuple(range(cfg.num_dense_layers, cfg.num_layers))


# -- the seeded initialiser (chipbench/references/dots3_note.py keeps a copy) -----


# Elements a piece of a drawn tensor may hold. The chip's compiler takes 7 s
# over one draw of 126M elements and half a second over a loop of 8M-element
# pieces, and the reference's child, which draws the same weights, has 120 s in
# all (the first chip run spent 115 of them here).
DRAW_PIECE = 1 << 23


def draw_pieces(shape: tuple[int, ...]) -> int:
    """The pieces a tensor is drawn in: the fewest equal parts of its leading
    axis that hold no more than ``DRAW_PIECE`` elements each."""
    n = 1
    for d in shape:
        n *= d
    return next((p for p in range(1, shape[0] + 1) if shape[0] % p == 0 and n // p <= DRAW_PIECE), shape[0])


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    """Normal(0, std) of ``shape``, a piece of the leading axis at a time under
    its own key (``fold_in(key, piece)``)."""
    p = draw_pieces(shape)
    part = (shape[0] // p, *shape[1:])
    pieces = lax.map(lambda i: (jax.random.normal(jax.random.fold_in(key, i), part, jnp.float32) * std).astype(dtype),
                     jnp.arange(p))
    return pieces.reshape(shape)


def _attn_shapes(cfg: ModelConfig, geo: ModelConfig, indexer: bool) -> dict:
    """name -> (shape, fan_in) of one attention block at geometry ``geo``."""
    D, H, rq, rkv = cfg.hidden_size, geo.num_heads, geo.q_lora_rank, geo.kv_lora_rank
    dn, dr, dv = geo.qk_nope_head_dim, geo.qk_rope_head_dim, geo.v_head_dim
    shapes = {"w_qa": ((D, rq), D), "w_qn": ((rq, H * dn), rq), "w_qr": ((rq, H * dr), rq),
              "w_kva": ((D, rkv + dr), D), "w_uk": ((H, dn, rkv), rkv), "w_uv": ((H, rkv, dv), rkv),
              "wo": ((H * dv, D), H * dv), "w_og": ((D, H), D)}
    if indexer:
        Hi, di = cfg.index_n_heads, cfg.index_head_dim
        shapes.update({"wi_q": ((rq, Hi * di), rq), "wi_k": ((D, di), D), "wi_w": ((D, Hi), D)})
    return shapes


def _ff_shapes(cfg: ModelConfig, dense: bool) -> dict:
    D = cfg.hidden_size
    I = cfg.intermediate_size if dense else cfg.num_shared_experts * (cfg.moe_intermediate_size or cfg.intermediate_size)
    shapes = {"w_gate": ((D, I), D), "w_up": ((D, I), D), "w_down": ((I, D), I)}
    if not dense:
        shapes["w_router"] = ((D, cfg.router_width), D)
    return shapes


def _layer_params(cfg: ModelConfig, geo: ModelConfig, key, dtype, *, indexer: bool, dense: bool) -> dict:
    """One layer's tensors but its routed experts, a jitted draw each."""
    D = cfg.hidden_size
    out = {}
    for n, (name, (shape, fan_in)) in enumerate({**_attn_shapes(cfg, geo, indexer), **_ff_shapes(cfg, dense)}.items()):
        std = fan_in ** -0.5 * (ROUTER_LOGIT_STD if name == "w_router" else 1.0)
        out[name] = _draw(jax.random.fold_in(key, n), shape, std, dtype)
    if not dense:
        out["router_bias"] = _draw(jax.random.fold_in(key, 90), (cfg.router_width,), EXPERT_BIAS_STD, jnp.float32)
    if indexer:
        out["ik_norm_w"] = jnp.ones((cfg.index_head_dim,), dtype)
        out["ik_norm_b"] = jnp.zeros((cfg.index_head_dim,), dtype)
    # The latent norms' gains undo the rescale (engine/longcat.py says why).
    out["attn_norm"] = jnp.ones((D,), dtype)
    out["mlp_norm"] = jnp.ones((D,), dtype)
    out["q_norm"] = jnp.full((geo.q_lora_rank,), (geo.q_lora_rank / D) ** 0.5 if cfg.mla_scale_q_lora else 1.0, dtype)
    out["kv_norm"] = jnp.full((geo.kv_lora_rank,), (geo.kv_lora_rank / D) ** 0.5 if cfg.mla_scale_kv_lora else 1.0, dtype)
    return out


def _stack(trees: list) -> dict:
    return jax.tree.map(lambda *a: jnp.stack(a), *trees)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params, a tensor of a layer at a time (one jitted draw
    each, then stacked): no float32 copy of more than one tensor of one layer
    exists. ``first``: layer 0; ``full`` ``[P, ..]`` and ``swa`` ``[P, n_win,
    ..]``: the periods' layers; ``experts``: every expert layer's held stacks
    as one ``[layers x E, ..]`` stack each (engine/longcat.py:stacked_experts
    says why), layer 1's first."""
    D, E = cfg.hidden_size, cfg.num_experts
    ie = cfg.moe_intermediate_size or cfg.intermediate_size
    P, n_win = periods(cfg)
    layer_key = functools.partial(jax.random.fold_in, jax.random.fold_in(key, 100))
    swa = cfg.swa
    full, win = [], []
    for p in range(P):
        first = 1 + p * (1 + n_win)
        full.append(_layer_params(cfg, cfg, layer_key(first), dtype, indexer=True, dense=False))
        win.append(_stack([_layer_params(cfg, swa, layer_key(first + 1 + j), dtype, indexer=False, dense=False)
                           for j in range(n_win)]))
    experts = {}
    for n, (name, shape, fan_in) in enumerate((("moe_gate", (E, D, ie), D), ("moe_up", (E, D, ie), D),
                                                ("moe_down", (E, ie, D), ie))):
        k = jax.random.fold_in(key, 200 + n)
        experts[name] = jnp.concatenate([
            _draw(jax.random.fold_in(k, l), shape, fan_in ** -0.5, dtype) for l in routed_layers(cfg)])
    return {
        "embed": _draw(jax.random.fold_in(key, 1), (cfg.vocab_size, D), D ** -0.5, dtype),
        "lm_head": _draw(jax.random.fold_in(key, 2), (D, cfg.vocab_size), D ** -0.5, dtype),
        "final_norm": jnp.ones((D,), dtype),
        "first": _layer_params(cfg, cfg, layer_key(0), dtype, indexer=True, dense=True),
        "full": _stack(full), "swa": _stack(win), "experts": experts,
    }


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
                  sharding=None, kv_quant: str = "none", window_blocks: int = 2) -> KVCache:
    """The full layers' latent rows and index keys under one set of block
    ids, the window layers' rows under ``window_blocks`` ids of their own."""
    if kv_quant != "none":
        raise ValueError("a latent (MLA) cache has no int8 form (kv_quant)")
    zeros = pool_zeros(sharding)
    nf, nw = len(cfg.full_layers), len(cfg.window_layers)
    return KVCache(zeros((nf, num_blocks, block_size, cfg.latent_page_width), dtype),
                   ikeys=zeros((nf, num_blocks, block_size, cfg.index_head_dim), dtype),
                   window=zeros((nw, window_blocks, block_size, cfg.swa.latent_page_width), dtype))


# -- pieces ----------------------------------------------------------------------


def index_key(u: jax.Array, lp: dict, cfg: ModelConfig, positions: jax.Array) -> jax.Array:
    """u [..., D] → the indexer's key of each token [..., index_head_dim]:
    ``LayerNorm(W_kI u)``, its first rope lanes rotated."""
    dr = cfg.qk_rope_head_dim
    k = jnp.dot(u, lp["wi_k"]).astype(jnp.float32)
    k = (k - jnp.mean(k, axis=-1, keepdims=True)) * lax.rsqrt(jnp.var(k, axis=-1, keepdims=True) + INDEX_NORM_EPS)
    k = (k * lp["ik_norm_w"].astype(jnp.float32) + lp["ik_norm_b"].astype(jnp.float32)).astype(u.dtype)
    rot = _rope_pairs(k[..., None, :dr], positions, cfg.rope_theta)[..., 0, :]
    return jnp.concatenate([rot, k[..., dr:]], axis=-1)


def index_query(u: jax.Array, c_q: jax.Array, lp: dict, cfg: ModelConfig, positions: jax.Array):
    """→ (qI [..., Hi, di] with its first rope lanes rotated, w [..., Hi]
    float32, the heads' weights times ``Hi ** -0.5 * di ** -0.5``)."""
    Hi, di, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q_scale = jnp.asarray((cfg.hidden_size / cfg.q_lora_rank) ** 0.5 if cfg.mla_scale_q_lora else 1.0, u.dtype)
    q = (jnp.dot(c_q, lp["wi_q"]) * q_scale).reshape(*u.shape[:-1], Hi, di)
    q = jnp.concatenate([_rope_pairs(q[..., :dr], positions, cfg.rope_theta), q[..., dr:]], axis=-1)
    w = jnp.dot(u, lp["wi_w"]).astype(jnp.float32) * (Hi ** -0.5 * di ** -0.5)
    return q, w


def layer(cfg: ModelConfig, geo: ModelConfig, lp: dict, x, cache, positions, valid, attend, moe_impl: str):
    """One layer over ``x`` [..., D] at geometry ``geo`` (``cfg`` or
    ``cfg.swa``). ``attend(lp, cache, u, c_q, q_n, q_r, latent) -> (o [..., H,
    dv], cache)`` writes the layer's rows and attends: the one thing prefill
    and decode, and the two kinds of layer, do differently. A layer with a
    router in ``lp`` is an expert layer; → (x, cache, its histogram or None)."""
    u = _rms(x, lp["attn_norm"], cfg.rms_norm_eps)
    c_q = mla_query_latent(u, lp, geo)
    q_n, q_r, latent = mla_project(u, lp, geo, positions, c_q)
    o, cache = attend(lp, cache, u, c_q, q_n, q_r, latent)
    with jax.named_scope("attn_gate"):
        g = jax.nn.sigmoid(jnp.dot(u, lp["w_og"]).astype(jnp.float32)).astype(o.dtype)
        o = o * g[..., None]
    with jax.named_scope("mla_out"):
        x = x + jnp.dot(o.reshape(*x.shape[:-1], geo.num_heads * geo.v_head_dim), lp["wo"])
    h = _rms(x, lp["mlp_norm"], cfg.rms_norm_eps)
    hist = None
    if "w_router" in lp:  # dyntpu: allow[DT003] reason=a key of the layer's parameter dict, known when traced: which kind of layer this is
        m, hist = moe(h, valid, lp, cfg, moe_impl)
        x = x + m
    with jax.named_scope("ffn_dense"):  # layer 0's feed-forward, or the shared expert
        return x + _mlp(h, lp), cache, hist


def _layers(cfg, params, x, cache, positions, valid, full_attend, window_attend, moe_impl):
    """Every layer: layer 0, then a scan over the periods. ``full_attend(ci)``
    and ``window_attend(wi)`` give the ``attend`` of the full layer whose rows
    are cache layer ``ci`` and of the window layer at ``wi`` of its pool."""
    P, n_win = periods(cfg)
    experts, swa = params["experts"], cfg.swa
    x, cache, _ = layer(cfg, cfg, params["first"], x, cache, positions, valid, full_attend(0), moe_impl)

    def period(carry, xs):
        x, cache = carry
        p, flp = xs
        first = p * (1 + n_win)  # this period's first layer among the expert layers
        x, cache, h0 = layer(cfg, cfg, {**flp, **experts, "moe_layer": first}, x, cache, positions, valid,
                             full_attend(1 + p), moe_impl)

        def window_layer(carry, j):
            # Out of the whole stack, where it lies: a period's slice handed to
            # this scan as its operand is a buffer that the outer body fills
            # anew every period (694 MB, twice a decode step at the cell's
            # widths: PERF.md section 6, PR 50).
            wlp = jax.tree.map(lambda a: a[p, j], params["swa"])
            x, cache, h = layer(cfg, swa, {**wlp, **experts, "moe_layer": first + 1 + j}, *carry, positions,
                                valid, window_attend(p * n_win + j), moe_impl)
            return (x, cache), h

        (x, cache), hw = lax.scan(window_layer, (x, cache), jnp.arange(n_win, dtype=jnp.int32))
        return (x, cache), jnp.concatenate([h0[None], hw])

    (x, cache), hist = lax.scan(period, (x, cache), (jnp.arange(P, dtype=jnp.int32), params["full"]))
    return x, cache, hist.reshape(P * (1 + n_win), -1)


def _no_lora(lora) -> None:
    if lora is not None:
        raise ValueError("LoRA banks cannot run a block='dots3' model")


# -- the programs ----------------------------------------------------------------


def _suffix_pages(tables, first_block, start_pos, true_len, nb: int, bs: int):
    """The pages a chunk's ``nb`` blocks go to, ``[Bp * nb]``: entries of
    ``tables`` from the chunk's first block on (``first_block``: the index in
    the sequence of the table's entry 0), the sink for a block past the row."""
    Bp = tables.shape[0]
    blocks = jnp.arange(nb, dtype=jnp.int32)[None, :]
    padded = jnp.concatenate([tables, jnp.zeros((Bp, nb), jnp.int32)], axis=1)
    ids = jnp.take_along_axis(padded, start_pos[:, None] // bs - first_block[:, None] + blocks, axis=1)
    return jnp.where(start_pos[:, None] + blocks * bs < true_len[:, None], ids, 0).reshape(Bp * nb)


def prefill_batch_impl(cfg, params, cache, tokens, block_tables, start_pos, true_len,
                       lora=None, adapter_slots=None, *, attn_impl: str = "auto",
                       experts: str | None = None, state_slots=None):
    """``model.prefill_batch_impl`` for this block: same arguments and contract
    (positions before the block-aligned ``start_pos`` are cached: the full
    layers' rows and index keys in the row's pages, the window layers' last
    ``sliding_window - 1`` of them in its window table; the suffix is computed
    here), and the routing histogram as a third result."""
    _no_lora(lora)
    Bp, T = tokens.shape
    bs, Wd, di = cache.block_size, cache.kv.shape[3], cache.ikeys.shape[3]
    swa, Ws = cfg.swa, cache.window.shape[3]
    nb = T // bs
    positions = start_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]   # [Bp, T]
    valid = positions < true_len[:, None]
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    impls = {resolve_prefill_impl(attn_impl, geo, bs, False)[0] for geo in (cfg, swa)}
    impl = "xla" if "xla" in impls else impls.pop()  # one form for both geometries
    if impl == "xla":
        attention = latent_prefill_attention_xla
    else:
        attention = functools.partial(latent_prefill_attention, interpret=(impl == "pallas_interpret"))
    flat_ids = _suffix_pages(block_tables, jnp.zeros((Bp,), jnp.int32), start_pos, true_len, nb, bs)
    # A window layer's positions count from its table's first block.
    w_first, w_tables = state_slots[:, 0], state_slots[:, 1:]
    w_flat_ids = _suffix_pages(w_tables, w_first, start_pos, true_len, nb, bs)
    w_start = start_pos - w_first * bs
    w_len = jnp.maximum(true_len - w_first * bs, 0)
    topk = cfg.index_topk
    every_row_dense = block_tables.shape[1] * bs <= topk  # static: the table cannot hold more
    qb = min(T, CHOICE_QUERIES)

    def by_query_blocks(attend_block, q_n, q_r, lp, geo, lanes):
        """``attend_block(i, q_lat, q_rope) -> o [Bp, H, qb, rank]`` over the
        chunk ``qb`` query positions at a time, absorbed and unabsorbed inside
        the block: the absorbed queries of a whole 2,048-token chunk are 268 MB
        at 128 heads, and so is what they attend. → [Bp, T, H, dv]."""
        def block(i):
            qn, qr = (lax.dynamic_slice_in_dim(a, i * qb, qb, 1) for a in (q_n, q_r))
            q_lat = jnp.einsum("bthn,hnc->bhtc", qn, lp["w_uk"])
            q_rope = _pad_row(jnp.moveaxis(qr, 2, 1), geo, lanes - geo.kv_lora_rank)
            return jnp.einsum("bhtc,hcv->bthv", attend_block(i, q_lat, q_rope), lp["w_uv"])

        if T == qb:
            return block(0)
        o = lax.map(block, jnp.arange(T // qb, dtype=jnp.int32))          # [T/qb, Bp, qb, H, dv]
        return jnp.moveaxis(o, 0, 1).reshape(Bp, T, *o.shape[3:])

    def full_attend(ci):
        scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5

        def attend(lp, cache, u, c_q, q_n, q_r, latent):
            with jax.named_scope("mla_kv_write"):
                kv = cache.kv.at[ci, flat_ids].set(_pad_row(latent, cfg).reshape(Bp * nb, bs, Wd))
                ik = cache.ikeys.at[ci, flat_ids].set(index_key(u, lp, cfg, positions).reshape(Bp * nb, bs, di))

            def dense(i, q_lat, q_rope):
                return attention(q_lat, q_rope, kv, ci, block_tables, start_pos + i * qb, true_len, scale=scale)

            def chosen():
                with jax.named_scope("dsa_index"):
                    q_idx, w = index_query(u, c_q, lp, cfg, positions)

                def block(i, q_lat, q_rope):  # CHOICE_QUERIES positions of every row: choose, then attend
                    start = start_pos + i * qb
                    with jax.named_scope("dsa_select"):
                        keep = dsa.prefill_keep(
                            lax.dynamic_slice_in_dim(q_idx, i * qb, qb, 1), lax.dynamic_slice_in_dim(w, i * qb, qb, 1),
                            ik, ci, block_tables, start, true_len, topk, kv.dtype)
                    with jax.named_scope("dsa_attend"):
                        return attention(q_lat, q_rope, kv, ci, block_tables, start, true_len, scale=scale, keep=keep)

                return by_query_blocks(block, q_n, q_r, lp, cfg, Wd)

            with jax.named_scope("mla_attn"):
                if every_row_dense:
                    o = by_query_blocks(dense, q_n, q_r, lp, cfg, Wd)
                else:
                    o = lax.cond(jnp.max(true_len) <= topk,
                                 lambda: by_query_blocks(dense, q_n, q_r, lp, cfg, Wd), chosen)
                return o, cache._replace(kv=kv, ikeys=ik)
        return attend

    def window_attend(wi):
        scale = (swa.qk_nope_head_dim + swa.qk_rope_head_dim) ** -0.5

        def attend(lp, cache, u, c_q, q_n, q_r, latent):
            with jax.named_scope("mla_kv_write"):
                win = cache.window.at[wi, w_flat_ids].set(_pad_row(latent, swa).reshape(Bp * nb, bs, Ws))

            def block(i, q_lat, q_rope):
                return attention(q_lat, q_rope, win, wi, w_tables, w_start + i * qb, w_len, scale=scale,
                                 window=cfg.sliding_window)

            with jax.named_scope("window_attn"):
                return by_query_blocks(block, q_n, q_r, lp, swa, Ws), cache._replace(window=win)
        return attend

    x, cache, hist = _layers(cfg, params, x, cache, positions, valid, full_attend, window_attend,
                             experts or expert_impl())
    last = jnp.clip(true_len - start_pos - 1, 0, T - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    with jax.named_scope("logits"):
        logits = _logits(cfg, params, x_last)
    return logits, cache, hist


def decode_step_impl(cfg, params, cache, tokens, positions, block_tables, active,
                     lora=None, adapter_slots=None, *, attn_impl: str = "auto",
                     experts: str | None = None, state_slots=None):
    """``model.decode_step_impl`` for this block: a position writes its rows
    (and its index key), chooses its tokens and attends them in the full
    layers, and attends its window in the window layers."""
    _no_lora(lora)
    impl = resolve_attn_impl(attn_impl)
    interpret = impl == "pallas_interpret"
    B = tokens.shape[0]
    bs, swa, topk = cache.block_size, cfg.swa, cfg.index_topk
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    rows = jnp.arange(B)
    blk = jnp.where(active, block_tables[rows, positions // bs], 0)
    off = jnp.where(active, positions % bs, 0)
    lengths = jnp.where(active, positions + 1, 0)
    w_first, w_tables = state_slots[:, 0], state_slots[:, 1:]
    w_at = jnp.clip(positions // bs - w_first, 0, w_tables.shape[1] - 1)
    w_blk = jnp.where(active, w_tables[rows, w_at], 0)
    w_lengths = jnp.where(active, positions + 1 - w_first * bs, 0)
    every_row_dense = block_tables.shape[1] * bs <= topk  # static

    def full_attend(ci):
        scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5

        def attend(lp, cache, u, c_q, q_n, q_r, latent):
            with jax.named_scope("mla_kv_write"):
                kv = cache.kv.at[ci, blk, off].set(_pad_row(latent, cfg))
                ik = cache.ikeys.at[ci, blk, off].set(index_key(u, lp, cfg, positions))
            q = absorb_query(q_n, q_r, lp, cfg)

            def dense():
                if impl == "xla":
                    return latent_decode_attention_xla(q, kv, ci, block_tables, lengths,
                                                       value_dim=cfg.kv_lora_rank, scale=scale)
                return latent_decode_attention(q, kv, ci, block_tables, lengths, value_dim=cfg.kv_lora_rank,
                                               scale=scale, interpret=interpret)

            def chosen(attend_set):
                """A branch that scores the rows' cached positions and hands
                the scores to ``attend_set``. The scan is in the branch: its
                scores reach the choice as the kernel lays them out (the same
                scores carried into a branch cost ``keep_topk`` 100 us more a
                layer on the v5e: PERF.md section 5, step 0 of PR 48)."""
                def branch():
                    with jax.named_scope("dsa_index"):
                        q_idx, w = index_query(u, c_q, lp, cfg, positions)
                        if impl == "xla":
                            return attend_set(dsa.index_scores_xla(q_idx, w, ik, ci, block_tables, lengths))
                        return attend_set(dsa.index_scores(q_idx, w, ik, ci, block_tables, lengths, interpret=interpret))
                return branch

            kw = dict(value_dim=cfg.kv_lora_rank, scale=scale)

            def walk(scores):  # the set as a mask over the walk of the rows' own pages
                with jax.named_scope("dsa_select"):
                    keep = dsa.keep_topk(scores, topk)
                with jax.named_scope("dsa_attend"):
                    if impl == "xla":
                        return latent_decode_attention_xla(q, kv, ci, block_tables, lengths, keep=keep, **kw)
                    return dsa.masked_decode_attention(q, kv, ci, block_tables, lengths, keep, interpret=interpret, **kw)

            def gather(scores):  # the set as positions, their rows gathered and attended alone
                with jax.named_scope("dsa_select"):
                    picked = dsa.select(scores, topk)
                with jax.named_scope("dsa_attend"):
                    counts = jnp.minimum(lengths, topk)
                    if impl == "xla":
                        return dsa.sparse_decode_attention_xla(q, kv, ci, block_tables, picked, counts, **kw)
                    return dsa.sparse_decode_attention(q, kv, ci, block_tables, picked, counts, interpret=interpret, **kw)

            with jax.named_scope("mla_attn"):
                if every_row_dense:
                    o = dense()
                else:  # no row past index_topk: every token; else the set, by the form the call's lengths make cheaper
                    walks = dsa.walk_is_cheaper(lengths, B, block_tables.shape[1] * bs, topk)
                    form = jnp.where(jnp.max(lengths) <= topk, 0, jnp.where(walks, 1, 2))
                    o = lax.switch(form, [dense, chosen(walk), chosen(gather)])
                return unabsorb_output(o, lp, cfg), cache._replace(kv=kv, ikeys=ik)
        return attend

    def window_attend(wi):
        scale = (swa.qk_nope_head_dim + swa.qk_rope_head_dim) ** -0.5

        def attend(lp, cache, u, c_q, q_n, q_r, latent):
            with jax.named_scope("mla_kv_write"):
                win = cache.window.at[wi, w_blk, off].set(_pad_row(latent, swa))
            with jax.named_scope("window_attn"):
                q = absorb_query(q_n, q_r, lp, swa)
                kw = dict(value_dim=swa.kv_lora_rank, scale=scale, window=cfg.sliding_window)
                if impl == "xla":
                    o = latent_decode_attention_xla(q, win, wi, w_tables, w_lengths, **kw)
                else:
                    o = latent_decode_attention(q, win, wi, w_tables, w_lengths, interpret=interpret, **kw)
                return unabsorb_output(o, lp, swa), cache._replace(window=win)
        return attend

    x, cache, hist = _layers(cfg, params, x, cache, positions, active, full_attend, window_attend,
                             experts or expert_impl())
    with jax.named_scope("logits"):
        logits = _logits(cfg, params, x)
    return logits, cache, hist


def multi_decode_impl(cfg, num_steps, mode, top_n, params, cache, tokens, positions,
                      block_tables, active, temperature, seeds, steps0, top_k, top_p,
                      freq_penalty, pres_penalty, penalty_tokens, chain_mask=None,
                      chain_src=None, last_toks=None, lora=None, adapter_slots=None,
                      *, attn_impl: str = "auto", experts: str | None = None, state_slots=None):
    """``model.multi_decode_impl`` for this block: the same fused window
    (``model.decode_window``) over this block's step, the window's routing
    histogram summed over its substeps after the cache. The rows' window
    tables hold the blocks of every substep's window."""
    def step(cache, tok, pos):
        return decode_step_impl(cfg, params, cache, tok, pos, block_tables, active, lora, adapter_slots,
                                attn_impl=attn_impl, experts=experts, state_slots=state_slots)

    hist0 = jnp.zeros((len(routed_layers(cfg)), cfg.num_experts + HIST_EXTRA), jnp.int32)
    return decode_window(
        step, hist0, cfg.vocab_size, num_steps, mode, top_n, cache, tokens, positions,
        temperature, seeds, steps0, top_k, top_p, freq_penalty, pres_penalty,
        penalty_tokens, chain_mask, chain_src, last_toks,
    )


# The jitted programs, under engine/model.py's names and with its donation.
_STATIC = ("attn_impl", "experts")
prefill_batch = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=_STATIC, donate_argnums=(2,))(prefill_batch_impl)


def prefill(cfg, params, cache, tokens, block_table, start_pos, true_len, lora=None, adapter_slot=None, **kw):
    """Single-sequence prefill, through ``prefill_batch``'s own one-row
    program: a chunk and a lone packed row of one shape are one compiled
    program, not two of 15-20 s each (nine buckets of them, a worker's start)."""
    logits, cache, hist = prefill_batch(
        cfg, params, cache, jnp.asarray(tokens)[None, :], jnp.asarray(block_table)[None, :],
        jnp.asarray(start_pos, jnp.int32).reshape(1), jnp.asarray(true_len, jnp.int32).reshape(1), lora, None,
        **{**kw, "state_slots": jnp.asarray(kw["state_slots"]).reshape(1, -1)})
    return logits[0], cache, hist


decode_step = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=_STATIC, donate_argnums=(2,))(decode_step_impl)
multi_decode = functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3), static_argnames=_STATIC, donate_argnums=(5,)
)(multi_decode_impl)
