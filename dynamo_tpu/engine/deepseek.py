"""The DeepSeek-V2 block (``ModelConfig.block == "deepseek"``): one layer
definition, used by the packed prefill, the single prefill and the decode
window, **each program one ``shard_map`` over the mesh's tp axes** where
there is a mesh. ``model.block_module(cfg)`` is this module for such a model:
``init_params``, ``init_kv_cache`` and the jitted ``prefill``,
``prefill_batch``, ``decode_step`` and ``multi_decode`` under engine/model.py's
names, each taking one keyword more, the ``mesh`` (None: one device, the same
code with no collective), and returning the expert layers' routing histogram
after what the dense block's returns, as engine/longcat.py's do.

The stream: ``x0 = embed(token)``; a layer is ``h = x + Attn(rms(x))``,
``out = h + FF(rms(h))``; logits ``= W_head rms(x_L)``.

- **Attn**: MLA as engine/longcat.py has it (low-rank query, one latent row a
  token, ``W_kvb`` absorbed both ways, both latent kernels), with static YaRN
  on the rope lanes (``longcat.yarn_inv_freq``) and the softmax scale
  ``(dn + dr)^-1/2 m(mscale_all_dim)^2`` (``softmax_scale``).
- **FF**: layer 0 a dense SwiGLU; every later layer ``longcat.moe`` under the
  group-limited router (``longcat.route``), all ``num_experts`` routed experts
  held, weights ``routed_scaling_factor * p`` not renormalised, beside the
  shared experts as one SwiGLU of their summed width.

**Across chips** (``param_specs``): the residual stream, the latents, the
page tables and the row operands are the same on every chip; ``w_qn``,
``w_qr``, ``w_uk``, ``w_uv`` and ``wo`` are split by heads, layer 0's
feed-forward and the shared experts by width, the routed experts in order (a
chip holds ``num_experts / tp`` consecutive ones: whole routing groups where
tp divides ``n_group``), embedding and head by vocabulary rows. Each chip
routes every token over all the experts and computes what its own give
(``longcat._moe_tokens`` with ``lax.axis_index`` times its share as the first
expert held); **one ``psum`` after the output projection and one after the
feed-forward** make the stream whole again, the embedding's rows are summed
the same way, and the logits are gathered once before sampling, which every
chip then does alike. The latent pool is replicated: one latent a token is
shared by every head, so each chip's heads read all of it. Inside the
``shard_map`` every kernel is per-device code, so the Pallas paths stay
(``SHARD_MAPPED``: engine/runner.py keeps ``decode=pallas`` under a mesh).

**Compile cost does not grow with depth.** Layer 0 alone, then one
``lax.scan`` over the expert layers, whose routed experts are closed over as
one ``[layers x held, ..]`` stack each (``longcat.stacked_experts`` says why).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.dots3 import _draw, _suffix_pages
from dynamo_tpu.engine.longcat import (
    HIST_EXTRA, ROUTER_LOGIT_STD, _mlp, _pad_row, _rms, absorb_query, expert_impl, mla_project, moe, route,
    unabsorb_output, yarn_mscale,
)
from dynamo_tpu.engine.model import KVCache, decode_window, pool_zeros
from dynamo_tpu.ops.paged_attention import (
    latent_decode_attention,
    latent_decode_attention_xla,
    latent_prefill_attention,
    latent_prefill_attention_xla,
    resolve_attn_impl,
    resolve_prefill_impl,
)
from dynamo_tpu.parallel.mesh import TP_AXES

Params = dict[str, Any]

START_LINE = " block=deepseek"  # what the engine's start line says of this block
UNCARRIED = ("latent pages", "latent (MLA) pages")
# The programs below are ``shard_map``ped over a mesh's tp axes: their kernels
# are per-device code and stay under a mesh (engine/runner.py), and parameters
# and cache are placed by ``param_specs`` and ``CACHE_SPEC`` (parallel/mesh.py).
SHARD_MAPPED = True
CACHE_SPEC = P()  # the latent pool, on every chip
_EXPERT_KEYS = ("moe_gate", "moe_up", "moe_down")
# After longcat's histogram columns of a chip: the groups its tokens' choices span.
_CHIP_HIST = HIST_EXTRA + 1


def routed_layers(cfg: ModelConfig) -> tuple[int, ...]:
    """The layers that route, in the order of the histogram's rows."""
    return tuple(range(cfg.num_dense_layers, cfg.num_layers))


def hist_extra(tp: int) -> int:
    """Columns of the histogram after the experts': longcat's five (zero-compute,
    absent, tokens routed, experts touched over the chips, calls), then the
    experts each chip touched (``tp`` columns), then the groups the tokens'
    choices span."""
    return HIST_EXTRA + tp + 1


def softmax_scale(cfg: ModelConfig) -> float:
    """``(dn + dr)^-1/2`` times YaRN's magnitude correction squared."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2


# -- placement ---------------------------------------------------------------------


def param_specs(cfg: ModelConfig) -> dict:
    """The parameters' ``PartitionSpec``s over ``TP_AXES``, in ``init_params``'s tree."""
    t = TP_AXES
    attn = {"w_qa": P(), "q_norm": P(), "w_qn": P(None, t), "w_qr": P(None, t), "w_kva": P(), "kv_norm": P(),
            "w_uk": P(t), "w_uv": P(t), "wo": P(t, None), "attn_norm": P(), "mlp_norm": P()}
    ff = {"w_gate": P(None, t), "w_up": P(None, t), "w_down": P(t, None)}
    return {"embed": P(t, None), "lm_head": P(None, t), "final_norm": P(), "first": {**attn, **ff},
            "layers": {k: P(None, *v) for k, v in {**attn, **ff, "w_router": P()}.items()},
            "experts": {k: P(None, t) for k in _EXPERT_KEYS}}


def _tp(mesh) -> int:
    return 1 if mesh is None else mesh.shape[TP_AXES[0]] * mesh.shape[TP_AXES[1]]


def _local_cfg(cfg: ModelConfig, tp: int) -> ModelConfig:
    """What one chip of ``tp`` runs: its heads and its experts, the router at
    its whole width."""
    return dataclasses.replace(cfg, num_heads=cfg.num_heads // tp, num_experts=cfg.num_experts // tp,
                               num_routed_experts=cfg.num_experts)


def _on_mesh(local, mesh, cfg: ModelConfig, params, *operands):
    """``local(params, *operands) -> (results the same on every chip, this
    chip's histogram [1, ..])`` over the mesh's tp axes, or as it is on one
    device. Parameters arrive as ``param_specs`` cuts them, operands whole."""
    if mesh is None:
        return local(params, *operands)
    return jax.shard_map(local, mesh=mesh, in_specs=(param_specs(cfg), *(P(),) * len(operands)),
                         out_specs=(P(), P(TP_AXES)), check_vma=False)(params, *operands)


def _psum(x, mesh):
    return x if mesh is None else lax.psum(x, TP_AXES)


# -- the seeded initialiser (chipbench/references/deepseek_v2.py keeps a copy) ------


def _draw_experts(key, first, count: int, shape: tuple[int, ...], std: float, dtype, layers: int):
    """Experts ``[first, first + count)`` of every expert layer, ``[layers,
    count, *shape]``: expert ``e`` of layer ``l`` under ``fold_in(fold_in(key,
    l), e)``, one at a time, so a chip draws its own and no float32 copy of
    more than one expert exists."""
    def one(l, e):
        k = jax.random.fold_in(jax.random.fold_in(key, l), e)
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    ids = first + jnp.arange(count, dtype=jnp.int32)
    return lax.map(lambda l: lax.map(lambda e: one(l, e), ids), jnp.arange(layers, dtype=jnp.int32))


def _layer_params(cfg: ModelConfig, key, dtype, dense: bool) -> dict:
    """One layer's tensors but its routed experts, a jitted draw each."""
    D, H, rq, rkv = cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    I = cfg.intermediate_size if dense else cfg.num_shared_experts * (cfg.moe_intermediate_size or cfg.intermediate_size)
    shapes = {"w_qa": ((D, rq), D), "w_qn": ((rq, H * dn), rq), "w_qr": ((rq, H * dr), rq),
              "w_kva": ((D, rkv + dr), D), "w_uk": ((H, dn, rkv), rkv), "w_uv": ((H, rkv, dv), rkv),
              "wo": ((H * dv, D), H * dv),
              "w_gate": ((D, I), D), "w_up": ((D, I), D), "w_down": ((I, D), I)}
    if not dense:
        shapes["w_router"] = ((D, cfg.num_experts), D)
    out = {}
    for n, (name, (shape, fan_in)) in enumerate(shapes.items()):
        std = fan_in ** -0.5 * (ROUTER_LOGIT_STD if name == "w_router" else 1.0)
        out[name] = _draw(jax.random.fold_in(key, n), shape, std, dtype)
    out["attn_norm"] = jnp.ones((D,), dtype)
    out["mlp_norm"] = jnp.ones((D,), dtype)
    out["q_norm"] = jnp.ones((rq,), dtype)
    out["kv_norm"] = jnp.ones((rkv,), dtype)
    return out


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16, mesh=None) -> Params:
    """Random-init params. ``first``: layer 0; ``layers`` ``[L - 1, ..]``: the
    expert layers but their routed experts; ``experts``: those, ``[L - 1, E,
    ..]`` each. Every value depends on the key and its place alone, not on the
    mesh: a tensor is drawn whole (in pieces of its leading axis) and an expert
    under a key of its own, which under a ``mesh`` each chip draws for the
    experts it holds (``ModelSharding.born_sharded`` runs this under ``jit``
    with ``param_specs`` as the results' shardings)."""
    D, E = cfg.hidden_size, cfg.num_experts
    ie = cfg.moe_intermediate_size or cfg.intermediate_size
    n_moe, tp = cfg.num_layers - cfg.num_dense_layers, _tp(mesh)
    layer_key = functools.partial(jax.random.fold_in, jax.random.fold_in(key, 100))
    experts = {}
    for n, (name, shape, fan_in) in enumerate((("moe_gate", (D, ie), D), ("moe_up", (D, ie), D), ("moe_down", (ie, D), ie))):
        k = jax.random.fold_in(key, 200 + n)

        def held(k=k, shape=shape, fan_in=fan_in):
            first = 0 if mesh is None else lax.axis_index(TP_AXES) * (E // tp)
            return _draw_experts(k, first, E // tp, shape, fan_in ** -0.5, dtype, n_moe)

        experts[name] = held() if mesh is None else jax.shard_map(
            held, mesh=mesh, in_specs=(), out_specs=P(None, TP_AXES), check_vma=False)()
    return {
        "embed": _draw(jax.random.fold_in(key, 1), (cfg.vocab_size, D), D ** -0.5, dtype),
        "lm_head": _draw(jax.random.fold_in(key, 2), (D, cfg.vocab_size), D ** -0.5, dtype),
        "final_norm": jnp.ones((D,), dtype),
        "first": _layer_params(cfg, layer_key(0), dtype, dense=True),
        "layers": jax.tree.map(lambda *a: jnp.stack(a), *[
            _layer_params(cfg, layer_key(l), dtype, dense=False) for l in routed_layers(cfg)]),
        "experts": experts,
    }


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
                  sharding=None, kv_quant: str = "none") -> KVCache:
    """One pool of a cache layer a layer of latent rows, no V (``KVCache``)."""
    if kv_quant != "none":
        raise ValueError("a latent (MLA) cache has no int8 form (kv_quant)")
    return KVCache(pool_zeros(sharding)((cfg.cache_layers, num_blocks, block_size, cfg.latent_page_width), dtype))


# -- the layer -------------------------------------------------------------------


def _token_groups(h, valid, lp: dict, cfg: ModelConfig):
    """The routing groups the valid tokens' choices span, summed over them
    (never over ``topk_group`` a token)."""
    topi, _ = route(h.reshape(-1, h.shape[-1]), lp, cfg)
    group = topi // (cfg.num_routed_experts // cfg.n_group)
    spanned = jnp.any(group[:, :, None] == jnp.arange(cfg.n_group)[None, None, :], axis=1)
    return jnp.sum(jnp.where(valid.reshape(-1, 1), spanned, False), dtype=jnp.int32)


def layer(cfg: ModelConfig, lp: dict, x, pool, positions, valid, attend, moe_impl: str, mesh):
    """One layer over ``x`` [..., D], at one chip's ``cfg`` (``_local_cfg``).
    ``attend(lp, pool, q_n, q_r, latent) -> (o [..., H, dv], pool)`` writes the
    layer's rows and attends: the one thing prefill and decode do differently.
    A layer with a router in ``lp`` is an expert layer; → (x, pool, this chip's
    histogram or None)."""
    u = _rms(x, lp["attn_norm"], cfg.rms_norm_eps)
    q_n, q_r, latent = mla_project(u, lp, cfg, positions)
    o, pool = attend(lp, pool, q_n, q_r, latent)
    with jax.named_scope("mla_out"):
        a = jnp.dot(o.reshape(*x.shape[:-1], cfg.num_heads * cfg.v_head_dim), lp["wo"])
    with jax.named_scope("tp_combine_attn"):
        x = x + _psum(a, mesh)
    h = _rms(x, lp["mlp_norm"], cfg.rms_norm_eps)
    hist = None
    if "w_router" in lp:  # dyntpu: allow[DT003] reason=a key of the layer's parameter dict, known when traced: which kind of layer this is
        m, hist = moe(h, valid, lp, cfg, moe_impl)
        with jax.named_scope("moe_route"):
            hist = jnp.concatenate([hist, _token_groups(h, valid, lp, cfg)[None]])
        with jax.named_scope("moe_shared"):
            y = m + _mlp(h, lp)
    else:
        with jax.named_scope("ffn_dense"):
            y = _mlp(h, lp)
    with jax.named_scope("tp_combine_ffn"):
        return x + _psum(y, mesh), pool, hist


def _layers(cfg, params, x, pool, positions, valid, attend_for, moe_impl, mesh):
    """Every layer at one chip's ``cfg``: layer 0, then a scan over the expert
    layers. ``attend_for(ci)`` gives the ``attend`` of cache layer ``ci``."""
    experts = {k: v.reshape(-1, *v.shape[2:]) for k, v in params["experts"].items()}
    if mesh is not None:
        experts["expert_offset"] = lax.axis_index(TP_AXES) * cfg.num_experts
    x, pool, _ = layer(cfg, params["first"], x, pool, positions, valid, attend_for(0), moe_impl, mesh)

    def body(carry, xs):
        lp, i = xs
        x, pool, hist = layer(cfg, {**lp, **experts, "moe_layer": i}, *carry, positions, valid,
                              attend_for(1 + i), moe_impl, mesh)
        return (x, pool), hist

    n = jax.tree.leaves(params["layers"])[0].shape[0]
    (x, pool), hist = lax.scan(body, (x, pool), (params["layers"], jnp.arange(n, dtype=jnp.int32)))
    return x, pool, hist  # hist [expert layers, held + _CHIP_HIST]


def _embed(cfg: ModelConfig, params, tokens, mesh):
    """The tokens' rows of an embedding split by vocabulary rows: each chip
    gives the rows it holds and zeros for the rest, summed over the chips."""
    with jax.named_scope("embed"):
        if mesh is None:
            return params["embed"][tokens]
        rows = params["embed"].shape[0]
        at = tokens - lax.axis_index(TP_AXES) * rows
        mine = (at >= 0) & (at < rows)
        return _psum(jnp.where(mine[..., None], params["embed"][jnp.clip(at, 0, rows - 1)], 0), mesh)


def _logits(cfg: ModelConfig, params, x, mesh):
    """→ float32 [.., V]: each chip's vocabulary rows, gathered in the
    product's own dtype (what ``model._logits`` rounds to before its cast)."""
    with jax.named_scope("logits"):
        y = jnp.dot(_rms(x, params["final_norm"], cfg.rms_norm_eps), params["lm_head"])
    if mesh is not None:
        with jax.named_scope("logits_gather"):
            y = lax.all_gather(y, TP_AXES, axis=y.ndim - 1, tiled=True)
    return y.astype(jnp.float32)


def _global_hist(cfg: ModelConfig, chips: jax.Array) -> jax.Array:
    """The chips' histograms ``[tp, layers, held + _CHIP_HIST]`` as one over
    every expert, ``[layers, E + hist_extra(tp)]``."""
    tp, n, _ = chips.shape
    held = cfg.num_experts // tp
    sizes = jnp.moveaxis(chips[:, :, :held], 0, 1).reshape(n, cfg.num_experts)
    zero, routed, calls, groups = (chips[0, :, held + i] for i in (0, 2, 4, 5))
    touched = chips[:, :, held + 3].T                                      # [layers, tp]
    absent = routed * cfg.num_experts_per_token - jnp.sum(sizes, axis=-1) - zero
    return jnp.concatenate([sizes, jnp.stack([zero, absent, routed, jnp.sum(touched, axis=-1), calls], axis=-1),
                            touched, groups[:, None]], axis=-1)


def _no_lora(lora) -> None:
    if lora is not None:
        raise ValueError("LoRA banks cannot run a block='deepseek' model")


# -- the programs ----------------------------------------------------------------


def _prefill_local(cfg, mesh, attn_impl, moe_impl, params, pool, tokens, block_tables, start_pos, true_len):
    """One chip's prefill, ``model.prefill_batch_impl``'s contract."""
    lcfg = _local_cfg(cfg, _tp(mesh))
    Bp, T = tokens.shape
    bs, Wd = pool.shape[2], pool.shape[3]
    positions = start_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]   # [Bp, T]
    valid = positions < true_len[:, None]
    x = _embed(cfg, params, tokens, mesh)
    impl, _ = resolve_prefill_impl(attn_impl, lcfg, bs, False)
    scale = softmax_scale(cfg)
    if impl == "xla":
        attention = functools.partial(latent_prefill_attention_xla, scale=scale)
    else:
        attention = functools.partial(latent_prefill_attention, scale=scale, interpret=(impl == "pallas_interpret"))

    nb = T // bs
    flat_ids = _suffix_pages(block_tables, jnp.zeros((Bp,), jnp.int32), start_pos, true_len, nb, bs)

    def attend_for(ci):
        def attend(lp, pool, q_n, q_r, latent):
            with jax.named_scope("mla_kv_write"):
                pool = pool.at[ci, flat_ids].set(_pad_row(latent, cfg).reshape(Bp * nb, bs, Wd))
            with jax.named_scope("mla_attn"):  # out of the pages, prefix and chunk alike, head-major (engine/longcat.py)
                q_lat = jnp.einsum("bthn,hnc->bhtc", q_n, lp["w_uk"])
                q_rope = _pad_row(jnp.moveaxis(q_r, 2, 1), cfg, Wd - cfg.kv_lora_rank)
                o = attention(q_lat, q_rope, pool, ci, block_tables, start_pos, true_len)
                return jnp.einsum("bhtc,hcv->bthv", o, lp["w_uv"]), pool
        return attend

    x, pool, hist = _layers(lcfg, params, x, pool, positions, valid, attend_for, moe_impl, mesh)
    last = jnp.clip(true_len - start_pos - 1, 0, T - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return (_logits(cfg, params, x_last, mesh), pool), hist[None]


def prefill_batch_impl(cfg, params, cache, tokens, block_tables, start_pos, true_len,
                       lora=None, adapter_slots=None, *, attn_impl: str = "auto",
                       experts: str | None = None, mesh=None):
    """``model.prefill_batch_impl`` for this block: same arguments and contract
    (prefix pages cached in whole blocks, suffix computed here), and a third
    result, the expert layers' routing histogram ``[layers, E + hist_extra]``."""
    _no_lora(lora)
    local = functools.partial(_prefill_local, cfg, mesh, attn_impl, experts or expert_impl())
    (logits, pool), hist = _on_mesh(local, mesh, cfg, params, cache.kv, tokens, block_tables, start_pos, true_len)
    return logits, KVCache(pool), _global_hist(cfg, hist)


def _decode_local(cfg, mesh, attn_impl, moe_impl, params, pool, tokens, positions, block_tables, active):
    """One chip's decode step → (logits [B, V], pool, this chip's histogram)."""
    lcfg = _local_cfg(cfg, _tp(mesh))
    impl = resolve_attn_impl(attn_impl)
    B = tokens.shape[0]
    bs = pool.shape[2]
    x = _embed(cfg, params, tokens, mesh)
    blk = jnp.where(active, block_tables[jnp.arange(B), positions // bs], 0)
    off = jnp.where(active, positions % bs, 0)
    lengths = jnp.where(active, positions + 1, 0)
    kw = dict(value_dim=cfg.kv_lora_rank, scale=softmax_scale(cfg))

    def attend_for(ci):
        def attend(lp, pool, q_n, q_r, latent):
            with jax.named_scope("mla_kv_write"):
                pool = pool.at[ci, blk, off].set(_pad_row(latent, cfg))
            with jax.named_scope("mla_attn"):
                q = absorb_query(q_n, q_r, lp, cfg)
                if impl == "xla":
                    o = latent_decode_attention_xla(q, pool, ci, block_tables, lengths, **kw)
                else:
                    o = latent_decode_attention(q, pool, ci, block_tables, lengths,
                                                interpret=(impl == "pallas_interpret"), **kw)
                return unabsorb_output(o, lp, cfg), pool
        return attend

    x, pool, hist = _layers(lcfg, params, x, pool, positions, active, attend_for, moe_impl, mesh)
    return _logits(cfg, params, x, mesh), pool, hist


def decode_step_impl(cfg, params, cache, tokens, positions, block_tables, active,
                     lora=None, adapter_slots=None, *, attn_impl: str = "auto",
                     experts: str | None = None, mesh=None):
    """``model.decode_step_impl`` for this block (absorbed attention over the
    latent pages), with the routing histogram as a third result."""
    _no_lora(lora)

    def local(*a):
        logits, pool, hist = _decode_local(cfg, mesh, attn_impl, experts or expert_impl(), *a)
        return (logits, pool), hist[None]

    (logits, pool), hist = _on_mesh(local, mesh, cfg, params, cache.kv, tokens, positions, block_tables, active)
    return logits, KVCache(pool), _global_hist(cfg, hist)


def multi_decode_impl(cfg, num_steps, mode, top_n, params, cache, tokens, positions,
                      block_tables, active, temperature, seeds, steps0, top_k, top_p,
                      freq_penalty, pres_penalty, penalty_tokens, chain_mask=None,
                      chain_src=None, last_toks=None, lora=None, adapter_slots=None,
                      *, attn_impl: str = "auto", experts: str | None = None, mesh=None):
    """``model.multi_decode_impl`` for this block: the same fused window
    (``model.decode_window``) over this block's step, the whole window inside
    the one ``shard_map`` (every chip samples the gathered logits alike), and
    the window's routing histogram, summed over the substeps, after the cache."""
    _no_lora(lora)
    moe_impl = experts or expert_impl()
    held = cfg.num_experts // _tp(mesh)

    def local(params, pool, tokens, positions, block_tables, active, sampling, chain):
        def step(cache, tok, pos):
            logits, pool, hist = _decode_local(cfg, mesh, attn_impl, moe_impl, params, cache.kv, tok, pos,
                                               block_tables, active)
            return logits, KVCache(pool), hist

        hist0 = jnp.zeros((len(routed_layers(cfg)), held + _CHIP_HIST), jnp.int32)
        *out, cache, hist = decode_window(step, hist0, cfg.vocab_size, num_steps, mode, top_n, KVCache(pool),
                                          tokens, positions, *sampling, *chain)
        return (*out, cache.kv), hist[None]

    sampling = (temperature, seeds, steps0, top_k, top_p, freq_penalty, pres_penalty, penalty_tokens)
    (*out, pool), hist = _on_mesh(local, mesh, cfg, params, cache.kv, tokens, positions, block_tables, active,
                                  sampling, (chain_mask, chain_src, last_toks))
    return (*out, KVCache(pool), _global_hist(cfg, hist))


def prefill_impl(cfg, params, cache, tokens, block_table, start_pos, true_len,
                 lora=None, adapter_slot=None, **kw):
    """Single-sequence prefill: the Bp=1 case of ``prefill_batch_impl``."""
    logits, cache, hist = prefill_batch_impl(
        cfg, params, cache, tokens[None, :], block_table[None, :],
        jnp.asarray(start_pos, jnp.int32).reshape(1), jnp.asarray(true_len, jnp.int32).reshape(1), lora, **kw)
    return logits[0], cache, hist


# The jitted programs, under engine/model.py's names and with its donation.
_STATIC = ("attn_impl", "experts", "mesh")
prefill = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=_STATIC, donate_argnums=(2,))(prefill_impl)
prefill_batch = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=_STATIC, donate_argnums=(2,))(prefill_batch_impl)
decode_step = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=_STATIC, donate_argnums=(2,))(decode_step_impl)
multi_decode = functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3), static_argnames=_STATIC, donate_argnums=(5,)
)(multi_decode_impl)
