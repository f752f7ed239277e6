"""The LongCat-Flash block (ISSUE 30) on the CPU at a toy size with seeded
weights: the program against the benchmark's plain reference, absorbed against
expanded attention, the router's arithmetic, the shares of an expert-parallel
layer adding up, latent pages under the prefix cache, and what refuses the block."""

import asyncio
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model_maps, references
from dynamo_tpu.engine import longcat
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.ops import paged_attention
from dynamo_tpu.runtime.engine import Context

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "chipbench", "configs", "rehearse-longcat-tiny.json")) as f:
    DOC = json.load(f)
CFG = model_maps.model_config(DOC)
REF = references.load("longcat_scmoe")
BS = 8


def doc_for(dtype: str, **over) -> dict:
    return {**DOC, **over, "served": {**DOC["served"], "dtype": dtype}}


def prompt(n: int, seed: int = 0) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(0, CFG.vocab_size, n)]


def serve_through_cache(params, dtype, toks, plen: int, mode: str, impl: str) -> np.ndarray:
    # "pallas_interpret" takes every kernel in interpret mode: the latent
    # prefill and decode attention and the megablox grouped product
    """Prefill ``toks[:plen]`` (cold, in two chunks, or its second half behind
    pages an earlier prefill cached) and decode the rest teacher-forced through
    the paged cache → float32 logits at positions plen-1 .. len(toks)-1."""
    cache = longcat.init_kv_cache(CFG, 32, BS, dtype)
    table = jnp.arange(1, 9, dtype=jnp.int32)
    kw = {"experts": "gmm_interpret"} if impl == "pallas_interpret" else {}
    pkw = {**kw, "attn_impl": impl}
    pad = lambda xs, n: jnp.zeros((n,), jnp.int32).at[:len(xs)].set(jnp.asarray(xs, jnp.int32))  # noqa: E731
    if mode == "cold":
        logits, cache, _ = longcat.prefill(CFG, params, cache, pad(toks[:plen], 48), table, 0, plen, **pkw)
    else:
        cut = 16  # whole blocks
        _, cache, _ = longcat.prefill(CFG, params, cache, pad(toks[:cut], 16), table, 0, cut, **pkw)
        if mode == "cached":  # another dispatch wrote the pages; only the table names them
            cache = jax.tree.map(jnp.copy, cache)
        logits, cache, _ = longcat.prefill(CFG, params, cache, pad(toks[cut:plen], 32), table, cut, plen, **pkw)
    out = [logits]
    for pos in range(plen, len(toks)):
        step, cache, _ = longcat.decode_step(
            CFG, params, cache, jnp.asarray([toks[pos], 0], jnp.int32), jnp.asarray([pos, 0], jnp.int32),
            jnp.stack([table, table]), jnp.asarray([True, False]), attn_impl=impl, **kw)
        out.append(step[0])
    return np.asarray(jnp.stack(out), np.float32)


# Tolerances, from these sizes on the CPU (seeds 0-2 read). float32 against the
# float32 reference differs by summation order alone: the widest gap must stay
# under 2e-4 (1e-5 read). bf16 weights are the same numbers on both sides, so
# bf16 reads what rounding activations and cached latents to 8 bits of mantissa
# costs: 0.011-0.019 in the mean over the logits; its widest gap (0.06-0.27)
# swings with a near-tie in the router's top-k and is not held. The same
# program on weights rounded to float8_e4m3 (3 bits) reads 0.127-0.142 in the
# mean: the limit 0.045 tells bf16 from the precision under it.
TOL = {"float32": ("max", 2e-4), "bfloat16": ("mean", 0.045)}


def gap_of(got: np.ndarray, want: np.ndarray, dtype: str) -> float:
    diff = np.abs(got - want[:len(got)])
    return float(diff.max() if TOL[dtype][0] == "max" else diff.mean())


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("mode", ["cold", "chunked", "cached"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_agrees_with_the_reference_forward(dtype, mode, impl):
    doc = doc_for(dtype)
    params = REF.weights(doc, 0)
    toks, plen = prompt(46), 40
    want = np.asarray(REF.forward(doc, params, toks), np.float32)[plen - 1:len(toks)]
    got = serve_through_cache(params, jnp.dtype(dtype), toks, plen, mode, impl)
    assert got.shape == want.shape
    assert gap_of(got, want, dtype) < TOL[dtype][1]


def test_a_precision_under_bf16_fails_the_bf16_tolerance():
    doc = doc_for("bfloat16")
    params = REF.weights(doc, 0)
    toks, plen = prompt(46), 40
    want = np.asarray(REF.forward(doc, params, toks), np.float32)[plen - 1:len(toks)]
    low = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype) if a.dtype == jnp.bfloat16 else a, params)
    got = serve_through_cache(low, jnp.bfloat16, toks, plen, "cold", "xla")
    assert gap_of(got, want, "bfloat16") > 2 * TOL["bfloat16"][1]


def test_the_programs_initialiser_is_the_references():
    mine = longcat.init_params(CFG, jax.random.PRNGKey(3), jnp.bfloat16)
    theirs = REF.weights(doc_for("bfloat16"), 3)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert all(jax.tree.leaves(jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), mine, theirs)))
    bias = mine["layers"]["router_bias"]
    assert float(jnp.std(bias)) > 0  # drawn, not zeros


def _sub(params, layer: int, j: int) -> dict:
    return {name: params["layers"][f"{name}_{j}"][layer] for name in longcat._SUB_KEYS}


def attend_expanded(q_n, q_r, ctx_latent, mask, sub: dict, cfg: ModelConfig):
    """The published form, the reference the absorbed paths are held to (until
    PR 44 the program's own prefill attention): q_n [B, T, H, dn], q_r
    [B, T, H, dr] against the latents of the whole context ``ctx_latent``
    [B, C, latent_dim] under the additive float32 ``mask`` [B, T, C] →
    [B, T, H, dv], the latents multiplied out by W_kvb for every head."""
    rkv = cfg.kv_lora_rank
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    c_kv, k_r = ctx_latent[..., :rkv], ctx_latent[..., rkv:]
    k_n = jnp.einsum("bcl,hnl->bchn", c_kv, sub["w_uk"])
    s = jnp.einsum("bthn,bchn->bhtc", q_n, k_n, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bthr,bcr->bhtc", q_r, k_r, preferred_element_type=jnp.float32)
    p = jax.nn.softmax(s * scale + mask[:, None], axis=-1).astype(q_n.dtype)
    return jnp.einsum("bhtc,bchv->bthv", p, jnp.einsum("bcl,hlv->bchv", c_kv, sub["w_uv"]))


def _paged(latents, lengths, table_width: int):
    """Rows of latents [B, n, latent_dim] into a pool's cache layer 1, row b's
    first ``lengths[b]`` positions on pages of its own (from page 1) → (pool, tables)."""
    B = latents.shape[0]
    pool = jnp.zeros((2, 1 + B * table_width, BS, CFG.latent_page_width), latents.dtype)
    tables = 1 + jnp.arange(B * table_width, dtype=jnp.int32).reshape(B, table_width)
    for b, n in enumerate(lengths):
        at = jnp.arange(n)
        pool = pool.at[1, tables[b, at // BS], at % BS].set(longcat._pad_row(latents[b, :n], CFG))
    return pool, tables


_ATTN_KW = dict(value_dim=CFG.kv_lora_rank, scale=(CFG.qk_nope_head_dim + CFG.qk_rope_head_dim) ** -0.5)


@pytest.mark.parametrize("kernel", ["xla", "pallas_interpret"])
def test_absorbed_attention_equals_expanded(kernel):
    """One query over 37 cached latents: W_kvb multiplied into the keys and
    values (expanded, as published) against W_uk absorbed into the query and
    W_uv applied to the attended latents, over latent pages."""
    params = longcat.init_params(CFG, jax.random.PRNGKey(1), jnp.float32)
    sub = _sub(params, 0, 1)
    n = 37
    h = jax.random.normal(jax.random.PRNGKey(2), (1, n, CFG.hidden_size), jnp.float32)
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    q_n, q_r, latent = longcat.mla_project(h, sub, CFG, pos)
    mask = jnp.where(jnp.arange(n)[None, :] <= jnp.arange(n)[:, None], 0.0, -1e9)[None]
    expanded = attend_expanded(q_n, q_r, latent, mask, sub, CFG)[0, -1]
    pool, table = _paged(latent, [n], 5)
    q = longcat.absorb_query(q_n[:, -1], q_r[:, -1], sub, CFG)
    if kernel == "xla":
        o = paged_attention.latent_decode_attention_xla(q, pool, 1, table, jnp.asarray([n]), **_ATTN_KW)
    else:
        o = paged_attention.latent_decode_attention(q, pool, 1, table, jnp.asarray([n]), interpret=True, **_ATTN_KW)
    absorbed = longcat.unabsorb_output(o, sub, CFG)[0]
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded), atol=2e-5)


# Prefill calls (T, table width, [(start_pos, true_len) a row]): positions
# before ``start_pos`` are cached, the chunk's own rows are in their pages too.
PREFILL_CALLS = {
    "a_chunk_from_position_0": (24, 4, [(0, 21)]),
    "a_suffix_behind_a_prefix_on_a_block_boundary": (16, 6, [(24, 37)]),
    "a_suffix_behind_a_prefix_off_a_block_boundary": (16, 6, [(13, 27)]),   # the engine never asks; the kernel takes it
    "the_second_chunk_of_a_chunked_prompt": (32, 8, [(32, 64)]),
    "a_pack_of_2_at_their_own_start_pos": (16, 6, [(8, 20), (24, 40)]),
    "a_pack_of_4_with_an_inactive_row": (16, 6, [(0, 9), (32, 45), (0, 0), (16, 32)]),
    "a_table_wider_than_the_context": (8, 40, [(8, 13)]),
}


@pytest.mark.parametrize("kernel", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("call", list(PREFILL_CALLS))
def test_absorbed_prefill_attention_equals_expanded(call, kernel):
    """A chunk of T queries a row behind whatever the row's pages hold, out of
    the pages (the kernel in interpret mode walks 2 pages a chunk and 8 queries
    a tile, so every call crosses chunks and tiles), against the published form
    over each row's own context under a causal mask. Queries at or past a
    row's ``true_len`` are unspecified, an inactive row's all are."""
    T, W, rows = PREFILL_CALLS[call]
    B, C = len(rows), max(n for _, n in rows)
    params = longcat.init_params(CFG, jax.random.PRNGKey(1), jnp.float32)
    sub = _sub(params, 0, 1)
    h = jax.random.normal(jax.random.PRNGKey(3), (B, C, CFG.hidden_size), jnp.float32)
    q_n, q_r, latent = longcat.mla_project(h, sub, CFG, jnp.broadcast_to(jnp.arange(C), (B, C)))
    pool, tables = _paged(latent, [n for _, n in rows], W)
    start, true_len = (jnp.asarray(a, jnp.int32) for a in zip(*rows))
    at = jnp.minimum(start[:, None] + jnp.arange(T)[None], C - 1)                # [B, T]
    take = lambda a: jnp.take_along_axis(a, at[:, :, None, None], axis=1)       # noqa: E731
    # head-major, the latent and the rope lanes apart: what prefill_batch_impl hands the attention
    q_lat = jnp.einsum("bthn,hnc->bhtc", take(q_n), sub["w_uk"])
    q_rope = longcat._pad_row(jnp.moveaxis(take(q_r), 2, 1), CFG, CFG.latent_page_width - CFG.kv_lora_rank)
    scale = _ATTN_KW["scale"]
    if kernel == "xla":
        o = paged_attention.latent_prefill_attention_xla(q_lat, q_rope, pool, 1, tables, start, true_len, scale=scale)
    else:
        o = paged_attention.latent_prefill_attention(
            q_lat, q_rope, pool, 1, tables, start, true_len, scale=scale,
            pages_per_chunk=2, q_tile=8, interpret=True)
    absorbed = jnp.einsum("bhtc,hcv->bthv", o, sub["w_uv"])
    mask = jnp.where(jnp.arange(C)[None, None, :] <= at[:, :, None], 0.0, -1e9)
    expanded = attend_expanded(take(q_n), take(q_r), latent, mask, sub, CFG)
    live = np.asarray(start[:, None] + jnp.arange(T)[None] < true_len[:, None])
    assert live.any(axis=1).tolist() == [n > 0 for _, n in rows]
    np.testing.assert_allclose(np.asarray(absorbed)[live], np.asarray(expanded)[live], atol=2e-5)
    assert np.isfinite(np.asarray(absorbed)).all()  # an inactive row's output is defined, not garbage


# -- the router ------------------------------------------------------------------


def _router_case():
    params = longcat.init_params(CFG, jax.random.PRNGKey(4), jnp.float32)
    lp = {**{k: v[0] for k, v in params["layers"].items()}, "moe_layer": jnp.int32(0)}
    h = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.hidden_size), jnp.float32)
    probs = jax.nn.softmax(h @ lp["w_router"], axis=-1)
    return lp, h, np.asarray(probs)


@pytest.mark.parametrize("what", ["bias_moves_the_choice", "not_the_weights", "no_renormalisation", "scaling"])
def test_router(what):
    lp, h, probs = _router_case()
    k, R = CFG.num_experts_per_token, CFG.router_width
    plain = {**lp, "router_bias": jnp.zeros((R,), jnp.float32)}
    topi0, topw0 = (np.asarray(a) for a in longcat.route(h, plain, CFG))
    if what == "bias_moves_the_choice":
        assert (np.sort(topi0, -1) == np.sort(np.argsort(-probs, -1)[:, :k], -1)).all()
        low = int(np.argmin(probs.sum(0)))  # the least likely expert, lifted over all
        topi, _ = longcat.route(h, {**lp, "router_bias": jnp.zeros((R,)).at[low].set(1.0)}, CFG)
        assert (np.asarray(topi) == low).any(-1).all() and not (topi0 == low).any(-1).all()
    elif what == "not_the_weights":
        bias = jnp.zeros((R,)).at[3].set(1.0)
        topi, topw = (np.asarray(a) for a in longcat.route(h, {**lp, "router_bias": bias}, CFG))
        got = np.take_along_axis(probs, topi, -1) * CFG.routed_scaling_factor
        np.testing.assert_allclose(topw, got, rtol=1e-6)  # scaling * p, with no bias in it
    elif what == "no_renormalisation":
        want = np.take_along_axis(probs, topi0, -1).sum(-1) * CFG.routed_scaling_factor
        np.testing.assert_allclose(topw0.sum(-1), want, rtol=1e-6)
        assert np.abs(topw0.sum(-1) - 1.0).min() > 1e-3
    else:
        half = dataclasses.replace(CFG, routed_scaling_factor=CFG.routed_scaling_factor / 2)
        np.testing.assert_allclose(np.asarray(longcat.route(h, plain, half)[1]) * 2, topw0, rtol=1e-6)


def test_zero_compute_experts_add_w_times_h():
    lp, h, probs = _router_case()
    n_routed, R = CFG.num_routed_experts, CFG.router_width
    bias = jnp.zeros((R,)).at[n_routed:].set(1.0)  # every choice a zero-compute expert
    y, hist = longcat.moe(h, jnp.ones((24,), bool), {**lp, "router_bias": bias}, CFG, "ragged_dot")
    topi, topw = longcat.route(h, {**lp, "router_bias": bias}, CFG)
    assert (np.asarray(topi) >= n_routed).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(topw.sum(-1, keepdims=True) * h), rtol=1e-5, atol=1e-6)
    E = CFG.num_experts
    assert hist[:E].sum() == 0 and hist[E] == 24 * CFG.num_experts_per_token and hist[E + 2] == 24
    assert hist[E + 3] == 0 and hist[E + 4] == 1  # no held expert was touched, in one call


@pytest.mark.parametrize("impl", ["ragged_dot", "gmm_interpret"])
def test_no_token_is_dropped_whatever_the_imbalance(impl):
    """Every token's every choice on ONE held expert's neighbours: the grouped
    product has room for all of them, and padding rows count nowhere."""
    lp, h, _ = _router_case()
    R, off, E = CFG.router_width, CFG.expert_offset, CFG.num_experts
    bias = jnp.zeros((R,)).at[off:off + E].set(1.0)   # both held experts always chosen
    lp = {**lp, "router_bias": bias}
    valid = jnp.arange(24) < 20
    y, hist = longcat.moe(h, valid, lp, CFG, impl)
    assert list(np.asarray(hist[:E])) == [20, 20] and hist[E + 2] == 20 and hist[E + 3] == E
    topi, topw = longcat.route(h, lp, CFG)
    want = np.zeros_like(np.asarray(h))
    for e in range(E):
        w_e = np.asarray(jnp.sum(jnp.where(topi == off + e, topw, 0.0), -1, keepdims=True))
        g, u, d = (np.asarray(lp[n][e]) for n in ("moe_gate", "moe_up", "moe_down"))
        x = np.asarray(h)
        want += w_e * ((np.asarray(jax.nn.silu(x @ g)) * (x @ u)) @ d)
    zero = np.asarray(jnp.sum(jnp.where(topi >= CFG.num_routed_experts, topw, 0.0), -1, keepdims=True) * h)
    np.testing.assert_allclose(np.asarray(y)[:20], (want + zero)[:20], rtol=2e-4, atol=2e-5)


# What a cell's groups look like to the kernel, at sizes the interpreter
# walks in seconds: (K, N, group sizes, rows, the tiles the rule must give).
GROUPED_CASES = {
    # groups that end inside a row tile, an empty one between, rows past the total
    "groups_straddle_row_tiles": (192, 384, [100, 60, 0, 200, 0, 30], 512, (128, 192, 384)),
    # the stacked [L*E] form: every layer's groups empty but one layer's
    "one_layer_of_a_stack": (128, 256, [0, 0, 0, 0, 130, 0, 5, 140, 0, 0, 0, 0], 384, (128, 128, 256)),
    # published widths that 1,024 does not divide: one K tile, N tiles that divide N
    "k_1536_n_2048": (1536, 2048, [7, 0, 250, 1, 90], 384, (128, 1536, 512)),
    "k_2048_n_1536": (2048, 1536, [129, 0, 0, 127, 3], 384, (128, 2048, 512)),
    # no [K, 128] tile fits: K in tiles that divide it, a group's rows over two of them
    "k_in_two_tiles": (6144, 256, [60, 150, 0, 40], 256, (128, 3072, 256)),
}


@pytest.mark.parametrize("case", sorted(GROUPED_CASES))
def test_the_grouped_product_under_its_own_tiles_is_the_ragged_product(case):
    """``gmm_interpret`` under ``gmm_tiling``'s tiles against ``lax.ragged_dot``
    in float32: every row of every group, whichever tiles it falls in."""
    K, N, sizes, rows, tiles = GROUPED_CASES[case]
    assert longcat.gmm_tiling(K, N, 4) == tiles
    kx, kw = jax.random.split(jax.random.PRNGKey(11))
    x = jax.random.normal(kx, (rows, K), jnp.float32)
    w = jax.random.normal(kw, (len(sizes), K, N), jnp.float32) * K ** -0.5
    groups = jnp.asarray(sizes, jnp.int32)
    live = sum(sizes)
    assert live < rows and any(sum(sizes[:i]) % tiles[0] for i in range(1, len(sizes)))
    got = longcat.grouped_expert_matmul(x, w, groups, impl="gmm_interpret")
    want = longcat.grouped_expert_matmul(x, w, groups, impl="ragged_dot")
    assert got.shape == (rows, N) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got)[:live], np.asarray(want)[:live], rtol=2e-5, atol=2e-5)


# -- the shares add up -----------------------------------------------------------


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(monkeypatch):
    """4 shares of 8 routed experts: over all shares, the held parts plus the
    zero-compute part and the dense path counted once equal the uncut
    reference's layer (every expert held)."""
    uncut_doc = doc_for("float32", num_layers=1, n_routed_experts=8, first_expert_held=0)
    full = REF.weights(uncut_doc, 7)
    toks = prompt(16, seed=3)
    T = len(toks)
    x = REF._embed(full, jnp.asarray(toks, jnp.int32))
    want = REF._layer(x, jnp.zeros((T,), jnp.int32), jnp.arange(T, dtype=jnp.int32), full["layers"],
                      jnp.int32(0), REF.sizes(uncut_doc))

    monkeypatch.setattr(longcat, "_logits", lambda _cfg, _params, x_last: x_last)  # the hidden state, before the final norm

    def hidden(first: int, held: bool) -> np.ndarray:
        cfg = dataclasses.replace(CFG, num_layers=1, expert_offset=first)
        layers = dict(full["layers"])
        for name in ("moe_gate", "moe_up", "moe_down"):
            part = layers[name][:, first:first + cfg.num_experts]
            layers[name] = part if held else jnp.zeros_like(part)
        out, _, _ = longcat.prefill_batch_impl(
            cfg, {**full, "layers": layers}, longcat.init_kv_cache(cfg, 8, BS, jnp.float32),
            jnp.asarray([toks], jnp.int32), jnp.asarray([[1, 2]], jnp.int32),
            jnp.asarray([0], jnp.int32), jnp.asarray([T], jnp.int32))
        return np.asarray(out[0], np.float64)

    once = hidden(0, held=False)  # the dense path and the zero-compute part, nothing held
    total = once + sum(hidden(first, held=True) - once for first in range(0, 8, 2))
    np.testing.assert_allclose(total, np.asarray(want[-1], np.float64), rtol=2e-4, atol=2e-4)
    assert np.abs(total - once).max() > 1e-2  # the held experts do add something


# -- latent pages under the block manager ------------------------------------------


def greedy(prompt_ids, max_tokens=6, **ktp) -> PreprocessedRequest:
    req = PreprocessedRequest(model="t", token_ids=list(prompt_ids))
    req.sampling.temperature = 0.0
    req.sampling.seed = 0
    req.stop.max_tokens = max_tokens
    if ktp:
        req.kv_transfer_params = ktp
    return req


def engine_args(**kw) -> EngineArgs:
    return EngineArgs(**{**dict(model=CFG, block_size=BS, num_kv_blocks=24, max_num_seqs=4, max_model_len=128,
                                max_prefill_tokens=64, dtype="float32"), **kw})


async def _tokens(engine, req) -> list[int]:
    return [t async for o in engine.generate(req, Context()) for t in o.get("token_ids", [])]


@pytest.mark.parametrize("case", ["prefix_hit", "eviction", "preemption"])
def test_prefix_cache_eviction_and_preemption_on_latent_pages(case):
    """The block manager's behaviour is unchanged on latent pages: a resent
    prompt hits its cached blocks and gives the same tokens; blocks evicted
    under pressure are recomputed to the same tokens; a sequence preempted for
    want of blocks requeues and finishes with the tokens it would have had."""
    first, other = prompt(40, seed=1), prompt(40, seed=2)

    async def go():
        if case == "preemption":  # 3 x (40 + 30) tokens want 27 blocks of a pool of 18
            engine = await TpuEngine(engine_args(num_kv_blocks=18)).start()
            try:
                alone = [await _tokens(engine, greedy(prompt(40, seed=s), 30)) for s in (1, 2, 3)]
                n0 = sum(engine.total_preemptions_by.values())
                together = await asyncio.gather(*(_tokens(engine, greedy(prompt(40, seed=s), 30)) for s in (1, 2, 3)))
                return alone, list(together), sum(engine.total_preemptions_by.values()) - n0
            finally:
                await engine.stop()
        engine = await TpuEngine(engine_args()).start()
        try:
            a = await _tokens(engine, greedy(first))
            hits0 = engine.pool.hit_blocks
            if case == "eviction":  # fill the pool with other prompts until the first one's blocks go
                for s in range(10, 16):
                    await _tokens(engine, greedy(prompt(40, seed=s)))
            b = await _tokens(engine, greedy(first))
            return a, b, engine.pool.hit_blocks - hits0
        finally:
            await engine.stop()

    a, b, n = asyncio.run(go())
    assert a == b and len(b[0] if case == "preemption" else b) > 0
    if case == "prefix_hit":
        assert n == (40 - 1) // BS
    elif case == "eviction":
        assert n < (40 - 1) // BS  # some of the history was gone and was recomputed
    else:
        assert n > 0


def test_pool_accounting_is_in_bytes_of_the_latent_page():
    args = engine_args(dtype="bfloat16")
    assert CFG.cache_layers == 2 * CFG.num_layers and CFG.latent_page_width == 128
    assert args.kv_bytes_per_block() == CFG.cache_layers * BS * CFG.latent_page_width * 2
    cache = longcat.init_kv_cache(CFG, args.num_kv_blocks, BS)
    assert cache.kv.shape == (4, 24, BS, 128) and cache.block_size == BS
    assert cache.kv.nbytes == args.num_kv_blocks * args.kv_bytes_per_block()


# -- what refuses the block --------------------------------------------------------


@pytest.mark.parametrize("kw,names", [
    (dict(kv_quant="int8"), "--kv-quant int8"),
    (dict(spec_tokens=2), "speculation"),
    (dict(lora_slots=2), "LoRA"),
    (dict(quant="int8"), "--quant int8"),
    (dict(host_kv_blocks=8), "KV tiers"),
    (dict(tp=2), "--tp"),
])
def test_engine_args_refuse_what_cannot_carry_the_block(kw, names):
    with pytest.raises(ValueError, match="longcat") as e:
        engine_args(**kw)
    assert names in str(e.value)


@pytest.mark.parametrize("what", ["embed", "spec_verify", "extract_pages", "inject_pages", "transfer", "migration"])
def test_mechanisms_refuse_the_block_by_name(what):
    if what == "embed":
        with pytest.raises(ValueError, match="embed_impl"):
            M.embed_impl(CFG, {}, jnp.zeros((8,), jnp.int32), jnp.int32(4))
    elif what == "spec_verify":
        with pytest.raises(ValueError, match="spec_verify_impl"):
            M.spec_verify_impl(CFG, 2, "greedy", 0, {}, None, *([None] * 8))
    elif what in ("extract_pages", "inject_pages"):
        from dynamo_tpu.engine.runner import LocalRunner

        runner = LocalRunner(engine_args())
        with pytest.raises(ValueError, match="transfer"):
            runner.extract_pages([1]) if what == "extract_pages" else runner.inject_pages([1], None, None)
    else:
        async def go():
            engine = await TpuEngine(engine_args()).start()
            try:
                if what == "transfer":
                    outs = [o async for o in engine.generate(greedy(prompt(20), do_remote_decode=True), Context())]
                    return outs[-1].get("error", "")
                got = await engine.run_on_engine_thread(lambda: engine.migration_begin("any"))
                return got.get("error", "")
            finally:
                await engine.stop()

        assert "latent" in asyncio.run(go())


# -- the block is chosen once, and every program has one shape ---------------------


@pytest.mark.parametrize("preset, hist", [("test-tiny", False), ("longcat-tiny", True)])
def test_the_runner_takes_its_programs_from_the_blocks_module(preset, hist):
    """``model.block_module`` names the module; the runner's programs return a
    routing histogram in the last place, None from the dense block."""
    from dynamo_tpu.engine.runner import LocalRunner

    cfg = ModelConfig.preset(preset)
    assert M.block_module(cfg) is (longcat if hist else M)
    runner = LocalRunner(EngineArgs(model=cfg, block_size=BS, num_kv_blocks=8, max_num_seqs=2,
                                    max_model_len=64, dtype="float32"))
    runner.start()
    assert ("experts=ragged_dot" in runner._start_line("")) == hist
    # both blocks' prefill resolves one way: the gather form off the TPU, with no reason of the block's own
    assert "attention: prefill=xla decode=xla" in runner._start_line("")
    assert runner._prefill_kw == {"attn_impl": "xla"}
    table = np.arange(1, 5, dtype=np.int32)
    ref = runner.prefill_chunk(np.zeros((16,), np.int32), table, 0, 9)
    assert (ref.hist is not None) == hist and len(ref.arrs) == 1
    assert len(runner.take_routed()) == (1 if hist else 0)
    step = runner.decode_step(np.zeros((2,), np.int32), np.asarray([9, 0], np.int32),
                              np.stack([table, table]), np.asarray([True, False]))
    assert (step.hist is not None) == hist
    if hist:
        E = cfg.num_experts
        assert step.hist.shape == (cfg.num_layers, E + longcat.HIST_EXTRA)
        assert int(step.hist[0, E + 2]) == 1 and int(step.hist[0, E + 4]) == 1  # one token, one call a layer


def test_a_block_without_a_module_is_refused():
    with pytest.raises(ValueError, match="no module runs block='mamba'"):
        M.block_module(dataclasses.replace(CFG, block="mamba"))


# -- the router's second arithmetic (engine/lfm2.py) leaves this block as it was ---


WAS = {  # read on the parent of PR 37 (longcat-tiny, float32, the CPU)
    "tokens": [[165, 447, 419, 311, 205, 234, 199, 199, 429, 468], [466, 232, 347, 242, 453, 494, 334, 249, 459, 51]],
    "topi": [[3, 0, 8], [8, 9, 0], [5, 3, 0], [9, 8, 0], [8, 1, 7], [3, 10, 0]],
    "topw": [[4.911398, 0.071298, 0.004912], [3.742863, 1.274542, 0.025198], [3.903823, 1.775397, 0.009748],
             [3.511818, 1.405415, 0.096554], [2.402556, 1.07643, 1.191123], [3.790177, 1.631835, 0.203196]],
}


@pytest.mark.parametrize("what", ["route", "served_tokens"])
def test_longcat_routes_and_serves_what_it_did_before_the_second_router_arithmetic(what):
    """``route`` gained the sigmoid arithmetic behind a configuration field;
    this block's choice (softmax, on ``p + bias``) and weights (``6 p``, not
    renormalised) and the tokens it serves are the parent's."""
    cfg = ModelConfig.preset("longcat-tiny")
    assert cfg.router_scoring == "softmax" and not cfg.norm_topk_prob
    if what == "route":
        params = longcat.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        lp = {k: v[1] for k, v in params["layers"].items()}
        xt = jax.random.normal(jax.random.PRNGKey(5), (6, cfg.hidden_size), jnp.float32)
        topi, topw = longcat.route(xt, lp, cfg)
        assert np.asarray(topi).tolist() == WAS["topi"]
        np.testing.assert_allclose(np.asarray(topw), WAS["topw"], rtol=1e-4, atol=2e-6)
        p = jax.nn.softmax(jnp.dot(xt, lp["w_router"], precision="highest"), axis=-1)
        np.testing.assert_allclose(np.asarray(topw), 6.0 * np.take_along_axis(np.asarray(p), np.asarray(topi), -1), rtol=1e-4)
        return

    async def go():
        engine = await TpuEngine(EngineArgs(model=cfg, block_size=BS, num_kv_blocks=24, max_num_seqs=4,
                                            max_model_len=128, max_prefill_tokens=64, dtype="float32")).start()
        try:
            out = []
            for s in (1, 2):
                req = greedy([int(t) for t in np.random.RandomState(s).randint(0, cfg.vocab_size, 40)], 10)
                req.stop.ignore_eos = True
                out.append(await _tokens(engine, req))
            return out
        finally:
            await engine.stop()

    assert asyncio.run(go()) == WAS["tokens"]
