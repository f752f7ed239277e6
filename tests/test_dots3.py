"""The dots3-note block (ISSUE 47) on the CPU at a toy size with seeded weights:
the program against the benchmark's plain reference (absorbed against expanded
attention, the indexer's choice, the window), the kernels against their XLA
twins, the shares of an expert-parallel layer adding up, the window pool's two
lifetimes under the prefix cache, and what refuses the block."""

import asyncio
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model_maps, references
from dynamo_tpu.block_manager.pool import BlockPool
from dynamo_tpu.engine import dots3, longcat
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.side import WindowBlocks
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.ops import dsa
from dynamo_tpu.ops import paged_attention as pa
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.tokens import compute_block_hashes

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "chipbench", "configs", "rehearse-dots3-tiny.json")) as f:
    DOC = json.load(f)
CFG = model_maps.model_config(DOC)  # every kind of layer; window 7, index_topk 12: contexts pass both
REF = references.load("dots3_note")
BS = 8


def doc_for(dtype: str, **over) -> dict:
    return {**DOC, **over, "served": {**DOC["served"], "dtype": dtype}}


def prompt(n: int, seed: int = 0) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(0, CFG.vocab_size, n)]


def program_params(w: dict, cfg=CFG) -> dict:
    """The reference's weights (a list of layers) as the program's tree."""
    P, n_win = dots3.periods(cfg)
    L = w["layers"]
    own = lambda lp: {k: v for k, v in lp.items() if not k.startswith("moe_")}  # noqa: E731
    stack = lambda trees: jax.tree.map(lambda *a: jnp.stack(a), *trees)  # noqa: E731
    first = lambda p: 1 + p * (1 + n_win)  # noqa: E731
    return {"embed": w["embed"], "lm_head": w["lm_head"], "final_norm": w["final_norm"], "first": own(L[0]),
            "full": stack([own(L[first(p)]) for p in range(P)]),
            "swa": stack([stack([own(L[first(p) + 1 + j]) for j in range(n_win)]) for p in range(P)]),
            "experts": {n: jnp.concatenate([lp[n] for lp in L[1:]]) for n in ("moe_gate", "moe_up", "moe_down")}}


TABLE = jnp.arange(1, 9, dtype=jnp.int32)
# A window table that holds every block of the sequence from its first: nothing given back.
WHOLE = jnp.concatenate([jnp.zeros((1,), jnp.int32), TABLE])


def _pad(xs, n):
    return jnp.zeros((n,), jnp.int32).at[:len(xs)].set(jnp.asarray(xs, jnp.int32))


def serve_through_cache(params, dtype, toks, plen: int, mode: str, impl: str) -> np.ndarray:
    """Prefill ``toks[:plen]`` (cold, in two chunks, or its second part behind
    pages an earlier prefill cached) and decode the rest teacher-forced through
    the three pools → float32 logits at positions plen-1 .. len(toks)-1.
    "pallas_interpret" takes every kernel in interpret mode."""
    cache = dots3.init_kv_cache(CFG, 32, BS, dtype, window_blocks=32)
    kw = {"attn_impl": impl, **({"experts": "gmm_interpret"} if impl == "pallas_interpret" else {})}
    if mode == "cold":
        logits, cache, _ = dots3.prefill(CFG, params, cache, _pad(toks[:plen], 48), TABLE, 0, plen, state_slots=WHOLE, **kw)
    else:
        cut = 16  # whole blocks
        _, cache, _ = dots3.prefill(CFG, params, cache, _pad(toks[:cut], 16), TABLE, 0, cut, state_slots=WHOLE, **kw)
        if mode == "cached":  # another dispatch wrote the pages; only the tables name them
            cache = jax.tree.map(jnp.copy, cache)
        logits, cache, _ = dots3.prefill(CFG, params, cache, _pad(toks[cut:plen], 32), TABLE, cut, plen,
                                         state_slots=WHOLE, **kw)
    out = [logits]
    for pos in range(plen, len(toks)):
        step, cache, _ = dots3.decode_step(
            CFG, params, cache, jnp.asarray([toks[pos], 0], jnp.int32), jnp.asarray([pos, 0], jnp.int32),
            jnp.stack([TABLE, TABLE]), jnp.asarray([True, False]), state_slots=jnp.stack([WHOLE, WHOLE]), **kw)
        out.append(step[0])
    return np.asarray(jnp.stack(out), np.float32)


# Tolerances, from these sizes on the CPU (seeds 0-2 read). float32 against the
# float32 reference differs by summation order alone (the program absorbs W_kvb,
# the reference multiplies it out; the program attends the chosen rows, the
# reference masks a dense softmax): the widest gap must stay under 2e-4 (1.3e-5
# read). bf16 weights are the same numbers on both sides, so bf16 reads what
# rounding activations, latents and index keys costs, and here that is mostly
# the model's own discrete choices at a toy's sizes: 12 chosen of 40 tokens and
# 2 of 8 experts renormalised, so one near-tie exchanges a twelfth of a layer's
# attention or half its routed experts. Mean over the logits 0.21-0.30 (0.02
# with every token chosen and every expert taken: engine/longcat.py's reading);
# the same program on weights rounded to float8_e4m3 reads 0.62-0.75. The limit
# 0.45 tells bf16 from the precision under it; the widest gap (1.8-2.6) is not held.
TOL = {"float32": ("max", 2e-4), "bfloat16": ("mean", 0.45)}


def gap_of(got: np.ndarray, want: np.ndarray, dtype: str) -> float:
    diff = np.abs(got - want[:len(got)])
    return float(diff.max() if TOL[dtype][0] == "max" else diff.mean())


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("mode", ["cold", "chunked", "cached"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_agrees_with_the_reference_forward(dtype, mode, impl):
    """Logits at every position from the prompt's last on: 40 prompt tokens and
    6 decoded ones, past ``index_topk`` 12 and the window of 7 in every layer."""
    doc = doc_for(dtype)
    w = REF.weights(doc, 0)
    toks, plen = prompt(46), 40
    want = np.asarray(REF.forward(doc, w, toks), np.float32)[plen - 1:len(toks)]
    got = serve_through_cache(program_params(w), jnp.dtype(dtype), toks, plen, mode, impl)
    assert got.shape == want.shape
    assert gap_of(got, want, dtype) < TOL[dtype][1]


def test_a_precision_under_bf16_fails_the_bf16_tolerance():
    doc = doc_for("bfloat16")
    w = REF.weights(doc, 0)
    toks, plen = prompt(46), 40
    want = np.asarray(REF.forward(doc, w, toks), np.float32)[plen - 1:len(toks)]
    low = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype) if a.dtype == jnp.bfloat16 else a, w)
    assert gap_of(serve_through_cache(program_params(low), jnp.bfloat16, toks, plen, "cold", "xla"),
                  want, "bfloat16") > TOL["bfloat16"][1]


def test_the_programs_initialiser_is_the_references():
    mine = dots3.init_params(CFG, jax.random.PRNGKey(3), jnp.bfloat16)
    theirs = program_params(REF.weights(doc_for("bfloat16"), 3))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert all(jax.tree.leaves(jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), mine, theirs)))
    assert float(jnp.std(mine["full"]["router_bias"])) > 0  # drawn, not zeros


def test_a_chunk_of_several_query_blocks_is_the_chunk_at_once(monkeypatch):
    """The full layers choose and attend ``CHOICE_QUERIES`` positions at a time."""
    params = program_params(REF.weights(doc_for("float32"), 0))
    toks = jnp.asarray([prompt(48)], jnp.int32)

    def run():
        cache = dots3.init_kv_cache(CFG, 32, BS, jnp.float32, window_blocks=32)
        return dots3.prefill_batch_impl(CFG, params, cache, toks, TABLE[None], jnp.zeros((1,), jnp.int32),
                                        jnp.asarray([45], jnp.int32), attn_impl="xla", state_slots=WHOLE[None])[0]

    whole = run()
    monkeypatch.setattr(dots3, "CHOICE_QUERIES", 16)
    np.testing.assert_allclose(np.asarray(run()), np.asarray(whole), atol=2e-5)


def _layers_by_hand(cfg, params, x, cache, positions, valid, full_attend, window_attend, moe_impl):
    """``dots3._layers`` as a plain loop over the nine layers, a layer's
    tensors taken out of their stacks by hand: no scan, no operand of one."""
    P, n_win = dots3.periods(cfg)
    x, cache, _ = dots3.layer(cfg, cfg, params["first"], x, cache, positions, valid, full_attend(0), moe_impl)
    hist = []
    for p in range(P):
        first = p * (1 + n_win)
        lp = jax.tree.map(lambda a: a[p], params["full"])
        x, cache, h = dots3.layer(cfg, cfg, {**lp, **params["experts"], "moe_layer": first}, x, cache, positions,
                                  valid, full_attend(1 + p), moe_impl)
        hist.append(h)
        for j in range(n_win):
            lp = jax.tree.map(lambda a: a[p, j], params["swa"])
            x, cache, h = dots3.layer(cfg, cfg.swa, {**lp, **params["experts"], "moe_layer": first + 1 + j}, x, cache,
                                      positions, valid, window_attend(p * n_win + j), moe_impl)
            hist.append(h)
    return x, cache, jnp.stack(hist)


@pytest.mark.parametrize("program", ["prefill_batch", "decode_step"])
def test_the_layers_do_not_depend_on_how_a_window_layer_is_reached(program, monkeypatch):
    """The two scans, whose inner body indexes the whole ``swa`` stack, against
    the nine layers one after the other: the same logits and the same three
    pools bit for bit, the histogram's rows in the layers' order."""
    params = program_params(REF.weights(doc_for("float32"), 0))
    lengths = jnp.asarray([45, 29], jnp.int32)
    tables = jnp.arange(1, 17, dtype=jnp.int32).reshape(2, 8)
    state = jnp.concatenate([jnp.zeros((2, 1), jnp.int32), tables], axis=1)
    toks = jnp.asarray([_pad(prompt(int(n), seed=int(n)), 48) for n in lengths])
    zeros = jnp.zeros((2,), jnp.int32)

    def run():  # the weights an operand, as the engine's programs take them (closed over, XLA folds them as constants)
        cache = dots3.init_kv_cache(CFG, 32, BS, jnp.float32, window_blocks=32)
        out = jax.jit(lambda p, c: dots3.prefill_batch_impl(CFG, p, c, toks, tables, zeros, lengths,
                                                            attn_impl="xla", state_slots=state))(params, cache)
        if program == "decode_step":  # a step past index_topk and the window, over the pages that prefill wrote
            out = jax.jit(lambda p, c: dots3.decode_step_impl(
                CFG, p, c, jnp.asarray([3, 5], jnp.int32), lengths, tables, jnp.asarray([True, True]),
                attn_impl="xla", state_slots=state))(params, out[1])
        return out

    logits, cache, hist = run()
    monkeypatch.setattr(dots3, "_layers", _layers_by_hand)
    want_logits, want_cache, want_hist = run()
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    for name in ("kv", "ikeys", "window"):
        got, want = np.asarray(getattr(cache, name)), np.asarray(getattr(want_cache, name))
        np.testing.assert_array_equal(got, want)
        assert np.abs(want[:, 1:]).max() > 0  # written, not the zeros it began as
    assert hist.shape == (len(dots3.routed_layers(CFG)), CFG.num_experts + longcat.HIST_EXTRA)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(want_hist))
    assert len({tuple(row) for row in np.asarray(hist).tolist()}) == len(hist)  # no two rows alike, so their order is held


# -- the indexer's choice ----------------------------------------------------------


def _indexer_case(T: int = 40, seed: int = 0):
    """A full layer's inputs over T tokens in float32: the reference's chosen
    set [T, T], and the program's queries, weights and cached keys for it."""
    doc = doc_for("float32")
    w = REF.weights(doc, seed)
    z, lp = REF.sizes(doc), w["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(seed), (512, CFG.hidden_size), jnp.float32)
    seq, pos = jnp.zeros((512,), jnp.int32), jnp.arange(512, dtype=jnp.int32)
    c_q = REF._rms(REF._dot(u, lp["w_qa"]), lp["q_norm"], z.eps) * (z.D / z.full.rq) ** 0.5
    want = np.asarray(REF._chosen(u, c_q, seq, pos, lp, z))[:T, :T]
    c_q_mine = longcat.mla_query_latent(u[:T], lp, CFG)
    q_idx, wts = dots3.index_query(u[:T], c_q_mine, lp, CFG, pos[:T])
    keys = dots3.index_key(u[:T], lp, CFG, pos[:T])
    ikeys = jnp.zeros((1, 9, BS, CFG.index_head_dim), jnp.float32).at[0, 1:1 + T // BS].set(
        keys.reshape(T // BS, BS, -1))
    return want, q_idx, wts, ikeys


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_a_decode_rows_chosen_set_is_the_references(impl):
    """Every position t as a decode row of context t + 1: the positions the
    program attends are the reference's, all of them at or under ``index_topk``."""
    T = 40
    want, q_idx, wts, ikeys = _indexer_case(T)
    lengths = jnp.arange(1, T + 1, dtype=jnp.int32)
    tables = jnp.broadcast_to(TABLE, (T, 8))
    if impl == "xla":
        scores = dsa.index_scores_xla(q_idx, wts, ikeys, 0, tables, lengths)
    else:
        scores = dsa.index_scores(q_idx, wts, ikeys, 0, tables, lengths, interpret=True)
        np.testing.assert_allclose(np.asarray(scores), np.asarray(dsa.index_scores_xla(q_idx, wts, ikeys, 0, tables, lengths)),
                                   rtol=1e-5, atol=1e-5)
    picked = np.asarray(dsa.select(scores, CFG.index_topk))
    for t in range(T):
        n = min(t + 1, CFG.index_topk)
        assert set(picked[t, :n]) == set(np.flatnonzero(want[t])), t
    assert want[T - 1].sum() == CFG.index_topk and want[5].sum() == 6


def test_a_prefill_chunks_choice_is_the_references():
    T = 40
    want, q_idx, wts, ikeys = _indexer_case(T)
    keep = dsa.prefill_keep(q_idx[None], wts[None], ikeys, 0, TABLE[None], jnp.zeros((1,), jnp.int32),
                            jnp.asarray([T], jnp.int32), CFG.index_topk, jnp.float32)
    causal = np.tril(np.ones((T, T), bool))
    np.testing.assert_array_equal(np.asarray(keep[0, :, :T] != 0) & causal, want)


@pytest.mark.parametrize("k", [1, 5, 12, 64])
def test_keep_topk_is_lax_top_k_with_ties_taken_lowest_position_first(k):
    s = jnp.asarray(np.random.RandomState(k).randint(-3, 4, (6, 64)).astype(np.float32))  # many ties
    s = s.at[0, 10:].set(dsa.NEG_INF)
    want = np.zeros((6, 64), bool)
    np.put_along_axis(want, np.asarray(jax.lax.top_k(s, k)[1]), True, axis=1)
    np.testing.assert_array_equal(np.asarray(dsa.keep_topk(s, k)), want)


# -- the kernels against their XLA twins ---------------------------------------------


def _latent_pool(geo, n_blocks: int, seed: int):
    pool = jax.random.normal(jax.random.PRNGKey(seed), (2, n_blocks, BS, geo.latent_page_width), jnp.float32)
    return pool.at[..., geo.latent_dim:].set(0.0)


@pytest.mark.parametrize("geometry", ["full", "swa"])
def test_the_window_decode_kernel_is_its_xla_twin(geometry):
    geo = CFG if geometry == "full" else CFG.swa
    pool = _latent_pool(geo, 9, 1)
    q = jax.random.normal(jax.random.PRNGKey(2), (3, geo.num_heads, geo.latent_page_width), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [2, 4, 6, 8]], jnp.int32)
    lengths = jnp.asarray([30, 9, 0], jnp.int32)
    kw = dict(value_dim=geo.kv_lora_rank, scale=0.2, window=CFG.sliding_window)
    got = pa.latent_decode_attention(q, pool, 1, tables, lengths, interpret=True, **kw)
    want = pa.latent_decode_attention_xla(q, pool, 1, tables, lengths, **kw)
    np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]), rtol=2e-5, atol=2e-5)
    dense = pa.latent_decode_attention_xla(q, pool, 1, tables, lengths, value_dim=geo.kv_lora_rank, scale=0.2)
    assert np.abs(np.asarray(dense[0] - want[0])).max() > 1e-3  # the window does cut row 0's context


@pytest.mark.parametrize("mask", ["window", "keep"])
def test_the_masked_prefill_kernel_is_its_xla_twin(mask):
    geo = CFG.swa if mask == "window" else CFG
    pool = _latent_pool(geo, 9, 3)
    B, T, H, Dv = 2, 16, geo.num_heads, geo.kv_lora_rank
    q_lat = jax.random.normal(jax.random.PRNGKey(4), (B, H, T, Dv), jnp.float32)
    q_rope = jax.random.normal(jax.random.PRNGKey(5), (B, H, T, geo.latent_page_width - Dv), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    start, tlen = jnp.asarray([8, 16], jnp.int32), jnp.asarray([21, 32], jnp.int32)
    if mask == "window":
        kw = {"window": CFG.sliding_window}
    else:  # about half of every query's context, its own position always
        keep = jax.random.bernoulli(jax.random.PRNGKey(6), 0.5, (B, T, 32))
        own = (start[:, None] + jnp.arange(T)[None])[..., None] == jnp.arange(32)[None, None]
        kw = {"keep": (keep | own).astype(jnp.float32)}
    got = pa.latent_prefill_attention(q_lat, q_rope, pool, 0, tables, start, tlen, scale=0.2, interpret=True, **kw)
    want = pa.latent_prefill_attention_xla(q_lat, q_rope, pool, 0, tables, start, tlen, scale=0.2, **kw)
    for b in range(B):
        n = int(tlen[b] - start[b])
        np.testing.assert_allclose(np.asarray(got[b, :, :n]), np.asarray(want[b, :, :n]), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_a_row_at_or_under_index_topk_attends_as_dense_mla(impl):
    """The chosen-rows attend over a row that sees no more than ``index_topk``
    positions gives the dense latent attention's sums, whatever the scores."""
    pool = _latent_pool(CFG, 9, 7)
    q = jax.random.normal(jax.random.PRNGKey(8), (2, CFG.num_heads, CFG.latent_page_width), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    lengths = jnp.asarray([CFG.index_topk, 5], jnp.int32)
    scores = jnp.where(jnp.arange(32)[None] < lengths[:, None],
                       jax.random.normal(jax.random.PRNGKey(9), (2, 32)), dsa.NEG_INF)
    picked, counts = dsa.select(scores, CFG.index_topk), jnp.minimum(lengths, CFG.index_topk)
    kw = dict(value_dim=CFG.kv_lora_rank, scale=0.2)
    if impl == "xla":
        got = dsa.sparse_decode_attention_xla(q, pool, 0, tables, picked, counts, **kw)
    else:
        got = dsa.sparse_decode_attention(q, pool, 0, tables, picked, counts, interpret=True, **kw)
    want = pa.latent_decode_attention_xla(q, pool, 0, tables, lengths, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# -- a decode row's chosen set, as a mask over the walk or as gathered rows ------------


def _tie_scores(lengths, width: int, seed: int):
    """Scores of few distinct values, so the ``topk``-th is tied many ways and
    the ties lie on both sides of page boundaries; ``NEG_INF`` past a length."""
    s = jnp.asarray(np.random.RandomState(seed).randint(-2, 3, (len(lengths), width)).astype(np.float32))
    return jnp.where(jnp.arange(width)[None] < jnp.asarray(lengths)[:, None], s, dsa.NEG_INF)


CHOSEN_CASES = {
    # lengths of the rows, blocks of their table
    "rows_under_and_over_index_topk": ([40, 5, CFG.index_topk, 64], 8),
    "a_dead_row": ([40, 0, 33, 0], 8),
    "a_table_wider_than_any_row": ([30, 17, 25, 14], 24),
    "one_row_to_the_tables_end": ([64, 13, 0, 1], 8),
}


@pytest.mark.parametrize("scores", ["distinct", "tied_across_pages"])
@pytest.mark.parametrize("case", CHOSEN_CASES)
def test_the_masked_walk_attends_the_set_the_gather_form_attends(case, scores):
    """``keep_topk``'s mask over the latent decode kernel's walk against the
    XLA form over ``select``'s gathered rows, and against the gather form's
    kernel: one set, one softmax, the sums in another order. Both kernels in
    interpret mode; a dead row's output is nobody's."""
    lengths, W = CHOSEN_CASES[case]
    B, topk, width = len(lengths), CFG.index_topk, W * BS
    pool = _latent_pool(CFG, 1 + B * W, 11)
    tables = jnp.asarray(np.random.RandomState(3).permutation(np.arange(1, 1 + B * W)).reshape(B, W), jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(12), (B, CFG.num_heads, CFG.latent_page_width), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    if scores == "distinct":
        sc = jnp.where(jnp.arange(width)[None] < lens[:, None], jax.random.normal(jax.random.PRNGKey(13), (B, width)), dsa.NEG_INF)
    else:
        sc = _tie_scores(lengths, width, 14)
        kth = np.sort(np.asarray(sc[0]))[::-1][topk - 1]
        tied = np.flatnonzero(np.asarray(sc[0]) == kth)
        assert len(tied) > 2 and tied[0] // BS != tied[-1] // BS  # the k-th score ties across a page boundary
    picked, counts, keep = dsa.select(sc, topk), jnp.minimum(lens, topk), dsa.keep_topk(sc, topk)
    kw = dict(value_dim=CFG.kv_lora_rank, scale=0.2)
    want = dsa.sparse_decode_attention_xla(q, pool, 1, tables, picked, counts, **kw)
    gathered = dsa.sparse_decode_attention(q, pool, 1, tables, picked, counts, interpret=True, **kw)
    walked = dsa.masked_decode_attention(q, pool, 1, tables, lens, keep, interpret=True, **kw)
    twin = pa.latent_decode_attention_xla(q, pool, 1, tables, lens, keep=keep, **kw)
    live = np.asarray(lens) > 0
    for got in (walked, twin, gathered):
        np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5)
    # the mask's live part is the gathered positions, to the position
    for b in np.flatnonzero(live):
        n = int(counts[b])
        assert set(np.flatnonzero(np.asarray(keep[b, :lengths[b]]))) == set(np.asarray(picked[b, :n]).tolist())
    assert not np.asarray(walked)[~live].any()  # a dead row is one empty grid step
    over = np.flatnonzero(np.asarray(lens) > topk)
    if len(over):  # and the choice does cut those rows' context
        dense = pa.latent_decode_attention_xla(q, pool, 1, tables, lens, **kw)
        assert np.abs(np.asarray(dense - want))[over].max() > 1e-3


def _rule_width(topk: int) -> int:
    """The least table width, in positions, at which full rows tip the rule to
    the gather form (``dsa``'s constants: a position's walk against its share
    of the sort, the chosen rows' gathers whatever the length)."""
    return int(topk * dsa.GATHER_NS_PER_CHOSEN_ROW / (dsa.WALK_NS_PER_TOKEN - dsa.SORT_NS_PER_POSITION)) + 1


def test_walk_is_cheaper_at_the_corners_of_step_0s_table():
    """The cell's own calls walk; a full bucket at the table's whole width is
    where the forms meet; the walk grows with what is live and the gather form
    with the bucket and the table."""
    B, width, topk = 32, 32768, 2048
    def rule(live, length, B=B, width=width):
        return bool(dsa.walk_is_cheaper(np.asarray([length] * live + [0] * (B - live)), B, width, topk))
    assert rule(18, 24576) and rule(18, 32000) and rule(24, 24576) and rule(8, 32768)  # the cell: 18.7 rows live
    assert rule(1, 32768) and rule(32, 2049)                                           # one long row; a full bucket of short rows
    # what is live pays for the walk and what is padded for the gather: 24 rows
    # of a 24-row call (PR 47's arithmetic) stand nearer the line than 18 of 32
    full = int(B * (topk * dsa.GATHER_NS_PER_CHOSEN_ROW + width * dsa.SORT_NS_PER_POSITION) / dsa.WALK_NS_PER_TOKEN)
    assert rule(32, full // 32 - 1) and not rule(32, full // 32 + 1)
    assert 0.5 < full / (32 * 32768) < 1.5  # about level for a full batch at 32k
    # the published 524,288 positions: rows that long gather
    assert not rule(32, 400_000, width=524_288) and rule(2, 400_000, width=524_288)
    # the traced predicate is the host's, on the same lengths
    for lengths in ([24576] * 18 + [0] * 14, [32768] * 32, [full // 32 + 1] * 32, [full // 32 - 1] * 32):
        host = bool(dsa.walk_is_cheaper(np.asarray(lengths), B, width, topk))
        traced = bool(jax.jit(lambda l: dsa.walk_is_cheaper(l, B, width, topk))(jnp.asarray(lengths, jnp.int32)))
        assert host == traced, lengths


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("rows", ["short_rows_walk", "long_rows_gather"])
def test_a_decode_step_gives_the_same_logits_whichever_form_the_rule_takes(rows, impl, monkeypatch):
    """One decode step of the block over pages a prefill wrote, the rule
    decided by the rows' lengths alone (short rows in a wide table walk, rows
    that fill it gather), against the same step with the rule turned round."""
    topk = CFG.index_topk
    W = -(-_rule_width(topk) // BS) + 2
    assert W * BS <= DOC["max_position_embeddings"]
    long = W * BS - 3
    lengths = {"short_rows_walk": [40, 21], "long_rows_gather": [long, long - 9]}[rows]
    params = program_params(REF.weights(doc_for("float32"), 0))
    cache = dots3.init_kv_cache(CFG, 1 + 2 * W, BS, jnp.float32, window_blocks=1 + 2 * W)
    tables = jnp.arange(1, 1 + 2 * W, dtype=jnp.int32).reshape(2, W)
    state = jnp.concatenate([jnp.zeros((2, 1), jnp.int32), tables], axis=1)
    kw = {"attn_impl": impl, **({"experts": "gmm_interpret"} if impl == "pallas_interpret" else {})}
    T = -(-max(lengths) // 16) * 16
    toks = jnp.asarray([_pad(prompt(n - 1, seed=n), T) for n in lengths])
    _, cache, _ = dots3.prefill_batch(CFG, params, cache, toks, tables, jnp.zeros((2,), jnp.int32),
                                      jnp.asarray(lengths, jnp.int32) - 1, state_slots=state, attn_impl="xla")
    args = (jnp.asarray([3, 5], jnp.int32), jnp.asarray(lengths, jnp.int32) - 1, tables, jnp.asarray([True, True]))
    want_walk = rows == "short_rows_walk"
    assert bool(dsa.walk_is_cheaper(np.asarray(lengths), 2, W * BS, topk)) == want_walk

    def step():
        fn = jax.jit(lambda c: dots3.decode_step_impl(CFG, params, c, *args, state_slots=state, **kw)[0])
        return np.asarray(fn(cache))

    by_rule = step()
    monkeypatch.setattr(dsa, "walk_is_cheaper", lambda lengths, *a: lengths.sum() < 0 if want_walk else lengths.sum() >= 0)
    turned = step()
    np.testing.assert_allclose(by_rule, turned, rtol=2e-4, atol=2e-4)
    assert np.isfinite(by_rule).all() and np.abs(by_rule).max() > 0.1


def test_the_hosts_count_of_walked_steps_is_the_programs_predicate():
    """``engine_dsa_decode_{steps,walk_steps}_total``: what the scheduler books
    for a window is the rule on each step's lengths as the program forms them."""
    from types import SimpleNamespace

    stats = {"chosen": 0, "visible": 0, "dense": 0, "steps": 0, "walk_steps": 0}
    topk, bs, B, W, K = 2048, 32, 4, 1024, 8
    kind = SimpleNamespace(stats=stats, cfg=SimpleNamespace(index_topk=topk, sliding_window=513),
                           args=SimpleNamespace(block_size=bs, window_table_width=18),
                           _row=lambda seq, p0, width: np.zeros((1 + width,), np.int32))
    want_steps = want_walk = 0
    for pos0 in ([24000, 30000, 100], [2040, 2043], [32700] * 4, [5, 9]):
        WindowBlocks.decode_rows(kind, [None] * len(pos0), pos0, B, K, W)
        for j in range(K):
            positions = jnp.asarray(pos0 + [0] * (B - len(pos0)), jnp.int32) + j
            active = jnp.arange(B) < len(pos0)
            lengths = jnp.where(active, positions + 1, 0)  # engine/dots3.py:decode_step_impl
            if int(jnp.max(lengths)) > topk:
                want_steps += 1
                want_walk += bool(dsa.walk_is_cheaper(lengths, B, W * bs, topk))
    assert (stats["steps"], stats["walk_steps"]) == (want_steps, want_walk)
    assert 0 < want_walk < want_steps == 8 + 3 + 8  # four rows at the table's end gather; rows at 2,041 pass index_topk mid-window


# -- the shares add up -----------------------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """2 shares of 8 routed experts: the shares' routed parts plus the shared
    expert counted once equal the uncut reference's expert layer (all 8 held)."""
    uncut = doc_for("float32", n_routed_experts=8, first_expert_held=0)
    lp = REF.weights(uncut, 7)["layers"][2]  # a window layer's feed-forward: the expert layer is the same in both kinds
    h = jax.random.normal(jax.random.PRNGKey(1), (16, CFG.hidden_size), jnp.float32)
    want = np.asarray(REF._experts(h, lp, REF.sizes(uncut)), np.float64)
    valid = jnp.ones((16,), bool)
    total = np.asarray(longcat._mlp(h, lp), np.float64)  # the shared expert, once
    routed = []
    for first in (0, 4):
        cfg = dataclasses.replace(CFG, expert_offset=first)
        held = {n: lp[n][first:first + 4] for n in ("moe_gate", "moe_up", "moe_down")}
        m, hist = longcat.moe(h, valid, {**lp, **held, "moe_layer": 0}, cfg, "ragged_dot")
        routed.append(np.asarray(m, np.float64))
        assert int(hist[:4].sum() + hist[5]) == 16 * CFG.num_experts_per_token  # held here + absent
    np.testing.assert_allclose(total + sum(routed), want, rtol=2e-4, atol=2e-4)
    assert min(np.abs(r).max() for r in routed) > 1e-2  # each share does add something


# -- two lifetimes in one sequence ---------------------------------------------------


@pytest.mark.parametrize("cached,back,depth", [
    ("1111", 2, 4), ("1101", 2, 2), ("0011", 2, 4), ("0001", 2, 0), ("1000", 2, 1), ("0110", 2, 3),
    ("0101", 1, 4), ("1110", 1, 3), ("", 2, 0), ("0000", 3, 0), ("0111", 3, 4), ("1011", 3, 1),
])
def test_a_hit_is_as_deep_as_the_deepest_block_whose_window_blocks_are_resident(cached, back, depth):
    pool = BlockPool(16, BS)
    hashes = list(range(100, 100 + len(cached)))
    for h, c in zip(hashes, cached):
        if c == "1":
            pool.register_block(pool.allocate_block(), h, None)
    assert pool.window_depth(hashes, back) == depth


def test_what_a_sequence_wrote_and_passed_is_evicted_before_what_may_be_resumed_from():
    pool = BlockPool(5, BS)  # 4 blocks
    bids = [pool.allocate_block() for _ in range(4)]
    for i, bid in enumerate(bids[:3]):
        pool.register_block(bid, 100 + i, None)
    pool.free_sequence([bids[0]])             # a boundary: warm
    pool.free_sequence([bids[1]], cold=True)  # passed behind the window
    pool.free_sequence([bids[2]])
    pool.free_sequence([bids[3]], cold=True)  # never sealed: free at once
    assert pool.released == {"cached": 3, "free": 1} and pool.num_cached == 3
    assert pool.allocate_block() == bids[3] and pool.allocate_block() == bids[1]  # the free one, then the cold one
    assert pool.match_prefix([100]) == [bids[0]] and pool.evictions == 1
    claimed = pool.claim([100, 102])
    assert claimed == [bids[0], bids[2]] and pool.num_cached == 0


def test_the_lru_passes_a_spared_block_over_until_nothing_else_is_left():
    pool = BlockPool(10, BS)  # 9 blocks, of which the pool spares at most 3 (a quarter of 10, rounded up)
    bids = [pool.allocate_block() for _ in range(6)]
    for i, bid in enumerate(bids):
        pool.register_block(bid, 100 + i, None)
    pool.free_sequence(bids[:4], spare=True)  # the oldest of the LRU; the fourth is over the quarter: given back plain
    pool.free_sequence(bids[4:])
    assert pool._spared == set(bids[:3]) and pool.num_cached == 6
    got = [pool.allocate_block() for _ in range(3)]  # the free three first
    assert pool.evictions == 0 and [pool.allocate_block() for _ in range(3)] == bids[3:]  # then the plain ones, oldest first
    assert pool.match_prefix([100, 101, 102]) == bids[:3]
    assert pool.claim([100]) == [bids[0]] and bids[0] not in pool._spared  # a holder takes it out of the spared
    pool.free_sequence([bids[0]])                                          # given back plain, it is plain: the newest, the first to go
    assert [pool.allocate_block() for _ in range(3)] == bids[:3] and not pool._spared and pool.evictions == 6
    pool.free_sequence(got)


def greedy(prompt_ids, max_tokens=6, **ktp) -> PreprocessedRequest:
    req = PreprocessedRequest(model="t", token_ids=list(prompt_ids))
    req.sampling.temperature = 0.0
    req.sampling.seed = 0
    req.stop.max_tokens = max_tokens
    req.stop.ignore_eos = True
    if ktp:
        req.kv_transfer_params = ktp
    return req


def engine_args(**kw) -> EngineArgs:
    return EngineArgs(**{**dict(model=CFG, block_size=BS, num_kv_blocks=64, max_num_seqs=4, max_model_len=256,
                                max_prefill_tokens=32, dtype="float32", decode_steps=4), **kw})


async def _tokens(engine, req) -> list[int]:
    return [t async for o in engine.generate(req, Context()) for t in o.get("token_ids", [])]


def _alone(tokens: list[int], n: int) -> list[int]:
    async def go():
        engine = await TpuEngine(engine_args()).start()
        try:
            return await _tokens(engine, greedy(tokens, n))
        finally:
            await engine.stop()
    return asyncio.run(go())


def _poison_what_is_given_back(engine) -> None:
    """After every release, every free block of the window pool is overwritten
    on the device: a program dispatched later that read one would read 1e4s."""
    release = engine.side._release

    def poisoned(seq, indices, final=False):
        release(seq, indices, final)
        free = jnp.asarray(sorted(engine.side.pool._free), jnp.int32)
        cache = engine._runner.cache
        engine._runner.cache = cache._replace(window=cache.window.at[:, free].set(1e4))

    engine.side._release = poisoned


@pytest.mark.parametrize("evict", ["nothing", "the_boundary", "everything"])
def test_a_follow_up_turn_resumes_where_its_window_blocks_are_resident(evict):
    """A session's next turn resumes at its history's last sealed block when the
    window blocks before it are resident (its own were the live ones a moment
    ago), at an earlier block when the boundary's was evicted, from zero when
    all were: cut back, never wrong. Every window block a sequence gives back
    unsealed is poisoned on the device; the tokens are a cold engine's."""
    first = prompt(70, seed=1)

    async def go():
        engine = await TpuEngine(engine_args()).start()
        _poison_what_is_given_back(engine)
        try:
            a = await _tokens(engine, greedy(first, 20))
            second = first + a + prompt(13, seed=2)
            wp = engine.side.pool

            def drop():  # on the scheduler thread
                if evict == "everything":
                    return wp.clear()
                if evict == "the_boundary":  # the deepest cached block of the chain alone
                    deepest = next(wp._cached[h] for h in reversed(compute_block_hashes(second, BS)) if h in wp._cached)
                    wp._lru.pop(deepest)
                    wp._evict(deepest)
                    wp._free.append(deepest)
                return None

            await engine.run_on_engine_thread(drop)
            b = await _tokens(engine, greedy(second, 8))
            # on the scheduler thread: a stream's last delta is posted before its blocks go back
            active = await engine.run_on_engine_thread(lambda: wp.num_active)
            return a, second, b, dict(engine.side.stats), dict(wp.released), active
        finally:
            await engine.stop()

    a, second, b, stats, released, active = asyncio.run(go())
    want = {"nothing": "deepest", "the_boundary": "cut_back", "everything": "miss"}[evict]
    assert {k: stats[k] for k in ("deepest", "cut_back", "miss")} == {
        "deepest": 0, "cut_back": 0, "miss": 0, want: 1}
    assert (stats["recomputed_tokens"] > 0) == (evict != "nothing")
    assert released["cached"] > 0 and released["free"] > 0 and active == 0
    assert b == _alone(second, 8) and a == _alone(first, 20)


def test_what_the_engine_serves_is_the_references_best_at_every_token():
    """The whole path in float32 against the reference, where a fault in a
    table would be the same in every engine and so invisible to a comparison of
    engines: a 150-token prompt in chunks of 32, 40 tokens in windows of 4, then
    the next turn resumed behind its cached history: 70 served tokens, each the
    reference's best (contexts of 150-243 tokens: 12 chosen of them, a window of 7)."""
    async def go():
        engine = await TpuEngine(engine_args(max_model_len=512, num_kv_blocks=128)).start()
        try:
            first = prompt(150, seed=1)
            a = await _tokens(engine, greedy(first, 40))
            second = first + a + prompt(23, seed=2)
            return first, a, second, await _tokens(engine, greedy(second, 30)), dict(engine.side.stats)
        finally:
            await engine.stop()

    first, a, second, b, stats = asyncio.run(go())
    assert stats["deepest"] == 1 and stats["dense"] == 0
    doc = doc_for("float32")
    w = REF.weights(doc, 0)
    for history, served in ((first, a), (second, b)):
        logits = np.asarray(REF.forward(doc, w, history + served), np.float32)
        at = np.arange(len(history) - 1, len(history) + len(served) - 1)
        assert (logits[at].argmax(-1) == np.asarray(served)).all()


def test_a_shared_prompts_end_is_kept_for_the_next_to_share_it():
    """The first to run a prompt gives its window blocks back as it passes them
    (those a chunked prefill passes before its last chunk are unsealed: free). The second to share its first 64
    tokens finds the full-layer pages and no window block: it starts over, and
    keeps the blocks before the shared part's end until they are registered. The
    third resumes at the shared part's end."""
    shared = prompt(64, seed=5)

    async def go():
        engine = await TpuEngine(engine_args()).start()
        _poison_what_is_given_back(engine)
        try:
            out = []
            for seed, more in ((6, 70), (7, 30), (8, 30)):
                out.append(await _tokens(engine, greedy(shared + prompt(more, seed=seed), 6)))
                out.append(dict(engine.side.stats))
            return out
        finally:
            await engine.stop()

    t1, s1, t2, s2, t3, s3 = asyncio.run(go())
    assert (s1["miss"], s2["miss"], s3["miss"]) == (0, 1, 1) and s3["deepest"] == 1 and s3["cut_back"] == 0
    assert s2["recomputed_tokens"] == 64 == s3["recomputed_tokens"]
    assert t3 == _alone(shared + prompt(30, seed=8), 6) and t2 == _alone(shared + prompt(30, seed=7), 6)


def test_a_shared_prompts_end_is_spared_while_the_lru_holds_anything_else():
    """Of what an admission claimed, the blocks before a block that a second chain
    continues from (a document's end: the next session to start over on it
    resumes there) go back spared; a turn's own last boundary, which its next
    turn touches within a think time, goes back as it did. The LRU then evicts
    every other block before a spared one, however old that is."""
    shared = prompt(64, seed=5)

    async def go():
        engine = await TpuEngine(engine_args()).start()
        try:
            for seed in (6, 7):  # two chains leave the document's end
                await _tokens(engine, greedy(shared + prompt(30, seed=seed), 6))
            first = shared + prompt(30, seed=8)
            second = first + await _tokens(engine, greedy(first, 6)) + prompt(13, seed=9)  # claims the shared end
            await _tokens(engine, greedy(second, 12))                                    # claims its own last boundary
            wp = engine.side.pool
            own, end = compute_block_hashes(second, BS)[len(first + [0] * 5) // BS - 1], compute_block_hashes(shared, BS)[-1]

            def squeeze():  # on the scheduler thread: take every block the pool can give
                spared = {h for h, bid in wp._cached.items() if bid in wp._spared}
                fanout = engine.pool.hash_fanout(own), engine.pool.hash_fanout(end)
                taken = [wp.allocate_block() for _ in range(wp.num_free - 1)]
                left = set(wp._cached)
                wp.free_sequence(taken)
                return spared, fanout, left
            return own, end, await engine.run_on_engine_thread(squeeze), dict(engine.side.stats)
        finally:
            await engine.stop()

    own, end, (spared, fanout, left), stats = asyncio.run(go())
    assert stats["deepest"] == 3 and stats["cut_back"] == 0 == stats["miss"]  # two sessions and the second turn
    assert fanout == (1, 3)  # the turn's boundary has its one continuation; three chains leave the document's end
    assert spared == {end} and left == {end}  # the last cached block the pool gives up


def test_preempted_sequences_return_and_packed_rows_of_different_depths_agree_with_alone():
    """Three sessions whose cached histories are of different depths are sent
    at once into a pool too small for them: their prefills share waves, one is
    preempted for want of blocks and returns through admission, every released
    window block is poisoned, and each gets the tokens it gets alone."""
    async def go():
        engine = await TpuEngine(engine_args(num_kv_blocks=30, max_prefill_tokens=64)).start()
        _poison_what_is_given_back(engine)
        try:
            histories = []
            for s, n in ((1, 24), (2, 40), (3, 56)):  # turn one, one at a time
                p = prompt(n, seed=s)
                histories.append(p + await _tokens(engine, greedy(p, 6)) + prompt(9, seed=10 + s))
            n0 = sum(engine.total_preemptions_by.values())
            together = await asyncio.gather(*(_tokens(engine, greedy(h, 40)) for h in histories))
            wp = engine.side.pool
            held = await engine.run_on_engine_thread(lambda: wp.num_active)
            return histories, list(together), sum(engine.total_preemptions_by.values()) - n0, held, dict(engine.side.stats)
        finally:
            await engine.stop()

    histories, together, preempted, held, stats = asyncio.run(go())
    assert preempted > 0 and held == 0 and stats["deepest"] >= 3
    assert together == [_alone(h, 40) for h in histories]


def test_pool_accounting_is_in_bytes_of_the_three_pools():
    args = engine_args(dtype="bfloat16")
    nf, nw = len(CFG.full_layers), len(CFG.window_layers)
    assert (nf, nw) == (3, 6) and CFG.latent_page_width == 128 == CFG.swa.latent_page_width
    assert args.pool_bytes_per_block() == {"kv": nf * BS * 128 * 2, "ikeys": nf * BS * CFG.index_head_dim * 2}
    assert args.window_bytes_per_block() == nw * BS * 128 * 2
    assert (args.window_back_blocks, args.window_table_width) == (1, 3)
    cache = dots3.init_kv_cache(CFG, args.num_kv_blocks, BS, window_blocks=args.window_blocks)
    assert cache.kv.nbytes + cache.ikeys.nbytes == args.num_kv_blocks * args.kv_bytes_per_block()
    assert cache.window.nbytes == args.window_pool_bytes()
    assert CFG.param_count() == sum(a.size for a in jax.tree.leaves(
        jax.eval_shape(lambda: dots3.init_params(CFG, jax.random.PRNGKey(0)))))


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
    with pytest.raises(ValueError, match="dots3") as e:
        engine_args(**kw)
    assert names in str(e.value)


def test_a_layer_pattern_the_scan_cannot_run_is_refused():
    with pytest.raises(ValueError, match="whole periods"):
        engine_args(model=dataclasses.replace(CFG, layer_types=CFG.layer_types[:-1], num_layers=8))


@pytest.mark.parametrize("what", ["extract_pages", "transfer", "migration"])
def test_mechanisms_refuse_the_block_by_name(what):
    if what == "extract_pages":
        from dynamo_tpu.engine.runner import LocalRunner

        with pytest.raises(ValueError, match="dots3"):
            LocalRunner(engine_args()).extract_pages([1])
        return

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

    assert "window pool" in asyncio.run(go())


def test_the_runner_takes_its_programs_from_the_blocks_module():
    from dynamo_tpu.engine.runner import LocalRunner

    assert M.block_module(CFG) is dots3 and set(M.BLOCK_MODULES) == {"llama", "longcat", "lfm2", "sala", "dots3", "deepseek"}
    runner = LocalRunner(engine_args())
    runner.start()
    line = runner._start_line("")
    assert "block=dots3" in line and "experts=ragged_dot" in line and "attention: prefill=xla decode=xla" in line
    with pytest.raises(ValueError, match=r"no module runs block='mamba' \(llama, longcat, lfm2, sala, dots3, deepseek\)"):
        M.block_module(dataclasses.replace(CFG, block="mamba"))


def test_the_references_experts_over_the_tokens_that_chose_them_are_its_experts_over_every_token(monkeypatch):
    """In a row of more than 4 x ``FF_ROWS`` tokens the reference runs an expert
    over the tokens that chose it (gathered); the sums are the plain loop's."""
    doc = doc_for("float32")
    lp = REF.weights(doc, 5)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(2), (512, CFG.hidden_size), jnp.float32)
    plain = np.asarray(REF._experts(h, lp, REF.sizes(doc)))
    monkeypatch.setattr(REF, "FF_ROWS", 64)  # 512 tokens are a long row now: room for 128 an expert
    gathered = np.asarray(REF._experts(h, lp, REF.sizes(doc)))
    np.testing.assert_allclose(gathered, plain, rtol=1e-5, atol=1e-5)
    crowded = {**lp, "router_bias": lp["router_bias"].at[2].set(10.0)}  # every token chooses expert 2, the first held: over its room
    over = np.asarray(REF._experts(h, crowded, REF.sizes(doc)))
    monkeypatch.setattr(REF, "FF_ROWS", 4096)
    np.testing.assert_allclose(over, np.asarray(REF._experts(h, crowded, REF.sizes(doc))), rtol=1e-5, atol=1e-5)


def test_the_references_spans_of_a_long_row_are_the_row_at_once(monkeypatch):
    """A row of four query blocks or more is attended a span at a time, each
    against the positions up to its own end: the logits are the whole row's."""
    doc = doc_for("float32")
    w = REF.weights(doc, 0)
    toks = prompt(2048, seed=4)
    at = list(range(1500, 2048, 37))
    spans = np.asarray(REF.forward(doc, w, toks, positions=at, starts=(0, 900)))
    monkeypatch.setattr(REF, "SPANS", 1)
    jax.clear_caches()
    np.testing.assert_allclose(spans, np.asarray(REF.forward(doc, w, toks, positions=at, starts=(0, 900))), rtol=1e-4, atol=1e-4)
