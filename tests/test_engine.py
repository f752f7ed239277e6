"""Engine tests (CPU, 8 virtual devices via conftest).

Correctness strategy mirrors the reference's engine-trust model: the paged
model is cross-checked against an independent naive dense implementation
written here (different code path, same params), then the continuous-
batching engine is exercised through its async API.
"""

import asyncio

import numpy as np
import pytest
from engine_waves import one_wave

import jax
import jax.numpy as jnp

from dynamo_tpu.block_manager.pool import BlockPool, NoFreeBlocksError
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols import FinishReason, PreprocessedRequest
from dynamo_tpu.runtime.engine import Context

CFG = ModelConfig()  # test-tiny


# ---------------------------------------------------------------------------
# Naive reference forward (dense causal attention, no paging)
# ---------------------------------------------------------------------------


def naive_forward(cfg: ModelConfig, params, token_ids: list[int]) -> np.ndarray:
    """Logits for every position, computed with plain dense attention."""
    x = params["embed"][jnp.asarray(token_ids)]
    T = len(token_ids)
    positions = jnp.arange(T)
    G = cfg.num_heads // cfg.num_kv_heads

    def rms(h, w):
        hf = h.astype(jnp.float32)
        return (hf * jax.lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True) + cfg.rms_norm_eps)
                * w.astype(jnp.float32)).astype(h.dtype)

    lp = params["layers"]
    for li in range(cfg.num_layers):
        h = rms(x, lp["attn_norm"][li])
        q = (h @ lp["wq"][li]).reshape(T, cfg.num_heads, cfg.head_dim)
        k = (h @ lp["wk"][li]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"][li]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
        q = M._rope(q, positions, cfg.rope_theta)
        k = M._rope(k, positions, cfg.rope_theta)
        qg = q.reshape(T, cfg.num_kv_heads, G, cfg.head_dim)
        s = jnp.einsum("tkgh,skh->tkgs", qg, k).astype(jnp.float32) * cfg.head_dim**-0.5
        mask = jnp.where(jnp.arange(T)[None, :] <= jnp.arange(T)[:, None], 0.0, -1e9)
        s = s + mask[:, None, None, :]
        p = jax.nn.softmax(s, -1).astype(x.dtype)
        o = jnp.einsum("tkgs,skh->tkgh", p, v).reshape(T, cfg.q_size)
        x = x + o @ lp["wo"][li]
        h = rms(x, lp["mlp_norm"][li])
        g = h @ lp["w_gate"][li]
        u = h @ lp["w_up"][li]
        x = x + (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u) @ lp["w_down"][li]
    x = rms(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return np.asarray((x @ head).astype(jnp.float32))


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)


def test_prefill_matches_naive(params):
    bs = 4
    cache = M.init_kv_cache(CFG, 16, bs, jnp.float32)
    prompt = list(range(1, 11))  # 10 tokens
    table = np.zeros((8,), np.int32)
    table[:3] = [1, 2, 3]
    t_pad = 12
    toks = np.zeros((t_pad,), np.int32)
    toks[: len(prompt)] = prompt
    logits, cache = M.prefill(
        CFG, params, cache, jnp.asarray(toks), jnp.asarray(table),
        jnp.int32(0), jnp.int32(len(prompt)),
    )
    ref = naive_forward(CFG, params, prompt)
    np.testing.assert_allclose(np.asarray(logits), ref[-1], rtol=2e-4, atol=2e-4)


def test_decode_matches_naive(params):
    bs = 4
    cache = M.init_kv_cache(CFG, 16, bs, jnp.float32)
    prompt = list(range(1, 10))  # 9 tokens → block 3 partially filled
    table = np.zeros((8,), np.int32)
    table[:3] = [1, 2, 3]
    t_pad = 12
    toks = np.zeros((t_pad,), np.int32)
    toks[: len(prompt)] = prompt
    _, cache = M.prefill(
        CFG, params, cache, jnp.asarray(toks), jnp.asarray(table),
        jnp.int32(0), jnp.int32(len(prompt)),
    )
    # decode one new token (id 42) at position 9
    full = prompt + [42]
    tables = np.zeros((2, 8), np.int32)
    tables[0, :3] = [1, 2, 3]
    logits, cache = M.decode_step(
        CFG, params, cache,
        jnp.asarray(np.array([42, 0], np.int32)),
        jnp.asarray(np.array([9, 0], np.int32)),
        jnp.asarray(tables),
        jnp.asarray(np.array([True, False])),
    )
    ref = naive_forward(CFG, params, full)
    np.testing.assert_allclose(np.asarray(logits)[0], ref[-1], rtol=2e-4, atol=2e-4)


def test_prefix_cached_prefill_matches_full(params):
    """Prefill with start_pos>0 over cached blocks == prefill from scratch."""
    bs = 4
    prompt = list(range(7, 27))  # 20 tokens = 5 blocks
    table = np.zeros((8,), np.int32)
    table[:5] = [1, 2, 3, 4, 5]
    t_pad = 20

    cache = M.init_kv_cache(CFG, 16, bs, jnp.float32)
    toks = np.zeros((t_pad,), np.int32)
    toks[:20] = prompt
    full_logits, cache = M.prefill(
        CFG, params, cache, jnp.asarray(toks), jnp.asarray(table),
        jnp.int32(0), jnp.int32(20),
    )
    # Now pretend the first 3 blocks (12 tokens) were cache hits: rerun only
    # the suffix against the SAME cache (prefix blocks already populated).
    sfx = np.zeros((8,), np.int32)
    sfx[:8] = prompt[12:]
    sfx_logits, cache = M.prefill(
        CFG, params, cache, jnp.asarray(sfx), jnp.asarray(table),
        jnp.int32(12), jnp.int32(20),
    )
    np.testing.assert_allclose(
        np.asarray(sfx_logits), np.asarray(full_logits), rtol=2e-4, atol=2e-4
    )


# ---------------------------------------------------------------------------
# Block pool
# ---------------------------------------------------------------------------


def test_pool_prefix_reuse_and_events():
    events = []
    pool = BlockPool(8, 4, event_sink=events.append)
    ids, hit = pool.allocate_sequence([101, 102], 3)
    assert hit == 0 and len(ids) == 3
    pool.register_block(ids[0], 101, None)
    pool.register_block(ids[1], 102, 101)
    assert [e.kind for e in events] == ["stored", "stored"]
    pool.free_sequence(ids)
    # Same prefix → reuse both registered blocks.
    ids2, hit2 = pool.allocate_sequence([101, 102], 3)
    assert hit2 == 2 and ids2[:2] == ids[:2]
    pool.free_sequence(ids2)


def test_pool_eviction_emits_removed():
    events = []
    pool = BlockPool(4, 4, event_sink=events.append)  # 3 usable
    ids, _ = pool.allocate_sequence([], 3)
    for i, bid in enumerate(ids):
        pool.register_block(bid, 100 + i, None)
    pool.free_sequence(ids)          # all cached now
    ids2, hit = pool.allocate_sequence([999], 3)  # no match → must evict all
    assert hit == 0
    kinds = [e.kind for e in events]
    assert kinds.count("removed") >= 1
    pool.free_sequence(ids2)


def test_pool_exhaustion_raises():
    pool = BlockPool(4, 4)
    pool.allocate_sequence([], 3)
    with pytest.raises(NoFreeBlocksError):
        pool.allocate_sequence([], 1)


def test_pool_clear_emits_exact_removed_hashes():
    """clear() drops only ref-0 cached blocks and emits `removed` with
    exactly those hashes — referenced blocks stay registered so remote
    indexers don't desync (ADVICE r2)."""
    events = []
    pool = BlockPool(8, 4, event_sink=events.append)
    held, _ = pool.allocate_sequence([], 2)
    pool.register_block(held[0], 11, None)
    pool.register_block(held[1], 12, 11)
    idle, _ = pool.allocate_sequence([], 2)
    pool.register_block(idle[0], 21, None)
    pool.register_block(idle[1], 22, 21)
    pool.free_sequence(idle)  # → cached, evictable
    events.clear()
    dropped = pool.clear()
    assert dropped == 2
    assert len(events) == 1 and events[0].kind == "removed"
    assert sorted(events[0].block_hashes) == [21, 22]
    # Held blocks still prefix-matchable; idle ones gone.
    assert pool.match_prefix([11, 12]) == held
    assert pool.match_prefix([21]) == []


# ---------------------------------------------------------------------------
# Engine (async API)
# ---------------------------------------------------------------------------


def make_args(**kw) -> EngineArgs:
    defaults = dict(
        model=CFG, block_size=4, num_kv_blocks=64, max_num_seqs=4,
        max_model_len=128, max_prefill_tokens=64, dtype="float32",
    )
    defaults.update(kw)
    return EngineArgs(**defaults)


def greedy_request(prompt, max_tokens=8, **kw) -> PreprocessedRequest:
    req = PreprocessedRequest(model="t", token_ids=list(prompt))
    req.sampling.temperature = 0.0
    req.sampling.seed = 0  # greedy, but unseeded requests draw global RNG (DT004)
    req.stop.max_tokens = max_tokens
    for k, v in kw.items():
        setattr(req.stop, k, v)
    return req


async def run_one(engine, req, ctx=None):
    outs = []
    async for item in engine.generate(req, ctx or Context()):
        outs.append(item)
    return outs


def collect_tokens(outs):
    return [t for o in outs for t in o.get("token_ids", [])]


def test_engine_greedy_deterministic():
    async def go():
        engine = await TpuEngine(make_args()).start()
        try:
            a = await run_one(engine, greedy_request([1, 2, 3, 4, 5], 8))
            b = await run_one(engine, greedy_request([1, 2, 3, 4, 5], 8))
            assert collect_tokens(a) == collect_tokens(b)
            assert len(collect_tokens(a)) == 8
            assert a[-1]["finish_reason"] == "length"
            return a
        finally:
            await engine.stop()

    asyncio.run(go())


def test_engine_prefix_cache_hit_and_same_output():
    async def go():
        engine = await TpuEngine(make_args()).start()
        try:
            prompt = list(range(1, 21))  # 20 tokens = 5 blocks of 4
            a = await run_one(engine, greedy_request(prompt, 6))
            assert engine.pool.hit_blocks == 0
            b = await run_one(engine, greedy_request(prompt, 6))
            # max-hit rule: (20-1)//4 = 4 blocks reusable
            assert engine.pool.hit_blocks == 4
            assert collect_tokens(a) == collect_tokens(b)
        finally:
            await engine.stop()

    asyncio.run(go())


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_engine_cached_prefix_token_ids_pinned(kv_quant):
    """A fixed prompt at temperature 0, served twice and then forked after
    five blocks: the second and third answers are prefilled over cached
    pages. The ids are the parent commit's (c34b524, recorded before PR 28
    changed how prefill reads prefix pages out of the pool)."""
    async def go():
        engine = await TpuEngine(make_args(kv_quant=kv_quant)).start()
        try:
            prompt = [(7 * i + 3) % 97 + 1 for i in range(27)]
            fork = prompt[:20] + [200 + 3 * i for i in range(9)]
            a = await run_one(engine, greedy_request(prompt, 6))
            assert engine.pool.hit_blocks == 0
            b = await run_one(engine, greedy_request(prompt, 6))
            assert engine.pool.hit_blocks == 6
            c = await run_one(engine, greedy_request(fork, 6))
            assert engine.pool.hit_blocks == 11
            assert collect_tokens(a) == [495, 228, 109, 109, 109, 109]
            assert collect_tokens(b) == [495, 228, 109, 109, 109, 109]
            assert collect_tokens(c) == [109, 109, 109, 109, 109, 237]
        finally:
            await engine.stop()

    asyncio.run(go())


def test_engine_eos_stops_generation():
    async def go():
        engine = await TpuEngine(make_args()).start()
        try:
            prompt = [5, 6, 7, 8]
            first = collect_tokens(await run_one(engine, greedy_request(prompt, 4)))
            # Re-run declaring the first generated token as EOS → immediate stop.
            req = greedy_request(prompt, 4)
            req.eos_token_ids = [first[0]]
            outs = await run_one(engine, req)
            toks = collect_tokens(outs)
            assert toks == [first[0]]
            assert outs[-1]["finish_reason"] == "stop"
            # ignore_eos generates past it
            req2 = greedy_request(prompt, 4)
            req2.eos_token_ids = [first[0]]
            req2.stop.ignore_eos = True
            assert len(collect_tokens(await run_one(engine, req2))) == 4
        finally:
            await engine.stop()

    asyncio.run(go())


def test_engine_concurrent_requests():
    async def go():
        engine = await TpuEngine(make_args()).start()
        try:
            prompts = [[i, i + 1, i + 2] for i in range(1, 9)]
            results = await asyncio.gather(
                *(run_one(engine, greedy_request(p, 5)) for p in prompts)
            )
            for outs in results:
                assert len(collect_tokens(outs)) == 5
                assert outs[-1]["finish_reason"] == "length"
            # batched decode must agree with solo decode
            solo = await run_one(engine, greedy_request(prompts[0], 5))
            assert collect_tokens(results[0]) == collect_tokens(solo)
        finally:
            await engine.stop()

    asyncio.run(go())


def test_engine_cancellation():
    async def go():
        engine = await TpuEngine(make_args()).start()
        try:
            ctx = Context()
            req = greedy_request([1, 2, 3], 10_000)
            req.stop.max_tokens = None  # run "forever" (until max_model_len)
            got = []

            async def consume():
                async for item in engine.generate(req, ctx):
                    got.append(item)
                    if len(got) == 3:
                        ctx.cancel()

            await asyncio.wait_for(consume(), timeout=30)
            assert got, "should have received some tokens"
        finally:
            await engine.stop()

    asyncio.run(go())


def test_engine_preemption_recovers():
    async def go():
        # Tiny pool: 2 concurrent long generations must force preemption.
        engine = await TpuEngine(
            make_args(num_kv_blocks=14, max_model_len=32, max_num_seqs=2)
        ).start()
        try:
            p1, p2 = [1, 2, 3, 4, 5, 6], [9, 8, 7, 6, 5, 4]
            r1, r2 = await asyncio.gather(
                run_one(engine, greedy_request(p1, 20)),
                run_one(engine, greedy_request(p2, 20)),
            )
            # Both finish; preempted one recomputes and still yields 20 tokens
            # (token-for-token identical to a solo run, since greedy).
            solo1 = await run_one(engine, greedy_request(p1, 20))
            assert collect_tokens(r1) == collect_tokens(solo1)
            assert len(collect_tokens(r2)) == 20
        finally:
            await engine.stop()

    asyncio.run(go())


def test_engine_prefix_hit_after_sealed_tail_block_is_correct():
    """Regression: a block sealed by the final sampled token must NOT be
    prefix-hit later — its tail KV was never written (the token would only
    be written by a next decode step that never ran)."""

    async def go():
        engine = await TpuEngine(make_args()).start()
        fresh = await TpuEngine(make_args()).start()
        try:
            prompt = [1, 2, 3, 4]  # 1 full block of 4
            a = collect_tokens(await run_one(engine, greedy_request(prompt, 4)))
            # a[3] sealed block 1 at emit time; its KV is unwritten.
            follow = prompt + a
            b_warm = collect_tokens(await run_one(engine, greedy_request(follow, 3)))
            b_fresh = collect_tokens(await run_one(fresh, greedy_request(follow, 3)))
            assert b_warm == b_fresh
        finally:
            await engine.stop()
            await fresh.stop()

    asyncio.run(go())


def test_engine_seeded_sampling_reproducible():
    async def go():
        engine = await TpuEngine(make_args()).start()
        try:
            def seeded(seed):
                req = greedy_request([3, 1, 4, 1, 5], 8)
                req.sampling.temperature = 0.9
                req.sampling.seed = seed
                return req

            a = collect_tokens(await run_one(engine, seeded(7)))
            b = collect_tokens(await run_one(engine, seeded(7)))
            c = collect_tokens(await run_one(engine, seeded(8)))
            assert a == b
            assert a != c  # overwhelmingly likely with temp 0.9
        finally:
            await engine.stop()

    asyncio.run(go())


def test_engine_frequency_penalty_discourages_repeats():
    async def go():
        engine = await TpuEngine(make_args()).start()
        try:
            req = greedy_request([2, 2, 2], 12)
            base = collect_tokens(await run_one(engine, req))
            req2 = greedy_request([2, 2, 2], 12)
            req2.sampling.frequency_penalty = 2.0
            pen = collect_tokens(await run_one(engine, req2))
            # greedy with a strong penalty must diverge from unpenalized
            # greedy whenever the base repeats a token
            if len(set(base)) < len(base):
                assert pen != base
            # penalized run has strictly fewer repeats than an all-same run
            assert len(set(pen)) > 1 or len(set(base)) == 1
        finally:
            await engine.stop()

    asyncio.run(go())


def test_engine_multi_step_matches_single_step():
    """Fused multi_decode (decode_steps>1) must reproduce the per-step
    path exactly — greedy and seeded sampling."""

    async def go():
        multi = await TpuEngine(make_args(decode_steps=8)).start()
        single = await TpuEngine(make_args(decode_steps=1)).start()
        try:
            prompt = [4, 5, 6, 7, 8]
            a = collect_tokens(await run_one(multi, greedy_request(prompt, 13)))
            b = collect_tokens(await run_one(single, greedy_request(prompt, 13)))
            assert a == b and len(a) == 13

            def seeded():
                r = greedy_request(prompt, 13)
                r.sampling.temperature = 0.8
                r.sampling.seed = 123
                return r

            c = collect_tokens(await run_one(multi, seeded()))
            d = collect_tokens(await run_one(single, seeded()))
            assert c == d
        finally:
            await multi.stop()
            await single.stop()

    asyncio.run(go())


def test_engine_rejects_bad_input_without_dying():
    """Malformed requests error their own stream; the engine survives."""

    async def go():
        engine = await TpuEngine(make_args()).start()
        try:
            bad_empty = await run_one(engine, greedy_request([], 4))
            assert bad_empty[-1]["finish_reason"] == "error"
            bad_range = await run_one(engine, greedy_request([1, -5], 4))
            assert bad_range[-1]["finish_reason"] == "error"
            ok = await run_one(engine, greedy_request([1, 2, 3], 4))
            assert ok[-1]["finish_reason"] == "length"
        finally:
            await engine.stop()

    asyncio.run(go())


def test_engine_metrics_snapshot():
    async def go():
        engine = await TpuEngine(make_args()).start()
        try:
            await run_one(engine, greedy_request([1, 2, 3], 3))
            m = engine.metrics()
            assert m.worker.request_total_slots == 4
            assert m.kv.kv_total_blocks == 63
        finally:
            await engine.stop()

    asyncio.run(go())


def test_engine_pipelined_windows_parity():
    """The window pipeline (one in-flight window, stops discovered a
    window late) must produce identical greedy streams to the unpipelined
    engine, across stop positions that land mid-window, at window edges,
    and under concurrent mixed lengths."""

    async def collect(pipeline: bool):
        engine = await TpuEngine(
            make_args(decode_steps=4, pipeline_windows=pipeline, max_num_seqs=8,
                      num_kv_blocks=256)
        ).start()
        try:
            reqs = [
                greedy_request([1, 2, 3], 1),       # stops inside first window
                greedy_request([4, 5, 6, 7], 4),    # exactly one window
                greedy_request([8, 9], 6),          # mid second window
                greedy_request(list(range(10, 25)), 13),
            ]
            outs = await asyncio.gather(*(run_one(engine, r) for r in reqs))
            return [collect_tokens(o) for o in outs]
        finally:
            await engine.stop()

    async def go():
        a = await collect(True)
        b = await collect(False)
        assert a == b
        assert [len(x) for x in a] == [1, 4, 6, 13]

    asyncio.run(go())


def test_engine_pipelined_preemption_recovers():
    """KV pressure with an in-flight window: the engine must drain before
    preempting so no generated tokens are lost."""

    async def go():
        engine = await TpuEngine(
            make_args(decode_steps=4, pipeline_windows=True, max_num_seqs=2,
                      num_kv_blocks=24, max_model_len=64)
        ).start()
        try:
            outs = await asyncio.gather(
                run_one(engine, greedy_request([1, 2, 3, 4], 20)),
                run_one(engine, greedy_request([5, 6, 7, 8], 20)),
            )
            for o in outs:
                toks = collect_tokens(o)
                assert len(toks) == 20, f"lost tokens: {len(toks)}"
                assert o[-1]["finish_reason"] == "length"
        finally:
            await engine.stop()

    asyncio.run(go())


def test_engine_long_prompt_chunked_with_packed_wave():
    """A prompt whose suffix exceeds max_prefill_tokens takes the chunked
    singles path ([V] logits) while short prompts in the same wave pack
    ([Bp, V] rows); the mixed first-token sampling wave must handle both
    shapes (regression: row index on a [V] ref crashed the loop)."""

    async def go():
        engine = await TpuEngine(
            make_args(max_prefill_tokens=16, max_model_len=256, num_kv_blocks=128)
        ).start()
        try:
            outs = await asyncio.gather(
                run_one(engine, greedy_request(list(range(1, 100)), 5)),  # 99 > 16
                run_one(engine, greedy_request([1, 2, 3], 5)),
            )
            for o in outs:
                assert len(collect_tokens(o)) == 5
                assert o[-1]["finish_reason"] == "length"
        finally:
            await engine.stop()

    asyncio.run(go())


def test_engine_embed_chunk_pools_long_input():
    """Inputs beyond max_prefill_tokens chunk-pool (token-weighted mean
    of per-chunk embeddings) instead of erroring (VERDICT r4 weak #8);
    only max_model_len rejects."""

    async def go():
        engine = await TpuEngine(
            make_args(max_prefill_tokens=16, max_model_len=128, num_kv_blocks=128)
        ).start()
        try:
            short = await engine.embed([1, 2, 3])
            assert len(short) == CFG.hidden_size

            long_ids = [(7 * i) % 500 + 1 for i in range(40)]  # 3 chunks
            pooled = await engine.embed(long_ids)
            assert len(pooled) == CFG.hidden_size

            # Exact contract: token-weighted mean of per-chunk embeddings.
            chunks = [long_ids[i : i + 16] for i in range(0, 40, 16)]
            parts = [np.asarray(await engine.embed(c)) * len(c) for c in chunks]
            expect = sum(parts) / len(long_ids)
            np.testing.assert_allclose(np.asarray(pooled), expect, rtol=1e-5)

            with pytest.raises(Exception, match="max_model_len"):
                await engine.embed(list(range(1, 200)))
        finally:
            await engine.stop()

    asyncio.run(go())


def test_engine_packed_prefill_matches_singles(monkeypatch):
    """A wave's suffixes packed as rows of one dispatch (the programs the
    runner compiled at start) must produce the same greedy tokens as the
    singles a runner without those programs falls back to."""

    async def run_wave(packs: bool):
        engine = TpuEngine(make_args(max_num_seqs=8, num_kv_blocks=128))
        if not packs:
            monkeypatch.setattr(engine._runner, "_start_pack_compiles", lambda: None)
        await engine.start()
        try:
            assert engine._runner.packed_ready == (frozenset({(2, 32)}) if packs else frozenset())
            prompts = [[(7 * j + i) % 500 + 1 for j in range(10 + i)] for i in range(5)]
            outs = await one_wave(engine, [run_one(engine, greedy_request(p, 6)) for p in prompts])
            return [collect_tokens(o) for o in outs], dict(engine.prefill_dispatch_rows)
        finally:
            await engine.stop()

    async def go():
        packed, rows = await run_wave(True)
        singles, rows_singles = await run_wave(False)
        assert packed == singles
        assert all(len(t) == 6 for t in packed)
        assert rows == {2: 2, 1: 1} and rows_singles == {1: 5}

    asyncio.run(go())
