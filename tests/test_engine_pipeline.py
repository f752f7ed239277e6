"""Golden-equivalence suite for the decode-window pipeline.

The scheduler overlaps host and device freely — async fetches, up to
``pipeline_depth`` windows in flight, prefill interleave, tail-split
prefill chunking — but none of that may change WHAT is generated: for
any workload, the pipelined engine must produce byte-identical
token/logprob/top-logprob streams to the unpipelined one, across fused
window sizes, under preemption, mid-stream cancel, and prefill-only
(max_tokens=1) rows. CPU, test-tiny model, deterministic seeds.
"""

import asyncio

import pytest
from engine_waves import one_wave

from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.engine import Context

CFG = ModelConfig()  # test-tiny

DEPTHS = (0, 1, 2)


def make_args(**kw) -> EngineArgs:
    defaults = dict(
        model=CFG, block_size=4, num_kv_blocks=256, max_num_seqs=8,
        max_model_len=128, max_prefill_tokens=64, dtype="float32",
        decode_steps=4,
    )
    defaults.update(kw)
    return EngineArgs(**defaults)


def pipelined(depth: int, **kw) -> EngineArgs:
    return make_args(pipeline_depth=depth, pipeline_windows=depth > 0, **kw)


def request(prompt, max_tokens, temperature=0.0, seed=0, logprobs=False,
            top_logprobs=0) -> PreprocessedRequest:
    # seed always set: unseeded requests draw their sample seed from the
    # GLOBAL random module inside the engine, and tests that perturb that
    # stream shift the (sampling-dependent) outcomes of later suites.
    req = PreprocessedRequest(model="t", token_ids=list(prompt))
    req.sampling.temperature = temperature
    req.sampling.seed = seed
    req.sampling.logprobs = logprobs
    req.sampling.top_logprobs = top_logprobs
    req.stop.max_tokens = max_tokens
    req.stop.ignore_eos = True
    return req


async def run_stream(engine, req, ctx=None):
    """→ flattened (tokens, logprobs, top_logprobs, finish_reason).
    Flattened because delta boundaries are consumer-timing-dependent
    (coalescing); the golden invariant is the STREAM content."""
    toks, lps, tops = [], [], []
    finish = None
    async for item in engine.generate(req, ctx or Context()):
        toks.extend(item.get("token_ids") or [])
        lps.extend(item.get("log_probs") or [])
        tops.extend(item.get("top_log_probs") or [])
        if item.get("finish_reason"):
            finish = item["finish_reason"]
    return toks, lps, tops, finish


def mixed_workload(K: int):
    """Stops inside/at/past window boundaries, prefill-only rows, seeded
    sampling, logprobs and ranked alternatives, a tail-split-length
    prompt — all concurrently."""
    return [
        request([1, 2, 3], 1),                       # prefill-only (max_tokens=1)
        request([4, 5, 6, 7], max(1, K)),            # exactly one window
        request([8, 9], K + 2),                      # mid second window
        request([3, 1, 4, 1, 5], 11, temperature=0.8, seed=7, logprobs=True),
        request([9, 2, 6], 9, logprobs=True, top_logprobs=3),
        request(list(range(10, 47)), 13),            # 37-token prompt (odd bucket fit)
        request([5, 5, 5], 1),                       # second prefill-only row
    ]


async def run_workload(eargs: EngineArgs, K: int):
    engine = await TpuEngine(eargs).start()
    try:
        # One admission wave at every depth: which prefills share a packed
        # dispatch is then the workload's, not the machine's timing.
        return await one_wave(engine, [run_stream(engine, r) for r in mixed_workload(K)])
    finally:
        await engine.stop()


@pytest.mark.parametrize("K", [1, 4])
def test_pipeline_depths_golden_equivalence(K):
    """Token, logprob and top-logprob streams must be identical for
    pipeline_depth 0/1/2 at decode_steps K — including the max_tokens=1
    prefill-only rows that never ride a window."""

    async def go():
        results = {d: await run_workload(pipelined(d, decode_steps=K), K) for d in DEPTHS}
        for d in DEPTHS[1:]:
            assert results[d] == results[0], f"depth {d} diverged from unpipelined (K={K})"
        # Sanity on the baseline itself: everything finished by length,
        # prefill-only rows emitted exactly one token.
        for toks, _lps, _tops, finish in results[0]:
            assert finish == "length"
        assert len(results[0][0][0]) == 1
        assert len(results[0][6][0]) == 1
        # logprob/top_logprob requests actually carried payloads
        assert len(results[0][3][1]) == 11
        assert len(results[0][4][2]) == 9
        assert all(len(alts) == 3 for alts in results[0][4][2])
        return results

    asyncio.run(go())


def test_pipeline_depth_preemption_golden():
    """KV pressure forces preemption-by-recompute mid-stream; drained
    windows must land every token first, so the streams stay identical
    across depths and nothing is lost."""

    async def collect(depth):
        engine = await TpuEngine(pipelined(
            depth, max_num_seqs=2, num_kv_blocks=24, max_model_len=64,
        )).start()
        try:
            return await one_wave(engine, [
                run_stream(engine, request([1, 2, 3, 4], 20, logprobs=True)),
                run_stream(engine, request([9, 8, 7, 6], 20, logprobs=True)),
            ])
        finally:
            await engine.stop()

    async def go():
        base = await collect(0)
        for toks, lps, _tops, finish in base:
            assert len(toks) == 20 and len(lps) == 20 and finish == "length"
        for depth in DEPTHS[1:]:
            assert await collect(depth) == base, f"depth {depth} diverged under preemption"

    asyncio.run(go())


@pytest.mark.parametrize("depth", DEPTHS)
def test_pipeline_mid_window_cancel(depth):
    """Cancelling a stream mid-window terminates it cleanly at every
    depth (in-flight windows drain as zombie rows), and the engine keeps
    serving identical results afterwards."""

    async def go():
        engine = await TpuEngine(pipelined(depth)).start()
        try:
            ctx = Context()
            req = request([1, 2, 3], None)
            req.stop.max_tokens = None  # run until cancelled
            got = []

            async def consume():
                async for item in engine.generate(req, ctx):
                    got.extend(item.get("token_ids") or [])
                    if len(got) >= 3:
                        ctx.cancel()

            await asyncio.wait_for(consume(), timeout=30)
            assert got, "should have received tokens before cancel"
            # Engine must still produce the canonical stream afterwards.
            fresh = await TpuEngine(pipelined(0)).start()
            try:
                after = await run_stream(engine, request([4, 5, 6, 7], 9))
                solo = await run_stream(fresh, request([4, 5, 6, 7], 9))
                assert after == solo
            finally:
                await fresh.stop()
        finally:
            await engine.stop()

    asyncio.run(go())


def test_window_size_equivalence_across_depths():
    """decode_steps 1 vs 4 must agree with each other AND across depths
    (the K=1 per-step path force-drains the queue before every step)."""

    async def go():
        k1 = await run_workload(pipelined(2, decode_steps=1), 1)
        k4 = await run_workload(pipelined(2, decode_steps=4), 1)
        assert k1 == k4

    asyncio.run(go())
