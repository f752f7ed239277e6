"""Golden-equivalence suite for speculative decoding.

The speculative path (n-gram prompt-lookup drafts + single-pass batched
verification, engine/drafter.py + model.spec_verify) may change HOW
tokens are produced but never WHAT is produced at greedy: for any
workload, spec-on streams (tokens, logprobs, top_logprobs, finish
reasons) must be byte-identical to the dense path across draft lengths,
pipeline depths, stops landing mid-draft, max_tokens boundaries inside
an accepted run, and preemption during an in-flight verify. Sampled
rows keep their exact output distribution (rejection sampling); rows
that never draft ride the dense RNG stream, so they too are
byte-identical. CPU, test-tiny model, every request explicitly seeded
(PR 4 lesson: unseeded requests perturb the global RNG stream and flip
downstream sampling-dependent tests).

Stop STRINGS are a backend concern (jail scan over decoded text); the
engine-level stop is the eos token id, exercised here mid-draft — the
backend sees the same truncated token stream either way.
"""

import asyncio

import pytest
from engine_waves import one_wave

from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.drafter import NgramDrafter
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.engine import Context

CFG = ModelConfig()  # test-tiny

# Tiled patterns make the PROMPT n-gram-rich; acceptance then comes from
# the model's own repetitive generation (greedy decode of the tiny
# random-weight model settles into loops the drafter predicts).
LOOPY = ([1, 2, 3] * 6, [7, 8, 9, 4] * 4, [5, 6] * 8)


def spec_args(S: int, depth: int = 0, gate: float = 0.0, fused: bool = False,
              **kw) -> EngineArgs:
    # fused=False by default: the stepwise verify is bitwise identical to
    # the dense path BY CONSTRUCTION (same compiled decode step body), so
    # the byte-identity goldens hold on every backend — including this
    # suite's 8-virtual-device CPU platform, where the fused forward's
    # batched matmul reductions differ from the dense step's at the last
    # ulp. The fused path gets its own tokens-exact/logprobs-close test.
    defaults = dict(
        model=CFG, block_size=4, num_kv_blocks=256, max_num_seqs=8,
        max_model_len=128, max_prefill_tokens=64, dtype="float32",
        decode_steps=4, spec_tokens=S, spec_gate=gate, spec_fused=fused,
        pipeline_depth=depth, pipeline_windows=depth > 0,
    )
    defaults.update(kw)
    return EngineArgs(**defaults)


def request(prompt, max_tokens, temperature=0.0, seed=0, logprobs=False,
            top_logprobs=0, eos=()) -> PreprocessedRequest:
    req = PreprocessedRequest(model="t", token_ids=list(prompt))
    req.sampling.temperature = temperature
    req.sampling.seed = seed
    req.sampling.logprobs = logprobs
    req.sampling.top_logprobs = top_logprobs
    req.stop.max_tokens = max_tokens
    req.stop.ignore_eos = not eos
    req.stop.stop_token_ids = list(eos)
    return req


async def run_stream(engine, req):
    toks, lps, tops = [], [], []
    finish = None
    async for item in engine.generate(req, Context()):
        toks.extend(item.get("token_ids") or [])
        lps.extend(item.get("log_probs") or [])
        tops.extend(item.get("top_log_probs") or [])
        if item.get("finish_reason"):
            finish = item["finish_reason"]
    return toks, lps, tops, finish


def mixed_workload():
    """Loopy and incompressible prompts side by side, logprobs and
    ranked alternatives, a prefill-only row, and stops at/inside window
    and draft boundaries."""
    return [
        request(LOOPY[0], 24),
        request(LOOPY[1], 17, logprobs=True),
        request(LOOPY[2], 21, logprobs=True, top_logprobs=3),
        request([11, 13, 17, 19, 23, 29, 31, 37], 20),   # incompressible
        request([2, 4, 8], 1),                           # prefill-only
        request(list(range(40, 70)), 9),                 # odd bucket fit
    ]


async def run_workload(eargs: EngineArgs, reqs=None):
    engine = await TpuEngine(eargs).start()
    try:
        # One admission wave, spec on or off: which prefills share a packed
        # dispatch is then the workload's, not the machine's timing.
        out = await one_wave(engine, [run_stream(engine, r) for r in (reqs or mixed_workload())])
        stats = {
            "rows": engine.total_spec_rows,
            "proposed": engine.total_spec_proposed,
            "accepted": engine.total_spec_accepted,
            "emitted": engine.total_spec_emitted,
        }
        return out, stats
    finally:
        await engine.stop()


@pytest.mark.parametrize("S", [1, 2, 4, 8])
def test_spec_greedy_byte_identity(S):
    """Token, logprob and top-logprob streams must be identical with
    speculation on at every draft length — and the spec runs must have
    actually speculated (non-vacuous)."""

    async def go():
        dense, _ = await run_workload(spec_args(0))
        spec, stats = await run_workload(spec_args(S))
        assert spec == dense, f"S={S} diverged from the dense path"
        assert stats["rows"] > 0, f"S={S}: no verify pass ever dispatched"
        assert stats["accepted"] <= stats["proposed"]
        # Every live row-pass emits its accepted run plus one token.
        assert stats["emitted"] == stats["rows"] + stats["accepted"]
        for toks, _lps, _tops, finish in dense:
            assert finish == "length"

    asyncio.run(go())


@pytest.mark.parametrize("depth", [1, 2])
def test_spec_composes_with_pipeline(depth):
    """Speculation must ride the FIFO drain-order invariant alongside
    pipelined dense windows: a _Spec pass is a barrier, but before/after
    it the window pipeline runs at full depth — streams stay identical
    to the unpipelined dense engine."""

    async def go():
        dense, _ = await run_workload(spec_args(0))
        spec, stats = await run_workload(spec_args(4, depth=depth))
        assert spec == dense, f"S=4 depth={depth} diverged"
        assert stats["rows"] > 0

    asyncio.run(go())


def test_spec_stop_token_mid_draft():
    """An eos landing inside an accepted draft run must truncate the
    stream exactly where the dense path stops it (tokens past the stop
    are wasted device work, never surfaced)."""

    async def go():
        dense, _ = await run_workload(spec_args(0), [request(LOOPY[0], 24, seed=3)])
        toks = dense[0][0]
        assert len(toks) == 24
        # Stop on a token the dense stream emits mid-run (and mid-draft
        # for the spec engine, whose loop drafts run 8 deep).
        eos = toks[13]
        reqs = [request(LOOPY[0], 24, seed=3, eos=(eos,))]
        dense_stop, _ = await run_workload(spec_args(0), reqs)
        reqs = [request(LOOPY[0], 24, seed=3, eos=(eos,))]
        spec_stop, _ = await run_workload(spec_args(8), reqs)
        assert spec_stop == dense_stop
        assert spec_stop[0][3] == "stop"
        assert spec_stop[0][0][-1] == eos
        assert len(spec_stop[0][0]) < 24

    asyncio.run(go())


def test_spec_max_tokens_inside_accepted_run():
    """max_tokens boundaries landing anywhere inside an accepted run
    must truncate identically to the dense path."""

    async def go():
        for mt in (1, 2, 3, 5, 7, 10, 13):
            reqs = [request(LOOPY[0], mt, seed=1), request(LOOPY[2], mt, seed=2)]
            dense, _ = await run_workload(spec_args(0), reqs)
            reqs = [request(LOOPY[0], mt, seed=1), request(LOOPY[2], mt, seed=2)]
            spec, _ = await run_workload(spec_args(8), reqs)
            assert spec == dense, f"max_tokens={mt} diverged"
            assert all(len(s[0]) == mt for s in spec)
            assert all(s[3] == "length" for s in spec)

    asyncio.run(go())


def test_spec_preemption_golden():
    """KV pressure forces preemption-by-recompute while verifies are in
    flight; drained passes must land every kept token first and streams
    stay identical across spec on/off."""

    async def collect(S):
        engine = await TpuEngine(spec_args(
            S, max_num_seqs=2, num_kv_blocks=24, max_model_len=64,
        )).start()
        try:
            return await one_wave(engine, [
                run_stream(engine, request(LOOPY[0][:4], 20, logprobs=True)),
                run_stream(engine, request(LOOPY[1][:4], 20, logprobs=True)),
            ])
        finally:
            await engine.stop()

    async def go():
        base = await collect(0)
        for toks, lps, _tops, finish in base:
            assert len(toks) == 20 and len(lps) == 20 and finish == "length"
        for S in (2, 8):
            assert await collect(S) == base, f"S={S} diverged under preemption"

    asyncio.run(go())


def test_spec_sampled_rows():
    """Sampled rows: (a) seeded spec runs are deterministic; (b) rows
    that never draft ride the dense RNG stream byte-identically even
    inside a speculating engine; (c) drafted sampled rows may diverge
    from dense token-wise (different RNG stream) but the run completes
    with full-length streams — the distribution-preservation argument
    is rejection-sampling math, determinism is what's testable."""

    async def go():
        incompressible = [37, 11, 29, 5, 17, 2, 23, 41]
        reqs = lambda: [  # noqa: E731
            request(incompressible, 15, temperature=0.9, seed=11, logprobs=True),
            request(LOOPY[0], 15, temperature=0.7, seed=12),
            request(LOOPY[1], 15, seed=13),  # greedy row in the same batch
        ]
        dense, _ = await run_workload(spec_args(0), reqs())
        spec1, _ = await run_workload(spec_args(4), reqs())
        spec2, _ = await run_workload(spec_args(4), reqs())
        assert spec1 == spec2, "seeded speculative sampling must be deterministic"
        # The incompressible sampled row never drafts → exact dense match.
        assert spec1[0] == dense[0]
        # Greedy rows are byte-identical regardless of batch mode.
        assert spec1[2] == dense[2]
        assert all(len(s[0]) == 15 and s[3] == "length" for s in spec1)

    asyncio.run(go())


def test_spec_fused_tokens_exact_logprobs_close():
    """The fused single-pass verify (the production bandwidth path) must
    reproduce the dense GREEDY TOKEN stream exactly; its reported
    logprob values may differ from the stepwise dense kernel's at the
    last ulp (batched-matmul reduction order), so they are compared
    within tolerance rather than byte-for-byte."""

    async def go():
        dense, _ = await run_workload(spec_args(0))
        fused, stats = await run_workload(spec_args(8, fused=True))
        assert stats["rows"] > 0
        for (dt, dl, _dtop, df), (ft, fl, _ftop, ff) in zip(dense, fused):
            assert ft == dt and ff == df
            assert len(fl) == len(dl)
            for a, b in zip(dl, fl):
                assert abs(a - b) < 1e-4

    asyncio.run(go())


def test_spec_gate_disables_speculation():
    """An unattainable dispatch gate must keep the engine on the pure
    dense path (no verify ever dispatched) with identical output — the
    adaptive degradation endpoint for adversarial workloads."""

    async def go():
        dense, _ = await run_workload(spec_args(0))
        gated, stats = await run_workload(spec_args(8, gate=1e9))
        assert gated == dense
        assert stats["rows"] == 0

    asyncio.run(go())


def test_ngram_drafter():
    d = NgramDrafter(3)
    st = d.new_state()
    # No match on fresh history.
    assert d.draft([1, 2, 3, 4], st, 4) == []
    # Tail (2, 3, 4) matches the earlier occurrence; continuation + the
    # self-extending copy cycles the loop to the full requested length.
    toks = [1, 2, 3, 4, 9, 1, 2, 3, 4]
    st = d.new_state()
    assert d.draft(toks, st, 3) == [9, 1, 2]
    assert d.draft(toks, st, 8) == [9, 1, 2, 3, 4, 9, 1, 2]
    # Period-1 loop drafts max_len copies.
    st = d.new_state()
    assert d.draft([5, 6, 7, 7, 7, 7], st, 5) == [7] * 5
    # Incremental absorb: appending tokens keeps the index consistent.
    st = d.new_state()
    seq = [1, 2, 3, 4, 9]
    assert d.draft(seq, st, 4) == []
    seq += [1, 2, 3]
    assert d.draft(seq, st, 2) == [4, 9]
    # max_len=0 and short histories are safe no-ops.
    assert d.draft(seq, st, 0) == []
    assert d.draft([1, 2], d.new_state(), 4) == []
