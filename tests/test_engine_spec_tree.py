"""Golden-equivalence suite for TREE speculation (topology-masked
multi-path verification + tree drafting + Jacobi pool).

Same contract as tests/test_engine_spec.py, generalized to trees: tree
speculation may change HOW tokens are produced but never WHAT is
produced at greedy — for any tree shape (width x depth), spec-on token
streams and finish reasons must be byte-identical to the dense path,
including eos/max_tokens landing mid-branch, preemption during an
in-flight tree verify, and pipeline composition. Sampled rows keep
their exact output distribution (SpecInfer multi-round rejection
sampling; the distribution math is verified at the sampler level, the
engine level pins seeded determinism + the dense-stream exactness of
never-drafting rows).

Reported logprob VALUES of tree passes ride the fused forward (a
branched topology has no stepwise decode-step equivalent), so like the
linear fused path they may differ from dense at the last ulp on this
8-virtual-device CPU backend — token streams are compared byte-for-byte,
logprobs within tolerance.

Workload note: a BRANCHED dispatch needs the generated stream to revisit
a context with several recorded continuations, so the branchy prompts
tile period-4 [a, b, a, c] patterns and the engines run spec_ngram=1 —
empirically (fixed init seed 0) this makes the tiny model's greedy
output branch-rich. Every request is explicitly seeded (PR 4 lesson).
"""

import asyncio
import random

import pytest
from engine_waves import one_wave

from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.drafter import (
    JacobiPool,
    NgramDrafter,
    TreeDraft,
    TreeDrafter,
    build_drafter,
)
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.engine import Context

CFG = ModelConfig()  # test-tiny

# Period-4 patterns with a repeated token and DIVERGENT successors: the
# unigram context `a` continues with both b and c, so the tree drafter
# provably branches once generation (or the prompt tail) revisits it.
BRANCHY = ([3, 5, 3, 7] * 5, [10, 20, 10, 30] * 5, [9, 2, 9, 4] * 5)
LOOPY = ([1, 2, 3] * 6, [7, 8, 9, 4] * 4)


def tree_args(S: int, width: int = 2, depth: int = 0, pipeline: int = 0,
              gate: float = 0.0, **kw) -> EngineArgs:
    defaults = dict(
        model=CFG, block_size=4, num_kv_blocks=256, max_num_seqs=8,
        max_model_len=128, max_prefill_tokens=64, dtype="float32",
        decode_steps=4, spec_tokens=S, spec_gate=gate, spec_ngram=1,
        spec_tree_width=width, spec_tree_depth=depth,
        pipeline_depth=pipeline, pipeline_windows=pipeline > 0,
    )
    defaults.update(kw)
    return EngineArgs(**defaults)


def request(prompt, max_tokens, temperature=0.0, seed=0, logprobs=False,
            eos=()) -> PreprocessedRequest:
    req = PreprocessedRequest(model="t", token_ids=list(prompt))
    req.sampling.temperature = temperature
    req.sampling.seed = seed
    req.sampling.logprobs = logprobs
    req.stop.max_tokens = max_tokens
    req.stop.ignore_eos = not eos
    req.stop.stop_token_ids = list(eos)
    return req


async def run_stream(engine, req):
    toks, lps = [], []
    finish = None
    async for item in engine.generate(req, Context()):
        toks.extend(item.get("token_ids") or [])
        lps.extend(item.get("log_probs") or [])
        if item.get("finish_reason"):
            finish = item["finish_reason"]
    return toks, lps, finish


def mixed_workload():
    return [
        request(BRANCHY[0], 24, seed=1),
        request(BRANCHY[1], 20, seed=2, logprobs=True),
        request(LOOPY[0], 21, seed=3),
        request([11, 13, 17, 19, 23, 29, 31, 37], 16, seed=4),  # incompressible
        request([2, 4, 8], 1, seed=5),                          # prefill-only
        request(BRANCHY[2], 17, seed=6),
    ]


async def run_workload(eargs: EngineArgs, reqs=None):
    engine = await TpuEngine(eargs).start()
    try:
        # One admission wave, trees on or off: which prefills share a packed
        # dispatch is then the workload's, not the machine's timing.
        out = await one_wave(engine, [run_stream(engine, r) for r in (reqs or mixed_workload())])
        stats = {
            "rows": engine.total_spec_rows,
            "proposed": engine.total_spec_proposed,
            "accepted": engine.total_spec_accepted,
            "emitted": engine.total_spec_emitted,
            "tree_passes": engine.total_spec_tree_passes,
        }
        return out, stats
    finally:
        await engine.stop()


def _tokens_only(results):
    return [(toks, finish) for toks, _lps, finish in results]


@pytest.mark.parametrize("width,depth", [
    (1, 1), (1, 2), (1, 4), (2, 1), (2, 2), (2, 4), (4, 1), (4, 2), (4, 4),
])
def test_tree_greedy_byte_identity(width, depth):
    """Greedy token streams byte-identical to dense across the full
    width x depth grid; logprob values within fused-forward tolerance."""

    async def go():
        dense, _ = await run_workload(tree_args(0))
        spec, stats = await run_workload(tree_args(8, width=width, depth=depth))
        assert _tokens_only(spec) == _tokens_only(dense), (
            f"w={width} d={depth} diverged from the dense path"
        )
        for (_, dl, _f), (_, sl, _f2) in zip(dense, spec):
            assert len(dl) == len(sl)
            for a, b in zip(dl, sl):
                assert abs(a - b) < 1e-4
        assert stats["rows"] > 0, f"w={width} d={depth}: never speculated"
        assert stats["accepted"] <= stats["proposed"]
        # Every live row-pass emits its accepted run plus one token.
        assert stats["emitted"] == stats["rows"] + stats["accepted"]

    asyncio.run(go())


POOL_SEED = 20260928


class SeededPool(JacobiPool):
    """A Jacobi pool that has two candidates for EVERY context: what was
    recorded first, then two tokens drawn from the context and a seed.
    Whatever the random-weight model emits, the context it lands on has
    siblings to draft, so a branched pass is dispatched; a wrong draft is
    only ever rejected, so the streams must still be the dense ones."""

    def lookup(self, ctx):
        rng = random.Random(hash((POOL_SEED,) + tuple(ctx)))
        return super().lookup(ctx) + rng.sample(range(CFG.vocab_size), 2)


class SeededTreeDrafter(TreeDrafter):
    def new_state(self):
        st = super().new_state()
        st.pool = SeededPool(self.pool_g)
        return st


def test_tree_branched_pass_dispatches(monkeypatch):
    """A draft with siblings goes to the device as a BRANCHED pass (the
    tree op, counted in engine_spec_tree_passes_total), and rejected
    branches leave the greedy streams byte-identical to dense. The draft
    pool is seeded so that this holds whatever the weights emit (a
    suite-rot guard: every other test would pass vacuously if drafts
    always collapsed to chains)."""
    from dynamo_tpu.engine import engine as engine_mod

    async def go():
        dense, _ = await run_workload(tree_args(0))
        monkeypatch.setattr(
            engine_mod, "build_drafter",
            lambda args: SeededTreeDrafter(args.spec_ngram, 2, 4),
        )
        spec, stats = await run_workload(tree_args(8, width=2, depth=4))
        assert stats["tree_passes"] > 0, "no branched pass ever dispatched"
        assert _tokens_only(spec) == _tokens_only(dense)
        assert stats["emitted"] == stats["rows"] + stats["accepted"]

    asyncio.run(go())


def test_tree_width1_is_linear_path():
    """spec_tree_width=1 must build the PR 5 linear drafter — same
    streams AND the branched op structurally unreachable."""

    async def go():
        eargs = tree_args(8, width=1)
        assert type(build_drafter(eargs)) is NgramDrafter
        lin, ls = await run_workload(eargs)
        tree, ts = await run_workload(tree_args(8, width=2, depth=8))
        assert ls["tree_passes"] == 0
        assert _tokens_only(lin) == _tokens_only(tree)

    asyncio.run(go())


def test_tree_stop_token_mid_branch():
    """An eos landing inside an accepted tree run truncates exactly
    where the dense path stops."""

    async def go():
        reqs = lambda: [request(BRANCHY[0], 24, seed=3)]  # noqa: E731
        dense, _ = await run_workload(tree_args(0), reqs())
        toks = dense[0][0]
        assert len(toks) == 24
        eos = toks[13]
        mk = lambda: [request(BRANCHY[0], 24, seed=3, eos=(eos,))]  # noqa: E731
        dense_stop, _ = await run_workload(tree_args(0), mk())
        spec_stop, _ = await run_workload(tree_args(8, width=2, depth=4), mk())
        assert _tokens_only(spec_stop) == _tokens_only(dense_stop)
        assert spec_stop[0][2] == "stop"
        assert spec_stop[0][0][-1] == eos
        assert len(spec_stop[0][0]) < 24

    asyncio.run(go())


def test_tree_max_tokens_inside_accepted_run():
    async def go():
        for mt in (1, 2, 3, 5, 7, 13):
            mk = lambda: [request(BRANCHY[0], mt, seed=1),  # noqa: E731
                          request(BRANCHY[2], mt, seed=2)]
            dense, _ = await run_workload(tree_args(0), mk())
            spec, _ = await run_workload(tree_args(8, width=2, depth=4), mk())
            assert _tokens_only(spec) == _tokens_only(dense), f"max_tokens={mt}"
            assert all(len(s[0]) == mt for s in spec)
            assert all(s[2] == "length" for s in spec)

    asyncio.run(go())


def test_tree_preemption_golden():
    """KV pressure forces preemption-by-recompute while tree verifies
    are in flight; streams stay identical across spec on/off."""

    async def collect(S, width):
        engine = await TpuEngine(tree_args(
            S, width=width, depth=4, max_num_seqs=2, num_kv_blocks=24,
            max_model_len=64,
        )).start()
        try:
            return await one_wave(engine, [
                run_stream(engine, request(BRANCHY[0][:4], 20, seed=1)),
                run_stream(engine, request(BRANCHY[1][:4], 20, seed=2)),
            ])
        finally:
            await engine.stop()

    async def go():
        base = await collect(0, 1)
        for toks, _lps, finish in base:
            assert len(toks) == 20 and finish == "length"
        for width in (2, 4):
            got = await collect(8, width)
            assert _tokens_only(got) == _tokens_only(base), (
                f"width={width} diverged under preemption"
            )

    asyncio.run(go())


@pytest.mark.parametrize("pipeline", [1, 2])
def test_tree_composes_with_pipeline(pipeline):
    async def go():
        dense, _ = await run_workload(tree_args(0))
        spec, stats = await run_workload(
            tree_args(8, width=2, depth=4, pipeline=pipeline)
        )
        assert _tokens_only(spec) == _tokens_only(dense), f"depth={pipeline}"
        assert stats["rows"] > 0

    asyncio.run(go())


def test_tree_sampled_rows():
    """(a) seeded tree-spec sampling is deterministic; (b) a row that
    never drafts rides the dense RNG stream byte-identically even in a
    tree-speculating engine; (c) greedy rows in a sampled batch stay
    byte-identical to dense."""

    async def go():
        incompressible = [37, 11, 29, 5, 17, 2, 23, 41]
        reqs = lambda: [  # noqa: E731
            request(incompressible, 15, temperature=0.9, seed=11),
            request(BRANCHY[0], 15, temperature=0.7, seed=12),
            request(BRANCHY[1], 15, seed=13),  # greedy row, same batch
        ]
        dense, _ = await run_workload(tree_args(0), reqs())
        spec1, _ = await run_workload(tree_args(8, width=2, depth=4), reqs())
        spec2, _ = await run_workload(tree_args(8, width=2, depth=4), reqs())
        assert spec1 == spec2, "seeded tree sampling must be deterministic"
        assert spec1[0] == dense[0], "never-drafting sampled row diverged"
        assert _tokens_only([spec1[2]]) == _tokens_only([dense[2]])
        assert all(len(s[0]) == 15 and s[2] == "length" for s in spec1)

    asyncio.run(go())


def test_tree_int8_kv_golden():
    """Tree speculation composes with int8 KV storage: the compaction
    relocates pages AND scale sidecars, so tree-on streams match the
    int8 dense path byte-for-byte."""

    async def go():
        dense, _ = await run_workload(tree_args(0, kv_quant="int8"))
        spec, stats = await run_workload(
            tree_args(8, width=2, depth=4, kv_quant="int8")
        )
        assert _tokens_only(spec) == _tokens_only(dense)
        assert stats["rows"] > 0

    asyncio.run(go())


def test_tree_gate_disables_speculation():
    async def go():
        dense, _ = await run_workload(tree_args(0))
        gated, stats = await run_workload(
            tree_args(8, width=2, depth=4, gate=1e9)
        )
        assert _tokens_only(gated) == _tokens_only(dense)
        assert stats["rows"] == 0

    asyncio.run(go())


# ---------------------------------------------------------------------------
# Drafter units: continuation sets (the NgramDrafter bugfix), tree
# construction, Jacobi pool lifecycle.
# ---------------------------------------------------------------------------


def test_ngram_continuation_sets():
    """The index keeps per-context occurrence SETS (the PR 5 drafter
    dropped all but the most recent match); linear drafting still uses
    the newest occurrence, byte-for-byte the old behavior."""
    d = NgramDrafter(2)
    st = d.new_state()
    toks = [1, 2, 7, 0, 1, 2, 9, 0, 1, 2]
    out = d.draft(toks, st, 2)
    assert out == [9, 0]  # most recent continuation wins, as before
    # Both continuations of context (1, 2) are retained for the tree.
    occ = st.index[(1, 2)]
    assert len(occ) == 2
    assert [toks[e + 1] for e in occ] == [7, 9]


def test_tree_drafter_branches_on_continuation_sets():
    td = TreeDrafter(2, width=2, depth=4)
    st = td.new_state()
    hist = [1, 2, 3, 7, 5, 1, 2, 3, 9, 6, 1, 2, 3]
    # Wrong-n context first: TreeDrafter(2) keys on bigrams (2, 3).
    t = td.draft_tree(hist, st, budget=6)
    assert not t.is_chain()
    roots = [t.tokens[i] for i, p in enumerate(t.parents) if p == 0]
    assert roots[0] == 9 and 7 in roots  # most recent continuation first
    depths = t.depths()
    assert depths[0] == 0 and max(depths) <= 4
    assert all(p < i + 1 for i, p in enumerate(t.parents))  # topological


def test_tree_draft_budget_and_depth_caps():
    td = TreeDrafter(1, width=4, depth=2)
    st = td.new_state()
    hist = [5, 1, 5, 2, 5, 3, 5]
    t = td.draft_tree(hist, st, budget=5)
    assert len(t) <= 5
    assert t.max_depth <= 2
    # Chain helper agreement.
    chain = TreeDraft([4, 5, 6], [0, 1, 2])
    assert chain.is_chain() and chain.chain_tokens() == [4, 5, 6]
    assert TreeDraft([4, 5], [0, 0]).is_chain() is False


def test_jacobi_pool_drafts_without_history_hits():
    """Zero history repetition: the pool alone (refreshed from verify
    cand predictions) must produce drafts — the Lookahead property that
    makes generic traffic speculable."""
    td = TreeDrafter(3, width=2, depth=4)
    st = td.new_state()
    hist = [40, 41]
    assert len(td.draft_tree(hist, st, budget=4)) == 0  # nothing known yet
    # One verify pass's feedback: root token 41, model predicted 42.
    td.observe(st, hist, [41], [0], 1, [42])
    t = td.draft_tree(hist, st, budget=4)
    assert t.tokens[:1] == [42]
    # Chained pool predictions extend the draft: (41, 42) -> 43.
    td.observe(st, hist + [42], [42], [0], 1, [43])
    t2 = td.draft_tree(hist, st, budget=4)
    assert t2.tokens[:2] == [42, 43]


def test_jacobi_pool_caps_and_ranking():
    pool = JacobiPool(2)
    for _ in range(3):
        pool.record((1, 2), 7)
    pool.record((1, 2), 9)
    assert pool.lookup((1, 2)) == [7, 9]  # hit-ranked
    assert pool.lookup((9, 9)) == []
    # Candidate cap evicts the coldest, never the just-recorded token.
    for tok in (11, 12, 13, 14, 15):
        pool.record((3, 3), tok)
    cands = pool.lookup((3, 3))
    assert len(cands) <= 4 and 15 in cands
