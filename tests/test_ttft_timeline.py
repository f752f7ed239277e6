"""The first token's timeline as spans, the step loop and the decode rows as
counters, the names the benchmark's reducers search for, and the scheduler's
phases in a profiler trace (ISSUE 26). CPU, test-tiny."""

import asyncio
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.mocker.engine import MockerArgs, MockerEngine
from dynamo_tpu.ops import paged_attention
from dynamo_tpu.runtime import tracing
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.metrics import MetricsRegistry

CFG = ModelConfig()  # test-tiny
TIMELINE = ("engine.dispatch", "engine.first_wait", "engine.deliver")


@pytest.fixture
def fresh_recorder():
    rec = tracing.SpanRecorder(capacity=512, ledger_capacity=8)
    prev = tracing.set_recorder(rec)
    yield rec
    tracing.set_recorder(prev)


def make_args(**kw) -> EngineArgs:
    defaults = dict(
        model=CFG, block_size=4, num_kv_blocks=64, max_num_seqs=4,
        max_model_len=128, max_prefill_tokens=64, dtype="float32",
    )
    defaults.update(kw)
    return EngineArgs(**defaults)


def greedy_request(prompt, max_tokens=8) -> PreprocessedRequest:
    req = PreprocessedRequest(model="t", token_ids=list(prompt))
    req.sampling.temperature = 0.0
    req.sampling.seed = 0
    req.stop.max_tokens = max_tokens
    return req


async def serve(engine, prompts, max_tokens=8, traced=True):
    """Serve ``prompts`` at once, each under a ``wire.serve`` span of its own
    as the endpoint server would open. → [(wire.serve span | None, outputs)]."""
    async def one(prompt):
        ws = tracing.start_span("wire.serve") if traced else None
        ctx = Context(trace=ws.trace_context() if traced else None)
        outs = [o async for o in engine.generate(greedy_request(prompt, max_tokens), ctx)]
        if ws is not None:
            ws.end()
        return ws, outs

    return await asyncio.gather(*(one(p) for p in prompts))


async def settle(engine) -> None:
    """Two empty jobs on the scheduler thread: the second runs in a step
    after the one whose ``_update_gauges`` pushed the last counters."""
    await engine.run_on_engine_thread(lambda: None)
    await engine.run_on_engine_thread(lambda: None)


def tokens_of(outs) -> list[int]:
    return [t for o in outs for t in o.get("token_ids", [])]


def spans_under(rec, ws) -> dict[str, list]:
    by_name: dict[str, list] = {}
    for s in rec.spans(ws.trace_id):
        if s.parent_id == ws.span_id:
            by_name.setdefault(s.name, []).append(s)
    return by_name


def test_timeline_spans_nest_under_wire_serve_and_sum_to_prefill(fresh_recorder):
    async def go():
        engine = await TpuEngine(make_args()).start()
        try:
            return await serve(engine, [range(1, 20), range(30, 45)], max_tokens=6)
        finally:
            await engine.stop()

    for ws, outs in asyncio.run(go()):
        assert len(tokens_of(outs)) == 6
        got = spans_under(fresh_recorder, ws)
        for name in ("engine.queue", "engine.prefill", "engine.decode", *TIMELINE):
            assert len(got.get(name, [])) == 1, (name, sorted(got))
        assert "engine.blocked" not in got
        parts = sum(got[name][0].duration_s for name in TIMELINE)
        assert parts == pytest.approx(got["engine.prefill"][0].duration_s, abs=1e-3)
        assert all(got[name][0].duration_s >= 0 for name in TIMELINE)
        disp = got["engine.dispatch"][0].attrs
        assert disp["chunks"] == 1 and disp["wave"] in (1, 2) and disp["windows_in_flight"] >= 0
        wait = got["engine.first_wait"][0].attrs
        assert wait["blocked"] in (True, False)
        # a sample that lay ready was not waited for, and the other way round
        assert wait["ready_unread_ms"] >= 0 and not (wait["blocked"] and wait["ready_unread_ms"])
        # first_wait starts where dispatch ends, deliver where first_wait ends
        d, w = got["engine.dispatch"][0], got["engine.first_wait"][0]
        assert d.start_ts + d.duration_s == pytest.approx(w.start_ts, abs=5e-3)


def test_a_long_prompt_counts_its_chunks(fresh_recorder):
    async def go():
        engine = await TpuEngine(make_args(max_prefill_tokens=16)).start()
        try:
            return await serve(engine, [range(1, 41)], max_tokens=2)
        finally:
            await engine.stop()

    (ws, _outs), = asyncio.run(go())
    assert spans_under(fresh_recorder, ws)["engine.dispatch"][0].attrs["chunks"] == 3


@pytest.mark.parametrize("kw, n_prompts, reason", [
    (dict(num_kv_blocks=10), 2, "blocks"),   # 9 usable blocks, a prompt takes 6 and grows
    # Two slots and three prompts: one slot would compile test_engine_qos's shapes
    # in this process, and that test's timing counts on compiling them itself.
    (dict(max_num_seqs=2), 3, "slots"),
    (dict(), 2, None),
])
def test_blocked_span_only_when_admission_had_to_wait(fresh_recorder, kw, n_prompts, reason):
    async def go():
        engine = await TpuEngine(make_args(**kw)).start()
        try:
            # Both requests arrive while the scheduler thread is held in a
            # job, so one step sees both: the first is admitted, the second
            # meets the full slot or the short pool whatever the machine's load.
            hold = asyncio.ensure_future(engine.run_on_engine_thread(lambda: time.sleep(0.3)))
            await asyncio.sleep(0.05)
            prompts = [range(1, 25), range(40, 64), range(70, 94)][:n_prompts]
            served = await serve(engine, prompts, max_tokens=8)
            await hold
            return served
        finally:
            await engine.stop()

    served = asyncio.run(go())
    blocked = []
    for ws, outs in served:
        assert len(tokens_of(outs)) == 8 and outs[-1]["finish_reason"] == "length"
        got = spans_under(fresh_recorder, ws)
        blocked += got.get("engine.blocked", [])
        assert sum(got[n][0].duration_s for n in TIMELINE) == pytest.approx(
            got["engine.prefill"][0].duration_s, abs=1e-3)
    if reason is None:
        assert blocked == []
    else:
        assert [s.attrs["reason"] for s in blocked] == [reason]
        # blocked lies inside the queue wait of the request that was held back
        ws = next(w for w, _ in served if w.trace_id == blocked[0].trace_id)
        queue = spans_under(fresh_recorder, ws)["engine.queue"][0]
        assert 0 < blocked[0].duration_s <= queue.duration_s + 1e-3


def test_recorder_off_serves_the_same_and_records_nothing():
    async def go(traced):
        engine = await TpuEngine(make_args()).start()
        try:
            return await serve(engine, [range(1, 20)], max_tokens=6, traced=traced)
        finally:
            await engine.stop()

    rec = tracing.SpanRecorder(capacity=64)
    prev = tracing.set_recorder(rec)
    try:
        (_ws, with_rec), = asyncio.run(go(True))
        n_spans = len(rec.spans())
        tracing.set_recorder(None)
        (_none, without), = asyncio.run(go(False))
        assert tokens_of(without) == tokens_of(with_rec)
        assert len(rec.spans()) == n_spans  # no stamp was turned into a span
    finally:
        tracing.set_recorder(prev)


def counter(reg: MetricsRegistry, name: str, **labels) -> float:
    return reg.counter(name).value(**labels)


def test_step_loop_and_decode_row_counters(fresh_recorder):
    reg = MetricsRegistry()
    prompts = [range(1, 20), range(30, 45), range(50, 59)]
    bs = 4

    async def go():
        engine = TpuEngine(make_args(block_size=bs))
        engine.bind_metrics(reg)
        await engine.start()
        try:
            first = await serve(engine, prompts[:1], max_tokens=7)
            await settle(engine)
            mid = {k: counter(reg, "engine_decode_row_steps_total", kind=k)
                   for k in ("dispatched", "emitted")}
            rest = await serve(engine, prompts[1:], max_tokens=11)
            await settle(engine)
            return first + rest, mid, engine
        finally:
            await engine.stop()

    served, mid, engine = asyncio.run(go())
    streamed = sum(len(tokens_of(outs)) for _, outs in served)
    assert streamed == 7 + 2 * 11
    emitted = counter(reg, "engine_decode_row_steps_total", kind="emitted")
    dispatched = counter(reg, "engine_decode_row_steps_total", kind="dispatched")
    # every token but a request's first (the admission sample) came out of a decode window
    assert emitted == streamed - len(served)
    assert 0 < mid["emitted"] == 7 - 1 and mid["dispatched"] < dispatched
    assert emitted <= dispatched and dispatched % engine.args.decode_buckets[0] == 0

    phases = {p: counter(reg, "engine_step_phase_seconds_total", phase=p) for p in engine.phase_s}
    assert {"idle", "housekeeping", "admission", "admit_alloc", "prefill_dispatch", "stack_rows",
            "first_dispatch", "plan", "decode_dispatch", "emit", "gauges"} <= set(phases)
    assert all(v > 0 for v in phases.values())
    for p, secs in engine.phase_s.items():
        assert phases[p] <= secs  # pushed once a step
    cpu = counter(reg, "engine_sched_cpu_seconds_total")
    assert 0 < cpu <= sum(engine.phase_s.values()) + 60.0

    hit = counter(reg, "kv_pool_hit_blocks_total")
    miss = counter(reg, "kv_pool_miss_blocks_total")
    assert hit + miss == sum((len(p) - 1) // bs for p in prompts)
    text = reg.render()
    assert 'dynamo_tpu_engine_decode_row_steps_total{kind="emitted"}' in text
    assert "\nprocess_cpu_seconds_total " in text


def test_pool_counts_hits_and_misses_across_an_eviction():
    async def go():
        # 15 usable blocks: the second distinct prompt evicts the first one's cached blocks.
        engine = await TpuEngine(make_args(num_kv_blocks=16)).start()
        try:
            await serve(engine, [range(1, 34)], max_tokens=2, traced=False)
            await serve(engine, [range(1, 34)], max_tokens=2, traced=False)   # 8 blocks hit
            hits = engine.pool.hit_blocks
            await serve(engine, [range(100, 140)], max_tokens=2, traced=False)
            await serve(engine, [range(1, 34)], max_tokens=2, traced=False)   # evicted: all miss
            return hits, engine.pool.hit_blocks, engine.pool.miss_blocks
        finally:
            await engine.stop()

    hits, hits_after, misses = asyncio.run(go())
    assert hits == hits_after == 8 and misses == 8 + 9 + 8


def test_mocker_records_the_same_timeline(fresh_recorder):
    async def go():
        engine = MockerEngine(MockerArgs(block_size=4, num_kv_blocks=64, speedup=200.0,
                                         max_num_seqs=1))
        return await serve(engine, [range(1, 20), range(30, 50)], max_tokens=4)

    served = asyncio.run(go())
    reasons = []
    for ws, _outs in served:
        got = spans_under(fresh_recorder, ws)
        for name in ("engine.queue", "engine.prefill", "engine.decode", *TIMELINE):
            assert len(got.get(name, [])) == 1, (name, sorted(got))
        parts = sum(got[name][0].duration_s for name in TIMELINE)
        assert parts == pytest.approx(got["engine.prefill"][0].duration_s, abs=2e-3)
        reasons += [s.attrs["reason"] for s in got.get("engine.blocked", [])]
    assert reasons == ["slots"]  # one slot: the second request found it taken


# -- the names the benchmark's reducers search for ------------------------------


def scoped(lowered_text: str, scope: str) -> bool:
    """Some operation's name in the lowered program lies under ``scope``."""
    return f'"{scope}/' in lowered_text or f"/{scope}/" in lowered_text


def test_jitted_steps_and_kernel_keep_the_names_the_reducers_search_for():
    # chipbench/trace_reduce.py and the readers find the programs by
    # "multi_decode", "prefill_batch", "prefill" in jit_<name> and the kernel
    # by "paged_decode_attention"; none of them may be edited with the program.
    assert M.multi_decode.__name__ == "multi_decode_impl"
    assert M.prefill_batch.__name__ == "prefill_batch_impl"
    assert M.prefill.__name__ == "prefill_impl"
    assert paged_attention.paged_decode_attention.__name__ == "paged_decode_attention"

    params = M.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    cache = M.init_kv_cache(CFG, 16, 4, jnp.float32)
    B, W, K = 8, 4, 2
    z = np.zeros((B,), np.int32)
    lowered = M.multi_decode.lower(
        CFG, K, "greedy", 0, params, cache, jnp.asarray(z), jnp.asarray(z),
        jnp.zeros((B, W), jnp.int32), jnp.zeros((B,), bool), jnp.ones((B,), jnp.float32),
        jnp.zeros((B,), jnp.uint32), jnp.asarray(z), jnp.asarray(z), jnp.ones((B,), jnp.float32),
        jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.float32), jnp.full((B, 1), -1, jnp.int32),
        jnp.zeros((B,), bool), jnp.asarray(z), jnp.zeros((5,), jnp.int32), None, None,
        attn_impl="pallas_interpret",
    )
    text = lowered.as_text(debug_info=True)
    assert "jit_multi_decode_impl" in text
    assert "paged_decode_attention" in text
    for scope in ("embed", "attn_qkv", "kv_write", "attn", "attn_out", "ffn", "logits", "sample"):
        assert scoped(text, scope), scope

    toks = jnp.zeros((2, 8), jnp.int32)
    lowered = M.prefill_batch.lower(
        CFG, params, cache, toks, jnp.zeros((2, W), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.full((2,), 5, jnp.int32), None, None)
    text = lowered.as_text(debug_info=True)
    assert "jit_prefill_batch_impl" in text
    for scope in ("embed", "attn_qkv", "kv_write", "attn", "attn_out", "ffn", "logits"):
        assert scoped(text, scope), scope


def test_a_profiler_trace_holds_the_scheduler_phases(tmp_path):
    from chipbench import trace_reduce

    async def go():
        engine = await TpuEngine(make_args()).start()
        try:
            await serve(engine, [range(1, 12)], max_tokens=4, traced=False)  # compile first
            jax.profiler.start_trace(str(tmp_path))
            try:
                await serve(engine, [range(20, 40), range(50, 65)], max_tokens=10, traced=False)
            finally:
                jax.profiler.stop_trace()
        finally:
            await engine.stop()

    asyncio.run(go())
    doc = trace_reduce.load_events(str(tmp_path))
    spans = [e for p in doc["planes"] for ln in p["lines"] for e in ln["events"]
             if e[0].startswith("sched.")]
    names = {e[0] for e in spans}
    # chipbench/host_phases.py names a device gap by these; its readers and
    # PERF.md's tables are keyed by them, so none may be renamed with the program.
    assert {"sched.idle", "sched.housekeeping", "sched.admission", "sched.admit_alloc",
            "sched.prefill_dispatch", "sched.stack_rows", "sched.first_dispatch", "sched.plan",
            "sched.decode_dispatch", "sched.emit", "sched.gauges"} <= names, names
    assert names & {"sched.drain_sync", "sched.drain_ready"}
    assert all(e[2] >= 0 for e in spans)
    # the phases of one thread do not overlap: each closes before the next opens
    one = sorted((e[1], e[1] + e[2]) for e in spans)
    assert all(a[1] <= b[0] + 1e3 for a, b in zip(one, one[1:]))


LONGCAT_SCOPES = ("mla_q", "mla_kv_write", "mla_attn", "mla_out", "ffn_dense",
                  "moe_route", "moe_experts", "moe_zero")


def test_longcat_programs_keep_their_scope_and_kernel_names():
    """The LongCat block's programs under the same jit names, its scopes, and
    the two kernels by the names chipbench/layer_metrics/_latent.py searches."""
    from dynamo_tpu.engine import longcat

    cfg = ModelConfig.preset("longcat-tiny")
    assert paged_attention.latent_decode_attention.__name__ == "latent_decode_attention"
    assert longcat.grouped_expert_matmul.__name__ == "grouped_expert_matmul"
    params = longcat.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cache = longcat.init_kv_cache(cfg, 16, 8, jnp.float32)
    B, W, K = 8, 4, 2
    z = np.zeros((B,), np.int32)
    text = longcat.multi_decode.lower(
        cfg, K, "greedy", 0, params, cache, jnp.asarray(z), jnp.asarray(z),
        jnp.zeros((B, W), jnp.int32), jnp.zeros((B,), bool), jnp.ones((B,), jnp.float32),
        jnp.zeros((B,), jnp.uint32), jnp.asarray(z), jnp.asarray(z), jnp.ones((B,), jnp.float32),
        jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.float32), jnp.full((B, 1), -1, jnp.int32),
        jnp.zeros((B,), bool), jnp.asarray(z), jnp.zeros((5,), jnp.int32), None, None,
        attn_impl="pallas_interpret", experts="gmm_interpret",
    ).as_text(debug_info=True)
    assert "jit_multi_decode_impl" in text
    assert "latent_decode_attention" in text and "grouped_expert_matmul" in text
    for scope in ("embed", *LONGCAT_SCOPES, "logits", "sample"):
        assert scoped(text, scope), scope
    text = longcat.prefill_batch.lower(
        cfg, params, cache, jnp.zeros((2, 8), jnp.int32), jnp.zeros((2, W), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.full((2,), 5, jnp.int32), None, None,
    ).as_text(debug_info=True)
    assert "jit_prefill_batch_impl" in text
    for scope in ("embed", *LONGCAT_SCOPES, "logits"):
        assert scoped(text, scope), scope
    assert "jit_prefill_impl" in longcat.prefill.lower(
        cfg, params, cache, jnp.zeros((8,), jnp.int32), jnp.zeros((W,), jnp.int32), 0, 5,
    ).as_text()


def test_longcat_expert_counters_ride_the_token_fetches(fresh_recorder):
    """moe_assignments_total{kind}, moe_expert_tokens_total{layer,expert},
    moe_tokens_routed_total, and by program moe_expert_calls_total and
    moe_experts_touched_total, fed from the histogram the prefill and decode
    programs return with the tokens: every assignment is held, zero or absent."""
    cfg = ModelConfig.preset("longcat-tiny")
    reg = MetricsRegistry()

    async def go():
        engine = TpuEngine(make_args(model=cfg, block_size=8))
        engine.bind_metrics(reg)
        await engine.start()
        try:
            out = await serve(engine, [range(1, 20), range(30, 45)], max_tokens=7)
            await settle(engine)
        finally:
            await engine.stop()
        return out

    out = asyncio.run(go())
    assert all(len(tokens_of(o)) == 7 for _, o in out)
    kinds = {k: counter(reg, "moe_assignments_total", kind=k) for k in ("held", "zero", "absent")}
    routed = counter(reg, "moe_tokens_routed_total")
    assert routed > 0 and all(v > 0 for v in kinds.values())
    assert sum(kinds.values()) == routed * cfg.num_experts_per_token
    held = sum(
        counter(reg, "moe_expert_tokens_total", layer=str(l), expert=str(cfg.expert_offset + e))
        for l in range(cfg.num_layers) for e in range(cfg.num_experts))
    assert held == kinds["held"]
    calls = {p: counter(reg, "moe_expert_calls_total", program=p) for p in ("prefill", "decode")}
    # Two prompts: a part each, or one part for both where one wave admitted
    # them and they went out packed (a pack is one grouped product a layer).
    assert calls["prefill"] in (cfg.num_layers, cfg.num_layers * 2)
    assert calls["decode"] >= cfg.num_layers * 6 and calls["decode"] % cfg.num_layers == 0
    touched = sum(counter(reg, "moe_experts_touched_total", program=p) for p in calls)
    assert 0 < touched <= min(held, sum(calls.values()) * cfg.num_experts)
    # Prompt tokens and every decoded token but each request's last, a layer each
    # (a window also routes the rows it runs past a stop: at least this many).
    assert routed >= cfg.num_layers * (19 + 15 + 2 * 6)


LFM2_SCOPES = ("conv_in", "conv_state", "conv_mix", "conv_out", "attn_qkv", "attn_qk_norm", "kv_write",
               "attn", "attn_out", "ffn_dense", "moe_route", "moe_experts")


@pytest.mark.parametrize("program", ["multi_decode", "prefill_batch"])
def test_lfm2_programs_keep_their_scope_and_kernel_names(program):
    """The LFM2 block's programs under the dense block's jit names, its
    scopes, and the kernels by the names the readers search for
    (``paged_decode_attention``, ``paged_prefill_attention``, ``gmm`` under
    ``grouped_expert_matmul``: chipbench/layer_metrics/_whole.py)."""
    from dynamo_tpu.engine import lfm2

    cfg = ModelConfig.preset("lfm2-tiny")
    params = lfm2.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cache = lfm2.init_kv_cache(cfg, 16, 8, jnp.float32)
    B, W, K = 8, 4, 2
    z = np.zeros((B,), np.int32)
    kw = dict(attn_impl="pallas_interpret", experts="gmm_interpret")
    if program == "multi_decode":
        text = lfm2.multi_decode.lower(
            cfg, K, "greedy", 0, params, cache, jnp.asarray(z), jnp.asarray(z),
            jnp.zeros((B, W), jnp.int32), jnp.zeros((B,), bool), jnp.ones((B,), jnp.float32),
            jnp.zeros((B,), jnp.uint32), jnp.asarray(z), jnp.asarray(z), jnp.ones((B,), jnp.float32),
            jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.float32), jnp.full((B, 1), -1, jnp.int32),
            jnp.zeros((B,), bool), jnp.asarray(z), jnp.zeros((5,), jnp.int32), None, None, **kw,
        ).as_text(debug_info=True)
        assert "jit_multi_decode_impl" in text and "paged_decode_attention" in text
        scopes = ("embed", *LFM2_SCOPES, "logits", "sample")
    else:
        text = lfm2.prefill_batch.lower(
            cfg, params, cache, jnp.zeros((2, 16), jnp.int32), jnp.zeros((2, W), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.full((2,), 5, jnp.int32), None, None, **kw,
        ).as_text(debug_info=True)
        assert "jit_prefill_batch_impl" in text and "paged_prefill_attention" in text
        assert "jit_prefill_impl" in lfm2.prefill.lower(
            cfg, params, cache, jnp.zeros((16,), jnp.int32), jnp.zeros((W,), jnp.int32), 0, 5).as_text()
        scopes = ("embed", *LFM2_SCOPES, "logits")
    assert "grouped_expert_matmul" in text
    for scope in scopes:
        assert scoped(text, scope), scope


def counter_of(page: str, name: str, label: str) -> float | None:
    """A series' value on a rendered ``/metrics`` page; None where the page has no such series."""
    for line in page.splitlines():
        if line.startswith(f"dynamo_tpu_{name}{{") and label in line:
            return float(line.rsplit(" ", 1)[1])
    return None


def test_conv_state_and_pool_series_are_pinned_names(fresh_recorder):
    """engine_conv_state_resumes_total{source} (all three sources from the
    start) and kv_pool_bytes{kind} for a model with conv layers; kv_pool_bytes
    alone, one kind, for a dense one."""
    pages = {}
    for preset in ("lfm2-tiny", "test-tiny"):
        reg = MetricsRegistry()

        async def go():
            engine = TpuEngine(make_args(model=ModelConfig.preset(preset), block_size=8))
            engine.bind_metrics(reg)
            await engine.start()
            try:
                await serve(engine, [range(1, 20), range(1, 20)], max_tokens=3)
                await settle(engine)
            finally:
                await engine.stop()

        asyncio.run(go())
        pages[preset] = reg.render()
    sources = {s: counter_of(pages["lfm2-tiny"], "engine_conv_state_resumes_total", f'source="{s}"')
               for s in ("cache", "zero", "recompute")}
    assert sources["recompute"] == 0 and sources["cache"] + sources["zero"] == 2 and sources["zero"] >= 1
    assert counter_of(pages["lfm2-tiny"], "kv_pool_bytes", 'kind="conv"') > 0
    assert counter_of(pages["lfm2-tiny"], "kv_pool_bytes", 'kind="kv"') > 0
    assert counter_of(pages["test-tiny"], "kv_pool_bytes", 'kind="kv"') > 0
    assert 'kind="conv"' not in pages["test-tiny"] and "engine_conv_state_resumes_total{" not in pages["test-tiny"]
    # kv_page_bytes: what one page descriptor moves, K and V of a block of 8 float32 positions.
    for preset, page in pages.items():
        assert f"dynamo_tpu_kv_page_bytes {2 * 8 * ModelConfig.preset(preset).kv_size * 4}" in page


def test_prefill_dispatch_rows_series_are_pinned_names(fresh_recorder):
    """engine_prefill_dispatch_rows_total{rows} and engine_prefill_rows_total
    (docs/observability.md): a wave of two short prompts is one dispatch of
    two rows once the runner has the program, and the start line names the
    limit the pack stayed under."""
    reg = MetricsRegistry()

    async def go():
        engine = TpuEngine(make_args())
        engine.bind_metrics(reg)
        await engine.start()
        try:
            assert engine._runner.packed_ready == {(2, 32)}
            # Both arrive while the scheduler thread is held: one wave.
            hold = asyncio.ensure_future(engine.run_on_engine_thread(lambda: time.sleep(0.3)))
            await asyncio.sleep(0.05)
            await serve(engine, [range(1, 20), range(3, 25)], max_tokens=3)
            await hold
            await settle(engine)
            return engine._runner._start_line("")
        finally:
            await engine.stop()

    line = asyncio.run(go())
    page = reg.render()
    assert counter_of(page, "engine_prefill_dispatch_rows_total", 'rows="2"') == 1
    assert "dynamo_tpu_engine_prefill_rows_total 2" in page
    assert re.search(r" prefill_pack<=\d+ tok( \(|$)", line)
