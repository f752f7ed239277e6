"""The scheduler thread's account of itself (ISSUE 43): one current phase on
two clocks that tiles the thread, why an admission pass stopped, what stood
ahead of a wave, how long a ready first-token sample lay unread, and the
device's dry time between a floor and a ceiling. CPU, test-tiny."""

import asyncio
import time
import types

import numpy as np
import pytest

from dynamo_tpu.engine import engine as engine_mod
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine, _First
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.metrics import MetricsRegistry

# The phases every step of a serving engine passes through; the drains
# (drain_sync, drain_ready, first_sample) depend on the machine's timing.
EVERY_STEP = ("idle", "housekeeping", "admission", "admit_alloc", "prefill_dispatch",
              "first_dispatch", "stack_rows", "plan", "decode_dispatch", "emit", "gauges")


def make_args(**kw) -> EngineArgs:
    # tests/test_ttft_timeline.py's geometry: no program compiles here that it does not compile
    defaults = dict(
        model=ModelConfig(), block_size=4, num_kv_blocks=64, max_num_seqs=4,
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


async def serve(engine, prompts, max_tokens=8) -> list:
    async def one(prompt):
        return [o async for o in engine.generate(greedy_request(prompt, max_tokens), Context())]
    return await asyncio.gather(*(one(p) for p in prompts))


async def settle(engine) -> None:
    """Two empty jobs on the scheduler thread: the second runs in a step
    after the one whose ``_update_gauges`` pushed the last counters."""
    await engine.run_on_engine_thread(lambda: None)
    await engine.run_on_engine_thread(lambda: None)


def series(reg: MetricsRegistry, name: str) -> dict[str, float]:
    """{label text: value} of one family on the registry's page."""
    out = {}
    for line in reg.render().splitlines():
        key, _, val = line.rpartition(" ")
        if key.partition("{")[0] == "dynamo_tpu_" + name:
            out[key.partition("{")[2].rstrip("}")] = float(val)
    return out


# -- the phases tile the thread, on two clocks ------------------------------------


@pytest.fixture(scope="module")
def served_engine():
    """One engine serving two rounds; the pages before and after the second,
    and the engine's own totals at the end."""
    reg = MetricsRegistry()

    async def go():
        engine = TpuEngine(make_args())
        engine.bind_metrics(reg)
        await engine.start()
        try:
            await serve(engine, [range(1, 20)], max_tokens=6)
            await settle(engine)
            names = ("engine_step_phase_seconds_total", "engine_step_phase_cpu_seconds_total",
                     "engine_step_phase_total", "engine_sched_wall_seconds_total",
                     "engine_sched_cpu_seconds_total")
            before = {n: series(reg, n) for n in names}
            await serve(engine, [range(30, 50), range(60, 75), range(80, 99)], max_tokens=12)
            await settle(engine)
            after = {n: series(reg, n) for n in names}
            return before, after, engine
        finally:
            await engine.stop()

    return asyncio.run(go())


def grown(pages, name: str) -> dict[str, float]:
    before, after, _ = pages
    return {k: v - before[name].get(k, 0.0) for k, v in after[name].items()}


def test_the_phases_sum_to_the_threads_wall_seconds(served_engine):
    wall = grown(served_engine, "engine_sched_wall_seconds_total")[""]
    phases = sum(grown(served_engine, "engine_step_phase_seconds_total").values())
    assert wall > 0
    # one partial `gauges` phase at each end of the window is all they can differ by
    assert 0.99 * wall <= phases <= wall + 1e-3


def test_the_phases_cpu_sums_to_the_threads_cpu_seconds(served_engine):
    cpu = grown(served_engine, "engine_sched_cpu_seconds_total")[""]
    by_phase = sum(grown(served_engine, "engine_step_phase_cpu_seconds_total").values())
    assert 0 < by_phase <= cpu + 1e-3 and by_phase >= 0.95 * cpu


def test_a_stopped_thread_is_in_no_phase_and_its_totals_cover_its_run(served_engine):
    _, _, engine = served_engine
    assert engine._cur is None and engine._anno is None
    # closed by _run's last line: every second from its first line is in a phase
    assert sum(engine.phase_s.values()) == pytest.approx(engine._cur_t - engine._t_run, abs=1e-6)


@pytest.mark.parametrize("phase", EVERY_STEP)
def test_each_phase_is_on_both_clocks_and_counted(served_engine, phase):
    _, after, engine = served_engine
    label = f'phase="{phase}"'
    assert engine.phase_n[phase] > 0 and engine.phase_s[phase] > 0
    # CPU time cannot pass wall time, but for a tick of the CPU clock (10 ms on some hosts)
    assert engine.phase_cpu_s[phase] <= engine.phase_s[phase] + 0.011 + 2e-6 * engine.phase_n[phase]
    # pushed once a step, so the page trails the engine's own totals
    assert 0 < after["engine_step_phase_seconds_total"][label] <= engine.phase_s[phase]
    assert after["engine_step_phase_cpu_seconds_total"].get(label, 0.0) <= engine.phase_cpu_s[phase]
    assert 0 < after["engine_step_phase_total"][label] <= engine.phase_n[phase]


def test_a_window_is_one_pass_through_decode_dispatch(served_engine):
    _, _, engine = served_engine
    K = engine.args.decode_steps
    # every decode step was dispatched in a window of K: one _enter out of the phase a window
    assert engine.phase_n["decode_dispatch"] * K == engine.total_decode_steps
    assert set(engine.phase_s) <= set(EVERY_STEP) | {"drain_sync", "drain_ready", "first_sample"}


# -- why an admission pass stopped ---------------------------------------------------


@pytest.mark.parametrize("kw, n_prompts, reason", [
    (dict(), 1, "empty"),
    (dict(admission_budget_tokens=30), 3, "budget"),   # 24 + 24 tokens spend it, a third waits
    (dict(max_num_seqs=2), 3, "slots"),
    (dict(num_kv_blocks=10), 2, "blocks"),             # 9 usable blocks, a prompt takes 6
])
def test_admission_says_why_it_stopped(kw, n_prompts, reason):
    reg = MetricsRegistry()

    async def go():
        engine = TpuEngine(make_args(**kw))
        engine.bind_metrics(reg)
        await engine.start()
        try:
            # The requests arrive while the scheduler thread is held in a job,
            # so one admission pass sees them all, whatever the machine's load.
            hold = asyncio.ensure_future(engine.run_on_engine_thread(lambda: time.sleep(0.3)))
            await asyncio.sleep(0.05)
            prompts = [range(1, 25), range(40, 64), range(70, 94)][:n_prompts]
            outs = await serve(engine, prompts, max_tokens=8)
            await hold
            await settle(engine)
            return outs, await engine.run_on_engine_thread(lambda: dict(engine.admission_stops))
        finally:
            await engine.stop()

    outs, stops = asyncio.run(go())
    assert all(o[-1]["finish_reason"] == "length" for o in outs)
    assert stops[reason] >= 1
    others = {"empty", "budget", "slots", "blocks"} - {reason, "empty"}
    assert all(stops[r] == 0 for r in others), stops
    assert stops["empty"] >= 1            # the pass that admitted the last request drained the queue
    page = series(reg, "engine_admission_stops_total")
    assert page[f'reason="{reason}"'] == stops[reason]


# -- the probe: stub arrays on a stepped clock ----------------------------------------


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self) -> float:
        return self.t


class Stub:
    """A device array whose computation ends at ``at`` on the clock."""

    def __init__(self, clock: Clock, at: float):
        self.clock, self.at = clock, at

    def is_ready(self) -> bool:
        return self.clock.t >= self.at

    def __array__(self, dtype=None, copy=None):
        return np.zeros((1,), np.int32)


@pytest.fixture
def stepped(monkeypatch):
    """An engine that never starts, its module's clocks stepped by the test."""
    clock = Clock()
    monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(
        perf_counter=clock, thread_time=clock, monotonic=time.monotonic, sleep=time.sleep))
    engine = TpuEngine(make_args())
    engine._running.append(object())   # a request runs: a dry device is a gap
    return engine, clock


def probe_every(engine, clock, until: float, step: float) -> None:
    while clock.t + step <= until + 1e-9:
        clock.t += step
        engine._probe(clock.t)


@pytest.mark.parametrize("step, offset", [(1.0, 0.0), (1.0, 0.5), (0.25, 0.1), (4.0, 1.0), (20.0, 3.0)])
def test_the_true_dry_time_lies_between_the_floor_and_the_ceiling(stepped, step, offset):
    engine, clock = stepped
    t0 = clock.t
    engine._dispatching()
    engine._dispatched([Stub(clock, t0 + 10.0)])      # the program runs for 10 s
    clock.t += offset
    engine._probe(clock.t)
    probe_every(engine, clock, t0 + 25.0, step)
    clock.t = t0 + 25.0
    engine._dispatching()                             # the next program goes out at 25 s
    floor, ceiling = engine.device_dry_s["floor"], engine.device_dry_s["ceiling"]
    assert floor <= 15.0 + 1e-9 <= ceiling + 2e-9
    # their gap is no more than the spacing of the probes around the program's end
    assert ceiling - floor <= max(step, offset) + 1e-9
    if step <= 1.0:
        assert floor > 0


def test_nothing_is_dry_while_a_dispatched_program_can_still_run(stepped):
    engine, clock = stepped
    engine._dispatching()
    engine._dispatched([Stub(clock, clock.t)])        # done at once: the device is dry
    probe_every(engine, clock, clock.t + 2.0, 1.0)
    floor = engine.device_dry_s["floor"]
    assert floor == pytest.approx(1.0)                # from the first probe that found it done
    # A dispatch call opens: until its outputs are here the last program's
    # ready outputs say nothing of the one that may already be on the device.
    engine._dispatching()
    floor = engine.device_dry_s["floor"]
    probe_every(engine, clock, clock.t + 3.0, 1.0)
    assert engine.device_dry_s["floor"] == floor
    # dispatched, not yet queued for fetch, still running: neither bound moves
    engine._dispatched([Stub(clock, clock.t + 5.0)])
    ceiling = engine.device_dry_s["ceiling"]
    probe_every(engine, clock, clock.t + 4.0, 1.0)
    assert engine.device_dry_s == {"floor": floor, "ceiling": ceiling}


@pytest.mark.parametrize("ends_at, dry", [
    (2.0, 3.0),    # the program before finished 2 s into a dispatch call of 5 s: dry for 3 s of it
    (9.0, 0.0),    # it was still running when the call returned: the device never waited
])
def test_a_slow_dispatch_call_is_under_the_ceiling(stepped, ends_at, dry):
    engine, clock = stepped
    t0 = clock.t
    engine._dispatching()
    engine._dispatched([Stub(clock, t0 + 10.0 + ends_at)])
    clock.t = t0 + 10.0
    engine._dispatching()                             # busy: nothing counted so far
    assert engine.device_dry_s == {"floor": 0.0, "ceiling": 0.0}
    clock.t = t0 + 15.0                               # the call took 5 s on the host
    engine._dispatched([Stub(clock, t0 + 30.0)])
    engine._probe(clock.t)
    assert engine.device_dry_s["floor"] == 0.0        # no probe ever found it done
    assert dry <= engine.device_dry_s["ceiling"] <= 5.0
    assert (engine.device_dry_s["ceiling"] == 0.0) == (dry == 0.0)


def test_an_engine_without_requests_is_not_dry(stepped):
    engine, clock = stepped
    engine._running.clear()
    engine._dispatching()
    engine._dispatched([Stub(clock, clock.t)])
    probe_every(engine, clock, clock.t + 5.0, 1.0)
    assert engine.device_dry_s == {"floor": 0.0, "ceiling": 0.0}


@pytest.mark.parametrize("ready_at, waited, unread", [
    (2.5, "host", 5.0),      # seen ready by the probe at 3 s, its fetch ended at 8 s
    (50.0, "device", 0.0),   # the fetch blocked on the device
])
def test_a_first_sample_seen_ready_counts_the_time_it_lay_unread(stepped, ready_at, waited, unread):
    engine, clock = stepped
    t0 = clock.t
    first = _First([], Stub(clock, t0 + ready_at), Stub(clock, t0 + ready_at), None)
    engine._fetchq.append(first)
    probe_every(engine, clock, t0 + 8.0, 1.0)
    assert (first.t_ready is None) == (waited == "device")
    engine._fetchq.popleft()
    engine._drain_one(first)
    assert engine.first_fetches == {"device": int(waited == "device"), "host": int(waited == "host")}
    assert engine.first_ready_unread_s == pytest.approx(unread)
    assert engine._cur is None                         # the drain left the phase it found


def test_a_ready_sample_no_probe_had_seen_counts_from_its_fetch(stepped):
    engine, clock = stepped
    first = _First([], Stub(clock, clock.t), Stub(clock, clock.t), None)
    engine._drain_one(first)                           # never queued, so never probed
    assert engine.first_fetches == {"device": 0, "host": 1}
    assert engine.first_ready_unread_s == 0.0          # the stepped clock did not move in the fetch
