"""The per-layer readers and ``host_phases.py`` that ISSUE 26 adds to the
benchmark, each on recorded ``/metrics`` text or on a recorded event list: no
chip, no JAX. A page that lacks the program's new series (the parent's) must
read as nothing, not raise."""

import importlib
import json
import os

import pytest

from chipbench import arith, host_phases

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
P = "dynamo_tpu_phase_duration_seconds"


def page(**series) -> dict:
    return arith.parse_prom("\n".join(f"{k} {v}" for k, v in series.items()))


def phase(span: str, total: float, n: int) -> dict:
    return {f'{P}_sum{{phase="{span}"}}': total, f'{P}_count{{phase="{span}"}}': n}


# Two workers and a frontend, scraped before and after a 10 s window, as the
# registries render them (``MetricsRegistry.render``).
WORKER_BEFORE = page(**phase("engine.queue", 1.0, 10), **phase("engine.prefill", 2.0, 10),
                     **phase("engine.dispatch", 0.5, 10), **phase("engine.first_wait", 1.4, 10),
                     **phase("engine.deliver", 0.1, 10),
                     **{'dynamo_tpu_engine_step_phase_seconds_total{phase="idle"}': 5.0,
                        'dynamo_tpu_engine_step_phase_seconds_total{phase="drain_sync"}': 1.0,
                        'dynamo_tpu_engine_decode_row_steps_total{kind="dispatched"}': 1000,
                        'dynamo_tpu_engine_decode_row_steps_total{kind="emitted"}': 900,
                        "dynamo_tpu_engine_sched_cpu_seconds_total": 3.0,
                        "dynamo_tpu_kv_pool_hit_blocks_total": 100,
                        "dynamo_tpu_kv_pool_miss_blocks_total": 900})
WORKER_AFTER = page(**phase("engine.queue", 5.0, 30), **phase("engine.prefill", 12.0, 30),
                    **phase("engine.dispatch", 2.5, 30), **phase("engine.first_wait", 9.4, 30),
                    **phase("engine.deliver", 0.3, 30), **phase("engine.blocked", 0.6, 2),
                    **{'dynamo_tpu_engine_step_phase_seconds_total{phase="idle"}': 6.0,
                       'dynamo_tpu_engine_step_phase_seconds_total{phase="drain_sync"}': 6.0,
                       'dynamo_tpu_engine_step_phase_seconds_total{phase="first_sample"}': 1.0,
                       'dynamo_tpu_engine_step_phase_seconds_total{phase="emit"}': 3.0,
                       'dynamo_tpu_engine_decode_row_steps_total{kind="dispatched"}': 5000,
                       'dynamo_tpu_engine_decode_row_steps_total{kind="emitted"}': 3900,
                       "dynamo_tpu_engine_sched_cpu_seconds_total": 5.5,
                       "dynamo_tpu_kv_pool_hit_blocks_total": 1000,
                       "dynamo_tpu_kv_pool_miss_blocks_total": 1000})
TTFT = "dynamo_tpu_http_time_to_first_token_seconds"
LAG = "dynamo_tpu_frontend_loop_lag_seconds"
FRONT_BEFORE = page(**{f'{TTFT}_sum{{model="m"}}': 6.0, f'{TTFT}_count{{model="m"}}': 20,
                       f"{LAG}_sum": 0.1, f"{LAG}_count": 100,
                       "process_cpu_seconds_total": 12.0})
FRONT_AFTER = page(**{f'{TTFT}_sum{{model="m"}}': 30.0, f'{TTFT}_count{{model="m"}}': 60,
                      f"{LAG}_sum": 0.5, f"{LAG}_count": 300,
                      "process_cpu_seconds_total": 19.0})


def ctx(**over) -> dict:
    prom = {"frontend.before": FRONT_BEFORE, "frontend.after": FRONT_AFTER}
    for r in (0, 1):
        prom[f"worker{r}.before"], prom[f"worker{r}.after"] = WORKER_BEFORE, WORKER_AFTER
    base = {"prom": prom, "replicas": 2, "seconds": 10.0, "records": [], "gauges": [],
            "stats": {}, "trace": None, "here": os.path.join(ROOT, "chipbench"), "t0_unix": 0.0}
    return {**base, **over}


def old_program(c: dict) -> dict:
    """The same pages from a program without this PR's spans and counters."""
    new = ("engine.blocked", "engine.dispatch", "engine.first_wait", "engine.deliver",
           "engine_step_phase", "engine_decode_row", "engine_sched_cpu", "kv_pool_",
           "frontend_loop_lag", "process_cpu")
    prom = {who: {k: v for k, v in p.items() if not any(n in k for n in new)}
            for who, p in c["prom"].items()}
    return {**c, "prom": prom}


def read(name: str, c: dict):
    return importlib.import_module(f"chipbench.layer_metrics.{name}").read(c)


# Each value below is worked out by hand from the pages above, two workers alike.
@pytest.mark.parametrize("name, want, on_old_program", [
    ("queue_blocked_mean_ms", 1000 * (2 * 0.6) / (2 * 20), None),
    ("admit_dispatch_mean_ms", 1000 * 2.0 / 20, None),
    ("first_wait_mean_ms", 1000 * 8.0 / 20, None),
    ("first_deliver_mean_ms", 1000 * 0.2 / 20, None),
    ("sched_cpu_share", 100 * 2.5 / 10.0, None),
    # not idle 5 + 1 + 3 = 9 s a worker, of which 2.5 s on the CPU
    ("sched_wait_device_share", 100 * (9.0 - 2.5) / 9.0, None),
    ("decode_row_yield", 100 * 3000 / 4000, None),
    ("pool_hit_window_share", 100 * 900 / 1000, None),
    ("frontend_path_mean_ms", 1000 * (24.0 / 40 - 4.0 / 20 - 10.0 / 20), 1000 * (0.6 - 0.2 - 0.5)),
    ("frontend_loop_lag_ms", 1000 * 0.4 / 200, None),
    ("frontend_cpu_share", 100 * 7.0 / 10.0, None),
])
def test_reader_on_recorded_metrics_text(name, want, on_old_program):
    assert read(name, ctx()) == pytest.approx(want)
    got = read(name, old_program(ctx()))
    assert got == (None if on_old_program is None else pytest.approx(on_old_program))


def without(c: dict, *marks: str) -> dict:
    """The workers' pages after the window with the series that hold a mark left out."""
    for r in (0, 1):
        c["prom"][f"worker{r}.after"] = {k: v for k, v in WORKER_AFTER.items()
                                         if not any(m in k for m in marks)}
    return c


def test_nothing_blocked_reads_zero():
    assert read("queue_blocked_mean_ms", without(ctx(), "engine.blocked")) == 0.0


def test_a_scheduler_idle_all_window_has_no_wait_share():
    # only the idle phase grew: there is no busy time to take a share of
    c = without(ctx(), "drain_sync", "first_sample", "emit")
    assert read("sched_wait_device_share", c) is None
    # and one with no CPU counter (an older program) leaves the metric out
    assert read("sched_wait_device_share", without(ctx(), "engine_sched_cpu")) is None


# -- the scheduler thread's own account (ISSUE 43) --------------------------------


def series(name: str, label: str, **values: float) -> dict:
    return {f'dynamo_tpu_engine_{name}{{{label}="{k}"}}': v for k, v in values.items()}


# What a worker of PR 43 adds to the pages above, before and after the window.
ACCOUNT_BEFORE = {
    **series("step_phase_cpu_seconds_total", "phase", admission=0.2, admit_alloc=0.2, plan=0.1,
             decode_dispatch=0.5, emit=1.0, gauges=1.0),
    **series("step_phase_total", "phase", decode_dispatch=40, emit=40),
    "dynamo_tpu_engine_sched_wall_seconds_total": 50.0,
    **series("admission_stops_total", "reason", empty=30, budget=5),
    "dynamo_tpu_engine_prefill_waves_total": 20, "dynamo_tpu_engine_wave_windows_ahead_total": 10,
    **series("first_fetch_total", "waited", device=15),
    **series("device_dry_seconds_total", "bound", ceiling=1.0),
}
ACCOUNT_AFTER = {
    # grown: admission 0.3 + admit_alloc 0.2; plan 0.4 + decode_dispatch 0.5 + stack_rows 0.1;
    # emit 0.5 + drain_sync 0.25 (new in the window); gauges 0.25: 2.5 s in all
    **series("step_phase_cpu_seconds_total", "phase", admission=0.5, admit_alloc=0.4, plan=0.5,
             decode_dispatch=1.0, stack_rows=0.1, emit=1.5, drain_sync=0.25, gauges=1.25),
    **series("step_phase_total", "phase", decode_dispatch=90, emit=100),
    "dynamo_tpu_engine_sched_wall_seconds_total": 60.0,
    **series("admission_stops_total", "reason", empty=80, budget=35, slots=8, blocks=2),
    "dynamo_tpu_engine_prefill_waves_total": 60, "dynamo_tpu_engine_wave_windows_ahead_total": 70,
    **series("first_fetch_total", "waited", device=35, host=20),
    "dynamo_tpu_engine_first_ready_unread_seconds_total": 0.1,
    **series("device_dry_seconds_total", "bound", floor=0.2, ceiling=1.5),
}


def with_account(c: dict) -> dict:
    for r in (0, 1):
        c["prom"][f"worker{r}.before"] = {**WORKER_BEFORE, **ACCOUNT_BEFORE}
        c["prom"][f"worker{r}.after"] = {**WORKER_AFTER, **ACCOUNT_AFTER}
    return c


# By hand from the pages above, two workers alike; every one None on the parent's pages.
@pytest.mark.parametrize("name, want", [
    ("sched_cpu_per_window_ms", 1000 * 2.5 / 50),
    ("sched_cpu_admission_share", 100 * 0.5 / 2.5),
    ("sched_cpu_dispatch_share", 100 * 1.0 / 2.5),
    ("sched_cpu_emit_share", 100 * 0.75 / 2.5),
    ("admission_budget_stop_share", 100 * 30 / 40),
    ("wave_windows_ahead_mean", 60 / 40),
    ("first_ready_unread_mean_ms", 1000 * 0.1 / 40),
    ("device_dry_floor_share", 100 * 0.2 / 10),
    ("device_dry_ceiling_share", 100 * 0.5 / 10),
])
def test_reader_of_the_scheduler_threads_account(name, want):
    assert read(name, with_account(ctx())) == pytest.approx(want)
    assert read(name, ctx()) is None   # the parent's pages: the series are not there


def test_a_thread_that_was_never_dry_reads_zero_not_nothing():
    c = with_account(ctx())
    for r in (0, 1):
        c["prom"][f"worker{r}.after"] = {k: v for k, v in c["prom"][f"worker{r}.after"].items()
                                         if "device_dry" not in k and "ready_unread" not in k}
        c["prom"][f"worker{r}.before"] = {k: v for k, v in c["prom"][f"worker{r}.before"].items()
                                          if "device_dry" not in k}
    assert read("device_dry_floor_share", c) == 0.0
    assert read("device_dry_ceiling_share", c) == 0.0
    assert read("first_ready_unread_mean_ms", c) == 0.0


def test_every_new_reader_is_listed_and_every_listed_reader_exists():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        mod = m["name"].replace("-", "_").replace(".", "_")
        assert callable(importlib.import_module(f"chipbench.layer_metrics.{mod}").read), m["name"]
        assert set(m["workloads"]) <= cells


# -- host_phases.py on a recorded event list ------------------------------------

# Nanoseconds. The device runs three operations with two gaps between them;
# the scheduler thread idles, admits, dispatches and emits meanwhile.
EVENTS = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_multi_decode_impl(1)", 0, 1000]]},
        {"name": "XLA Ops", "events": [["fusion.1", 0, 1000], ["fusion.2", 1500, 500],
                                       ["paged_decode_attention.3", 4000, 1000]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["sched.idle", 900, 300], ["sched.admission", 1200, 400],
                                      ["sched.emit", 2500, 1000], ["$other", 0, 5000]]},
        {"name": "other thread", "events": [["sched.decode_dispatch", 3800, 400]]}]},
]}


def test_host_phases_lays_the_scheduler_over_the_device_gaps():
    r = host_phases.attribute(EVENTS)
    assert r["window_s"] == pytest.approx(5000e-9) and r["n_gaps"] == 2
    assert r["idle_s"] == pytest.approx((500 + 2000) * 1e-9)
    assert r["phases_seen"] == 4
    # gap 1000-1500: idle 1000-1200, admission 1200-1500; gap 2000-4000: emit 2500-3500, dispatch 3800-4000
    assert r["idle_by_phase"] == pytest.approx({
        "sched.emit": 1000e-9, "none": 800e-9, "sched.admission": 300e-9,
        "sched.idle": 200e-9, "sched.decode_dispatch": 200e-9})
    assert r["idle_host_busy_s"] == pytest.approx(1500e-9)
    assert r["sched_s"] == pytest.approx({"sched.admission": 400e-9, "sched.decode_dispatch": 400e-9,
                                          "sched.emit": 1000e-9, "sched.idle": 300e-9})
    longest, second = r["gaps"]
    assert longest[:2] == pytest.approx([2000e-9, 2000e-9])
    assert longest[2] == pytest.approx({"sched.emit": 1000e-9, "sched.decode_dispatch": 200e-9})
    assert second[2] == pytest.approx({"sched.idle": 200e-9, "sched.admission": 300e-9})
    text = host_phases.table(r)
    assert "sched.emit" in text and "60.0%" in text


def test_host_phases_on_a_trace_without_annotations_sees_none():
    doc = {"planes": [EVENTS["planes"][0], {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["$other", 0, 5000]]}]}]}
    r = host_phases.attribute(doc)
    assert r["phases_seen"] == 0 and r["idle_host_busy_s"] == 0.0
    assert r["idle_by_phase"] == pytest.approx({"none": 2500e-9})
    # the reader leaves the metric out: no trace, or no annotation in it
    assert read("idle_host_busy_share", ctx()) is None


def test_overlap_sweeps_both_lists_once():
    gaps = [(0.0, 10.0), (20.0, 30.0), (40.0, 50.0)]
    spans = [(5.0, 25.0), (28.0, 29.0), (45.0, 100.0)]
    assert host_phases.overlap(gaps, spans) == [5.0, 6.0, 5.0]
    assert host_phases.overlap(gaps, []) == [0.0, 0.0, 0.0]


# -- the grouped expert product's roofline, by program (PR 30) --------------------

LONGCAT_CONFIG = os.path.join(ROOT, "chipbench", "configs", "longcat-flash-omni-ep32.json")


def expert_ctx(**drop) -> dict:
    """One worker whose counters grew over the window by 4,000 decode calls
    touching 40,000 experts (10 a call) and 400 prefill calls touching 5,200
    (13 a call); a trace of 10 decode windows (8 steps, 4 layers: 320 calls)
    and 25 + 5 prefill executions (120 calls)."""
    touched, calls = "dynamo_tpu_moe_experts_touched_total", "dynamo_tpu_moe_expert_calls_total"
    after = page(**{f'{touched}{{program="decode"}}': 41000, f'{calls}{{program="decode"}}': 4100,
                    f'{touched}{{program="prefill"}}': 5300, f'{calls}{{program="prefill"}}': 410})
    before = page(**{f'{touched}{{program="decode"}}': 1000, f'{calls}{{program="decode"}}': 100,
                     f'{touched}{{program="prefill"}}': 100, f'{calls}{{program="prefill"}}': 10})
    trace = {"modules": {"jit_multi_decode_impl": [1.5, 10], "jit_prefill_batch_impl": [1.3, 25],
                         "jit_prefill_impl": [0.1, 5]},
             "ops_by_module": {"jit_multi_decode_impl": {"gmm": 0.4, "latent_decode_attention": 0.3},
                               "jit_prefill_batch_impl": {"gmm": 0.15}, "jit_prefill_impl": {"gmm": 0.05}},
             "op_counts": {"gmm": 1320}}
    with open(LONGCAT_CONFIG) as f:
        config = json.load(f)
    c = ctx(replicas=1, trace=trace, config=config, stats={"0.0": {"kind": "TPU v5 lite"}})
    c["prom"] = {"worker0.before": before, "worker0.after": after}
    for who, mark in drop.items():
        c["prom"][who] = {k: v for k, v in c["prom"][who].items() if mark not in k}
    return c


@pytest.mark.parametrize("name, calls, touched_a_call, gmm_s", [
    ("moe_expert_roofline", 10 * 8 * 4, 10.0, 0.4),
    ("moe_prefill_expert_roofline", (25 + 5) * 4, 13.0, 0.2),
])
def test_expert_roofline_counts_its_calls_in_the_trace(name, calls, touched_a_call, gmm_s):
    """Calls from the trace's executions, experts touched a call from the
    program's counters by program, one expert 3 x 6144 x 2048 x 2 B, over the
    gmm kernel's seconds in those programs; nothing is scaled by how much of
    the window the trace held."""
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        bw = json.load(f)["devices"]["TPU v5 lite"]["hbm_bytes_per_s"]
    want = 100 * calls * touched_a_call * (3 * 6144 * 2048 * 2) / bw / gmm_s
    assert read(name, expert_ctx()) == pytest.approx(want) and want < 100
    longer = expert_ctx()
    longer["seconds"] = 45.0  # the window's length is not in it
    assert read(name, longer) == pytest.approx(want)
    assert read(name, expert_ctx(**{"worker0.after": "moe_expert_calls"})) is None  # the parent's pages
    assert read(name, {**expert_ctx(), "trace": None}) is None
