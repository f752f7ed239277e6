"""The benchmark's own tests; run by hand, on the CPU, from the repo's root:

    python3 -m pytest chipbench/tests -q -p no:cacheprovider

(not under ``tests/``, which the benchmark PR may not touch). The kill test is
a script of its own, ``chipbench/tests/kill_test.py``.
"""

import hashlib
import json
import math
import os

import pytest

from chipbench import arith, client, generators, trace_reduce
from chipbench.run import make_result

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")) if f.endswith(".json"))


def plan_of(mix: str, seed: int, seconds: float = 20.0) -> dict:
    with open(os.path.join(BENCH, "traffic", mix + ".json")) as f:
        params = json.load(f)
    return generators.load(params["kind"]).generate(params, seed, seconds, 152064)


def digest(plan: dict) -> str:
    return hashlib.sha256(json.dumps(plan, sort_keys=True).encode()).hexdigest()


@pytest.mark.parametrize("mix", MIXES)
def test_generator_is_deterministic_in_the_seed_and_differs_across_seeds(mix):
    big = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
    assert digest(plan_of(mix, big)) == digest(plan_of(mix, big))
    assert digest(plan_of(mix, big)) != digest(plan_of(mix, big + 1))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_sizes_and_arrivals_with_other_tokens(mix):
    a, b = plan_of(mix, 1), plan_of(mix, 2)
    if a["mode"] == "open":
        sizes = lambda p: sorted((len(r["prompt"]), r["max_tokens"]) for r in p["requests"])
        assert sizes(a) == sizes(b) and len(a["requests"]) > 0
        assert [r["due"] for r in a["requests"]] == [r["due"] for r in b["requests"]]
        assert a["requests"][0]["prompt"] != b["requests"][0]["prompt"]
        assert all(x["due"] <= y["due"] for x, y in zip(a["requests"], a["requests"][1:]))
    else:
        sizes = lambda p: [sorted((len(t["new"]), t["max_tokens"]) for t in c["turns"])
                           for c in p["clients"]]
        assert sizes(a) == sizes(b)
        assert [len(c["prefill"]) for c in a["clients"]] == [len(c["prefill"]) for c in b["clients"]]


def test_sessions_resend_history_and_stay_under_the_limit():
    with open(os.path.join(BENCH, "traffic", "sessions.json")) as f:
        params = json.load(f)
    plan = plan_of("sessions", 5)
    for c in plan["clients"]:
        hist = len(c["prefill"])
        for t in c["turns"]:
            if t["base"] is not None:
                hist = params["system_tokens"]
            hist += len(t["new"]) + t["max_tokens"]
            assert hist <= params["max_history"]
        assert c["prefill"][:16] in [s[:16] for s in plan["system_prompts"]]


def rec(kind="open", due=0.0, sent=0.0, first=None, last=None, chunks=(), status="ok"):
    return {"kind": kind, "due": due, "sent": sent, "first": first, "last": last,
            "chunks": list(chunks), "status": status}


def test_pctl_nearest_rank_and_failures_as_the_worst():
    assert arith.pctl([1, 2, 3, 4], 50) == 2
    assert arith.pctl(list(range(1, 101)), 95) == 95
    assert arith.pctl([1.0, math.inf], 95) == math.inf
    assert math.isnan(arith.pctl([], 50))
    assert arith.finite_or_cap(math.inf, 40000.0) == 40000.0


def test_ttft_is_timed_from_the_due_time_in_an_open_loop():
    late = rec(due=1.0, sent=1.5, first=2.0, last=3.0, chunks=[(2.0, 1), (3.0, 8)])
    closed = rec(kind="closed", due=None, sent=1.5, first=2.0, last=3.0, chunks=[(2.0, 1)])
    failed = rec(due=1.0, sent=1.0, status="failed")
    waiting = rec(due=9.0, sent=9.0, status="cut")
    got = arith.ttft_samples([late, closed, failed, waiting], t_end=10.0)
    assert got == [1.0, 0.5, math.inf, 1.0]


def test_tpot_is_per_request_and_needs_a_decode_window():
    r = rec(first=1.0, last=3.0, chunks=[(1.0, 1), (2.0, 8), (3.0, 8)])
    short = rec(first=1.0, last=1.2, chunks=[(1.0, 1), (1.2, 3)])
    failed = rec(status="failed")
    assert arith.tpot_samples([r, short, failed]) == [2.0 / 16, math.inf]


def test_tokens_in_window_leave_out_failed_requests_and_the_outside():
    ok = rec(chunks=[(0.5, 1), (1.5, 8), (2.5, 8)])
    bad = rec(chunks=[(1.0, 8)], status="failed")
    cut = rec(chunks=[(1.9, 8)], status="cut")
    assert arith.tokens_in_window([ok, bad, cut], 1.0, 2.0) == 16


def test_prometheus_deltas():
    before = arith.parse_prom('a_total{x="1"} 2\na_total{x="2"} 3\n# HELP\n')
    after = arith.parse_prom('a_total{x="1"} 5\na_total{x="2"} 3\nb{phase="q"} 1.5\n')
    assert arith.prom_delta(before, after, "a_total") == 3
    assert arith.prom_delta(before, after, "a_total", 'x="2"') == 0
    assert arith.prom_delta(before, after, "b", 'phase="q"') == 1.5
    assert arith.prom_delta(before, after, "missing") is None


def test_the_synthetic_tokenizer_text_round_trips():
    assert client.text_token_ids("T1a T0 Tff") == [26, 0, 255]
    assert client.text_token_ids(" T2") == [2]


def test_judge_names_what_is_wrong_with_a_stream():
    good = {"error": None, "finish_reason": "length", "max_tokens": 9, "prompt_tokens": 4,
            "usage": {"completion_tokens": 9, "prompt_tokens": 4}, "chunks": [(0, 1), (1, 8)]}
    assert client.judge(good) is None
    assert "finish_reason" in client.judge({**good, "finish_reason": None})
    assert "completion tokens" in client.judge({**good, "chunks": [(0, 1)]})
    assert "prompt tokens" in client.judge({**good, "prompt_tokens": 5})


def test_result_line_holds_exactly_the_contract_keys():
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1}
    line = json.dumps(make_result(True, 10, 0, {"setup_s": 1.5}, {"setup_s": "s"}, dev, None))
    doc = json.loads(line)
    assert set(doc) == {"correct", "attempted", "failed", "metrics", "device"}
    assert doc["metrics"] == {"setup_s": {"value": 1.5, "unit": "s"}}
    traced = make_result(False, 1, 1, {}, {}, dev, {"device_ops": [], "idle_gaps": []})
    assert set(traced) == {"correct", "attempted", "failed", "metrics", "device", "breakdown"}


def test_trace_reduction_on_the_recorded_trace():
    path = os.path.join(HERE, "trace_small.json")
    with open(path) as f:
        doc = json.load(f)
    out = trace_reduce.reduce(doc)
    with open(os.path.join(HERE, "trace_small.expected.json")) as f:
        want = json.load(f)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    assert out["window_s"] == pytest.approx(want["window_s"])
    assert out["top_ops"][0][0] == want["top_op"]
    assert sum(n for _, n in out["modules"].values()) == want["executions"]


def test_union_and_gaps_on_a_hand_made_trace():
    ev = lambda n, a, d: [n, a * 1e9, d * 1e9]
    doc = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [ev("x", 0.0, 10.0)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [ev("jit_step(1)", 1.0, 2.0), ev("jit_step(1)", 5.0, 2.0)]},
            {"name": "XLA Ops", "events": [ev("fusion.1", 1.0, 1.5), ev("copy.2", 2.0, 1.0),
                                           ev("fusion.3", 5.0, 2.0)]}]}]}
    out = trace_reduce.reduce(doc)
    assert out["window_s"] == pytest.approx(6.0)        # the device's first event to its last
    assert out["busy_s"] == pytest.approx(4.0)          # [1,3] and [5,7]
    assert out["modules"] == {"jit_step": [pytest.approx(4.0), 2]}
    assert out["top_ops"][0] == ["fusion.3", pytest.approx(2.0)]
    assert out["idle_gaps"][0] == [pytest.approx(2.0), pytest.approx(2.0)]
    assert out["ops_by_module"]["jit_step"]["copy"] == pytest.approx(1.0)


def test_operation_names_are_cut_from_the_hlo_text():
    hlo = ("%paged_decode_attention.5 = bf16[32,512,28]{2,1,0:T(8,128)(2,1)S(1)} "
           "custom-call(s32[1]{0:T(128)} %dynamic_slice.1)")
    assert trace_reduce.short(hlo) == "paged_decode_attention.5 bf16[32,512,28]"
    assert trace_reduce.kind(trace_reduce.short(hlo)) == "paged_decode_attention"
    assert trace_reduce.short("%while.35 = (s32[]{:T(128)}, bf16[32,3584]{1,0}) while(...)") == "while.35 s32[]"
    assert trace_reduce.kind("while.35 s32[]") in trace_reduce.CONTAINERS
    assert trace_reduce.short("jit_multi_decode_impl(123)") == "jit_multi_decode_impl(123)"
