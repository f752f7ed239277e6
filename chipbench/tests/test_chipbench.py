"""The benchmark's own tests; run by hand, on the CPU, from the repo's root:

    python3 -m pytest chipbench/tests -q -p no:cacheprovider

(not under ``tests/``, which the benchmark PR may not touch; tier-1 collects
every case here through ``tests/test_chipbench_spread.py``). The cases of this
file need no JAX; those that do are in ``parity_cases.py``, imported at the
end, and import JAX inside the case. The kill test is a script of its own,
``chipbench/tests/kill_test.py``.
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


# -- spread as the driver has it, the word, the bucket share, BENCHMARK.json ------
# (ISSUE 25 asked for these under tests/ too; a benchmark PR may add no file there.)


@pytest.mark.parametrize("values, want", [
    ([100, 101, 102, 103, 104, 150], 4 / 102.5),    # one far run is left out
    ([100, 101, 102, 103, 140, 150], 40 / 102.5),   # two far runs are not: 150 goes, 140 stays
    ([50, 100, 101, 102, 103, 104], 4 / 101.5),     # the far run may be the lowest
    ([100, 100, 100, 100], 0.0),                    # leaving one out narrows nothing: same width
    ([100, 110], 10 / 105),                         # fewer than three: none is left out
    ([100], 0.0),
])
def test_ledger_spread_leaves_out_the_one_farthest_run(values, want):
    assert arith.ledger_spread(values) == pytest.approx(want)


def test_ledger_spread_of_nothing_is_not_a_number():
    assert math.isnan(arith.ledger_spread([]))
    assert math.isnan(arith.quartile_spread([1.0]))


def test_the_two_spreads_disagree_on_far_runs():
    # Quartiles of six runs lie a quarter of the way from the second value to
    # the first and from the fifth to the sixth: an end counts for a quarter.
    one_far = [100, 100.5, 101, 101.5, 102, 120]
    assert arith.quartile_spread(one_far) == pytest.approx(6.125 / 101.25)
    assert arith.ledger_spread(one_far) == pytest.approx(2 / 101.25)       # left out
    two_ends = [90, 100, 100.5, 101, 101.5, 112]
    assert arith.quartile_spread(two_ends) == pytest.approx(6.625 / 100.75)
    assert arith.ledger_spread(two_ends) == pytest.approx(11.5 / 100.75)   # 112 goes, 90 stays


@pytest.mark.parametrize("side_a, side_b, bound, word", [
    ([100, 101, 102.5], [100, 101, 102.5], 0.05, "judgeable"),     # spread 2.5/101 < 2.5%
    ([100, 101, 102.6, 110], [100, 101, 102], 0.05, "unsteady"),   # A spreads 2.6/101.8 > 2.5%
    ([100, 100, 100], [100, 100, 102.5], 0.05, "judgeable"),       # exactly half the bound passes
    ([100, 100, 100], [102.4, 102.4, 102.4], 0.05, "judgeable"),   # medians 2.4% apart
    ([100, 100, 100], [102.5, 102.5, 102.5], 0.05, "unsteady"),    # medians half the bound apart
    ([100, 100, 100], [97.5, 97.5, 97.5], 0.05, "unsteady"),       # better or worse alike
])
def test_the_word_turns_at_half_the_bound(side_a, side_b, bound, word):
    assert arith.aa_word(side_a, side_b, bound)["word"] == word


def gauges(active_by_poll, seconds=10.0):
    key = 'dynamo_tpu_fleet_worker_active_slots{worker="1"}'
    return {"gauges": [{"t": 0.5 * i, key: v} for i, v in enumerate(active_by_poll)],
            "seconds": seconds, "decode_buckets": [8, 32, 64]}


@pytest.mark.parametrize("active, want", [
    ([40, 45, 50, 64, 33], 100.0),                   # all polls in one bucket
    ([30, 31, 32, 33, 40, 20, 1, 8, 9, 32], 60.0),   # 6 of 10 in <=32, 2 in <=8, 2 in <=64
    ([], None),                                      # no polls
])
def test_batch_bucket_main_share_on_a_made_up_series(active, want):
    from chipbench.layer_metrics import batch_bucket_main_share
    got = batch_bucket_main_share.read(gauges(active))
    assert got is None if want is None else got == pytest.approx(want)


def test_bucket_shares_count_each_worker_and_only_the_window():
    from chipbench.layer_metrics import batch_bucket_main_share, gauge_samples
    ctx = gauges([], seconds=1.0)
    ctx["gauges"] = [{"t": -0.5, 'dynamo_tpu_fleet_worker_active_slots{worker="1"}': 1.0},
                     {"t": 0.5, 'dynamo_tpu_fleet_worker_active_slots{worker="1"}': 40.0,
                      'dynamo_tpu_fleet_worker_active_slots{worker="2"}': 20.0,
                      'dynamo_tpu_fleet_worker_total_slots{worker="1"}': 64.0},
                     {"t": 1.5, 'dynamo_tpu_fleet_worker_active_slots{worker="1"}': 2.0}]
    assert gauge_samples(ctx, "dynamo_tpu_fleet_worker_active_slots") == [40.0, 20.0]
    assert batch_bucket_main_share.read(ctx) == pytest.approx(50.0)
    assert arith.bucket_shares([70.0], [8, 32, 64]) == {8: 0.0, 32: 0.0, 64: 100.0}


def benchmark_json() -> dict:
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_every_cell_a_metric_lists_exists(group):
    bench = benchmark_json()
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench[group]:
        assert set(m.get("workloads", [])) <= cells, m["name"]
        if group == "per_layer":
            moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
            assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells)), m["name"]
            reader = m["name"].replace("-", "_").replace(".", "_") + ".py"
            assert os.path.isfile(os.path.join(BENCH, "layer_metrics", reader)), m["name"]


def test_every_judged_metric_has_a_bound_within_the_contract():
    for m in benchmark_json()["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1, m["name"]


@pytest.mark.parametrize("cell", [w["name"] for w in benchmark_json()["workloads"]])
def test_every_cell_is_judged_and_its_files_exist(cell):
    bench = benchmark_json()
    w = next(x for x in bench["workloads"] if x["name"] == cell)
    judged = [m["name"] for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    assert "setup_s" in judged and len(judged) >= 2, judged
    assert any(cell in m.get("workloads", [cell]) for m in bench["per_layer"])
    assert os.path.isfile(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    assert os.path.isfile(os.path.join(os.path.dirname(BENCH), cfg["file"]))


@pytest.mark.parametrize("pairs, order", [
    (1, "AB"), (2, "ABBA"), (3, "ABBAAB"), (6, "ABBAABBAABBA"),
])
def test_aa_pairs_take_turns_to_go_first_and_share_a_seed(pairs, order):
    from chipbench import prove
    plan = prove.aa_plan(pairs)
    assert "".join(side for side, _, _ in plan) == order
    assert [i for _, i, _ in plan] == [i for i in range(pairs) for _ in "AB"]
    assert all(trace == 0 for _, _, trace in plan)


def test_aa_summary_prints_the_word_for_judged_metrics_and_not_for_phases():
    from chipbench import prove
    by_set = {"A": {"out_tok_s": [800.0, 801.0, 802.0], "warm_up_s": [40.0, 41.0, 50.0]},
              "B": {"out_tok_s": [800.0, 830.0, 860.0], "warm_up_s": [40.0, 41.0, 42.0]}}
    lines = prove.summary(by_set, {"out_tok_s": 0.015}, aa=True)
    assert any(ln.startswith("aa out_tok_s:") and ln.endswith("unsteady") for ln in lines)
    assert any(ln.startswith("aa warm_up_s:") and ln.endswith("not judged") for ln in lines)


def test_the_result_line_carries_what_prove_reads_under_one_ignored_key():
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1}
    doc = make_result(True, 1, 0, {}, {}, dev, None, {"setup_phases": {"warm_up_s": 40.0}})
    assert set(doc) == {"correct", "attempted", "failed", "metrics", "device", "unjudged"}


def test_every_line_of_text_in_benchmark_json_is_within_the_contract():
    bench = benchmark_json()
    for entry in bench["workloads"] + bench["configs"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"], entry["name"]


# -- configurations by name: maps, references, rehearsal files, parity limits (PR 29) --------

from chipbench import host_phases, lookup, model_maps, parity  # noqa: E402
from chipbench.model_maps import dense_gqa  # noqa: E402

# What the parent's ``launch_worker.model_fields`` gave for each file (commit ebf62a4).
PARENT_FIELDS = {
    "qwen2.5-7b-int8": {"vocab_size": 152064, "hidden_size": 3584, "intermediate_size": 18944,
                        "num_layers": 28, "num_heads": 28, "num_kv_heads": 4, "rope_theta": 1000000.0,
                        "rms_norm_eps": 1e-06, "tie_embeddings": False, "head_dim": 128,
                        "attn_bias": True, "max_position": 4096, "name": "qwen2.5-7b-int8"},
    "mistral-7b-v0.3-int8": {"vocab_size": 32768, "hidden_size": 4096, "intermediate_size": 14336,
                             "num_layers": 32, "num_heads": 32, "num_kv_heads": 8, "rope_theta": 1000000.0,
                             "rms_norm_eps": 1e-05, "tie_embeddings": False, "head_dim": 128,
                             "attn_bias": False, "max_position": 4096, "name": "mistral-7b-v0.3-int8"},
    "qwen2.5-7b-int8-x4": {"vocab_size": 152064, "hidden_size": 3584, "intermediate_size": 18944,
                           "num_layers": 28, "num_heads": 28, "num_kv_heads": 4, "rope_theta": 1000000.0,
                           "rms_norm_eps": 1e-06, "tie_embeddings": False, "head_dim": 128,
                           "attn_bias": True, "max_position": 4096, "name": "qwen2.5-7b-int8-x4"},
    "rehearse-tiny": {"vocab_size": 512, "hidden_size": 128, "intermediate_size": 256, "num_layers": 2,
                      "num_heads": 4, "num_kv_heads": 2, "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
                      "tie_embeddings": True, "head_dim": 32, "attn_bias": False, "max_position": 4096,
                      "name": "rehearse-tiny"},
}


def config_doc(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(PARENT_FIELDS))
def test_the_dense_map_gives_what_the_launcher_gave(name):
    doc = config_doc(name)
    assert doc["model_map"] == "dense_gqa"
    assert dense_gqa.fields(doc) == PARENT_FIELDS[name]
    assert model_maps.fields(doc) == PARENT_FIELDS[name]


def test_a_file_without_a_map_ends_with_the_maps_there_are():
    doc = config_doc("rehearse-tiny")
    del doc["model_map"]
    with pytest.raises(lookup.Missing, match=r'no "model_map" key.*dense_gqa'):
        model_maps.fields(doc)


def test_an_unknown_map_ends_with_the_maps_there_are():
    with pytest.raises(lookup.Missing, match=r"no model_maps/latent_moe\.py; there are \[.*'dense_gqa'"):
        model_maps.fields({**config_doc("rehearse-tiny"), "model_map": "latent_moe"})


def test_a_field_the_program_lacks_is_named_beside_the_fields_it_has(tmp_path, monkeypatch):
    os.makedirs(tmp_path / "model_maps")
    (tmp_path / "model_maps" / "latent.py").write_text(
        "from chipbench.model_maps import dense_gqa\n"
        "def fields(doc):\n    return {**dense_gqa.fields(doc), 'kv_lora_rank': 512}\n")
    monkeypatch.setenv("CHIPBENCH_PATH", str(tmp_path))
    with pytest.raises(lookup.Missing, match=r"\['kv_lora_rank'\].*ModelConfig does not have.*num_kv_heads"):
        model_maps.model_config({**config_doc("rehearse-tiny"), "model_map": "latent"})


def test_run_ends_without_a_result_on_a_configuration_without_a_map(tmp_path, monkeypatch):
    from chipbench import run
    doc = config_doc("rehearse-tiny")
    del doc["model_map"]
    os.makedirs(tmp_path / "configs")
    (tmp_path / "configs" / "rehearse-tiny.json").write_text(json.dumps(doc))
    monkeypatch.setenv("CHIPBENCH_PATH", str(tmp_path))
    with pytest.raises(run.NoResult, match="model_map") as e:
        run.load_cell("qwen2.5-7b-int8.chat", rehearse=True)
    assert e.value.code == run.EXIT_NO_PROGRAM


def test_a_control_overrides_the_served_block_and_leaves_the_rest():
    """The program's own lower precision as a control: ``--param served.extra_flags=...``
    reaches the worker's flags; the file, its limits and the mix are the cell's."""
    from chipbench import run
    spec = run.load_cell("qwen2.5-7b-int8.chat", rehearse=False)
    opts = run.parse(["--workload", "qwen2.5-7b-int8.chat", "--param", 'served.extra_flags=["--kv-quant","int8"]',
                      "--param", "rate_rps=2"])
    control, cell = run.Run(opts, spec), run.Run(run.parse(["--workload", "qwen2.5-7b-int8.chat"]), spec)
    flags = run.worker_flags(control.config, "tcp://s", "/t")
    assert flags[-2:] == ["--kv-quant", "int8"] and "--kv-quant" not in run.worker_flags(cell.config, "tcp://s", "/t")
    assert control.traffic["rate_rps"] == 2 and "served.extra_flags" not in control.traffic
    assert {k: v for k, v in control.config.items() if k != "served"} == {
        k: v for k, v in cell.config.items() if k != "served"}
    assert spec["config"]["served"]["extra_flags"] == []  # the cell's own file is not touched


@pytest.mark.parametrize("config", [c["name"] for c in benchmark_json()["configs"]] + ["qwen2.5-7b-int8-x4"])
def test_every_configuration_names_files_that_exist_and_how_it_is_compared(config):
    doc = config_doc(config)
    assert os.path.isfile(lookup.find("model_maps", doc["model_map"], ".py"))
    assert os.path.isfile(lookup.find("references", doc["reference"], ".py"))
    toy = config_doc(doc["rehearse"])
    assert toy["model_map"] and toy["reference"]
    assert all(toy["parity"][name] > 0 for name in parity.JUDGED)  # the toy's own limits, for --rehearse
    assert doc["parity"]["rows"] >= 2 and len(doc["parity"]["why"]) > 40
    assert doc["served"]["max_model_len"] % 512 == 0  # a row of the sample is that long


@pytest.mark.parametrize("cell", [w["name"] for w in benchmark_json()["workloads"]])
def test_every_cell_has_limits_of_its_own_and_a_rehearsal_takes_the_toys(cell):
    from chipbench import run
    with open(lookup.find("limits", cell, ".json")) as f:
        own = json.load(f)
    assert set(own) == {*parity.JUDGED, "why"} and len(own["why"]) > 200  # the readings it was set from
    spec = run.load_cell(cell, rehearse=False)
    assert all(spec["parity"][name] == own[name] > 0 for name in parity.JUDGED)
    assert spec["parity"]["rows"] == spec["config"]["parity"]["rows"]
    toy = run.load_cell(cell, rehearse=True)
    assert toy["parity"] == toy["config"]["parity"] and toy["config"]["name"] == "rehearse-tiny"


def test_a_cell_without_limits_does_not_pass():
    """A cell whose PR forgot ``limits/<cell>.json`` is held to the configuration's
    block alone, which gives no limit: the verdict says which are missing."""
    bare = config_doc("qwen2.5-7b-int8")["parity"]
    assert parity.verdict({"tokens": 3, "max_logit_gap": 0.0, "mean_logit_gap": 0.0}, bare) == [
        "no limit for max_logit_gap: limits/<cell>.json has to give one",
        "no limit for mean_logit_gap: limits/<cell>.json has to give one"]


@pytest.mark.parametrize("read, why", [
    ({"tokens": 10, "max_logit_gap": 0.5, "mean_logit_gap": 0.01}, []),                    # at the edge
    ({"tokens": 10, "max_logit_gap": 0.49, "mean_logit_gap": 0.0}, []),                    # under
    ({"tokens": 10, "max_logit_gap": 0.51, "mean_logit_gap": 0.01}, ["max_logit_gap"]),    # over one
    ({"tokens": 10, "max_logit_gap": 0.1, "mean_logit_gap": 0.011}, ["mean_logit_gap"]),
    ({"tokens": 10, "max_logit_gap": 9.0, "mean_logit_gap": 1.0}, ["max_logit_gap", "mean_logit_gap"]),
    ({"tokens": 10, "max_logit_gap": float("nan"), "mean_logit_gap": 0.0}, ["max_logit_gap"]),
    ({"tokens": 0}, ["no served token"]),
])
def test_the_verdict_turns_at_the_limit(read, why):
    got = parity.verdict(read, {"max_logit_gap": 0.5, "mean_logit_gap": 0.01})
    assert len(got) == len(why) and all(w in g for w, g in zip(why, got)), got


def test_a_missing_limit_does_not_pass():
    got = parity.verdict({"tokens": 3, "max_logit_gap": 0.0, "mean_logit_gap": 0.0}, {"max_logit_gap": 1.0})
    assert got == ["no limit for mean_logit_gap: limits/<cell>.json has to give one"]


def test_readings_from_hand_made_gaps():
    read = parity.readings([0.0, 0.0, 0.25, 0.75])
    assert read == {"tokens": 4, "max_logit_gap": 0.75, "mean_logit_gap": 0.25, "flipped_share": 0.5}
    assert parity.readings([]) == {"tokens": 0}


def finished(prompt: list[int], answer: list[int], status: str = "ok", history: int | None = None) -> dict:
    return {"status": status, "prompt": prompt, "answer": answer,
            **({} if history is None else {"history_tokens": history})}


def test_the_sample_holds_the_longest_finished_request_and_follows_the_seed():
    recs = [finished([n] * n, [2] * 8) for n in (5, 90, 7, 40, 12, 33, 21, 60)]
    recs += [finished([1] * 500, [2] * 3, "cut"), finished([1] * 600, [], "failed")]
    seqs = parity.sequences(recs)
    assert [len(s["tokens"]) for s in seqs] == [13, 98, 15, 48, 20, 41, 29, 68]  # only finished requests
    assert all(s["served"] == [[len(s["tokens"]) - 8, len(s["tokens"])]] for s in seqs)
    for seed in (1, 2 ** 31 + 5):
        rows, left = parity.pick_sample(seqs, seed, rows=2, row_tokens=128)
        assert len(rows) == 2 and len(rows[0][0]["tokens"]) == 98  # the longest, first
        assert all(sum(len(s["tokens"]) for s in row) <= 128 for row in rows)
        assert sorted(map(id, left + rows[0] + rows[1])) == sorted(map(id, seqs))  # each once
        assert (rows, left) == parity.pick_sample(seqs, seed, 2, 128)
        again, _ = parity.pick_sample(left, seed, 2, 128)  # a second sample shares nothing with the first
        assert again and not {id(s) for row in again for s in row} & {id(s) for row in rows for s in row}
    picks = {tuple(len(s["tokens"]) for s in parity.pick_sample(seqs, s, 2, 128)[0][1]) for s in range(20)}
    assert len(picks) > 1
    assert parity.pick_sample(parity.sequences(recs[-2:]), 1, 2, 128) == ([], [])
    assert parity.pick_sample(seqs, 1, 2, 12) == ([], [])  # no sequence fits a row


def test_a_sessions_last_turn_carries_the_spans_of_the_turns_it_resends():
    """Turn 2 resends turn 1's prompt and answer, turn 3 starts over from a system
    prompt, another client's turn looks like nothing else, and a failed turn breaks
    its chain: each served token is read once, behind the history it was served after."""
    sys_p, a1, a2 = [9] * 6, [11, 12, 13], [21, 22]
    t1 = finished(sys_p + [1, 1], a1, history=6)
    t2 = finished(t1["prompt"] + a1 + [2, 2, 2], a2, history=len(t1["prompt"]) + 3)
    t3 = finished(sys_p + [3], [31], history=6)
    other = finished([7] * 4 + [5], [41, 42], history=4)
    broken = finished(other["prompt"] + [41, 42] + [6], [51], "failed", history=7)
    after = finished(broken["prompt"] + [51] + [8], [61], history=9)
    seqs = parity.sequences([t1, other, t2, broken, t3, after])
    assert [(s["tokens"], s["served"]) for s in seqs] == [
        (other["prompt"] + [41, 42], [[5, 7]]),
        (t2["prompt"] + a2, [[8, 11], [14, 16]]),
        (t3["prompt"] + [31], [[7, 8]]),
        (after["prompt"] + [61], [[10, 11]])]
    for s in seqs:  # every span holds served tokens and nothing else
        assert all(s["tokens"][a:b] in ([41, 42], a1, a2, [31], [61]) for a, b in s["served"])


def test_idle_gaps_are_named_by_the_phase_over_them_on_the_recorded_trace():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        doc = json.load(f)
    bare = host_phases.attribute(doc)
    labels = host_phases.label_gaps(bare)
    assert labels and len(labels) <= 10 and all(name == "no_sched_phase" for name, _ in labels)
    assert [s for _, s in labels] == sorted((s for _, s in labels), reverse=True)
    # The same device plane under a scheduler thread that dispatched all through it,
    # but for the second half of the longest gap, which it spent emitting.
    dev = next(p for p in doc["planes"] if p["name"].startswith("/device:"))
    events = [e for ln in dev["lines"] for e in ln["events"]]
    t_min, t_max = min(e[1] for e in events), max(e[1] + e[2] for e in events)
    start_s, dur_s, _ = bare["gaps"][0]
    mid = t_min + (start_s + 0.4 * dur_s) * 1e9
    host = {"name": "/host:CPU", "lines": [{"name": "sched", "events": [
        ["sched.decode_dispatch", t_min, mid - t_min], ["sched.emit", mid, t_max - mid]]}]}
    labelled = host_phases.label_gaps(host_phases.attribute({"planes": doc["planes"] + [host]}))
    assert labelled[0] == ["sched.emit", pytest.approx(dur_s)]
    assert {name for name, _ in labelled[1:]} <= {"sched.decode_dispatch", "sched.emit"}


from chipbench.tests.parity_cases import *  # noqa: E402,F401,F403 - the cases that need JAX, on the CPU
