"""The readers PR 37 added (``chipbench/layer_metrics``): a number where the
trace and the ``/metrics`` pages hold what they read, None where either lacks
it (the parent of the PR that adds a reader is traced with that reader too)."""

import json
import os

import pytest

from chipbench.layer_metrics import (
    conv_state_resume_share,
    moe_expert_roofline_whole,
    moe_prefill_expert_roofline_whole,
)

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "chipbench")
with open(os.path.join(HERE, "configs", "lfm2-24b-a2b-pp4.json")) as f:
    LFM2 = json.load(f)
with open(os.path.join(HERE, "configs", "longcat-flash-omni-ep32.json")) as f:
    LONGCAT = json.load(f)

P = "dynamo_tpu_"


def pages(touched=(320.0, 640.0), calls=(8.0, 16.0), resumes=(90.0, 0.0)) -> dict:
    """before/after pages of one worker: (decode, prefill) experts touched and calls, (cache, recompute) resumes."""
    after = {}
    if touched:
        for program, t, c in zip(("decode", "prefill"), touched, calls):
            after[f'{P}moe_experts_touched_total{{program="{program}"}}'] = t
            after[f'{P}moe_expert_calls_total{{program="{program}"}}'] = c
        after[f'{P}moe_assignments_total{{kind="held"}}'] = 4000.0
    if resumes:
        after[f'{P}engine_conv_state_resumes_total{{source="cache"}}'] = resumes[0]
        after[f'{P}engine_conv_state_resumes_total{{source="recompute"}}'] = resumes[1]
        after[f'{P}engine_conv_state_resumes_total{{source="zero"}}'] = 7.0
    return {"worker0.before": {}, "worker0.after": after}


def trace(gmm_events=24 + 48, attn_events=2, decode_s=0.012, prefill_s=0.030) -> dict:
    """One decode step (2 attention layers, 8 expert layers x 3 kernels) and 16 prefill calls."""
    counts = {"paged_decode_attention": attn_events}
    if gmm_events:
        counts["gmm"] = gmm_events
    return {"op_counts": counts,
            "ops_by_module": {"jit_multi_decode_impl": {"gmm": decode_s, "paged_decode_attention": 0.001},
                              "jit_prefill_batch_impl": {"gmm": prefill_s}},
            "modules": {"jit_multi_decode_impl": [0.02, 1], "jit_prefill_batch_impl": [0.04, 2]}}


def ctx(**kw) -> dict:
    base = {"trace": trace(), "prom": pages(), "config": LFM2, "replicas": 1, "here": HERE,
            "stats": {"0.0": {"kind": "TPU v5 lite"}}}
    return {**base, **kw}


def test_the_whole_expert_rooflines_count_their_calls_from_the_trace():
    # decode: 8 calls of 40 experts x 18.87 MB over 819 GB/s = 7.37 ms, over 12 ms of gmm
    assert moe_expert_roofline_whole.read(ctx()) == pytest.approx(100 * 8 * 40 * 18874368 / 819e9 / 0.012, rel=1e-6)
    # prefill: (72 - 24) / 3 = 16 calls of 40 experts, over 30 ms
    assert moe_prefill_expert_roofline_whole.read(ctx()) == pytest.approx(100 * 16 * 40 * 18874368 / 819e9 / 0.030, rel=1e-6)
    assert conv_state_resume_share.read(ctx()) == 100.0
    assert conv_state_resume_share.read(ctx(prom=pages(resumes=(90.0, 10.0)))) == 90.0


@pytest.mark.parametrize("lacks", ["no_trace", "no_gmm", "no_attention_kernel", "no_counters", "another_block"])
def test_a_reader_that_finds_nothing_returns_none(lacks):
    c = {"no_trace": ctx(trace=None), "no_gmm": ctx(trace=trace(gmm_events=0)),
         "no_attention_kernel": ctx(trace=trace(attn_events=0)), "no_counters": ctx(prom=pages(touched=None, resumes=None)),
         "another_block": ctx(config=LONGCAT)}[lacks]
    assert moe_expert_roofline_whole.read(c) is None
    assert moe_prefill_expert_roofline_whole.read(c) is None
    if lacks == "no_counters":
        assert conv_state_resume_share.read(c) is None
        assert conv_state_resume_share.read(ctx(prom={})) is None
