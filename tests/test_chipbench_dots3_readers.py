"""The readers PR 47 added (``chipbench/layer_metrics``): a number where the
trace and the ``/metrics`` pages hold what they read, None where either lacks
it (the parent of the PR that adds a reader is traced with that reader too)."""

import json
import os

import pytest

from chipbench import kernels_dots3
from chipbench.layer_metrics import (
    dsa_chosen_share,
    dsa_decode_attn_roofline,
    dsa_index_roofline,
    dsa_walk_share,
    moe_expert_roofline_share,
    window_resume_share,
)

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "chipbench")
with open(os.path.join(HERE, "configs", "dots3-note-prev-ep16.json")) as f:
    DOTS3 = json.load(f)
with open(os.path.join(HERE, "configs", "longcat-flash-omni-ep32.json")) as f:
    LONGCAT = json.load(f)

P = "dynamo_tpu_engine_"
MOE = "dynamo_tpu_moe_"


def pages(chosen=2048.0 * 40, visible=24000.0 * 40, resumes=(9.0, 1.0, 0.0), touched=480.0, calls=64.0,
          steps=(30.0, 40.0)) -> dict:
    after = {}
    if chosen is not None:
        after.update({P + "dsa_chosen_tokens_total": chosen, P + "dsa_visible_tokens_total": visible})
    if steps is not None:
        after.update({P + "dsa_decode_walk_steps_total": steps[0], P + "dsa_decode_steps_total": steps[1]})
    if resumes is not None:
        after.update({P + f'window_resume_total{{outcome="{o}"}}': n
                      for o, n in zip(("deepest", "cut_back", "miss"), resumes)})
    if touched is not None:
        after.update({MOE + 'experts_touched_total{program="decode"}': touched,
                      MOE + 'expert_calls_total{program="decode"}': calls,
                      MOE + 'experts_touched_total{program="prefill"}': 999.0,
                      MOE + 'expert_calls_total{program="prefill"}': 99.0})
    return {"worker0.before": {}, "worker0.after": after}


def trace(attend_events=3, index_events=3, attend_s=0.0009, index_s=0.0006, gmm_s=0.008) -> dict:
    """One decode step: 3 full layers' indexer scan and chosen-rows attend, 8 expert layers' products."""
    counts, ops = {}, {}
    if attend_events:
        counts["latent_sparse_decode_attention"], ops["latent_sparse_decode_attention"] = attend_events, attend_s
    if index_events:
        counts["dsa_index_scores"], ops["dsa_index_scores"] = index_events, index_s
    if gmm_s:
        counts["gmm"], ops["gmm"] = 24, gmm_s
    return {"op_counts": counts, "ops_by_module": {"jit_multi_decode_impl": ops, "jit_prefill_batch_impl": {"gmm": 1.0}}}


def records(rows=10) -> list[dict]:
    """``rows`` requests in decode all through the traced second, 24,000 tokens of context each."""
    return [{"first": 0.0, "last": 10.0, "status": "ok", "prompt_tokens": 24000, "chunks": []} for _ in range(rows)]


def ctx(**kw) -> dict:
    base = {"trace": trace(), "prom": pages(), "config": DOTS3, "replicas": 1, "here": HERE, "records": records(),
            "stats": {"0.0": {"kind": "TPU v5 lite"}}, "t0": 100.0, "t0_unix": 100.0,
            "trace_marks": {"asked_start": 1.0, "asked_stop": 2.0}}
    return {**base, **kw}


def test_the_readers_divide_the_yardsticks_least_by_the_kernels_seconds():
    with open(os.path.join(HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"]["TPU v5 lite"]
    # 10 rows x 2,048 chosen rows of 576 values of 2 B against 2 x 128 x (576 + 512) operations a row
    rows = 10 * 2048
    least = rows * max(576 * 2 / peak["hbm_bytes_per_s"], 2 * 128 * 1088 / peak["bf16_flops"])
    assert dsa_decode_attn_roofline.read(ctx()) == pytest.approx(100 * least / (0.0009 / 3))
    # 10 rows x 24,000 keys of 128 values of 2 B
    assert kernels_dots3.index_key_bytes(240000, DOTS3) == 240000 * 256
    assert dsa_index_roofline.read(ctx()) == pytest.approx(100 * 240000 * 256 / peak["hbm_bytes_per_s"] / (0.0006 / 3))
    # one step = 8 expert layers' calls, 7.5 experts touched a call, 3 x 5120 x 1536 x 2 B an expert
    least_call = 7.5 * 3 * 5120 * 1536 * 2 / peak["hbm_bytes_per_s"]
    assert moe_expert_roofline_share.read(ctx()) == pytest.approx(100 * 8 * least_call / 0.008)
    assert dsa_chosen_share.read(ctx()) == pytest.approx(100 * 2048 / 24000)
    assert window_resume_share.read(ctx()) == pytest.approx(90.0)
    assert dsa_walk_share.read(ctx()) == pytest.approx(75.0)
    assert dsa_walk_share.read(ctx(prom=pages(steps=(0.0, 8.0)))) == 0.0  # every step gathered: a number, not None
    for reader in (dsa_decode_attn_roofline, dsa_index_roofline, moe_expert_roofline_share):
        assert 0 < reader.read(ctx()) <= 100


@pytest.mark.parametrize("reader,without", [
    (dsa_decode_attn_roofline, dict(trace=trace(attend_events=0))),
    (dsa_decode_attn_roofline, dict(trace=None)),
    (dsa_decode_attn_roofline, dict(config=LONGCAT)),
    (dsa_decode_attn_roofline, dict(records=[])),
    (dsa_index_roofline, dict(trace=trace(index_events=0))),
    (dsa_index_roofline, dict(config=LONGCAT)),
    (dsa_index_roofline, dict(trace_marks={})),
    (moe_expert_roofline_share, dict(trace=trace(gmm_s=0))),
    (moe_expert_roofline_share, dict(trace=trace(attend_events=0))),
    (moe_expert_roofline_share, dict(prom=pages(touched=None))),
    (moe_expert_roofline_share, dict(config=LONGCAT)),
    (dsa_chosen_share, dict(prom=pages(chosen=None))),
    (dsa_chosen_share, dict(prom={})),
    (dsa_walk_share, dict(prom=pages(steps=None))),      # the parent of the PR that added it: no such series
    (dsa_walk_share, dict(prom={})),
    (dsa_walk_share, dict(prom=pages(steps=(0.0, 0.0)))),  # no decode step past index_topk in the window
    (window_resume_share, dict(prom=pages(resumes=None))),
    (window_resume_share, dict(prom=pages(resumes=(0.0, 0.0, 0.0)))),
])
def test_a_reader_without_its_kernel_or_counter_reads_none(reader, without):
    assert reader.read(ctx(**without)) is None
