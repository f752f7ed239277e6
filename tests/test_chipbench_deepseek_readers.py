"""The readers PR 52 added (``chipbench/layer_metrics``) for a layer shared by
four chips: a number where the trace of device plane 0 and the ``/metrics``
pages hold what they read, None where either lacks it (the parent of the PR
that adds a reader, and every one-chip cell, is traced with that reader too)."""

import json
import os

import pytest

from chipbench import kernels_deepseek
from chipbench.layer_metrics import (
    collective_decode_ms,
    collective_share,
    mla_decode_attn_roofline_tp,
    moe_chip_load_imbalance,
    moe_expert_roofline_tp,
    moe_prefill_expert_roofline_tp,
)

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "chipbench")
with open(os.path.join(HERE, "configs", "deepseek-v2-tp4.json")) as f:
    DEEPSEEK = json.load(f)
with open(os.path.join(HERE, "configs", "longcat-flash-omni-ep32.json")) as f:
    LONGCAT = json.load(f)

MOE = "dynamo_tpu_moe_"


def pages(touched=(1440.0, 1500.0, 1400.0, 1300.0), calls=40.0, loads=(300.0, 100.0, 100.0, 100.0)) -> dict:
    after = {}
    if touched is not None:
        for program, scale in (("decode", 1.0), ("prefill", 0.5)):
            after[MOE + f'expert_calls_total{{program="{program}"}}'] = calls
            after.update({MOE + f'experts_touched_total{{program="{program}",chip="{c}"}}': n * scale
                          for c, n in enumerate(touched)})
    if loads is not None:  # one expert of each chip in each of two layers
        after.update({MOE + f'expert_tokens_total{{layer="{l}",expert="{40 * c + 3}"}}': n
                      for l in (1, 2) for c, n in enumerate(loads)})
    return {"worker0.before": {}, "worker0.after": after}


def trace(gmm_s=0.100, attn_s=0.0096, collectives=True) -> dict:
    """One decode window of 8 steps (6 layers' attention, 5 expert layers'
    products a step) and two prefills, on device plane 0."""
    decode = {"fusion": 0.05}
    prefill = {"fusion": 0.02}
    counts = {}
    if gmm_s:
        decode["gmm"], prefill["gmm"], counts["gmm"] = gmm_s, gmm_s / 4, 120 + 30
    if attn_s:
        decode["latent_decode_attention"], counts["latent_decode_attention"] = attn_s, 48
    if collectives:
        decode.update({"psum": 0.004, "all-gather-start": 0.001, "all-gather-done": 0.003})
        prefill.update({"all-reduce": 0.002})
    return {"op_counts": counts, "busy_s": 0.2, "window_s": 0.25,
            "modules": {"jit_multi_decode_impl": [0.1, 1], "jit_prefill_batch_impl": [0.04, 2]},
            "ops_by_module": {"jit_multi_decode_impl": decode, "jit_prefill_batch_impl": prefill}}


def records(rows=60) -> list[dict]:
    """``rows`` requests in decode all through the traced second, 1,800 tokens of context each."""
    return [{"first": 0.0, "last": 10.0, "status": "ok", "prompt_tokens": 1800, "chunks": []} for _ in range(rows)]


def ctx(**kw) -> dict:
    base = {"trace": trace(), "prom": pages(), "config": DEEPSEEK, "replicas": 1, "here": HERE, "records": records(),
            "stats": {"0.0": {"kind": "TPU v5 lite"}}, "t0": 100.0, "t0_unix": 100.0,
            "trace_marks": {"asked_start": 1.0, "asked_stop": 2.0}}
    return {**base, **kw}


def test_one_chips_share_is_the_whole_row_and_a_quarter_of_the_heads():
    with open(os.path.join(HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"]["TPU v5 lite"]
    assert kernels_deepseek.chips(DEEPSEEK) == 4 and kernels_deepseek.chips(LONGCAT) == 1
    assert kernels_deepseek.expert_layers(DEEPSEEK) == 5
    assert kernels_deepseek.expert_bytes(DEEPSEEK) == 3 * 5120 * 1536 * 2
    # bytes-bound: 1,152 B a token at 819 GB/s against 2 x 32 x 1,088 operations at 197 TFLOP/s
    a_token = kernels_deepseek.latent_decode_least_s(1.0, DEEPSEEK, peak)
    assert a_token == pytest.approx(1152 / peak["hbm_bytes_per_s"])
    assert a_token > 2 * 32 * 1088 / peak["bf16_flops"]


def test_the_readers_divide_one_chips_least_by_plane_0s_seconds():
    with open(os.path.join(HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"]["TPU v5 lite"]
    least = 60 * 1800 * 1152 / peak["hbm_bytes_per_s"]
    assert mla_decode_attn_roofline_tp.read(ctx()) == pytest.approx(100 * least / (0.0096 / 48))
    # chip 0 touched 36 experts a call; one window = 8 steps x 5 expert layers' calls
    least_call = 36 * 3 * 5120 * 1536 * 2 / peak["hbm_bytes_per_s"]
    assert moe_expert_roofline_tp.read(ctx()) == pytest.approx(100 * 40 * least_call / 0.100)
    # two prefills x 5 expert layers, 18 experts a call
    assert moe_prefill_expert_roofline_tp.read(ctx()) == pytest.approx(100 * 10 * least_call / 2 / 0.025)
    assert collective_share.read(ctx()) == pytest.approx(100 * 0.010 / 0.2)
    assert collective_decode_ms.read(ctx()) == pytest.approx(1000 * 0.008 / 8)
    assert moe_chip_load_imbalance.read(ctx()) == pytest.approx(600 / (1200 / 4))
    for reader in (mla_decode_attn_roofline_tp, moe_expert_roofline_tp, moe_prefill_expert_roofline_tp, collective_share):
        assert 0 < reader.read(ctx()) <= 100


@pytest.mark.parametrize("reader,without", [
    (mla_decode_attn_roofline_tp, dict(trace=trace(attn_s=0))),
    (mla_decode_attn_roofline_tp, dict(trace=None)),
    (mla_decode_attn_roofline_tp, dict(config=LONGCAT)),
    (mla_decode_attn_roofline_tp, dict(trace_marks={})),
    (moe_expert_roofline_tp, dict(trace=trace(gmm_s=0))),
    (moe_expert_roofline_tp, dict(trace=None)),
    (moe_expert_roofline_tp, dict(prom=pages(touched=None))),   # the parent: no counter by chip
    (moe_expert_roofline_tp, dict(config=LONGCAT)),
    (moe_prefill_expert_roofline_tp, dict(prom={})),
    (moe_prefill_expert_roofline_tp, dict(config=LONGCAT)),
    (collective_share, dict(trace=trace(collectives=False))),  # a one-chip cell: nothing is exchanged
    (collective_share, dict(trace=None)),
    (collective_decode_ms, dict(trace=trace(collectives=False))),
    (collective_decode_ms, dict(trace=None)),
    (moe_chip_load_imbalance, dict(prom=pages(loads=None))),
    (moe_chip_load_imbalance, dict(config=LONGCAT)),
    (moe_chip_load_imbalance, dict(prom={})),
])
def test_a_reader_whose_source_is_absent_gives_none(reader, without):
    assert reader.read(ctx(**without)) is None
