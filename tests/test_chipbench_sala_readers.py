"""The readers PR 45 added (``chipbench/layer_metrics``): a number where the
trace and the ``/metrics`` pages hold what they read, None where either lacks
it (the parent of the PR that adds a reader is traced with that reader too)."""

import json
import os

import pytest

from chipbench import kernels_sala
from chipbench.layer_metrics import (
    lightning_decode_roofline,
    sparse_decode_attn_roofline,
    sparse_read_share,
    state_resume_share,
)

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "chipbench")
with open(os.path.join(HERE, "configs", "minicpm-sala-int8.json")) as f:
    SALA = json.load(f)
with open(os.path.join(HERE, "configs", "qwen2.5-7b-int8.json")) as f:
    QWEN = json.load(f)

P = "dynamo_tpu_engine_"


def pages(chosen=640.0, visible=2500.0, cached=160000.0, again=320.0) -> dict:
    after = {}
    if chosen is not None:
        after.update({P + "sparse_blocks_chosen_total": chosen, P + "sparse_blocks_visible_total": visible})
    if cached is not None:
        after.update({P + "state_cached_tokens_total": cached, P + "state_recomputed_tokens_total": again})
    return {"worker0.before": {}, "worker0.after": after}


def trace(step_events=24, attend_events=8, step_s=0.0024, attend_s=0.0016) -> dict:
    """One decode step: 24 lightning layers' step kernel, 8 sparse layers' attend."""
    counts, ops = {}, {}
    if step_events:
        counts["lightning_decode"], ops["lightning_decode"] = step_events, step_s
    if attend_events:
        counts["paged_decode_attention"], ops["paged_decode_attention"] = attend_events, attend_s
    return {"op_counts": counts, "ops_by_module": {"jit_multi_decode_impl": ops}}


def records(rows=10) -> list[dict]:
    """``rows`` requests in decode all through the traced second, 16,000 tokens of context each."""
    return [{"first": 0.0, "last": 10.0, "status": "ok", "prompt_tokens": 16000, "chunks": []} for _ in range(rows)]


def ctx(**kw) -> dict:
    base = {"trace": trace(), "prom": pages(), "config": SALA, "replicas": 1, "here": HERE, "records": records(),
            "stats": {"0.0": {"kind": "TPU v5 lite"}}, "t0": 100.0, "t0_unix": 100.0,
            "trace_marks": {"asked_start": 1.0, "asked_stop": 2.0}}
    return {**base, **kw}


def test_the_readers_divide_the_yardsticks_bytes_by_the_kernels_seconds():
    # 10 rows: the state in and out is 2 x 32 x 128 x 128 x 4 B a row, q, k, v, o 4 x 32 x 128 x 2 B
    least = 10 * (2 * 32 * 128 * 128 * 2 + 4 * 32 * 128 * 2) / 819e9
    assert kernels_sala.lightning_step_bytes(10, SALA) == 10 * (2097152 + 32768)
    assert lightning_decode_roofline.read(ctx()) == pytest.approx(100 * least / (0.0024 / 24), rel=1e-9)
    # 10 rows x 2 KV heads x 64 blocks x 64 tokens x K and V x 128 lanes x 2 B
    chosen = 10 * 2 * 64 * 64 * 2 * 128 * 2
    assert kernels_sala.chosen_page_bytes(10, SALA) == chosen
    assert sparse_decode_attn_roofline.read(ctx()) == pytest.approx(100 * chosen / 819e9 / (0.0016 / 8), rel=1e-9)
    assert kernels_sala.compressed_key_bytes(160000, SALA) == 160000 / 16 * 2 * 128 * 2
    assert kernels_sala.lightning_prefill_ops(512, SALA) == 512 * 32 * (4 * 128 * 128 + 4 * 128 * 128)
    assert sparse_read_share.read(ctx()) == pytest.approx(25.6)
    assert state_resume_share.read(ctx()) == pytest.approx(99.8)
    assert (kernels_sala.lightning_layers(SALA), kernels_sala.sparse_layers(SALA)) == (24, 8)


@pytest.mark.parametrize("lacks", ["no_trace", "no_kernels", "no_counters", "another_block", "no_marks", "nobody_decoding"])
def test_a_reader_that_finds_nothing_returns_none(lacks):
    c = {"no_trace": ctx(trace=None), "no_kernels": ctx(trace=trace(step_events=0, attend_events=0)),
         "no_counters": ctx(prom=pages(chosen=None, cached=None)), "another_block": ctx(config=QWEN),
         "no_marks": ctx(trace_marks={}), "nobody_decoding": ctx(records=[])}[lacks]
    if lacks == "no_counters":
        assert sparse_read_share.read(c) is None and state_resume_share.read(c) is None
        assert sparse_read_share.read(ctx(prom={})) is None and state_resume_share.read(ctx(prom={})) is None
        return
    assert lightning_decode_roofline.read(c) is None
    assert sparse_decode_attn_roofline.read(c) is None
