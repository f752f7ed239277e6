"""Sequences the scheduler preempted in the window."""
from chipbench.layer_metrics import worker_delta


def read(ctx):
    return worker_delta(ctx, "dynamo_tpu_engine_preemptions_total")
