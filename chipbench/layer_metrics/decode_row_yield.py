"""Tokens the decode windows delivered to a live sequence over the batch-bucket
rows x steps they were dispatched with: what padding rows, rows past a stop
and finished rows cost. engine_decode_row_steps_total{kind}."""
from chipbench.layer_metrics import worker_delta

NAME = "dynamo_tpu_engine_decode_row_steps_total"


def read(ctx):
    emitted = worker_delta(ctx, NAME, 'kind="emitted"')
    dispatched = worker_delta(ctx, NAME, 'kind="dispatched"')
    return 100.0 * emitted / dispatched if emitted is not None and dispatched else None
