"""Mean wait in the engine's queue: phase_duration_seconds{phase="engine.queue"},
growth of sum over growth of count. The histogram's buckets (0.1, 0.25, 0.5,
1 s) are too coarse for a percentile."""
from chipbench.layer_metrics import worker_delta


def read(ctx):
    s = worker_delta(ctx, "dynamo_tpu_phase_duration_seconds_sum", 'phase="engine.queue"')
    n = worker_delta(ctx, "dynamo_tpu_phase_duration_seconds_count", 'phase="engine.queue"')
    return 1000.0 * s / n if s is not None and n else None
