"""The block pool's own prefix hit rate at the end of the window. It is a
ratio since the worker started, warm-up and pre-fill included: the pool
exports no numerator or denominator, so a figure for the window alone cannot
be had (PERF.md, Open questions)."""
from chipbench.layer_metrics import gauge_series


def read(ctx):
    series = gauge_series(ctx, "dynamo_tpu_fleet_worker_prefix_hit_rate")
    return 100.0 * series[-1] / ctx["replicas"] if series else None
