"""Of the window's prefill rows that had cached blocks behind them, the share
that found their conv state there too: growth of
``engine_conv_state_resumes_total{source="cache"}`` over ``cache`` +
``recompute`` (a row whose K and V were cached further than its conv state
runs its conv layers over those positions again). None for a program without
the counter, or a window in which no row resumed."""
from chipbench.layer_metrics import worker_delta

NAME = "dynamo_tpu_engine_conv_state_resumes_total"


def read(ctx):
    cache = worker_delta(ctx, NAME, 'source="cache"')
    again = worker_delta(ctx, NAME, 'source="recompute"')
    if cache is None or not cache + (again or 0.0):
        return None
    return 100.0 * cache / (cache + (again or 0.0))
