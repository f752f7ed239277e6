"""Of the prompt tokens the window's admissions found pages for in the prefix
cache, the share whose lightning state was there too, so that prefill resumed
past them: 1 - growth of ``engine_state_recomputed_tokens_total`` over growth
of ``engine_state_cached_tokens_total`` (a chain is cut back to its deepest
snapshot and the cached pages past it are computed again). None for a program
without the counters, or a window in which no admission found a cached page."""
from chipbench.layer_metrics import worker_delta

P = "dynamo_tpu_engine_state_"


def read(ctx):
    cached, again = worker_delta(ctx, P + "cached_tokens_total"), worker_delta(ctx, P + "recomputed_tokens_total")
    if not cached or again is None:
        return None
    return 100.0 * (1.0 - again / cached)
