"""Mean of engine.deliver: the first-token sample on the host to the first delta
the request's coroutine yields (emit, the hop to the event loop, the queue)."""
from chipbench.layer_metrics._prom import phase_mean_ms


def read(ctx):
    return phase_mean_ms(ctx, "engine.deliver")
