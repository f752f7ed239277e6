"""Mean of engine.first_wait: the wave's prefills dispatched to its first-token
sample on the host. The device's part of a first token: the decode windows
queued ahead, the prefill programs and the fetch."""
from chipbench.layer_metrics._prom import phase_mean_ms


def read(ctx):
    return phase_mean_ms(ctx, "engine.first_wait")
