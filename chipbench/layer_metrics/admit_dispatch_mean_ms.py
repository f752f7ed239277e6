"""Mean of engine.dispatch: admission (blocks allocated) to the return of
_dispatch_prefills for the wave, when every prefill chunk is on the device's queue."""
from chipbench.layer_metrics._prom import phase_mean_ms


def read(ctx):
    return phase_mean_ms(ctx, "engine.dispatch")
