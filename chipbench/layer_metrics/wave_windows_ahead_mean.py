"""Decode windows dispatched and not yet drained when an admission wave's
prefills had gone out, mean over the window's waves: growth of
engine_wave_windows_ahead_total over engine_prefill_waves_total. What a first
token waits behind on the device."""
from chipbench.layer_metrics import worker_delta


def read(ctx):
    waves = worker_delta(ctx, "dynamo_tpu_engine_prefill_waves_total")
    if not waves:
        return None
    return (worker_delta(ctx, "dynamo_tpu_engine_wave_windows_ahead_total") or 0.0) / waves
