"""Mean of frontend_loop_lag_seconds: by how much a 50 ms sleep in the
frontend's event loop overslept, which is what every callback waits."""
from chipbench.layer_metrics._prom import frontend_mean_ms


def read(ctx):
    return frontend_mean_ms(ctx, "dynamo_tpu_frontend_loop_lag_seconds")
