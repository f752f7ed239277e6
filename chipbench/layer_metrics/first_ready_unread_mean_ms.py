"""How long a first-token sample lay ready on the device before the scheduler
thread had read it, mean over the window's first fetches: growth of
engine_first_ready_unread_seconds_total over engine_first_fetch_total (both
``waited`` labels; a fetch that blocked on the device counts 0)."""
from chipbench.layer_metrics import worker_delta


def read(ctx):
    fetches = worker_delta(ctx, "dynamo_tpu_engine_first_fetch_total")
    if not fetches:
        return None
    unread = worker_delta(ctx, "dynamo_tpu_engine_first_ready_unread_seconds_total") or 0.0
    return 1000.0 * unread / fetches
