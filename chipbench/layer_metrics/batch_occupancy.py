"""Mean of running sequences over decode slots, polled every 0.5 s."""
from chipbench.layer_metrics import gauge_series


def read(ctx):
    active = gauge_series(ctx, "dynamo_tpu_fleet_worker_active_slots")
    total = gauge_series(ctx, "dynamo_tpu_fleet_worker_total_slots")
    if not active or not total or not max(total):
        return None
    return 100.0 * (sum(active) / len(active)) / max(total)
