"""Highest share of the KV pool's blocks in use, polled every 0.5 s."""
from chipbench.layer_metrics import gauge_series


def read(ctx):
    used = gauge_series(ctx, "dynamo_tpu_fleet_worker_kv_active_blocks")
    total = gauge_series(ctx, "dynamo_tpu_fleet_worker_kv_total_blocks")
    return 100.0 * max(used) / max(total) if used and total and max(total) else None
