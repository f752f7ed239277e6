"""Share of the window's 0.5 s polls of a worker's running sequences that fall
in the decode batch bucket most polls fall in: under ~95% the cell sits on a
bucket's edge and its decode step costs now one bucket's time, now the next's."""
from chipbench import arith
from chipbench.layer_metrics import gauge_samples


def read(ctx):
    active = gauge_samples(ctx, "dynamo_tpu_fleet_worker_active_slots")
    return arith.main_bucket_share(active, list(ctx.get("decode_buckets") or []))
