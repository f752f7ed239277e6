"""The block pool's prefix hit share in the window alone: growth of
kv_pool_hit_blocks_total over hit + miss (pool_hit_lifetime_share is the ratio
since the worker started, warm-up and pre-fill included)."""
from chipbench.layer_metrics import worker_delta


def read(ctx):
    hit = worker_delta(ctx, "dynamo_tpu_kv_pool_hit_blocks_total")
    miss = worker_delta(ctx, "dynamo_tpu_kv_pool_miss_blocks_total")
    if hit is None or miss is None or not hit + miss:
        return None
    return 100.0 * hit / (hit + miss)
