"""Share of prompt blocks the chosen replica already held, as the router
counted: growth of router_overlap_blocks_total over router_isl_blocks_total."""
from chipbench.layer_metrics import frontend_delta


def read(ctx):
    hit = frontend_delta(ctx, "dynamo_tpu_router_overlap_blocks_total")
    isl = frontend_delta(ctx, "dynamo_tpu_router_isl_blocks_total")
    return 100.0 * hit / isl if hit is not None and isl else None
