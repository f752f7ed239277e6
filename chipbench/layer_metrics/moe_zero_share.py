"""Share of the window's expert assignments that went to zero-compute experts
(they add w*h and touch no weights): moe_assignments_total{kind}."""
from chipbench.layer_metrics import worker_delta

NAME = "dynamo_tpu_moe_assignments_total"


def read(ctx):
    kinds = [worker_delta(ctx, NAME, f'kind="{k}"') for k in ("zero", "held", "absent")]
    if kinds[0] is None or not sum(k or 0.0 for k in kinds):
        return None
    return 100.0 * kinds[0] / sum(k or 0.0 for k in kinds)
