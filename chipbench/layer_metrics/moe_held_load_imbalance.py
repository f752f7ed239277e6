"""Highest over mean of the window's assignments to the routed experts held
here (moe_expert_tokens_total by layer and expert): 1 where the load is even."""
from chipbench.layer_metrics import _latent


def read(ctx):
    loads = list(_latent.expert_tokens(ctx).values())
    if not loads or not sum(loads):
        return None
    return max(loads) / (sum(loads) / len(loads))
