"""The grouped expert product's share of its HBM roofline in the prefill
programs where a chip holds every expert of its layers: as
``moe_expert_roofline.whole`` reads the decode window, over the ``gmm`` events
the decode window does not account for (``_whole.roofline``)."""
from chipbench.layer_metrics import _whole


def read(ctx):
    return _whole.roofline(ctx, "prefill")
