"""The grouped expert product's share of its HBM roofline in the decode window
where a chip holds every expert of its layers: experts touched a call times one
expert's bytes over the peak bandwidth, over the ``gmm`` seconds a call, the
calls counted from the trace's own ``op_counts`` (``_whole.roofline``)."""
from chipbench.layer_metrics import _whole


def read(ctx):
    return _whole.roofline(ctx, "decode")
