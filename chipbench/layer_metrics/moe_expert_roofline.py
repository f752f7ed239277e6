"""The grouped expert product's share of its HBM roofline in the decode window:
the least time the chip could take for the calls the traced seconds held (the
weights of the experts a call touches, read once: ``kernels_latent.expert_bytes``
over the peak) over the device time of the ``gmm`` kernel inside the decode
window's executions (``_latent.expert_product_roofline`` says what is counted
where)."""
from chipbench.layer_metrics import _latent


def read(ctx):
    return _latent.expert_product_roofline(ctx, "decode")
