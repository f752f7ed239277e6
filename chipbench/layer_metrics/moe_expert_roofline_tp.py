"""The grouped expert product's share of ONE CHIP'S HBM roofline in the decode
window under ``--tp``: the weights of the experts chip 0 holds that a call
touches, read once, over the device time of the ``gmm`` kernel inside the
decode window's executions on device plane 0
(``_deepseek.expert_product_roofline`` says what is counted where)."""
from chipbench.layer_metrics import _deepseek


def read(ctx):
    return _deepseek.expert_product_roofline(ctx, "decode")
