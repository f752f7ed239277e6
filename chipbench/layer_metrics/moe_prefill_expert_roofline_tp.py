"""The grouped expert product's share of ONE CHIP'S HBM roofline in the prefill
programs under ``--tp``: as ``moe_expert_roofline.tp`` reads it in the decode
window, a call an expert layer of each execution (this cell's prompts are one
part of up to 1,024 tokens; a longer one has more calls than are counted, and
reads lower)."""
from chipbench.layer_metrics import _deepseek


def read(ctx):
    return _deepseek.expert_product_roofline(ctx, "prefill")
