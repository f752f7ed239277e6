"""The grouped expert product's share of its HBM roofline in the prefill
programs (``jit_prefill_batch_impl``, ``jit_prefill_impl``): as
``moe_expert_roofline`` reads it in the decode window, a call a layer of each
execution (this cell's prompts are one part of up to 512 tokens; a longer one
has more calls than are counted, and reads lower)."""
from chipbench.layer_metrics import _latent


def read(ctx):
    return _latent.expert_product_roofline(ctx, "prefill")
