"""Highest chip's assignments over the mean of the chips', from the window's
assignments to each routed expert (moe_expert_tokens_total by layer and expert;
a chip holds ``n_routed_experts / chips`` consecutive experts): 1 where the
chips' load is even."""
from chipbench import kernels_deepseek
from chipbench.layer_metrics import _deepseek, _latent


def read(ctx):
    if not _deepseek.is_deepseek(ctx):
        return None
    chips = kernels_deepseek.chips(ctx["config"])
    held = ctx["config"]["n_routed_experts"] // chips
    loads = [0.0] * chips
    for (_, expert), n in _latent.expert_tokens(ctx).items():
        loads[int(expert) // held] += n
    return max(loads) / (sum(loads) / chips) if sum(loads) else None
