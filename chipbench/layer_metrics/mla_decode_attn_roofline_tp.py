"""The latent decode attention kernel's share of ONE CHIP'S roofline under
``--tp``: the least time chip 0 could take over the live latents (the whole row
of every token of context, its own heads' operations:
``kernels_deepseek.latent_decode_least_s`` of the context the client saw in
decode during the traced seconds) over the kernel's device time a call on
device plane 0. One call is one layer of one step."""
from chipbench import kernels, kernels_deepseek
from chipbench.layer_metrics import _deepseek, _latent


def read(ctx):
    marks = ctx["trace_marks"]
    got = _latent.decode_kernel(ctx, "latent_decode_attention")
    if got is None or "asked_start" not in marks or not _deepseek.is_deepseek(ctx):
        return None
    secs, calls = got
    to_mono = ctx["t0"] - ctx["t0_unix"]
    context = kernels.decode_context_tokens(
        ctx["records"], marks["asked_start"] + to_mono, marks["asked_stop"] + to_mono)
    least_s = kernels_deepseek.latent_decode_least_s(context, ctx["config"], _latent.peak(ctx))
    return 100.0 * least_s / (secs / calls)
