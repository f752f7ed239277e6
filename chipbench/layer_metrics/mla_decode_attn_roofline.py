"""The latent decode attention kernel's share of its roofline: the least time
the chip could take over the live latents (``kernels_latent.latent_decode_least_s``
of the context the client saw in decode during the traced seconds) over the
kernel's device time a call. One call is one attention sub-block of one step."""
from chipbench import kernels, kernels_latent
from chipbench.layer_metrics import _latent


def read(ctx):
    marks = ctx["trace_marks"]
    got = _latent.decode_kernel(ctx, "latent_decode_attention")
    if got is None or "asked_start" not in marks or ctx["replicas"] != 1:
        return None
    secs, calls = got
    to_mono = ctx["t0"] - ctx["t0_unix"]
    context = kernels.decode_context_tokens(
        ctx["records"], marks["asked_start"] + to_mono, marks["asked_stop"] + to_mono)
    least_s = kernels_latent.latent_decode_least_s(context, ctx["config"], _latent.peak(ctx))
    return 100.0 * least_s / (secs / calls)
