"""What the MiniCPM-SALA readers share (``kernels_sala.py`` has the operations
and bytes): the configuration's own keys, the rows and context in decode over
the traced seconds as the client saw them, and a kernel's seconds a call
inside the decode window. A configuration without lightning layers, a trace
without the kernel or a ``/metrics`` page without the counters gives None."""
from chipbench import kernels_sala
from chipbench.layer_metrics import _latent

DECODE = _latent.DECODE


def is_sala(ctx) -> bool:
    return "mixer_types" in ctx["config"] and "lightning_nh" in ctx["config"]


def decoding(ctx) -> tuple[float, float] | None:
    """(requests in decode, their tokens of context) over the traced seconds;
    None behind a router, where the client cannot tell which replica holds a stream."""
    marks = ctx.get("trace_marks") or {}
    if "asked_start" not in marks or ctx["replicas"] != 1:
        return None
    to_mono = ctx["t0"] - ctx["t0_unix"]
    rows, context = kernels_sala.decoding(ctx["records"], marks["asked_start"] + to_mono, marks["asked_stop"] + to_mono)
    return (rows, context) if rows else None


def bytes_roofline(ctx, least_bytes_a_call: float, *kernels: str) -> float | None:
    """100 x the least seconds the chip could take to move ``least_bytes_a_call``
    over the seconds a call of ``kernels`` took inside the decode window."""
    got = _latent.decode_kernel(ctx, *kernels)
    if got is None:
        return None
    secs, calls = got
    return 100.0 * least_bytes_a_call / _latent.peak(ctx)["hbm_bytes_per_s"] / (secs / calls)
