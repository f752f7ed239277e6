"""The Pallas decode kernel's share of its HBM roofline: the least time the
chip could take to read the live KV (``kernels.decode_attention_bytes`` of the
context the client saw in decode during the traced seconds, over the peak in
``peaks.json``) over the kernel's device time a call. Bound by bytes."""
import json
import os

from chipbench import kernels


def read(ctx):
    t, marks = ctx["trace"], ctx["trace_marks"]
    if not t or "asked_start" not in marks or ctx["replicas"] != 1:
        return None  # behind a router the client cannot tell which replica holds a stream
    secs = t["ops_by_module"].get("jit_multi_decode_impl", {}).get("paged_decode_attention")
    calls = t.get("op_counts", {}).get("paged_decode_attention")
    if not secs or not calls:
        return None
    with open(os.path.join(ctx["here"], "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    kind = next(iter(ctx["stats"].values()))["kind"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    to_mono = ctx["t0"] - ctx["t0_unix"]
    lo, hi = marks["asked_start"] + to_mono, marks["asked_stop"] + to_mono
    context = kernels.decode_context_tokens(ctx["records"], lo, hi)
    least_s = kernels.decode_attention_bytes(context, ctx["config"]) / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / (secs / calls)
