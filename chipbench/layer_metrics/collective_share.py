"""Seconds of collective operations (all-reduce, all-gather, reduce-scatter,
all-to-all, collective-permute) inside the decode and prefill programs on device
plane 0, over that device's busy seconds: what exchanging between the chips
costs of the time the chip works. None on a trace without one."""
from chipbench.layer_metrics import _deepseek


def read(ctx):
    t = ctx["trace"]
    secs = _deepseek.collective_seconds(ctx, ("jit_multi_decode", "jit_prefill", "jit_decode_step"))
    if secs is None or t["busy_s"] <= 0:
        return None
    return 100.0 * secs / t["busy_s"]
