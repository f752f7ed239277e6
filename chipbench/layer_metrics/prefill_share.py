"""Device time in prefill programs over device busy time."""
from chipbench.layer_metrics import module_seconds


def read(ctx):
    t = ctx["trace"]
    if not t or t["busy_s"] <= 0:
        return None
    return 100.0 * module_seconds(t, "prefill")[0] / t["busy_s"]
