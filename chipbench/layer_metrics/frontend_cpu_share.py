"""CPU the frontend process burned in the window, as a share of one core
(process_cpu_seconds_total): near 100% one Python frontend is the limit."""
from chipbench.layer_metrics import frontend_delta


def read(ctx):
    cpu = frontend_delta(ctx, "process_cpu_seconds_total")
    return 100.0 * cpu / ctx["seconds"] if cpu is not None and ctx["seconds"] else None
