"""CPU the scheduler thread burned itself (time.thread_time) as a share of the
window, mean over the workers: near 100% the host is the limit, far under it
the thread waits (on the device, for work or for the GIL)."""
from chipbench.layer_metrics import worker_delta


def read(ctx):
    cpu = worker_delta(ctx, "dynamo_tpu_engine_sched_cpu_seconds_total")
    if cpu is None or not ctx["seconds"]:
        return None
    return 100.0 * cpu / ctx["replicas"] / ctx["seconds"]
