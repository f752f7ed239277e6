"""CPU the scheduler thread burned (time.thread_time) for each decode window it
dispatched: growth of engine_sched_cpu_seconds_total over growth of
engine_step_phase_total{phase="decode_dispatch"}, the thread leaving that phase
once a window. Admission, prefill dispatch and emission are in it: it is the
host's whole cost of a cycle, to hold against the window's device time."""
from chipbench.layer_metrics import worker_delta


def read(ctx):
    cpu = worker_delta(ctx, "dynamo_tpu_engine_sched_cpu_seconds_total")
    windows = worker_delta(ctx, "dynamo_tpu_engine_step_phase_total", 'phase="decode_dispatch"')
    return 1000.0 * cpu / windows if cpu is not None and windows else None
