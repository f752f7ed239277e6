"""Share of the scheduler thread's busy wall time that it spent off the CPU:
every step-loop phase but ``idle`` (engine_step_phase_seconds_total{phase}) less
the thread's own CPU seconds (engine_sched_cpu_seconds_total), over those
phases. Off the CPU inside a phase the thread waits: on the device, in a drain
or in a dispatch call that returns only when the device's queue has room, or
for the GIL, which the two clocks cannot tell apart."""
from chipbench.layer_metrics import worker_delta
from chipbench.layer_metrics._prom import step_phase_seconds


def read(ctx):
    phases = step_phase_seconds(ctx)
    busy = sum(secs for phase, secs in phases.items() if phase != "idle")
    cpu = worker_delta(ctx, "dynamo_tpu_engine_sched_cpu_seconds_total")
    if not busy or cpu is None:
        return None
    return 100.0 * (busy - cpu) / busy
