"""Share of the scheduler thread's CPU seconds spent reading results and
handing tokens on: ``emit``, ``drain_ready``, ``drain_sync`` and
``first_sample`` of engine_step_phase_cpu_seconds_total{phase}."""
from chipbench.layer_metrics._sched import cpu_share


def read(ctx):
    return cpu_share(ctx, ("emit", "drain_ready", "drain_sync", "first_sample"))
