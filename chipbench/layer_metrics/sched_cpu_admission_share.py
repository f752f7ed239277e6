"""Share of the scheduler thread's CPU seconds spent admitting: the phases
``admission`` (the queue, the budget, the stamps) and ``admit_alloc`` (prefix
match, eviction, allocation) of engine_step_phase_cpu_seconds_total{phase}."""
from chipbench.layer_metrics._sched import cpu_share


def read(ctx):
    return cpu_share(ctx, ("admission", "admit_alloc"))
