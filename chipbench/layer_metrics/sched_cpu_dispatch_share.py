"""Share of the scheduler thread's CPU seconds spent sending work to the
device: ``prefill_dispatch``, ``stack_rows``, ``first_dispatch``, ``plan`` (the
window's rows) and ``decode_dispatch`` of
engine_step_phase_cpu_seconds_total{phase}."""
from chipbench.layer_metrics._sched import cpu_share


def read(ctx):
    return cpu_share(ctx, ("prefill_dispatch", "stack_rows", "first_dispatch", "plan",
                           "decode_dispatch"))
