"""Least share of the scheduler thread's wall time in which the device had
nothing dispatched left to run while requests ran or waited:
engine_device_dry_seconds_total{bound="floor"} (from a probe that found the last
dispatched program's outputs ready to the next dispatch call) over
engine_sched_wall_seconds_total. A plain run has it: no trace is needed."""
from chipbench.layer_metrics._sched import dry_share


def read(ctx):
    return dry_share(ctx, "floor")
