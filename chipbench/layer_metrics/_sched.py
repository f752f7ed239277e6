"""What the readers of the scheduler thread's own account share (PR 43): its
CPU seconds by step-loop phase, and the device's dry time between its two
bounds. A page without the series, as a checkout before them serves, gives
None, so the metric is left out of that line."""
from chipbench.layer_metrics import worker_delta

CPU = "dynamo_tpu_engine_step_phase_cpu_seconds_total"
WALL = "dynamo_tpu_engine_sched_wall_seconds_total"
DRY = "dynamo_tpu_engine_device_dry_seconds_total"


def cpu_share(ctx: dict, phases: tuple[str, ...]) -> float | None:
    """The scheduler threads' CPU seconds in ``phases`` over those in all
    phases, in percent, over the window and the workers."""
    total = worker_delta(ctx, CPU)
    if not total:
        return None
    part = sum(worker_delta(ctx, CPU, f'phase="{p}"') or 0.0 for p in phases)
    return 100.0 * part / total


def dry_share(ctx: dict, bound: str) -> float | None:
    """A bound of the device's dry seconds over the scheduler thread's wall
    seconds: both summed over the workers, so their mean. A thread that was
    never dry has no such series yet and reads 0."""
    wall = worker_delta(ctx, WALL)
    if not wall:
        return None
    return 100.0 * (worker_delta(ctx, DRY, f'bound="{bound}"') or 0.0) / wall
