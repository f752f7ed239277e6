"""What the readers added with the program's own timeline share: a span's
mean from ``phase_duration_seconds``, and the step loop's seconds by phase.
A series the program does not export gives None, so a checkout that lacks the
spans or counters leaves the metric out of its line."""
from chipbench.layer_metrics import frontend_delta, worker_delta

PHASE = "dynamo_tpu_phase_duration_seconds"


def phase_sum_count(ctx: dict, span: str) -> tuple[float | None, float | None]:
    """Growth in the window of a span's seconds and of its count, over the workers."""
    label = f'phase="{span}"'
    return worker_delta(ctx, PHASE + "_sum", label), worker_delta(ctx, PHASE + "_count", label)


def phase_mean_ms(ctx: dict, span: str) -> float | None:
    s, n = phase_sum_count(ctx, span)
    return 1000.0 * s / n if s is not None and n else None


def frontend_mean_ms(ctx: dict, histogram: str) -> float | None:
    """Mean of a histogram on the frontend's page over the window, in ms."""
    s = frontend_delta(ctx, histogram + "_sum")
    n = frontend_delta(ctx, histogram + "_count")
    return 1000.0 * s / n if s is not None and n else None


def step_phase_seconds(ctx: dict) -> dict[str, float]:
    """{step-loop phase: seconds in the window}, summed over the workers."""
    name, mark = "dynamo_tpu_engine_step_phase_seconds_total", 'phase="'
    total: dict[str, float] = {}
    for r in range(ctx["replicas"]):
        before = ctx["prom"].get(f"worker{r}.before") or {}
        for key, val in (ctx["prom"].get(f"worker{r}.after") or {}).items():
            base, _, rest = key.partition("{")
            if base == name and mark in rest:
                phase = rest.split(mark, 1)[1].split('"', 1)[0]
                total[phase] = total.get(phase, 0.0) + val - before.get(key, 0.0)
    return total
