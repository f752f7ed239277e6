"""Mean time a request stood blocked at admission for want of a slot or of KV
blocks: growth of phase_duration_seconds{phase="engine.blocked"} seconds over
the count of engine.queue, so a window in which nothing was blocked reads 0.
A program without the timeline (no engine.dispatch series) reads nothing."""
from chipbench.layer_metrics._prom import phase_sum_count


def read(ctx):
    _, admitted = phase_sum_count(ctx, "engine.queue")
    _, timeline = phase_sum_count(ctx, "engine.dispatch")
    if not admitted or timeline is None:
        return None
    blocked_s, _ = phase_sum_count(ctx, "engine.blocked")
    return 1000.0 * (blocked_s or 0.0) / admitted
