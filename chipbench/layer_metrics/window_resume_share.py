"""Of the window's admissions that found cached full-layer pages, the share
that resumed at the deepest of them because the window layers' blocks before it
were resident: growth of ``engine_window_resume_total{outcome="deepest"}`` over
the growth of every outcome (``cut_back``: an earlier block; ``miss``: position
0). None for a program without the counter, or a window with no such admission."""
from chipbench.layer_metrics import worker_delta

NAME = "dynamo_tpu_engine_window_resume_total"


def read(ctx):
    deepest, every = worker_delta(ctx, NAME, 'outcome="deepest"'), worker_delta(ctx, NAME)
    if deepest is None or not every:
        return None
    return 100.0 * deepest / every
