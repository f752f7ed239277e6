"""Of the window's decode steps whose full layers attended a chosen set (some
row past ``index_topk``), the share that attended it as a mask over the walk of
the rows' own pages, and not by gathering the chosen rows: growth of
``engine_dsa_decode_walk_steps_total`` over ``engine_dsa_decode_steps_total``
(``ops/dsa.py:walk_is_cheaper``, on the lengths the host dispatched). None for
a program without the counters, or a window with no such step."""
from chipbench.layer_metrics import worker_delta

P = "dynamo_tpu_engine_dsa_decode_"


def read(ctx):
    walked, steps = worker_delta(ctx, P + "walk_steps_total"), worker_delta(ctx, P + "steps_total")
    if walked is None or not steps:
        return None
    return 100.0 * walked / steps
