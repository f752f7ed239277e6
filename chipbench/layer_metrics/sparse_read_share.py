"""The blocks the sparse layers' attention went over, as a share of the blocks
its query positions could see: growth over the window of
``engine_sparse_blocks_chosen_total`` over ``engine_sparse_blocks_visible_total``
(decode steps and prefill tokens alike, counted from what each dispatch was
given: a decode step its chosen table, a prefill past ``dense_len`` every page
of its table's width while the choice is a mask there, so this reads over 100
until a prefill attends its chosen blocks alone; 100 while every position is
within ``dense_len``). None for a program without the counters."""
from chipbench.layer_metrics import worker_delta

P = "dynamo_tpu_engine_sparse_blocks_"


def read(ctx):
    chosen, visible = worker_delta(ctx, P + "chosen_total"), worker_delta(ctx, P + "visible_total")
    if chosen is None or not visible:
        return None
    return 100.0 * chosen / visible
