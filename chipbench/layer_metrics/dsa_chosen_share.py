"""The cached tokens the full layers' decode rows attended, as a share of the
tokens they could see: growth over the window of
``engine_dsa_chosen_tokens_total`` over ``engine_dsa_visible_tokens_total``.
100 would mean the choice is not in effect (every row at or under
``index_topk``). None for a program without the counters."""
from chipbench.layer_metrics import worker_delta

P = "dynamo_tpu_engine_dsa_"


def read(ctx):
    chosen, visible = worker_delta(ctx, P + "chosen_tokens_total"), worker_delta(ctx, P + "visible_tokens_total")
    if chosen is None or not visible:
        return None
    return 100.0 * chosen / visible
