"""Mean TTFT the client saw (from its send) minus the mean the frontend
observed itself (``http_time_to_first_token_seconds``, growth of sum over
growth of count in the window): connection, parsing and the first write."""
from chipbench.layer_metrics import frontend_delta


def read(ctx):
    mine = [r["first"] - r["sent"] for r in ctx["records"] if r["first"] and r["status"] != "failed"]
    s = frontend_delta(ctx, "dynamo_tpu_http_time_to_first_token_seconds_sum")
    n = frontend_delta(ctx, "dynamo_tpu_http_time_to_first_token_seconds_count")
    if not mine or not n:
        return None
    return (sum(mine) / len(mine) - s / n) * 1000.0
