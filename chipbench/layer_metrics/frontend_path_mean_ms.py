"""The frontend's and the wire's part of a first token: the mean TTFT the
frontend observed (http_time_to_first_token_seconds) less the workers' mean of
engine.queue + engine.prefill (submit to first delta inside the engine):
preprocessing, routing, the hop to the worker and back, detokenizing.

Also logs the whole timeline of a first token on one line (``ttft timeline:``),
the client's mean first, so that a reader of the log sees whether the parts
leave a hole: client = gap + frontend path + queue + dispatch + first wait +
deliver, where the last three are recorded apart from engine.prefill."""
import json

from chipbench.layer_metrics._prom import frontend_mean_ms, phase_mean_ms
from chipbench.procs import log


def read(ctx):
    ttft = frontend_mean_ms(ctx, "dynamo_tpu_http_time_to_first_token_seconds")
    queue, prefill = phase_mean_ms(ctx, "engine.queue"), phase_mean_ms(ctx, "engine.prefill")
    if ttft is None or queue is None or prefill is None:
        return None
    path = ttft - queue - prefill
    mine = [r["first"] - r["sent"] for r in ctx["records"] if r["first"] and r["status"] != "failed"]
    parts = {"client_mean_ms": 1000.0 * sum(mine) / len(mine) if mine else None,
             "frontend_mean_ms": ttft, "frontend_path_mean_ms": path,
             "queue_wait_mean_ms": queue, "engine_prefill_mean_ms": prefill}
    for name, span in (("admit_dispatch_mean_ms", "engine.dispatch"),
                       ("first_wait_mean_ms", "engine.first_wait"),
                       ("first_deliver_mean_ms", "engine.deliver")):
        parts[name] = phase_mean_ms(ctx, span)
    log("ttft timeline: " + json.dumps({k: round(v, 3) for k, v in parts.items() if v is not None}))
    return path
