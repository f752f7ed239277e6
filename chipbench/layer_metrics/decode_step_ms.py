"""Device time of one decode step: seconds of the decode-window program's
executions in the trace over executions times steps per window."""
from chipbench.layer_metrics import module_seconds
from chipbench.run import engine_args


def read(ctx):
    if not ctx["trace"]:
        return None
    secs, n = module_seconds(ctx["trace"], "multi_decode")
    steps = engine_args(ctx["config"]).decode_steps
    return 1000.0 * secs / (n * steps) if n else None
