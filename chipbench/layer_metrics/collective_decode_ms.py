"""Milliseconds of collective operations a decode step: their seconds inside the
decode-window program's executions on device plane 0 over executions times
steps per window. None on a trace without one."""
from chipbench.layer_metrics import _deepseek, module_seconds
from chipbench.run import engine_args


def read(ctx):
    secs = _deepseek.collective_seconds(ctx, ("jit_multi_decode",))
    if secs is None:
        return None
    n = module_seconds(ctx["trace"], "multi_decode")[1]
    return 1000.0 * secs / (n * engine_args(ctx["config"]).decode_steps) if n else None
