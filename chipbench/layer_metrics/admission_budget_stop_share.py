"""Of the admission passes that stopped with requests still waiting, the share
that stopped on the wave's token budget and not for want of a slot or of KV
blocks: growth of engine_admission_stops_total{reason="budget"} over ``budget``
+ ``slots`` + ``blocks``. None where no pass left anyone waiting."""
from chipbench.layer_metrics import worker_delta

NAME = "dynamo_tpu_engine_admission_stops_total"


def read(ctx):
    stops = {r: worker_delta(ctx, NAME, f'reason="{r}"') for r in ("budget", "slots", "blocks")}
    held = sum(v or 0.0 for v in stops.values())
    return 100.0 * (stops["budget"] or 0.0) / held if held else None
