"""``batch_bucket_main_share`` in a cell above its knee, which reports no TPOT:
there the bucket a decode window pads to sets the tokens completed a second."""
from chipbench.layer_metrics.batch_bucket_main_share import read  # noqa: F401
