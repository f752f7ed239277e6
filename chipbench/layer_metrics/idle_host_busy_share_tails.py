"""``idle_host_busy_share`` in a cell below its knee, which reports no
throughput: there an idle device with the scheduler at work delays every
running stream's next token."""
from chipbench.layer_metrics.idle_host_busy_share import read  # noqa: F401
