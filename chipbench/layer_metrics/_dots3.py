"""What the dots3 readers share (``kernels_dots3.py`` has the operations and
bytes): which configuration they are for. A configuration without an indexer
and a window, a trace without the kernel or a ``/metrics`` page without the
counters gives None."""


def is_dots3(ctx) -> bool:
    return "index_topk" in ctx["config"] and "sliding_window_size" in ctx["config"]
