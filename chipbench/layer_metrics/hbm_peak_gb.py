"""The fullest chip's peak at the window's end: ``peak_bytes_in_use`` plus
``peak_bytes_reserved`` of ``memory_stats()`` (``run.py:held_bytes``)."""
from chipbench.run import held_bytes


def read(ctx):
    peaks = [held_bytes(m) for s in ctx["stats"].values() for m in s.get("memory", [])]
    return max(peaks) / 1e9 if peaks and max(peaks) > 0 else None
