"""The indexer's scan's share of its HBM roofline: the bytes a call must read
(one index key a cached position of every decoding row:
``kernels_dots3.index_key_bytes`` of the context the client saw in decode over
the traced seconds) over the peak bandwidth, over the device seconds a call of
``dsa_index_scores`` in the decode window. Bound by bytes. None without the
kernel in the trace."""
from chipbench import kernels_dots3
from chipbench.layer_metrics import _dots3, _sala


def read(ctx):
    if not ctx["trace"] or not _dots3.is_dots3(ctx):
        return None
    seen = _sala.decoding(ctx)
    if seen is None:
        return None
    return _sala.bytes_roofline(ctx, kernels_dots3.index_key_bytes(seen[1], ctx["config"]), "dsa_index_scores")
