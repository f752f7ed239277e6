"""The sparse layers' decode attend's share of its HBM roofline: the bytes a
call must read (for every decoding row and KV head the K and V of the 64
blocks it chose, that head's lanes: ``kernels_sala.chosen_page_bytes`` of the
rows the client saw in decode over the traced seconds) over the peak
bandwidth, over the device seconds a call of ``paged_decode_attention`` in the
decode window (every call of it there walks a chosen table: the cell's rows
are all past ``dense_len``). Bound by bytes. The choice before the attend
(the gather of the compressed keys, the scores, the top-k) is XLA operations
the reduced trace cannot tell from the layer's others, so its seconds are not
in here: PERF.md section 5 has them from a scratch profile. None without the
kernel in the trace."""
from chipbench import kernels_sala
from chipbench.layer_metrics import _sala


def read(ctx):
    if not ctx["trace"] or not _sala.is_sala(ctx):
        return None
    seen = _sala.decoding(ctx)
    if seen is None:
        return None
    return _sala.bytes_roofline(ctx, kernels_sala.chosen_page_bytes(seen[0], ctx["config"]), "paged_decode_attention")
