"""The lightning step kernel's share of its HBM roofline: the bytes a call must
move (every decoding row's state ``[H, d, d]`` in and out in the pool's dtype, and its q, k,
v and output: ``kernels_sala.lightning_step_bytes`` of the rows the client saw
in decode over the traced seconds) over the peak bandwidth, over the kernel's
(``lightning_decode``) device seconds a call in the decode window. Bound by
bytes. None without the kernel in the trace."""
from chipbench import kernels_sala
from chipbench.layer_metrics import _sala


def read(ctx):
    if not ctx["trace"] or not _sala.is_sala(ctx):
        return None
    seen = _sala.decoding(ctx)
    if seen is None:
        return None
    return _sala.bytes_roofline(ctx, kernels_sala.lightning_step_bytes(seen[0], ctx["config"]), "lightning_decode")
