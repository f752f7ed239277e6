"""The full layers' chosen-rows attend's share of its roofline: the least
seconds the chip could take over ``index_topk`` cached rows a decoding row
(``kernels_dots3.chosen_attend_least_s`` of the rows the client saw in decode
over the traced seconds: the larger of the rows' bytes over the peak bandwidth
and their operations over the peak rate) over the device seconds a call of
``latent_sparse_decode_attention`` in the decode window (every call of it there
attends a chosen set: the cell's rows are all past ``index_topk``). The gather
of the chosen rows ahead of the kernel is XLA operations the reduced trace
cannot tell from the layer's others, so its seconds are not in here: PERF.md
section 5 has them from step 0's table. None without the kernel in the trace."""
from chipbench import kernels_dots3
from chipbench.layer_metrics import _dots3, _latent, _sala


def read(ctx):
    if not ctx["trace"] or not _dots3.is_dots3(ctx):
        return None
    seen, got = _sala.decoding(ctx), _latent.decode_kernel(ctx, "latent_sparse_decode_attention")
    if seen is None or got is None:
        return None
    secs, calls = got
    return 100.0 * kernels_dots3.chosen_attend_least_s(seen[0], ctx["config"], _latent.peak(ctx)) / (secs / calls)
