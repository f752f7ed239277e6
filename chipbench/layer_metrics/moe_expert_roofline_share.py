"""The grouped expert product's share of its HBM roofline in the decode window
of a configuration that holds a share of each expert layer under the dots3
keys: experts touched a call (the window's ``moe_experts_touched_total`` over
``moe_expert_calls_total``, ``program="decode"``) times one expert's bytes over
the peak bandwidth, over the ``gmm`` seconds a call, the calls counted from the
trace's own ``op_counts`` as ``_whole.py`` counts them: the decode window's
steps are the ``latent_sparse_decode_attention`` events (only that program runs
it, once a full layer of a step) over the full layers, its calls the steps
times the expert layers. None without the kernels or the counters."""
from chipbench import kernels_dots3
from chipbench.layer_metrics import _dots3, _latent, _whole, worker_delta


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if not t or not _dots3.is_dots3(ctx):
        return None
    steps = t.get("op_counts", {}).get("latent_sparse_decode_attention", 0) / kernels_dots3.full_layers(config)
    calls = steps * kernels_dots3.expert_layers(config)
    secs = sum(s for kind, s in t.get("ops_by_module", {}).get(_latent.DECODE, {}).items() if _whole._is_gmm(kind))
    label = 'program="decode"'
    touched = worker_delta(ctx, "dynamo_tpu_moe_experts_touched_total", label)
    counted = worker_delta(ctx, "dynamo_tpu_moe_expert_calls_total", label)
    if not calls or not secs or not touched or not counted:
        return None
    least_call = touched / counted * kernels_dots3.expert_bytes(config) / _latent.peak(ctx)["hbm_bytes_per_s"]
    return 100.0 * calls * least_call / secs
