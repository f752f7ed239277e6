"""What the two ``.whole`` expert readers share: the grouped expert product's
share of its roofline where every expert of a layer is held
(``kernels_lfm2.py``), with the calls counted from the trace's own
``op_counts`` and never from a program's executions (a traced window cuts
executions at both ends, and the seconds of a cut one are in the sum).

A call is an expert layer's three ``gmm`` kernels. ``op_counts`` counts the
``gmm`` events of both kinds of program together, so the decode window's are
told apart by the decode attention kernel, which only that program runs, once
an attention layer of a step: steps = its events over the attention layers,
decode calls = steps x expert layers, and the prefill programs' calls are the
rest of the ``gmm`` events over three. What a call must read is the experts it
touches, once: experts touched a call is the window's
``moe_experts_touched_total`` over ``moe_expert_calls_total``, by program; its
assignments (for the operations' term) the window's held assignments over all
calls. A trace without the kernels, or a ``/metrics`` page without the
counters, gives None."""
from chipbench import kernels_lfm2
from chipbench.layer_metrics import _latent, worker_delta

GMM, ATTN = "gmm", "paged_decode_attention"


def _is_gmm(kind: str) -> bool:
    return kind == GMM or "grouped_expert_matmul" in kind


def roofline(ctx, program: str) -> float | None:
    t, config = ctx["trace"], ctx["config"]
    if not t or "moe_intermediate_size" not in config or "layer_types" not in config:
        return None
    counts = t.get("op_counts", {})
    n_gmm = sum(n for kind, n in counts.items() if _is_gmm(kind))
    steps = counts.get(ATTN, 0) / max(1, kernels_lfm2.attention_layers(config))
    decode_calls = steps * kernels_lfm2.expert_layers(config)
    calls = decode_calls if program == "decode" else n_gmm / 3.0 - decode_calls
    modules = [m for m in t.get("ops_by_module", {})
               if (m == _latent.DECODE if program == "decode" else m.startswith("jit_prefill"))]
    secs = sum(s for m in modules for kind, s in t["ops_by_module"][m].items() if _is_gmm(kind))
    label = f'program="{program}"'
    touched = worker_delta(ctx, "dynamo_tpu_moe_experts_touched_total", label)
    counted = worker_delta(ctx, "dynamo_tpu_moe_expert_calls_total", label)
    all_calls = worker_delta(ctx, "dynamo_tpu_moe_expert_calls_total")
    held = worker_delta(ctx, "dynamo_tpu_moe_assignments_total", 'kind="held"')
    if not n_gmm or not steps or calls <= 0 or not secs or not touched or not counted:
        return None
    least = kernels_lfm2.least_call_s(touched / counted, (held or 0.0) / (all_calls or counted), config, _latent.peak(ctx))
    return 100.0 * calls * least / secs
