"""What the ``--tp`` readers share (``kernels_deepseek.py`` has one chip's
operations and bytes): which configuration they are for, the grouped expert
product's share of its roofline on chip 0, and the collectives' seconds on
device plane 0. A configuration that is not a group-limited expert model under
``--tp``, a trace without the operations or a ``/metrics`` page without the
counters gives None."""
from chipbench import kernels_deepseek
from chipbench.layer_metrics import _latent, module_seconds, worker_delta
from chipbench.run import engine_args

# A collective's name in the device plane: the HLO operation's, or, for one that a ``shard_map``
# body calls, the JAX primitive's (``psum.100`` is an all-reduce: my chip run, PR 52).
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
               "psum", "all_gather", "psum_scatter", "all_to_all", "ppermute")


def is_deepseek(ctx) -> bool:
    return "n_group" in ctx["config"] and "topk_group" in ctx["config"]


def expert_product_roofline(ctx, program: str) -> float | None:
    """``_latent.expert_product_roofline`` for one chip of several: the experts
    CHIP 0 touched a call (``moe_experts_touched_total{program,chip="0"}`` over
    ``moe_expert_calls_total{program}``) times an expert's bytes over the peak,
    times the calls the traced seconds held (the program's executions times the
    expert layers, times a window's steps in decode), over the seconds of the
    ``gmm`` kernel inside those executions on device plane 0."""
    t = ctx["trace"]
    if not t or not is_deepseek(ctx):
        return None
    label = f'program="{program}"'
    touched = worker_delta(ctx, "dynamo_tpu_moe_experts_touched_total", label, 'chip="0"')
    calls = worker_delta(ctx, "dynamo_tpu_moe_expert_calls_total", label)
    if not touched or not calls:
        return None
    config, layers = ctx["config"], kernels_deepseek.expert_layers(ctx["config"])
    if program == "decode":
        modules = [_latent.DECODE]
        traced_calls = module_seconds(t, "multi_decode")[1] * engine_args(config).decode_steps * layers
    else:
        modules = [m for m in t["ops_by_module"] if m.startswith("jit_prefill")]
        traced_calls = sum(t["modules"][m][1] for m in modules) * layers
    secs = sum(s for m in modules for kind, s in t["ops_by_module"].get(m, {}).items()
               if kind == "gmm" or "grouped_expert_matmul" in kind)
    if not secs or not traced_calls:
        return None
    least_call = touched / calls * kernels_deepseek.expert_bytes(config) / _latent.peak(ctx)["hbm_bytes_per_s"]
    return 100.0 * traced_calls * least_call / secs


def collective_seconds(ctx, programs: tuple[str, ...]) -> float | None:
    """Seconds of collective operations on device plane 0 inside the programs
    whose name starts with one of ``programs``; None where there is none."""
    t = ctx["trace"]
    if not t:
        return None
    secs = sum(s for m, kinds in t["ops_by_module"].items() if m.startswith(programs)
               for kind, s in kinds.items() if any(c in kind for c in COLLECTIVES))
    return secs or None
