"""What the LongCat readers share: the device's peaks, a kernel's seconds and
calls inside the decode-window program, the grouped expert product's share of
its roofline in one kind of program, and the expert counters' growth. A program
without the kernel or the counters (the parent of PR 30) gives None."""
import json
import os

from chipbench import kernels_latent
from chipbench.layer_metrics import module_seconds, worker_delta
from chipbench.run import engine_args

DECODE = "jit_multi_decode_impl"
EXPERT_TOKENS = "dynamo_tpu_moe_expert_tokens_total"


def peak(ctx) -> dict:
    with open(os.path.join(ctx["here"], "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    kind = next(iter(ctx["stats"].values()))["kind"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return peaks[kind]


def decode_kernel(ctx, *names: str) -> tuple[float, int] | None:
    """(seconds, calls) in the trace of the decode window's operations whose
    kind holds one of ``names``."""
    t = ctx["trace"]
    if not t:
        return None
    secs = sum(s for kind, s in t["ops_by_module"].get(DECODE, {}).items()
               if any(n in kind for n in names))
    calls = sum(k for kind, k in t.get("op_counts", {}).items() if any(n in kind for n in names))
    return (secs, calls) if secs and calls else None


def expert_product_roofline(ctx, program: str) -> float | None:
    """The grouped expert product's share of its roofline in the decode window
    (``program`` "decode") or in the prefill programs ("prefill"), from the
    traced seconds alone but for one ratio. Its calls there are counted from
    the trace: the program's executions times its calls an execution (a layer
    of each of a window's steps; a layer of a prefill, whose prompts here are
    one part of up to 512 tokens). What a call must read is the weights of the
    experts it touches, once: the program counts those and its calls by
    program (``moe_experts_touched_total``, ``moe_expert_calls_total``), and
    their ratio over the window, experts touched a call, is the one number
    taken from outside the traced seconds. Over the seconds of the ``gmm``
    kernel inside those executions."""
    t = ctx["trace"]
    label = f'program="{program}"'
    touched = worker_delta(ctx, "dynamo_tpu_moe_experts_touched_total", label)
    calls = worker_delta(ctx, "dynamo_tpu_moe_expert_calls_total", label)
    if not t or not touched or not calls:
        return None
    config, layers = ctx["config"], ctx["config"]["num_layers"]
    if program == "decode":
        modules = [DECODE]
        traced_calls = module_seconds(t, "multi_decode")[1] * engine_args(config).decode_steps * layers
    else:
        modules = [m for m in t["ops_by_module"] if m.startswith("jit_prefill")]
        traced_calls = sum(t["modules"][m][1] for m in modules) * layers
    secs = sum(s for m in modules for kind, s in t["ops_by_module"].get(m, {}).items()
               if kind == "gmm" or "grouped_expert_matmul" in kind)
    if not secs or not traced_calls:
        return None
    # Bytes-bound: a call's 16-33 assignments are 13-25 us of operations
    # (``kernels_latent.expert_ops``) against a millisecond of weight bytes.
    least_call = touched / calls * kernels_latent.expert_bytes(config) / peak(ctx)["hbm_bytes_per_s"]
    return 100.0 * traced_calls * least_call / secs


def expert_tokens(ctx) -> dict[tuple[str, str], float]:
    """{(layer, expert): assignments in the window}, over the workers."""
    out: dict[tuple[str, str], float] = {}
    for r in range(ctx["replicas"]):
        before = ctx["prom"].get(f"worker{r}.before") or {}
        for key, val in (ctx["prom"].get(f"worker{r}.after") or {}).items():
            base, _, rest = key.partition("{")
            if base != EXPERT_TOKENS:
                continue
            labels = dict(part.split("=", 1) for part in rest.rstrip("}").split(","))
            at = (labels["layer"].strip('"'), labels["expert"].strip('"'))
            out[at] = out.get(at, 0.0) + val - before.get(key, 0.0)
    return out
