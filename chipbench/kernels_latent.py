"""Operations and bytes of the two kernels the LongCat block brings, from
shapes: the yardstick's side of their roofline shares (the time comes from the
trace). Beside ``kernels.py``, which has the GQA decode kernel's.

**Latent decode attention** (``ops/paged_attention.py:latent_decode_attention``,
one call = one attention sub-block of one decode step): for every token of
context of every sequence in the batch it has to read the cache row once,
``kv_lora_rank + qk_rope_head_dim`` = 576 values of 2 B (the pool stores 640
lanes: the padding is the kernel's cost, not the yardstick's), and do
``2 x heads x (576 + 512)`` operations (scores against the whole row, values
from its first 512). 121 operations a byte: near the ridge, bytes-bound on v5e.

**Grouped expert product** (``engine/longcat.py:grouped_expert_matmul``, the
megablox ``gmm`` kernel; three products a call: gate, up, down; one call a layer
of a decode step or of a 512-token part of a prefill): it has to read the
weights of the experts the call touches, once each (the program counts them and
its calls by program: ``moe_experts_touched_total``, ``moe_expert_calls_total``),
and do ``2 x 3 x D x I_e`` operations an assignment. At 1-10 tokens an expert a
call is bytes-bound by two orders of magnitude, so the readers hold the kernel
to the bytes alone; ``expert_ops`` is here for a cell whose calls are not.
"""

from __future__ import annotations


def latent_row_bytes(config: dict, itemsize: int = 2) -> int:
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * itemsize


def latent_decode_least_s(context_tokens: float, config: dict, peak: dict) -> float:
    """The least seconds one call can take over ``context_tokens`` cached tokens."""
    ops = 2 * config["num_attention_heads"] * (
        config["kv_lora_rank"] + config["qk_rope_head_dim"] + config["kv_lora_rank"])
    return context_tokens * max(latent_row_bytes(config) / peak["hbm_bytes_per_s"],
                                ops / peak["bf16_flops"])


def expert_bytes(config: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * config["hidden_size"] * config["expert_ffn_hidden_size"] * itemsize


def expert_ops(config: dict) -> int:
    """Operations of one assignment (a token through one expert's three matrices)."""
    return 2 * 3 * config["hidden_size"] * config["expert_ffn_hidden_size"]
