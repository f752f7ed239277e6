"""Operations and bytes of ONE CHIP'S SHARE of the two kernels the DeepSeek
block runs under ``--tp``, from shapes: the yardstick's side of the roofline
shares read on device plane 0 (the time comes from the trace). Beside
``kernels_latent.py``, which has the same kernels on one chip.

**Latent decode attention** (``ops/paged_attention.py:latent_decode_attention``,
one call = one layer of one decode step, on every chip): the latent pool is on
every chip, so a chip reads the WHOLE cache row of every token of context,
``kv_lora_rank + qk_rope_head_dim`` = 576 values of 2 B, and does its own heads'
operations, ``2 x heads / chips x (576 + 512)``: a quarter of the operations over
all of the bytes, 30 operations a byte at four chips, bytes-bound on v5e.

**Grouped expert product** (``engine/longcat.py:grouped_expert_matmul``, three
products a call: gate, up, down; one call a layer of a decode step or of a part
of a prefill): a chip reads the weights of the experts it holds that the call
touches, once each (``moe_experts_touched_total{program,chip}`` over
``moe_expert_calls_total{program}``), bytes-bound by two orders of magnitude.
"""

from __future__ import annotations


def chips(config: dict) -> int:
    """The chips that share each layer: the worker's ``--tp``."""
    flags = config["served"].get("extra_flags", [])
    return int(flags[flags.index("--tp") + 1]) if "--tp" in flags else 1


def latent_row_bytes(config: dict, itemsize: int = 2) -> int:
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * itemsize


def latent_decode_least_s(context_tokens: float, config: dict, peak: dict) -> float:
    """The least seconds one call can take on one chip over ``context_tokens``
    cached tokens: the whole row's bytes, its own heads' operations."""
    ops = 2 * config["num_attention_heads"] // chips(config) * (
        config["kv_lora_rank"] + config["qk_rope_head_dim"] + config["kv_lora_rank"])
    return context_tokens * max(latent_row_bytes(config) / peak["hbm_bytes_per_s"], ops / peak["bf16_flops"])


def expert_bytes(config: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] * itemsize


def expert_ops(config: dict) -> int:
    """Operations of one assignment (a token through one expert's three matrices)."""
    return 2 * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]
