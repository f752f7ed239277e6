"""Operations and bytes of the dots3 block's own kernels (``engine/dots3.py``,
``ops/dsa.py``), from shapes: the yardstick's side of ``dsa_decode_attn_roofline``,
``dsa_index_roofline`` and ``moe_expert_roofline.share`` (the time comes from the
trace). Whatever implements a kernel, these count what the mathematics needs and
no more.

**The chosen-rows attend** (``latent_sparse_decode_attention``, one call = one
full layer of one decode step): a decoding row attends ``index_topk`` cached
rows, each read once: ``kv_lora_rank + qk_rope_head_dim`` = 576 values of 2 B
(the pool stores 640 lanes, and the program gathers the rows before it attends
them: both are the kernel's cost, not the yardstick's), and ``2 x heads x (576
+ 512)`` operations a row. At 128 heads that is 242 operations a byte, on the
v5e's ridge: ``kernels_latent.latent_decode_least_s`` takes the larger of the
two terms.

**The indexer's scan** (``dsa_index_scores``, one call = one full layer of one
decode step): every cached position's index key is read once, ``index_head_dim``
values of 2 B, and scored by ``index_n_heads`` heads: ``2 x 64 x 128`` operations
against 256 B, 64 operations a byte: bound by bytes.

**The grouped expert product** (``engine/longcat.py:grouped_expert_matmul``, the
megablox ``gmm`` kernel; three products a call, one call an expert layer of a
decode step): the weights of the experts the call touches, once each. Bound by
bytes by two orders of magnitude at under one token an expert.
"""

from __future__ import annotations

from chipbench import kernels_latent


def full_layers(config: dict) -> int:
    return sum(1 for t in config["layer_types"] if t == "full_attention")


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def chosen_attend_least_s(rows: float, config: dict, peak: dict) -> float:
    """The least seconds one call can take for ``rows`` decoding rows, each
    past ``index_topk`` visible positions."""
    return kernels_latent.latent_decode_least_s(rows * config["index_topk"], config, peak)


def index_key_bytes(context_tokens: float, config: dict, itemsize: int = 2) -> float:
    """Bytes one call of the indexer's scan must read for rows that hold
    ``context_tokens`` tokens together."""
    return context_tokens * config["index_head_dim"] * itemsize


def expert_bytes(config: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] * itemsize
