"""Operations and bytes of the grouped expert product where a chip holds every
expert of its layers (the LFM2-MoE block, ``engine/lfm2.py``), from shapes: the
yardstick's side of ``moe_expert_roofline.whole`` and
``moe_prefill_expert_roofline.whole`` (the time comes from the trace). Beside
``kernels_latent.py``, whose functions read LongCat's key names.

**Grouped expert product** (``engine/longcat.py:grouped_expert_matmul``, the
megablox ``gmm`` kernel; three kernels a call: gate, up, down; one call an
expert layer of a decode step or of a part of a prefill of up to 512 tokens):
it has to read the weights of the experts the call touches, once each, and do
``2 x 3 x D x I_e`` operations an assignment. With 4 of 64 experts a token a
decode call of 60 rows touches about 62 experts (1.17 GB) for 240 assignments
(4.5 GFLOP: 23 us of operations against 1.4 ms of bytes), and a 512-token part
all 64 for 2,048 assignments (0.2 ms against 1.5): bytes-bound either way, so
the readers hold the kernel to ``least_call_s``'s larger term, which is the
bytes.
"""

from __future__ import annotations


def expert_bytes(config: dict, itemsize: int = 2) -> int:
    """One expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] * itemsize


def expert_ops(config: dict) -> int:
    """Operations of one assignment (a token through one expert's three matrices)."""
    return 2 * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]


def attention_layers(config: dict) -> int:
    return sum(1 for t in config["layer_types"][:config["num_hidden_layers"]] if t == "full_attention")


def least_call_s(touched: float, assignments: float, config: dict, peak: dict) -> float:
    """The least seconds one call can take that touches ``touched`` experts
    for ``assignments`` token-expert pairs: the larger of its bytes over the
    peak bandwidth and its operations over the peak rate."""
    return max(touched * expert_bytes(config) / peak["hbm_bytes_per_s"],
               assignments * expert_ops(config) / peak["bf16_flops"])
