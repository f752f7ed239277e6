"""Operations and bytes of the MiniCPM-SALA block's own kernels
(``engine/sala.py``, ``ops/lightning.py``, ``ops/sparse_attention.py``), from
shapes: the yardstick's side of ``lightning_decode_roofline`` and
``sparse_decode_attn_roofline`` (the time comes from the trace), and of the
two shares PERF.md gives from a scratch profile because the reduced trace has
no time for them (the prefill scan and the choice are XLA operations among a
layer's other fusions). Whatever implements a kernel, these count what the
mathematics needs and no more.

**Lightning step** (one call = one lightning layer of one decode step): every
row's state ``[H, d, d]`` is read once and written once in the pool's dtype
(the configuration's ``assumed.lightning_state_itemsize``: 2 B, bfloat16), and
q, k, v and the output are a few KB a row. 1 MB + 1 MB a row at the published
sizes against 3 operations a state element: bound by bytes.

**Lightning prefill** (one call = one lightning layer of one prefill dispatch):
the chunk form does, a token and head, ``2 C d`` operations for ``q k^T``,
``2 C d`` for the product with v, ``2 d^2`` to read the carried state and ``2
d^2`` to add to it, with chunks of C tokens: ``(4 C d + 4 d^2) H`` a token.
Bound by operations (the state moves once a chunk).

**Sparse decode attention** (one call = one sparse layer of one decode step).
The attend reads, a row and KV head, the K and V of the blocks it chose: that
head's lanes of ``min(visible, topk)`` pages (every visible page at or under
``dense_len``): ``chosen_page_bytes``. The program's page walk reads both
heads' lanes of a page for each head's choice, twice these bytes, which is
what the share then shows. The choice before it reads the compressed keys of
the row's visible blocks (``block_size / stride`` a block, both KV heads):
``compressed_key_bytes``. Both bound by bytes.
"""

from __future__ import annotations


def _sparse(config: dict) -> dict:
    return config["assumed"]["sparse_config"]


def lightning_layers(config: dict) -> int:
    return sum(1 for t in config["mixer_types"] if t == "lightning-attn")


def sparse_layers(config: dict) -> int:
    return sum(1 for t in config["mixer_types"] if t == "minicpm4")


def lightning_step_bytes(rows: float, config: dict) -> float:
    """Bytes one step call must move for ``rows`` sequences."""
    H, d = config["lightning_nh"], config["lightning_head_dim"]
    state = config["assumed"].get("lightning_state_itemsize", 2)
    return rows * (2 * H * d * d * state + 4 * H * d * 2)  # the state in and out; q, k, v, o (bf16)


def lightning_prefill_ops(tokens: float, config: dict, chunk: int = 128) -> float:
    """Operations one prefill call needs for ``tokens`` tokens."""
    H, d = config["lightning_nh"], config["lightning_head_dim"]
    return tokens * H * (4 * chunk * d + 4 * d * d)


def chosen_page_bytes(rows: float, config: dict, itemsize: int = 2) -> float:
    """Bytes one sparse layer's attend must read for ``rows`` sequences, every
    one of them past ``dense_len`` with more than ``topk`` blocks in view (the
    sessions-long cell: 128 blocks at least): K and V, a head's lanes."""
    s = _sparse(config)
    return rows * config["num_key_value_heads"] * s["topk"] * s["block_size"] * 2 * config["head_dim"] * itemsize


def compressed_key_bytes(context_tokens: float, config: dict, itemsize: int = 2) -> float:
    """Bytes one sparse layer's choice must read for sequences that hold
    ``context_tokens`` tokens together."""
    s = _sparse(config)
    return context_tokens / s["kernel_stride"] * config["num_key_value_heads"] * config["head_dim"] * itemsize


def decoding(records: list[dict], t_lo: float, t_hi: float, step: float = 0.05) -> tuple[float, float]:
    """Mean over [t_lo, t_hi] of (the requests in their decode phase, their
    tokens of context together), as the client saw them: ``kernels.py``'s
    ``decode_context_tokens`` with the count beside it."""
    rows = total = 0.0
    n, t = 0, t_lo
    while t <= t_hi:
        for r in records:
            if r["first"] is None or r["first"] > t or r["status"] == "failed":
                continue
            if r["last"] is not None and r["last"] < t and r["status"] == "ok":
                continue
            rows += 1
            total += r["prompt_tokens"] + sum(k for tc, k in r["chunks"] if tc <= t)
        n, t = n + 1, t + step
    return (rows / n, total / n) if n else (0.0, 0.0)
