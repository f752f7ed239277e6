"""Operations and bytes of the program's kernels, from shapes: the yardstick's
side of a roofline share. The time comes from the trace.

``ops/paged_attention.py`` decode kernel, one call = one layer of one decode
step: it has to read the K and V of every token of context of every sequence
in the batch, once, and little else (queries and outputs are a few KB a row).
Bound by bytes on every chip in ``peaks.json``: two operations a byte.
"""

from __future__ import annotations


def kv_bytes_per_token_per_layer(config: dict, kv_itemsize: int = 2) -> int:
    head_dim = config.get("assumed", {}).get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"])
    return 2 * config["num_key_value_heads"] * head_dim * kv_itemsize  # K and V


def decode_attention_bytes(context_tokens: float, config: dict) -> float:
    """Bytes one call must read for a batch whose sequences hold
    ``context_tokens`` tokens of context together."""
    return context_tokens * kv_bytes_per_token_per_layer(config)


def decode_context_tokens(records: list[dict], t_lo: float, t_hi: float, step: float = 0.05) -> float:
    """Mean, over [t_lo, t_hi], of the tokens of context of the requests in
    their decode phase, as the client saw them: after the first chunk and
    before the last, prompt plus the tokens received so far. A lower bound
    of what the server held: it runs ahead of the client by a window."""
    total, n, t = 0.0, 0, t_lo
    while t <= t_hi:
        ctx = 0
        for r in records:
            if r["first"] is None or r["first"] > t or r["status"] == "failed":
                continue
            if r["last"] is not None and r["last"] < t and r["status"] == "ok":
                continue
            ctx += r["prompt_tokens"] + sum(k for tc, k in r["chunks"] if tc <= t)
        total, n, t = total + ctx, n + 1, t + step
    return total / n if n else 0.0
