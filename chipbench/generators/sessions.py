"""Closed loop of multi-turn sessions: every turn resends the whole history.

After ``benchmarks/synthesize.py`` (prefix forest, token-id prompts): a
session draws one of a few system prompts (Zipf), then turns of new user
tokens answered by the model; the next prompt is history + answer + new
tokens, so the prefix cache can hold all but the newest part.
"""

from __future__ import annotations

import random

import numpy as np


def zipf_choice(rng: random.Random, n: int, s: float) -> int:
    weights = [1.0 / (k + 1) ** s for k in range(n)]
    return rng.choices(range(n), weights)[0]


def generate(params: dict, seed: int, seconds: float, vocab_size: int) -> dict:
    shape = random.Random(params["shape_seed"])
    tokens = np.random.default_rng(seed)
    n_sys, sys_len = params["system_prompts"], params["system_tokens"]
    lo_u, hi_u = params["user_tokens"]
    lo_a, hi_a = params["answer_tokens"]
    max_hist = params["max_history"]
    n_turns = int(seconds / max(0.25, params["think_mean_s"])) + 8

    def ids(n: int) -> list[int]:
        return tokens.integers(min(1000, vocab_size // 2), vocab_size - 1, size=n).tolist()

    system_prompts = [ids(sys_len) for _ in range(n_sys)]
    clients = []
    for _ in range(params["clients"]):
        # The shape: start depth, system prompts in the order sessions take
        # them, and the turns' sizes and think times. The seed draws the token
        # ids and nothing else: a closed loop has no arrivals to reorder, and
        # its own feedback already makes two runs of one script differ.
        depth = shape.randrange(sys_len, max_hist - hi_u - hi_a, 16)
        bases = [zipf_choice(shape, n_sys, params["zipf_s"]) for _ in range(n_turns + 1)]
        turns = [(min(5.0 * params["think_mean_s"], shape.expovariate(1.0 / params["think_mean_s"])),
                  shape.randint(lo_u, hi_u), shape.randint(lo_a, hi_a)) for _ in range(n_turns)]
        prefill = system_prompts[bases[0]] + ids(depth - sys_len)
        hist, next_base, script = depth, 1, []
        for think, new, out in turns:
            base = None
            if hist + new + out > max_hist:
                base, next_base = bases[next_base], next_base + 1
                hist = sys_len
            hist += new + out
            script.append({"think_s": think, "base": base, "new": ids(new), "max_tokens": out})
        clients.append({"prefill": prefill, "turns": script})
    return {"mode": "closed", "clients": clients, "system_prompts": system_prompts,
            "shares_prefix": True, "prompt_max": max_hist}
