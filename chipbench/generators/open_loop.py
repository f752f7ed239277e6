"""Open loop: arrivals on a schedule, each request independent of the rest."""

from __future__ import annotations

import math
import random

import numpy as np


def draw(rng: random.Random, spec: dict) -> int:
    """One length from {"dist": "lognormal", median, sigma, min, max} or
    {"dist": "uniform", min, max}."""
    if spec["dist"] == "lognormal":
        x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return int(max(spec["min"], min(spec["max"], round(x))))


def gaps(rng: random.Random, arrivals: dict | str, rate: float, seconds: float) -> list[float]:
    """Inter-arrival gaps whose running sum stays inside ``seconds``."""
    spec = {"dist": arrivals} if isinstance(arrivals, str) else arrivals
    out, t = [], 0.0
    while True:
        if spec["dist"] == "poisson":
            g = rng.expovariate(rate)
        elif spec["dist"] == "gamma":  # mean 1/rate, coefficient of variation cv
            k = 1.0 / (spec["cv"] ** 2)
            g = rng.gammavariate(k, 1.0 / (rate * k))
        else:
            raise ValueError(f"unknown arrival process {spec['dist']!r}")
        if t + g >= seconds:
            return out
        t += g
        out.append(g)


def generate(params: dict, seed: int, seconds: float, vocab_size: int) -> dict:
    shape = random.Random(params["shape_seed"])
    gap_list = gaps(shape, params.get("arrivals", "poisson"), params["rate_rps"], seconds)
    n = len(gap_list)
    classes = params["classes"]
    total_w = sum(c["weight"] for c in classes)
    sizes: list[tuple[int, int]] = []
    for i, c in enumerate(classes):
        # Exact shares: the last class takes what rounding left.
        k = n - len(sizes) if i == len(classes) - 1 else round(n * c["weight"] / total_w)
        sizes += [(draw(shape, c["prompt"]), draw(shape, c["output"])) for _ in range(k)]
    # One sample path for every seed: the same sizes and gaps in the same
    # order; the seed draws the token ids and nothing else. Shuffled or
    # rotated by the seed, the bursts that make a tail moved from run to run
    # and TTFT's 95th percentile spread by 5-30% (PERF.md, PR 23).
    shape.shuffle(sizes)
    pairs = list(zip(gap_list, sizes))
    tokens = np.random.default_rng(seed)
    requests, t = [], 0.0
    for g, (p, m) in pairs:
        t += g
        requests.append({"due": t, "max_tokens": m,
                         "prompt": tokens.integers(min(1000, vocab_size // 2), vocab_size - 1, size=p).tolist()})
    return {"mode": "open", "requests": requests, "shares_prefix": False,
            "prompt_max": max((p for p, _ in sizes), default=0)}
