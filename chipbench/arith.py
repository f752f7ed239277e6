"""The benchmark's arithmetic: percentiles, per-request latencies, rates, and
reading a Prometheus exposition. ``pctl`` and ``slo_attribution`` are after ``bench.py:199-227``.

A request record (``client.py`` writes them) is a dict with:

    due, sent, first, last   seconds on the run's monotonic clock (None = never)
    chunks                   [(t, n_tokens), ...] content chunks as they arrived
    status                   "ok" | "failed" | "cut"  (cut = in flight when the
                             window closed; neither completed nor failed)
    prompt_tokens, max_tokens, usage, finish_reason, error, kind ("open"|"closed")
"""

from __future__ import annotations

import math


def pctl(values: list[float], q: float) -> float:
    """Nearest-rank percentile, q in [0, 100]; inf counts as the worst."""
    if not values:
        return float("nan")
    s = sorted(values)
    k = max(0, min(len(s) - 1, math.ceil(q / 100.0 * len(s)) - 1))
    return s[k]


def ttft_samples(records: list[dict], t_end: float) -> list[float]:
    """Seconds from the due time (open loop) or the send time (closed loop)
    to the first content chunk, one per request. A failed request is the
    worst (inf). A request cut at the end of the window before its first
    chunk counts with the time it had waited by then."""
    out = []
    for r in records:
        start = r["due"] if r["kind"] == "open" else r["sent"]
        if start is None:
            continue
        if r["status"] == "failed":
            out.append(math.inf)
        elif r["first"] is not None:
            out.append(r["first"] - start)
        elif r["status"] == "cut":
            out.append(max(0.0, t_end - start))
    return out


def tpot_samples(records: list[dict], min_tokens: int = 9) -> list[float]:
    """Per request, (last chunk - first chunk) / (output tokens - 1). Per
    request and not per gap: the engine emits a decode window of several
    tokens at once, so single gaps are zeros and window times. Completed
    requests, and streams cut after at least ``min_tokens`` tokens (the
    first token and one whole decode window). Failed requests are the worst."""
    out = []
    for r in records:
        if r["status"] == "failed":
            out.append(math.inf)
            continue
        n = sum(k for _, k in r["chunks"])
        if r["first"] is None or r["last"] is None or n < max(2, min_tokens):
            continue
        out.append((r["last"] - r["first"]) / (n - 1))
    return out


def tokens_in_window(records: list[dict], t0: float, t_end: float) -> int:
    """Content tokens received in [t0, t_end]; a failed request's do not count."""
    return sum(k for r in records if r["status"] != "failed"
               for t, k in r["chunks"] if t0 <= t <= t_end)


def finite_or_cap(x: float, cap: float) -> float:
    """A percentile that landed on a failed request reads as ``cap`` (the
    window's length): a number the driver can compare, and far off."""
    return cap if not math.isfinite(x) else x


# -- Prometheus text ----------------------------------------------------------


def parse_prom(text: str) -> dict[str, float]:
    """``name{labels}`` -> value, for every sample line."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        key, _, val = line.rpartition(" ")
        try:
            out[key] = float(val)
        except ValueError:
            continue
    return out


def prom_sum(samples: dict[str, float], name: str, *labels: str) -> float | None:
    """Sum of the series ``name`` whose label text holds every one of
    ``labels``; None when there is no such series."""
    total, seen = 0.0, False
    for key, val in samples.items():
        base, _, rest = key.partition("{")
        if base == name and all(lab in rest for lab in labels):
            total += val
            seen = True
    return total if seen else None


def prom_delta(before: dict, after: dict, name: str, *labels: str) -> float | None:
    """after - before of a counter; a series that was not there before
    started at 0. None when it is not there after."""
    b = prom_sum(after, name, *labels)
    if b is None:
        return None
    return b - (prom_sum(before, name, *labels) or 0.0)


def slo_attribution(ttft_s: list[float], tpot_s: list[float], ttft_limit: float,
                    tpot_limit: float) -> dict:
    """Share of requests inside each limit (``bench.py:209``); printed on an
    earlier line until a later benchmark issue fixes the limits."""
    def share(xs: list[float], lim: float) -> float:
        return 100.0 * sum(1 for x in xs if x <= lim) / len(xs) if xs else float("nan")
    return {"ttft_ok_pct": share(ttft_s, ttft_limit), "tpot_ok_pct": share(tpot_s, tpot_limit)}
