"""The benchmark's arithmetic: percentiles, per-request latencies, rates, the
spread of repeated runs, and reading a Prometheus exposition. ``pctl`` and ``slo_attribution`` are after ``bench.py:199-227``.

A request record (``client.py`` writes them) is a dict with:

    due, sent, first, last   seconds on the run's monotonic clock (None = never)
    chunks                   [(t, n_tokens), ...] content chunks as they arrived
    status                   "ok" | "failed" | "cut"  (cut = in flight when the
                             window closed; neither completed nor failed)
    prompt_tokens, max_tokens, usage, finish_reason, error, kind ("open"|"closed")
"""

from __future__ import annotations

import math
import statistics


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


# -- spread of repeated runs, and where a batch sits in its buckets -------------


def quartile_spread(values: list[float]) -> float:
    """Distance between the first and third quartile over the median. With
    six runs the quartiles lie a quarter of the way from the second value to
    the first and from the fifth to the sixth: an end counts for a quarter."""
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("nan")


def ledger_spread(values: list[float]) -> float:
    """The spread as the driver's ledger has it: highest less lowest over the
    median of all the runs, after leaving out the one run farthest from that
    median where that narrows it. One far run does no harm, two do. With
    fewer than three runs none is left out."""
    if not values:
        return float("nan")
    med = statistics.median(values)
    if not med:
        return float("nan")
    width = max(values) - min(values)
    if len(values) >= 3:
        rest = sorted(values, key=lambda v: abs(v - med))[:-1]
        width = min(width, max(rest) - min(rest))
    return width / med


def aa_word(side_a: list[float], side_b: list[float], bound: float) -> dict:
    """Two sides of one tree, judged as the driver judges parent and change:
    ``judgeable`` when each side's ledger spread is at most half the bound and
    the medians differ by less than half the bound (as a share of A's)."""
    sa, sb = ledger_spread(side_a), ledger_spread(side_b)
    ma, mb = statistics.median(side_a), statistics.median(side_b)
    apart = abs(mb - ma) / abs(ma) if ma else float("nan")
    ok = sa <= bound / 2 and sb <= bound / 2 and apart < bound / 2
    return {"spread_a": sa, "spread_b": sb, "median_a": ma, "median_b": mb,
            "medians_apart": apart, "word": "judgeable" if ok else "unsteady"}


def bucket_shares(samples: list[float], buckets: list[int]) -> dict[int, float] | None:
    """Share (%) of ``samples`` (running sequences, one a poll) that a decode
    batch bucket serves: the smallest bucket that holds the sample. A sample
    over the last bucket counts there. None without samples or buckets."""
    if not samples or not buckets:
        return None
    buckets = sorted(buckets)
    counts = dict.fromkeys(buckets, 0)
    for v in samples:
        counts[next((b for b in buckets if v <= b), buckets[-1])] += 1
    return {b: 100.0 * n / len(samples) for b, n in counts.items()}


def main_bucket_share(samples: list[float], buckets: list[int]) -> float | None:
    """Share (%) of the polls that fall in the bucket most polls fall in: near
    100 the batch sits inside one bucket, near 50 it sits on an edge and a
    decode step costs now one bucket's time and now the next one's."""
    shares = bucket_shares(samples, buckets)
    return max(shares.values()) if shares else None


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
