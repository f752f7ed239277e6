"""One reader per per-layer metric, found by the metric's name in
BENCHMARK.json (``-`` and ``.`` in a name become ``_`` in the file's).

A reader is ``read(ctx) -> float | None``. ``ctx`` holds what a traced run
gathered: ``records`` (the client's request records, see ``arith.py``),
``prom`` ({"frontend.before", "frontend.after", "worker<r>.before", ...} ->
parsed ``/metrics``), ``gauges`` (the exporter's page polled every 0.5 s in
the window), ``stats`` ({"<rank>.<k>"} -> the launcher's snapshots, k = 0 at
the window's start and 1 at its end), ``trace`` (``trace_reduce.reduce``'s
result for rank 0, or None), ``config``, ``traffic``, ``seconds``,
``replicas``, ``t0``, ``t_end``, ``worker_logs``, ``here``, ``decode_buckets``
(the configuration's ``EngineArgs.decode_buckets``, as warm-up took them).
A plain run fills ``records``, ``gauges``, ``config``, ``traffic``,
``seconds``, ``replicas`` and ``decode_buckets`` too, for its log line.

A reader that finds nothing to read returns None (or raises): the harness
then leaves the metric out of the line and says so on an earlier one.
"""

from chipbench import arith


def worker_delta(ctx: dict, name: str, *labels: str) -> float | None:
    """Sum over the workers of a counter's growth over the window."""
    total, seen = 0.0, False
    for r in range(ctx["replicas"]):
        before, after = ctx["prom"].get(f"worker{r}.before"), ctx["prom"].get(f"worker{r}.after")
        if before is None or after is None:
            continue
        d = arith.prom_delta(before, after, name, *labels)
        if d is not None:
            total, seen = total + d, True
    return total if seen else None


def frontend_delta(ctx: dict, name: str, *labels: str) -> float | None:
    before, after = ctx["prom"].get("frontend.before"), ctx["prom"].get("frontend.after")
    if before is None or after is None:
        return None
    return arith.prom_delta(before, after, name, *labels)


def gauge_series(ctx: dict, name: str) -> list[float]:
    """The exporter's gauge ``name``, summed over workers, one value a poll."""
    out = []
    for sample in ctx["gauges"]:
        if 0.0 <= sample["t"] <= ctx["seconds"]:
            v = arith.prom_sum({k: x for k, x in sample.items() if k != "t"}, name)
            if v is not None:
                out.append(v)
    return out


def gauge_samples(ctx: dict, name: str) -> list[float]:
    """The exporter's gauge ``name``, one value a worker a poll: what each
    worker saw, where ``gauge_series`` gives the fleet's sum."""
    out = []
    for sample in ctx["gauges"]:
        if 0.0 <= sample["t"] <= ctx["seconds"]:
            out += [v for key, v in sample.items() if key.partition("{")[0] == name]
    return out


def module_seconds(trace: dict, pattern: str) -> tuple[float, int]:
    """Device seconds and executions of the programs whose name holds ``pattern``."""
    secs, n = 0.0, 0
    for name, (s, k) in trace["modules"].items():
        if pattern in name:
            secs, n = secs + s, n + k
    return secs, n
