#!/usr/bin/env python3
"""Which scheduler phase covers the device's idle gaps, from a profiler trace.

    python3 chipbench/host_phases.py <trace dir> [--json <out.json>]

The engine's scheduler thread wraps every section of its step loop in a
``jax.profiler.TraceAnnotation`` named ``sched.<phase>`` (``sched.idle``,
``sched.admission``, ``sched.prefill_dispatch``, ``sched.decode_dispatch``,
``sched.drain_sync``, ``sched.emit``, ...), so a trace holds the host's phases
on the clock of the device's operations. ``attribute`` lays the two over each
other:

    window_s          the device plane's first event to its last
    idle_s            the window less the union of the device's operations
    idle_host_busy_s  idle device time covered by a phase other than sched.idle:
                      the device had nothing to run while the scheduler worked
    idle_by_phase     {phase: idle device seconds under it}; "none" = under no phase
    gaps              the 20 longest gaps: [start_s, seconds, {phase: seconds}]
    sched_s           {phase: seconds the scheduler thread spent in it, in the whole trace}
    phases_seen       sched.* events in the trace (0: a program without them)

As in ``trace_reduce.py`` the arithmetic takes plain event lists, so that it
is tested without JAX on a small recorded list; only ``load_events`` needs
``jax.profiler.ProfileData``, and runs held to the CPU, never beside a
process that owns the chip. ``attribute_dir`` does that in a child.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.trace_reduce import MODULE_LINES, OPS_LINES, is_device, union  # noqa: E402

PREFIX = "sched."
IDLE = "sched.idle"


def overlap(gaps: list[tuple[float, float]], spans: list[tuple[float, float]]) -> list[float]:
    """For each of ``gaps`` (sorted, disjoint) the length of it that the
    ``spans`` (sorted, disjoint) cover. One sweep over both."""
    out, j = [], 0
    for a, b in gaps:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        k, got = j, 0.0
        while k < len(spans) and spans[k][0] < b:
            got += min(b, spans[k][1]) - max(a, spans[k][0])
            k += 1
        out.append(got)
    return out


def attribute(doc: dict, device_index: int = 0, top: int = 20) -> dict:
    devices = sorted((p for p in doc["planes"] if is_device(p["name"])), key=lambda p: p["name"])
    if not devices:
        raise ValueError("the trace has no device plane")
    dev = devices[device_index]
    events = [e for ln in dev["lines"] for e in ln["events"]]
    t_min = min((e[1] for e in events), default=0.0)
    t_max = max((e[1] + e[2] for e in events), default=t_min)
    ops = [e for ln in dev["lines"] if ln["name"] in OPS_LINES for e in ln["events"]]
    mods = [e for ln in dev["lines"] if ln["name"] in MODULE_LINES for e in ln["events"]]
    busy = union([(e[1], e[1] + e[2]) for e in (ops or mods)])
    edges = [(t_min, t_min)] + busy + [(t_max, t_max)]
    gaps = [(edges[k][1], edges[k + 1][0]) for k in range(len(edges) - 1)
            if edges[k + 1][0] > edges[k][1]]

    by_phase: dict[str, list[tuple[float, float]]] = {}
    for plane in doc["planes"]:
        if is_device(plane["name"]):
            continue
        for ln in plane["lines"]:
            for name, start, dur in ln["events"]:
                if name.startswith(PREFIX):
                    by_phase.setdefault(name, []).append((start, start + dur))
    seen = sum(len(v) for v in by_phase.values())
    by_phase = {name: union(spans) for name, spans in by_phase.items()}
    covered = {name: overlap(gaps, spans) for name, spans in by_phase.items()}
    working = union([s for name, spans in by_phase.items() if name != IDLE for s in spans])
    any_phase = union([s for spans in by_phase.values() for s in spans])

    idle_ns = sum(b - a for a, b in gaps)
    idle_by_phase = {name: sum(c) / 1e9 for name, c in covered.items() if sum(c) > 0}
    idle_by_phase["none"] = (idle_ns - sum(overlap(gaps, any_phase))) / 1e9
    longest = sorted(range(len(gaps)), key=lambda i: gaps[i][0] - gaps[i][1])[:top]
    return {
        "device_plane": dev["name"], "window_s": (t_max - t_min) / 1e9,
        "idle_s": idle_ns / 1e9, "idle_host_busy_s": sum(overlap(gaps, working)) / 1e9,
        "idle_by_phase": dict(sorted(idle_by_phase.items(), key=lambda kv: -kv[1])),
        "gaps": [[(gaps[i][0] - t_min) / 1e9, (gaps[i][1] - gaps[i][0]) / 1e9,
                  {name: c[i] / 1e9 for name, c in covered.items() if c[i] > 0}]
                 for i in longest],
        "n_gaps": len(gaps), "phases_seen": seen,
        "sched_s": {name: sum(b - a for a, b in spans) / 1e9 for name, spans in sorted(by_phase.items())},
    }


def label_gaps(report: dict, top: int = 10) -> list[list]:
    """[[label, seconds], ...] for the report's longest gaps: the phase that
    covers most of a gap, ``no_sched_phase`` where none covers any of it."""
    out = []
    for _start_s, dur_s, phases in report["gaps"][:top]:
        out.append([max(phases, key=phases.get) if phases else "no_sched_phase", dur_s])
    return out


def table(report: dict) -> str:
    idle = report["idle_s"]
    share = 100.0 * report["idle_host_busy_s"] / idle if idle else 0.0
    lines = [
        f"device {report['device_plane']}: window {report['window_s']:.4f} s, idle "
        f"{idle * 1e3:.3f} ms in {report['n_gaps']} gaps "
        f"({100.0 * idle / report['window_s'] if report['window_s'] else 0.0:.4f}%); "
        f"{report['phases_seen']} sched.* events",
        f"idle device time under a phase other than {IDLE}: "
        f"{report['idle_host_busy_s'] * 1e3:.3f} ms ({share:.1f}%)",
        "idle device time by phase: " + ", ".join(
            f"{name} {secs * 1e3:.3f} ms" for name, secs in report["idle_by_phase"].items()),
        "scheduler thread, seconds by phase in the whole trace: " + ", ".join(
            f"{name} {secs:.4f}" for name, secs in sorted(report["sched_s"].items(), key=lambda kv: -kv[1])),
        f"{'start s':>10} {'gap us':>10}  phases over it (us)",
    ]
    for start_s, dur_s, phases in report["gaps"]:
        cover = ", ".join(f"{n} {s * 1e6:.1f}" for n, s in sorted(phases.items(), key=lambda kv: -kv[1]))
        lines.append(f"{start_s:10.6f} {dur_s * 1e6:10.1f}  {cover or 'none'}")
    return "\n".join(lines)


@functools.lru_cache(maxsize=4)
def attribute_dir(trace_dir: str, timeout_s: float = 240.0) -> dict | None:
    """``attribute`` of the trace under ``trace_dir``, computed in a child
    that is held to the CPU. None when there is no trace or the child fails.
    A run asks twice (a reader and the breakdown) and pays once."""
    if not os.path.isdir(trace_dir):
        return None
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "host_phases.json")
        try:
            subprocess.run([sys.executable, os.path.abspath(__file__), trace_dir, "--json", out],
                           env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=timeout_s,
                           check=True, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
            with open(out) as f:
                return json.load(f)
        except (subprocess.SubprocessError, OSError, ValueError):
            return None


def main(argv: list[str]) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from chipbench.trace_reduce import load_events

    report = attribute(load_events(argv[0]))
    if "--json" in argv:
        with open(argv[argv.index("--json") + 1], "w") as f:
            json.dump(report, f)
    print(table(report))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
