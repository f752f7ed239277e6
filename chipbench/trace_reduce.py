#!/usr/bin/env python3
"""From a JAX profiler trace to the numbers the benchmark reports.

    python3 chipbench/trace_reduce.py <trace dir> <out.json> [--events <events.json> [t0_ms t1_ms]]

Two steps, so that the arithmetic can be tested on a small recorded trace
without JAX: ``load_events`` reads the ``.xplane.pb`` (needs
``jax.profiler.ProfileData``; run it held to the CPU, never beside a process
that owns the chip) into plain lists, and ``reduce`` turns those lists into

    window_s      the device plane's first event to its last
    busy_s        union of the intervals in which an operation ran on the device
    top_ops       [[name, seconds], ...] device operations by total time
    modules       {program name: [seconds, executions]} (the "XLA Modules" line:
                  one event for each execution of a jitted program)
    idle_gaps     [[start_s, seconds], ...] the longest gaps, start from the window's
    ops_by_module {program name: {op kind: seconds}} for ops inside each execution
    op_counts     {op kind: events}, fusions left out

``--events`` also writes the plain event lists (optionally a slice in
milliseconds from the window's start), which is how
``tests/trace_small.json`` was recorded.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def load_events(trace_dir: str) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns]]}]}]}"""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[short(ev.name), float(ev.start_ns), float(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CUSTOM" not in plane_name.upper()


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def short(name: str) -> str:
    """The device plane names an operation by its whole HLO text,
    ``%fusion.170 = bf16[32,18944]{1,0:T(8,128)} fusion(...)``. Keep the
    operation's own name and the shape of its result: ``fusion.170
    bf16[32,18944]``. Other names (programs, host events) pass unchanged."""
    m = re.match(r"%(\S+) = \(?(\w+\[[\d,]*\])?", name)
    if not m:
        return name[:120]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def kind(op: str) -> str:
    """``paged_decode_attention.5 bf16[..]`` -> ``paged_decode_attention``."""
    return re.sub(r"[.\d]+$", "", op.split(" ")[0])


# Operations that only contain others: their time is their children's.
CONTAINERS = ("while", "conditional", "call")


def module_name(name: str) -> str:
    """``jit_multi_decode(1234567)`` -> ``jit_multi_decode``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(doc: dict, device_index: int = 0) -> dict:
    devices = [p for p in doc["planes"] if is_device(p["name"])]
    if not devices:
        raise ValueError("the trace has no device plane: nothing ran on a device, or "
                         f"the planes are named otherwise: {[p['name'] for p in doc['planes']]}")
    dev = sorted(devices, key=lambda p: p["name"])[device_index]
    # The window is the device plane's own: its first event to its last. The
    # host's threads are traced from some tens of ms before the device's
    # trace starts until seconds after it ends (stop_trace writes the file
    # meanwhile), and neither stretch says anything about the device. An
    # idle stretch at either edge of the traced seconds is missed.
    t_min = min((e[1] for ln in dev["lines"] for e in ln["events"]), default=0.0)
    t_max = max((e[1] + e[2] for ln in dev["lines"] for e in ln["events"]), default=t_min)
    ops = [e for ln in dev["lines"] if ln["name"] in OPS_LINES for e in ln["events"]]
    mods = [e for ln in dev["lines"] if ln["name"] in MODULE_LINES for e in ln["events"]]
    busy_src = ops or mods
    merged = union([(e[1], e[1] + e[2]) for e in busy_src])
    busy_ns = sum(b - a for a, b in merged)

    top: dict[str, float] = {}
    for name, _, dur in ops:
        if kind(name) not in CONTAINERS:
            top[name] = top.get(name, 0.0) + dur
    modules: dict[str, list[float]] = {}
    for name, _, dur in mods:
        m = modules.setdefault(module_name(name), [0.0, 0])
        m[0] += dur / 1e9
        m[1] += 1
    # Ops inside each program's executions, for kernel time per program.
    ops_by_module: dict[str, dict[str, float]] = {}
    op_counts: dict[str, int] = {}
    spans = sorted((e[1], e[1] + e[2], module_name(e[0])) for e in mods)
    i = 0
    for name, start, dur in sorted(ops, key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] <= start:
            i += 1
        if i < len(spans) and spans[i][0] <= start:
            if kind(name) in CONTAINERS:
                continue
            by = ops_by_module.setdefault(spans[i][2], {})
            by[kind(name)] = by.get(kind(name), 0.0) + dur / 1e9
            op_counts[kind(name)] = op_counts.get(kind(name), 0) + 1

    edges = [(t_min, t_min)] + merged + [(t_max, t_max)]
    gaps = [((edges[k][1] - t_min) / 1e9, (edges[k + 1][0] - edges[k][1]) / 1e9)
            for k in range(len(edges) - 1) if edges[k + 1][0] > edges[k][1]]
    gaps.sort(key=lambda g: -g[1])
    return {
        "device_plane": dev["name"], "n_device_planes": len(devices),
        "window_s": (t_max - t_min) / 1e9, "busy_s": busy_ns / 1e9,
        "top_ops": sorted(([n, s / 1e9] for n, s in top.items()), key=lambda x: -x[1])[:30],
        "modules": modules, "ops_by_module": ops_by_module,
        "op_counts": {k: n for k, n in op_counts.items() if "fusion" not in k},
        "idle_gaps": [[a, d] for a, d in gaps[:20]],
        "lines": {ln["name"]: len(ln["events"]) for ln in dev["lines"]},
    }


def main(argv: list[str]) -> int:
    trace_dir, out = argv[0], argv[1]
    doc = load_events(trace_dir)
    if "--events" in argv:
        k = argv.index("--events")
        sliced = doc
        if len(argv) > k + 3:
            t_min = min(e[1] for p in doc["planes"] for ln in p["lines"] for e in ln["events"])
            lo, hi = t_min + float(argv[k + 2]) * 1e6, t_min + float(argv[k + 3]) * 1e6
            sliced = {"planes": [
                {"name": p["name"], "lines": [
                    {"name": ln["name"], "events": [e for e in ln["events"] if lo <= e[1] < hi]}
                    for ln in p["lines"]]} for p in doc["planes"] if is_device(p["name"])]}
        with open(argv[k + 1], "w") as f:
            json.dump(sliced, f)
    with open(out, "w") as f:
        json.dump(reduce(doc), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
