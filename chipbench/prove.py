#!/usr/bin/env python3
"""Run one cell the way a bound is set: sets of runs with the same seeds in
each set, all in one call, and the spread of every metric.

    chiprun -- python3 chipbench/prove.py --workload <cell> [--sets 2] [--runs 6] [--traced 1]

Every result line goes to ``chiprun_out/prove_<cell>.jsonl`` as it comes, the
children's logs of the last run to ``chiprun_out/logs/<cell>/``. A spread is
the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; the bound
is about five times the widest spread over the cells, never under 1%.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SEEDS = [2147483659, 3000000011, 1234567891, 4000000007, 987654321, 2718281828,
         3141592653, 1618033988, 2236067977, 1414213562]


def spread(values: list[float]) -> float:
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("nan")


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--traced", type=int, default=0, help="traced runs after the sets")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--events-ms", default=None,
                   help="a,b: also record that slice of the last trace as chiprun_out/trace_small.json")
    o = p.parse_args(argv)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"prove_{o.workload}.jsonl")
    plan = [(s, i, 0) for s in range(o.sets) for i in range(o.runs)]
    plan += [(o.sets, i, 1) for i in range(o.traced)]
    by_set: dict[int, dict[str, list[float]]] = {}
    for set_no, i, trace in plan:
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", o.workload,
               "--seed", str(SEEDS[i % len(SEEDS)]), "--trace", str(trace)]
        if o.seconds is not None:
            cmd += ["--seconds", str(o.seconds)]
        if o.rehearse:
            cmd.append("--rehearse")
        t = time.monotonic()
        proc = subprocess.run(cmd, capture_output=True, text=True)
        wall = time.monotonic() - t
        lines = proc.stdout.strip().splitlines()
        row = {"set": set_no, "run": i, "seed": SEEDS[i % len(SEEDS)], "trace": trace,
               "rc": proc.returncode, "wall_s": wall}
        if proc.returncode == 0 and lines:
            row["result"] = json.loads(lines[-1])
            if not trace:
                for name, m in row["result"]["metrics"].items():
                    by_set.setdefault(set_no, {}).setdefault(name, []).append(m["value"])
        else:
            row["stderr"] = proc.stderr[-2000:]
        row["log"] = [ln for ln in lines[:-1] if "[chipbench" in ln][-40:]
        with open(out_path, "a") as f:
            f.write(json.dumps(row) + "\n")
        short = {k: round(v["value"], 3) for k, v in row.get("result", {}).get("metrics", {}).items()}
        print(f"set {set_no} run {i} trace {trace} rc {proc.returncode} wall {wall:.0f}s "
              f"correct {row.get('result', {}).get('correct')} failed "
              f"{row.get('result', {}).get('failed')} {json.dumps(short)}", flush=True)
        if proc.returncode != 0:
            print(proc.stderr[-1500:], flush=True)
            print("\n".join(lines[-15:]), flush=True)
    if o.events_ms and o.traced:
        a, b = o.events_ms.split(",")
        subprocess.run([sys.executable, os.path.join(HERE, "trace_reduce.py"),
                        os.path.join(ROOT, "chipbench_out", o.workload, "trace", "trace_0"),
                        os.path.join(out_dir, "trace_small.reduced.json"), "--events",
                        os.path.join(out_dir, "trace_small.json"), a, b],
                       env={**os.environ, "JAX_PLATFORMS": "cpu"}, check=False)
    for sub in ("plain", "trace"):
        src = os.path.join(ROOT, "chipbench_out", o.workload, sub)
        if os.path.isdir(src):
            dst = os.path.join(out_dir, "logs", o.workload, sub)
            os.makedirs(dst, exist_ok=True)
            for name in os.listdir(src):
                if os.path.isfile(os.path.join(src, name)) and os.path.getsize(os.path.join(src, name)) < 8 << 20:
                    shutil.copy(os.path.join(src, name), dst)
    for set_no, metrics in sorted(by_set.items()):
        for name, vals in metrics.items():
            # The first run of a call may compile; it is shown and left in.
            print(f"set {set_no} {name}: median {statistics.median(vals):.4f} "
                  f"spread {100 * spread(vals):.2f}% n={len(vals)} values "
                  f"{[round(v, 3) for v in vals]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
