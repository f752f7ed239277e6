#!/usr/bin/env python3
"""Run one cell the way the driver judges it, all in one call.

    chiprun --timeout 3000 -- python3 chipbench/prove.py --workload <cell> --aa 6
    chiprun -- python3 chipbench/prove.py --workload <cell> --sets 2 --runs 6 [--traced 1]

``--aa N`` (default 6) runs one tree as the driver runs two: N pairs of runs,
sides A and B taking turns to go first, both runs of a pair on one seed. For
every end-to-end metric it prints each side's spread the ledger's way
(``arith.ledger_spread``: highest less lowest over the median, the one run
farthest from the median left out where that narrows it) beside the quartile
spread, how far apart the sides' medians are as a share of A's, the bound, and
one word: ``judgeable`` when both spreads are at most half the bound and the
medians less than half the bound apart, ``unsteady`` otherwise. An honest PR
that changes nothing is refused on a metric that reads ``unsteady`` here.

A bound is set from this spread: at least twice the widest ``--aa`` spread of
a side in any cell that judges the metric, at most eight times it (PERF.md
section 2 has the driver's limits), never under 1%. The quartile spread of six
runs gives the lowest and the highest run a quarter of their weight and reads
a cell in which one run in three lands elsewhere as steady: it is printed, and
sets nothing.

``--sets S --runs R`` runs S sets of R runs with the same seeds in each set.
Every result line goes to ``chiprun_out/prove_<cell>.jsonl`` as it comes, the
children's logs of the last run to ``chiprun_out/logs/<cell>/``, and those of
every run that is not correct to ``chiprun_out/logs/<cell>/<set>_<run>/``: the
next run overwrites them where they were written.
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
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import arith  # noqa: E402

SEEDS = [2147483659, 3000000011, 1234567891, 4000000007, 987654321, 2718281828,
         3141592653, 1618033988, 2236067977, 1414213562, 2147483777, 3999999979,
         1000000007, 2500000001, 1732050807, 2645751311, 3316624790, 1123581321,
         2020202021, 3535353541, 1357924680, 2468013579, 4123456789, 2999999929]


def aa_plan(pairs: int) -> list[tuple[str, int, int]]:
    """(side, pair, trace) in running order: A goes first in even pairs, B in odd."""
    plan = []
    for i in range(pairs):
        plan += [(side, i, 0) for side in ("AB" if i % 2 == 0 else "BA")]
    return plan


def bounds_of(workload: str) -> dict[str, float]:
    """The bound of every end-to-end metric the cell is judged on."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return {m["name"]: m["bound"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}


def summary(by_set: dict, bounds: dict[str, float], aa: bool) -> list[str]:
    """One line a set and metric, and under ``--aa`` one line a metric with the word."""
    out = []
    for set_no, metrics in sorted(by_set.items()):
        for name, vals in metrics.items():
            # The first run of a call may compile; it is shown and left in.
            out.append(f"set {set_no} {name}: median {statistics.median(vals):.4f} ledger spread "
                       f"{100 * arith.ledger_spread(vals):.2f}% quartile spread "
                       f"{100 * arith.quartile_spread(vals):.2f}% n={len(vals)} values "
                       f"{[round(v, 3) for v in vals]}")
    if aa and {"A", "B"} <= set(by_set):
        for name in by_set["A"]:
            a, b = by_set["A"][name], by_set["B"].get(name, [])
            if not b:
                continue
            if name in bounds:
                w = arith.aa_word(a, b, bounds[name])
                out.append(f"aa {name}: A {100 * w['spread_a']:.2f}% B {100 * w['spread_b']:.2f}% "
                           f"medians {w['median_a']:.4f} {w['median_b']:.4f} apart "
                           f"{100 * w['medians_apart']:.2f}% bound {100 * bounds[name]:.1f}% {w['word']}")
            else:  # printed, not judged: a phase of set-up, a metric the cell is not held to
                out.append(f"aa {name}: A {100 * arith.ledger_spread(a):.2f}% B "
                           f"{100 * arith.ledger_spread(b):.2f}% medians {statistics.median(a):.4f} "
                           f"{statistics.median(b):.4f} not judged")
    return out


def keep_logs(workload: str, sub: str, dst: str) -> None:
    """Copy what a run's children wrote (logs, stats, the sample and the reference's log)."""
    src = os.path.join(ROOT, "chipbench_out", workload, sub)
    if not os.path.isdir(src):
        return
    os.makedirs(dst, exist_ok=True)
    for name in os.listdir(src):
        if os.path.isfile(os.path.join(src, name)) and os.path.getsize(os.path.join(src, name)) < 8 << 20:
            shutil.copy(os.path.join(src, name), dst)


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--aa", type=int, default=None, const=6, nargs="?",
                   help="pairs of runs of one tree, judged as the driver judges two")
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--traced", type=int, default=0, help="traced runs after the sets")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--param", action="append", default=[], metavar="KEY=JSON",
                   help="passed to run.py: override one parameter of the mix")
    p.add_argument("--seed-offset", type=int, default=0,
                   help="start that far into the list of seeds: a second call on seeds of its own")
    p.add_argument("--tag", default="", help="suffix of the .jsonl's name")
    p.add_argument("--events-ms", default=None,
                   help="a,b: also record that slice of the last trace as chiprun_out/trace_small.json")
    o = p.parse_args(argv)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"prove_{o.workload}{o.tag}.jsonl")
    if o.aa is not None:
        plan = aa_plan(o.aa)
    else:
        plan = [(s, i, 0) for s in range(o.sets) for i in range(o.runs)]
    plan += [("traced", i, 1) for i in range(o.traced)]
    by_set: dict = {}
    for set_no, i, trace in plan:
        seed = SEEDS[(o.seed_offset + i) % len(SEEDS)]
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", o.workload,
               "--seed", str(seed), "--trace", str(trace)]
        if o.seconds is not None:
            cmd += ["--seconds", str(o.seconds)]
        if o.rehearse:
            cmd.append("--rehearse")
        for item in o.param:
            cmd += ["--param", item]
        t = time.monotonic()
        proc = subprocess.run(cmd, capture_output=True, text=True)
        wall = time.monotonic() - t
        lines = proc.stdout.strip().splitlines()
        row = {"set": set_no, "run": i, "seed": seed, "trace": trace,
               "rc": proc.returncode, "wall_s": wall, "param": o.param}
        extra = {}
        if proc.returncode == 0 and lines:
            row["result"] = json.loads(lines[-1])
            extra = row["result"].get("unjudged", {})
            if not trace:
                judged = {n: m["value"] for n, m in row["result"]["metrics"].items()}
                also = {**extra.get("end_to_end_all", {}), **extra.get("setup_phases", {})}
                for name, v in {**also, **judged}.items():
                    by_set.setdefault(set_no, {}).setdefault(name, []).append(v)
        else:
            row["stderr"] = proc.stderr[-2000:]
        row["log"] = [ln for ln in lines[:-1] if "[chipbench" in ln][-40:]
        with open(out_path, "a") as f:
            f.write(json.dumps(row) + "\n")
        short = {k: round(v["value"], 3) for k, v in row.get("result", {}).get("metrics", {}).items()}
        print(f"set {set_no} run {i} trace {trace} rc {proc.returncode} wall {wall:.0f}s "
              f"correct {row.get('result', {}).get('correct')} failed "
              f"{row.get('result', {}).get('failed')} {json.dumps(short)} {json.dumps(extra.get('batch'))}",
              flush=True)
        if proc.returncode != 0:
            print(proc.stderr[-1500:], flush=True)
            print("\n".join(lines[-15:]), flush=True)
        if not row.get("result", {}).get("correct"):
            keep_logs(o.workload, "trace" if trace else "plain",
                      os.path.join(out_dir, "logs", o.workload, f"{set_no}_{i}"))
    if o.events_ms and o.traced:
        a, b = o.events_ms.split(",")
        subprocess.run([sys.executable, os.path.join(HERE, "trace_reduce.py"),
                        os.path.join(ROOT, "chipbench_out", o.workload, "trace", "trace_0"),
                        os.path.join(out_dir, "trace_small.reduced.json"), "--events",
                        os.path.join(out_dir, "trace_small.json"), a, b],
                       env={**os.environ, "JAX_PLATFORMS": "cpu"}, check=False)
    for sub in ("plain", "trace"):
        keep_logs(o.workload, sub, os.path.join(out_dir, "logs", o.workload, sub))
    for line in summary(by_set, bounds_of(o.workload), o.aa is not None):
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
