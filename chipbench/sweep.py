#!/usr/bin/env python3
"""Find a cell's knee: the highest offered rate the system sustains.

    python3 chipbench/sweep.py --workload <cell> --rates 4,6,8,10,12 [--seconds 30] [--seed 1]
    python3 chipbench/sweep.py --workload <cell> --clients 32,48,64     (closed-loop mixes)

Runs ``run.py`` once per rate, one after another (a chip belongs to one
process at a time), with ``--param`` overriding the mix's ``rate_rps`` or ``clients``, and
prints one line per rate: offered and completed requests per second, tokens
per second, TTFT and TPOT tails, failures. The knee is the last rate at which
completed keeps up with offered and the TTFT tail has not taken off; a cell
below the knee sets its mix's ``rate_rps`` to about 0.8 of it, a cell above
to about 1.25 of it, and writes the sweep's lines into PERF.md. Run it
through the chip tool, e.g. ``chiprun -- python3 chipbench/sweep.py ...``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", default=None)
    p.add_argument("--clients", default=None)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rehearse", action="store_true")
    o = p.parse_args(argv)
    key, values = ("rate_rps", o.rates) if o.rates else ("clients", o.clients)
    rows = []
    for v in values.split(","):
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", o.workload, "--seed",
               str(o.seed), "--seconds", str(o.seconds), "--trace", "0", "--param", f"{key}={v}",
               "--parity", "0"]  # a sweep's lines are never results: the knee needs no reference
        if o.rehearse:
            cmd.append("--rehearse")
        proc = subprocess.run(cmd, capture_output=True, text=True)
        lines = proc.stdout.strip().splitlines()
        row = {"value": float(v), "rc": proc.returncode}
        for line in lines:
            m = re.search(r"requests: (\{.*?\}); all end-to-end numbers: (\{.*\})", line)
            if m:
                row.update(json.loads(m.group(1).replace("'", '"')))
                row.update(json.loads(m.group(2)))
        if proc.returncode == 0 and lines:
            res = json.loads(lines[-1])
            row.update(attempted=res["attempted"], correct=res["correct"])
            row["offered_rps"] = res["attempted"] / o.seconds
            row["completed_rps"] = row.get("ok", 0) / o.seconds
        else:
            row["stderr"] = proc.stderr[-500:]
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(os.path.dirname(HERE), "chiprun_out"), exist_ok=True)
    with open(os.path.join(os.path.dirname(HERE), "chiprun_out", f"sweep_{o.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
