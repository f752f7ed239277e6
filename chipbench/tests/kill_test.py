#!/usr/bin/env python3
"""SIGKILL the worker in the middle of a ``--rehearse`` window: the run has to
end with exit code 0, ``failed`` > 0, ``correct: false`` and no child alive,
and the next run has to start clean. By hand, on the CPU:

    python3 chipbench/tests/kill_test.py [workload]
"""

import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PIDS = os.path.join(ROOT, "chipbench_out", "stack.pids")  # [store, worker0, frontend, exporter]


def main() -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        workload = sys.argv[1] if len(sys.argv) > 1 else json.load(f)["workloads"][0]["name"]
    cmd = [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", workload,
           "--seed", "5", "--seconds", "20", "--trace", "0", "--rehearse"]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)
    lines, pids = [], []
    for line in proc.stdout:
        lines.append(line.rstrip())
        if "window opens" in line:
            time.sleep(5.0)
            with open(PIDS) as f:
                pids = json.load(f)
            os.kill(pids[1], signal.SIGKILL)  # [store, worker0, frontend, exporter]
            print(f"killed worker {pids[1]}", flush=True)
    rc = proc.wait()
    result = json.loads(lines[-1])
    print(lines[-1][:400])
    alive = []
    for pid in pids:
        try:
            os.killpg(pid, 0)
            alive.append(pid)
        except ProcessLookupError:
            pass
    ok = rc == 0 and result["failed"] > 0 and result["correct"] is False and not alive
    print(f"first run: rc={rc} failed={result['failed']} correct={result['correct']} "
          f"alive={alive} pid file left={os.path.exists(PIDS)}")
    nxt = subprocess.run([*cmd[:-5], "--seconds", "5", "--trace", "0", "--rehearse"],
                         capture_output=True, text=True)
    res2 = json.loads(nxt.stdout.strip().splitlines()[-1]) if nxt.returncode == 0 else {}
    ok = ok and nxt.returncode == 0 and res2.get("failed") == 0
    print(f"next run: rc={nxt.returncode} failed={res2.get('failed')} attempted={res2.get('attempted')}")
    print("KILL TEST", "PASSED" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
