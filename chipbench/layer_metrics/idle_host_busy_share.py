"""Share of the device's idle time during which the scheduler thread was in a
phase other than sched.idle, from rank 0's profiler trace
(``chipbench/host_phases.py``): an idle device with the scheduler busy waits
for the host. A program without the sched.* annotations reads nothing."""
import glob
import json
import os

from chipbench import host_phases


def trace_dir(ctx):
    """Rank 0's trace of this run: the one whose launcher said ``done`` after
    this run's window opened."""
    out_root = os.path.join(os.path.dirname(ctx["here"]), "chipbench_out")
    for done in glob.glob(os.path.join(out_root, "*", "trace", "trace_0.done")):
        try:
            with open(done) as f:
                if json.load(f)["t_unix"] >= ctx["t0_unix"]:
                    return done[: -len(".done")]
        except (OSError, ValueError, KeyError):
            continue
    return None


def read(ctx):
    path = trace_dir(ctx) if ctx["trace"] else None
    if path is None:
        return None
    report = host_phases.attribute_dir(path)
    if report is None or not report["phases_seen"]:
        return None
    return 100.0 * report["idle_host_busy_s"] / report["idle_s"] if report["idle_s"] else 0.0
