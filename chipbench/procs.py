"""Child processes of one benchmark run: start them, find their ports, and
make sure none outlives the run. Copied from ``chip_smoke.py`` (``Child``,
``free_port``), with a benchmark's policy instead of a smoke's: nothing here
raises over a child that exits badly; it is written to the log and the run
goes on to its result line.

The parent never imports JAX: a process that has touched JAX holds the chip.
"""

from __future__ import annotations

import atexit
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    """A line on stdout before the result line. Never raises: a closed pipe
    must not stop a teardown half way."""
    try:
        print(f"[chipbench {time.strftime('%H:%M:%S')}] {msg}", flush=True)
    except (OSError, ValueError):
        pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _group_gone(pgid: int) -> bool:
    try:
        os.killpg(pgid, 0)
    except ProcessLookupError:
        return True
    except PermissionError:
        return False
    return False


class Child:
    """One child process in a session of its own, output in a log file."""

    def __init__(self, name: str, argv: list[str], log_dir: str, env: dict | None = None):
        os.makedirs(log_dir, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(log_dir, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, *argv], cwd=ROOT, stdout=self._log, stderr=subprocess.STDOUT,
            env={**os.environ, "PYTHONUNBUFFERED": "1", **(env or {})},
            start_new_session=True,
        )
        self.pid = self.proc.pid

    def log_text(self) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return f.read()
        except OSError:
            return ""

    def wait_for(self, pattern: str, timeout: float) -> re.Match | None:
        """The first match of ``pattern`` in the log, or None when the child
        died or the time ran out."""
        rx = re.compile(pattern)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            m = rx.search(self.log_text())
            if m:
                return m
            if self.proc.poll() is not None:
                return rx.search(self.log_text())
            time.sleep(0.1)
        return None

    def signal(self, sig: int) -> bool:
        if self.proc.poll() is not None:
            return False
        try:
            self.proc.send_signal(sig)
            return True
        except (ProcessLookupError, OSError):
            return False


class Stack:
    """All children of one run, and the pid file that lets the next run reap
    what a killed run left behind."""

    def __init__(self, out_dir: str, pid_file: str):
        self.out_dir = out_dir
        self.children: list[Child] = []
        self.unclean: list[str] = []
        self.pid_file = pid_file
        self._stopped = False
        atexit.register(self.stop)

    def reap_leftovers(self) -> int:
        """Kill the process groups a killed earlier run wrote to the pid
        file. Returns how many were still alive."""
        try:
            with open(self.pid_file) as f:
                pids = json.load(f)
        except (OSError, ValueError):
            return 0
        n = 0
        for pid in pids:
            if not _group_gone(pid):
                n += 1
                try:
                    os.killpg(pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not all(_group_gone(p) for p in pids):
            time.sleep(0.1)
        try:
            os.remove(self.pid_file)
        except OSError:
            pass
        return n

    def start(self, name: str, argv: list[str], env: dict | None = None) -> Child:
        child = Child(name, argv, self.out_dir, env)
        self.children.append(child)
        os.makedirs(os.path.dirname(self.pid_file), exist_ok=True)
        with open(self.pid_file, "w") as f:
            json.dump([c.pid for c in self.children], f)
        return child

    def stop(self, term_wait: float = 15.0) -> None:
        """SIGTERM in reverse order of start with a bounded wait each, then
        SIGKILL of every process group; returns when every PID is gone."""
        if self._stopped:
            return
        self._stopped = True
        for child in reversed(self.children):
            # One after another, the store last: a worker that loses its
            # control plane first spends its teardown waiting for it.
            if not child.signal(signal.SIGTERM):
                rc = child.proc.poll()
                if rc not in (0, None):
                    self.unclean.append(f"{child.name}: had exited with code {rc}")
                continue
            t_term, forced = time.monotonic(), False
            try:
                try:
                    rc = child.proc.wait(min(3.0, term_wait))
                except subprocess.TimeoutExpired:
                    if child.name == "frontend":
                        # It drains its streams on the first signal and leaves
                        # at once on the second ("the operator wants out NOW").
                        # Not for the others: a worker's second SIGTERM finds
                        # its handler gone and kills it mid-teardown.
                        forced = True
                        child.signal(signal.SIGTERM)
                    rc = child.proc.wait(max(0.1, term_wait - 3.0))
                log(f"{child.name} stopped in {time.monotonic() - t_term:.1f} s (rc={rc})")
                if rc != 0 and not (forced and rc == 130):  # 130: the frontend's "out now"
                    self.unclean.append(f"{child.name}: exit code {rc} on SIGTERM")
            except subprocess.TimeoutExpired:
                self.unclean.append(f"{child.name}: needed SIGKILL")
        for child in self.children:
            try:
                os.killpg(child.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            try:
                child.proc.wait(10)
            except subprocess.TimeoutExpired:
                self.unclean.append(f"{child.name}: did not die of SIGKILL")
            child._log.close()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not all(_group_gone(c.pid) for c in self.children):
            time.sleep(0.05)
        for line in self.unclean:
            log(f"unclean child: {line}")
        try:
            os.remove(self.pid_file)
        except OSError:
            pass
