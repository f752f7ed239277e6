"""Spawned-process test harness.

Reference analogue: ``ManagedProcess`` (reference: tests/utils/
managed_process.py:69-99) — subprocess + readiness probe on stdout + log
capture + guaranteed teardown.
"""

from __future__ import annotations

import os
import re
import signal
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ManagedProcess:
    def __init__(self, args: list[str], name: str = "proc", env: dict | None = None):
        self.name = name
        full_env = dict(os.environ)
        full_env.setdefault("PYTHONUNBUFFERED", "1")
        # Workers/frontends in tests run on CPU: conftest sets
        # JAX_PLATFORMS=cpu in os.environ and subprocesses inherit it.
        full_env.update(env or {})
        self.proc = subprocess.Popen(
            [sys.executable, *args],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=REPO_ROOT,
            env=full_env,
        )
        self.lines: list[str] = []

    def wait_for(self, pattern: str, timeout: float = 30.0) -> re.Match:
        """Read stdout until a line matches ``pattern``."""
        rx = re.compile(pattern)
        deadline = time.monotonic() + timeout
        assert self.proc.stdout is not None
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"{self.name} exited rc={self.proc.returncode}:\n" + "".join(self.lines[-40:])
                )
            line = self.proc.stdout.readline()
            if not line:
                time.sleep(0.01)
                continue
            self.lines.append(line)
            m = rx.search(line)
            if m:
                return m
        raise TimeoutError(f"{self.name}: no match for {pattern!r} in:\n" + "".join(self.lines[-40:]))

    def kill(self, sig=signal.SIGKILL) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(sig)

    def terminate(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(5)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.terminate()
        return False
