#!/usr/bin/env python3
"""Start the program's worker on a configuration that lives in a file.

    python3 chipbench/launch_worker.py <config.json> <out_dir> <rank> [worker flags ...]

The only place the benchmark reaches into the program. The worker CLI takes
a preset name or a real checkpoint and nothing else, so this wrapper builds
a ``ModelConfig`` from the configuration's file through the map the file names
(``model_maps/<model_map>.py``), makes
``ModelConfig.preset(<name>)`` return it, and calls the worker's ``main()``
unchanged: same scheduler, block manager, runner, kernels and endpoint.

Only the process that holds the chip can trace it or read its memory, so
the wrapper also answers two signals from the harness:

    SIGUSR1  write ``<out_dir>/stats_<rank>_<k>.json`` (k = 0, 1, ...): the
             device as JAX reports it, ``memory_stats()`` and how many
             programs JAX compiled or took from its persistent cache so far.
    SIGUSR2  start a profiler trace into ``<out_dir>/trace_<rank>``; the
             next SIGUSR2 stops it and then writes ``trace_<rank>.done``.

Both are debts listed in PERF.md: configurations as files and a profiler
hook belong in the program. Nothing here names a model key: which keys a kind
of block has is the map's to say.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

class Monitor:
    """Counts JAX's compile requests and cache hits; answers the signals."""

    def __init__(self, out_dir: str, rank: str):
        self.out_dir, self.rank = out_dir, rank
        self.counts = {"compile_requests": 0, "cache_hits": 0, "backend_compile_s": 0.0}
        self._wake = threading.Event()
        self._pending: list[str] = []
        self._lock = threading.Lock()
        self._snapshots = 0
        self._tracing = False

    def install(self) -> None:
        import jax
        import jax.monitoring as mon

        # Name every program JAX builds in the worker's log, so that a compile
        # inside the window can be traced to its shape.
        jax.config.update("jax_log_compiles", True)

        def on_event(name: str, **_kw) -> None:
            if name == "/jax/compilation_cache/compile_requests_use_cache":
                self.counts["compile_requests"] += 1
            elif name == "/jax/compilation_cache/cache_hits":
                self.counts["cache_hits"] += 1

        def on_duration(name: str, secs: float, **_kw) -> None:
            if name == "/jax/core/compile/backend_compile_duration":
                self.counts["backend_compile_s"] += secs

        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_duration)
        signal.signal(signal.SIGUSR1, lambda *_: self._ask("stats"))
        signal.signal(signal.SIGUSR2, lambda *_: self._ask("trace"))
        threading.Thread(target=self._loop, name="chipbench-monitor", daemon=True).start()

    def _ask(self, what: str) -> None:
        # Signal handler: no I/O here, the thread does the work.
        with self._lock:
            self._pending.append(what)
        self._wake.set()

    def _loop(self) -> None:
        while True:
            self._wake.wait()
            self._wake.clear()
            with self._lock:
                todo, self._pending = self._pending, []
            for what in todo:
                try:
                    self._stats() if what == "stats" else self._trace()
                except Exception as e:  # noqa: BLE001 - a failed probe must not stop the worker
                    print(f"chipbench monitor: {what} failed: {type(e).__name__}: {e}", flush=True)

    def _write(self, name: str, doc: dict) -> None:
        path = os.path.join(self.out_dir, name)
        with open(path + ".tmp", "w") as f:
            json.dump(doc, f)
        os.replace(path + ".tmp", path)

    def _stats(self) -> None:
        import jax

        devs = jax.local_devices()
        mem = []
        for d in devs:
            try:
                mem.append(dict(d.memory_stats() or {}))
            except Exception:  # noqa: BLE001 - the CPU backend has none
                mem.append({})
        k, self._snapshots = self._snapshots, self._snapshots + 1
        self._write(f"stats_{self.rank}_{k}.json", {
            "t_unix": time.time(), "platform": devs[0].platform,
            "kind": devs[0].device_kind, "count": len(devs),
            "memory": mem, **self.counts,
        })

    def _trace(self) -> None:
        import jax

        trace_dir = os.path.join(self.out_dir, f"trace_{self.rank}")
        if not self._tracing:
            jax.profiler.start_trace(trace_dir)
            self._tracing = True
            self._write(f"trace_{self.rank}.started", {"t_unix": time.time()})
        else:
            jax.profiler.stop_trace()
            self._tracing = False
            self._write(f"trace_{self.rank}.done", {"t_unix": time.time()})


def main(argv: list[str]) -> int:
    config_path, out_dir, rank, flags = argv[0], argv[1], argv[2], argv[3:]
    with open(config_path) as f:
        doc = json.load(f)
    os.makedirs(out_dir, exist_ok=True)

    from chipbench import lookup, model_maps
    from dynamo_tpu.engine.config import ModelConfig

    try:
        model = model_maps.model_config(doc)
    except lookup.Missing as e:
        print(f"chipbench launcher: {e}", file=sys.stderr, flush=True)
        return 2
    original = ModelConfig.preset

    def preset(name: str) -> ModelConfig:
        return model if name == model.name else original(name)

    ModelConfig.preset = staticmethod(preset)
    Monitor(out_dir, rank).install()

    from dynamo_tpu.worker.__main__ import main as worker_main

    return worker_main(["--preset", model.name, *flags])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
