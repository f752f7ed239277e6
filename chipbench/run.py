#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One new process that starts store, worker(s), frontend and metrics exporter
as children (this parent never imports JAX), warms the cell's shapes up,
measures for ``--seconds``, stops every child, holds a sample of what the
window served against the configuration's plain reference in one more child
on the freed chip (``parity.py``), prints one JSON object as the last line of
its output and exits 0.

What may end a run with another code: no TPU where the cell needs one (the
worker's start line is the probe), a checkout without the program, or a bug
in the harness. A request that fails, times out or is refused, a scrape that
fails, a compile inside the window, a child that dies or exits badly, served
tokens that the reference would not have put first by more than the
configuration's limits: each is a count or ``correct: false`` in the result
line, never an exception.

``--rehearse`` (the driver never passes it) runs the same code path on the
CPU at a toy size: for debugging control flow here, not for numbers. Its
result says ``platform: cpu`` and ``correct: false``.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse
import asyncio
import hashlib
import importlib
import json
import math
import os
import random
import re
import shutil
import signal
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import arith, client, generators, host_phases, lookup, model_maps, parity  # noqa: E402
from chipbench.layer_metrics import gauge_samples, gauge_series  # noqa: E402
from chipbench.procs import Stack, free_port, log  # noqa: E402

HERE = os.path.join(ROOT, "chipbench")
OUT_ROOT = os.path.join(ROOT, "chipbench_out")

# Errors the engine contains so that a server keeps answering
# (chip_smoke.py:CONTAINED_ERRORS); in a benchmark each makes the run incorrect.
CONTAINED_ERRORS = ("engine loop crashed", "prefill dispatch failed",
                    "first-token sampling failed")
START_LINE = re.compile(
    r"engine start: platform=(?P<platform>\S+) device_kind='(?P<kind>[^']*)' "
    r"devices=(?P<devices>\d+) of (?P<visible>\d+) .*?dtype=(?P<dtype>\S+) "
    r"quant=(?P<quant>\S+) .*?decode=(?P<decode>\S+)(?P<rest>.*)"
)
EXIT_NO_PROGRAM, EXIT_NO_CHIP, EXIT_NO_STACK = 2, 3, 4
WORKER_START_S = 900.0   # a cold start makes 7.6 GB of weights and may compile
WARM_REQUEST_S = 900.0   # any warm-up request may wait for a cold compile
TRACE_S = 3.0            # length of the profiler's window in a traced run
PARITY_CHILD_S = 120.0   # the reference over a run's sample: half a minute warm, a minute more while it compiles


class NoResult(Exception):
    """The run cannot give numbers; exit with ``code`` and print no result."""

    def __init__(self, code: int, msg: str):
        super().__init__(msg)
        self.code = code


# -- the cell's files ----------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, rehearse: bool) -> dict:
    """The cell's entry, configuration, mix and metrics. ``rehearse`` puts the
    toy that the configuration names for the CPU in the configuration's place."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise NoResult(EXIT_NO_PROGRAM, f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    try:
        cfg_file = os.path.join(ROOT, cfg_entry["file"])
        config = load_json(cfg_file)
        if rehearse:  # the toy the configuration names, in the cell's own layout of replicas
            if not config.get("rehearse"):
                raise lookup.Missing(f"configuration {config['name']!r} names no \"rehearse\" file; "
                                     f"there are {lookup.names('configs', '.json')}")
            cfg_file = lookup.find("configs", config["rehearse"], ".json")
            config = {**load_json(cfg_file), "replicas": config.get("replicas", 1)}
        model_maps.fields(config)  # a file without a map, or with one that is not there, ends here
        traffic = load_json(lookup.find("traffic", cell["traffic"], ".json"))
        # What correct compares: the configuration says how (rows, and the toy its own limits);
        # the limits are the cell's, in a file of its own that a PR adds with the cell.
        limits = dict(config.get("parity") or {})
        if not rehearse and workload in lookup.names("limits", ".json"):
            limits.update(load_json(lookup.find("limits", workload, ".json")))
    except lookup.Missing as e:
        raise NoResult(EXIT_NO_PROGRAM, str(e)) from None

    def mine(metrics: list[dict]) -> list[dict]:
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return {"cell": cell, "config": config, "config_file": cfg_file, "traffic": traffic, "parity": limits,
            "end_to_end": mine(bench["end_to_end"]), "per_layer": mine(bench["per_layer"])}


def write_tokenizer(path: str, vocab_size: int) -> None:
    """A word-level tokenizer in which token ``i`` is the word ``T<hex i>``:
    every token the model emits shows in the stream's text (the byte tokenizer
    drops ids over 255, so a random-weight stream would carry no chunks), and
    the text says how many tokens a chunk holds and which."""
    os.makedirs(path, exist_ok=True)
    doc = {
        "version": "1.0", "truncation": None, "padding": None, "added_tokens": [],
        "normalizer": None, "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None, "decoder": None,
        "model": {"type": "WordLevel", "vocab": {f"T{i:x}": i for i in range(vocab_size)},
                  "unk_token": "T0"},
    }
    with open(os.path.join(path, "tokenizer.json"), "w") as f:
        json.dump(doc, f)


def engine_args(config: dict):
    """The program's own ``EngineArgs`` for this configuration's flags: its
    bucket tables and its prefill planner, without JAX."""
    from dynamo_tpu.worker.__main__ import _engine_args, parse_args

    args = parse_args(worker_flags(config, "tcp://127.0.0.1:1", "/nonexistent"))
    try:
        return _engine_args(args, model_maps.model_config(config))
    except lookup.Missing as e:
        raise NoResult(EXIT_NO_PROGRAM, str(e)) from None


def prefill_shapes(eargs, cached: int, suffix: int) -> frozenset:
    """The prefill programs one prompt dispatches, as the engine plans them
    (``engine.py:_dispatch_prefills``): ``cached`` tokens hit the prefix
    cache, ``suffix`` are computed. A suffix that fits one chunk unsplit goes
    to the packed program; a split or longer one to the chunked program,
    chunk by chunk. Each is (program, T bucket, table bucket)."""
    bs = eargs.block_size
    width = eargs.bucket_table(-(-(cached + suffix) // bs))
    if suffix > eargs.max_prefill_tokens:
        chunks = [eargs.max_prefill_tokens] * (suffix // eargs.max_prefill_tokens)
        chunks += [suffix % eargs.max_prefill_tokens] if suffix % eargs.max_prefill_tokens else []
    else:
        chunks = eargs.plan_prefill_chunks(suffix)
        if len(chunks) == 1:
            return frozenset([("packed", eargs.bucket_prefill(suffix), width)])
    return frozenset(("chunked", eargs.bucket_prefill(c), width) for c in chunks)


def warm_up_prompts(eargs, prompt_max: int, shares_prefix: bool, cached: int) -> list[tuple[int, int]]:
    """The fewest (cached, suffix) prompts that between them dispatch every
    prefill program a prompt of up to ``prompt_max`` tokens can: fresh ones,
    and behind a cached prefix where the cell shares prefixes. Greedy cover."""
    bs, room = eargs.block_size, eargs.max_model_len - eargs.decode_steps - 8
    top = min(prompt_max, room)
    cands = [(0, n) for n in range(bs, top + 1, bs)]
    if shares_prefix:
        cands += [(cached, n) for n in range(bs, min(top, room - cached) + 1, bs)]
    need = set().union(*(prefill_shapes(eargs, c, n) for c, n in cands)) if cands else set()
    picked = []
    while need:
        best = max(cands, key=lambda cn: (len(prefill_shapes(eargs, *cn) & need), -cn[1]))
        got = prefill_shapes(eargs, *best) & need
        if not got:
            break
        picked.append(best)
        need -= got
    return sorted(picked, key=lambda cn: (cn[0], cn[1]))


def worker_flags(config: dict, store_url: str, tok_dir: str) -> list[str]:
    """The worker's flags from the configuration's file. The weights' seed is
    fixed there and is not ``--seed``: the program bakes the seed into its
    weight-init program, so every new seed would compile that anew (27 s on
    the chip, PERF.md PR 23) and set-up would never be warm. The same
    ``--seed`` still gives the same weights and the same inputs."""
    s = config["served"]
    return ["--store-url", store_url, "--quant", s["quant"],
            "--num-kv-blocks", str(s["num_kv_blocks"]), "--block-size", str(s["block_size"]),
            "--max-model-len", str(s["max_model_len"]), "--max-num-seqs", str(s["max_num_seqs"]),
            "--tokenizer", f"hf:{tok_dir}", "--seed", str(s.get("weights_seed", 0)),
            *s.get("extra_flags", [])]


def held_bytes(mem: dict) -> int:
    """A chip's peak from ``memory_stats()``: the arrays' peak plus what the
    runtime reserved for the compiled programs' temporaries, which it counts
    apart (16.9 GB limit - 12.5 in use - 2.14 reserved = the 2.24 GB largest
    free block, my chip run, PR 23)."""
    return int(mem.get("peak_bytes_in_use", 0)) + int(mem.get("peak_bytes_reserved", 0))


def chip_env(rank: int) -> dict[str, str]:
    """One chip of the host as a TPU world of the rank's own
    (``worker/__main__.py:dp_rank_chip_env``; PERF.md finding 6)."""
    return {"TPU_VISIBLE_CHIPS": str(rank), "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


# -- the run -------------------------------------------------------------------


class Run:
    def __init__(self, opts: argparse.Namespace, spec: dict):
        self.opts, self.spec = opts, spec
        self.config, self.traffic = spec["config"], dict(spec["traffic"])
        for item in opts.param:  # of the mix, or "served.<key>" of the configuration's served block
            key, _, value = item.partition("=")
            if key.startswith("served."):
                self.config = {**self.config, "served": {**self.config["served"], key[7:]: json.loads(value)}}
            else:
                self.traffic[key] = json.loads(value)
        self.name = self.config["name"]
        self.replicas = int(self.config.get("replicas", 1))
        self.out_dir = os.path.join(OUT_ROOT, opts.workload, "trace" if opts.trace else "plain")
        self.stack = Stack(self.out_dir, os.path.join(OUT_ROOT, "stack.pids"))
        self.notes: list[str] = []       # why the run is not correct
        self.counts = {"scrape_failures": 0, "warmup_failed": 0, "prefill_failed": 0}
        self.records: list[dict] = []
        self.gauges: list[dict] = []     # polled exporter samples
        self.prom: dict[str, dict] = {}  # "<who>.<when>" -> parsed /metrics
        self.stats: dict[str, dict] = {}
        self.workers: list = []
        self.start_lines: list[dict] = []
        self.trace_marks: dict = {}
        self.decode_buckets: list[int] = []
        self.phases: dict[str, float] = {}   # set-up's parts, seconds; parity_s lies after the window
        self.parity: dict | None = None      # what compare() read, beside its limits

    def incorrect(self, why: str) -> None:
        self.notes.append(why)
        log(f"not correct: {why}")

    # -- stack ---------------------------------------------------------------

    def start_stack(self) -> None:
        n = self.stack.reap_leftovers()
        if n:
            log(f"reaped {n} process groups an earlier run left behind")
        for old in os.listdir(self.out_dir) if os.path.isdir(self.out_dir) else []:
            p = os.path.join(self.out_dir, old)
            if os.path.isfile(p):
                os.remove(p)
        for rank in range(self.replicas):
            shutil.rmtree(os.path.join(self.out_dir, f"trace_{rank}"), ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        tok_dir = os.path.join(OUT_ROOT, "tokenizers", str(self.config["vocab_size"]))
        if not os.path.exists(os.path.join(tok_dir, "tokenizer.json")):
            write_tokenizer(tok_dir, self.config["vocab_size"])
        base_env = {"JAX_PLATFORMS": "cpu"} if self.opts.rehearse else {}
        self.ports = {k: free_port() for k in ("store", "http", "exporter")}
        self.sys_ports = [free_port() for _ in range(self.replicas)]
        store_url = f"tcp://127.0.0.1:{self.ports['store']}"
        store = self.stack.start("store", ["-m", "dynamo_tpu.runtime.store_server", "--host",
                                           "127.0.0.1", "--port", str(self.ports["store"])],
                                 base_env)
        if store.wait_for(r"store server: tcp://", 60) is None:
            raise NoResult(EXIT_NO_STACK, "the store server did not start:\n" + store.log_text()[-2000:])
        flags = worker_flags(self.config, store_url, tok_dir)
        for rank in range(self.replicas):
            self.workers.append(self.start_worker(rank, flags, base_env))
        self.stack.start("frontend", ["-m", "dynamo_tpu.frontend", "--store-url", store_url,
                                      "--router-mode", "kv", "--host", "127.0.0.1",
                                      "--port", str(self.ports["http"])], base_env)
        self.stack.start("exporter", ["-m", "dynamo_tpu.metrics_exporter", "--store-url",
                                      store_url, "--host", "127.0.0.1", "--port",
                                      str(self.ports["exporter"]), "--interval", "0.5"], base_env)
        for rank in range(self.replicas):
            self.await_worker(rank, flags, base_env)
        self.phases["worker_ready_s"] = time.monotonic() - T_PROCESS_START

    def start_worker(self, rank: int, flags: list[str], base_env: dict):
        env = {**base_env, "DYNTPU_SYSTEM_ENABLED": "1",
               "DYNTPU_SYSTEM_PORT": str(self.sys_ports[rank])}
        if self.replicas > 1 and not self.opts.rehearse:
            env.update(chip_env(rank))
        return self.stack.start(
            f"worker{rank}", [os.path.join(HERE, "launch_worker.py"), self.spec["config_file"],
                              self.out_dir, str(rank), *flags], env)

    def await_worker(self, rank: int, flags: list[str], base_env: dict) -> None:
        """Wait until the worker serves. A worker that dies before that is
        started once more (the chip may still have been held by the run
        before); the second death ends the run without a result."""
        ready = rf"dynamo_tpu worker: serving {re.escape(self.name)}"
        for attempt in (1, 2):
            w = self.workers[rank]
            if w.wait_for(ready, WORKER_START_S) is not None:
                break
            tail = w.log_text()[-3000:]
            if attempt == 2 or w.proc.poll() is None:
                code = EXIT_NO_STACK if START_LINE.search(w.log_text()) else EXIT_NO_CHIP
                raise NoResult(code, f"worker {rank} did not come up (rc={w.proc.poll()}):\n{tail}")
            log(f"worker {rank} died while starting (rc={w.proc.poll()}); once more in 5 s:\n{tail[-800:]}")
            time.sleep(5.0)
            self.workers[rank] = self.start_worker(rank, flags, base_env)
        m = START_LINE.search(self.workers[rank].log_text())
        if m is None:
            raise NoResult(EXIT_NO_CHIP, f"worker {rank} logged no start line")
        line = m.groupdict()
        self.start_lines.append(line)
        log(f"worker {rank}: {m.group(0)[len('engine start: '):][:300]}")
        want = "cpu" if self.opts.rehearse else "tpu"
        if line["platform"] != want:
            raise NoResult(EXIT_NO_CHIP, f"worker {rank} runs on {line['platform']!r}: no {want} here")

    def check_start_lines(self) -> None:
        exp = self.config["expect_start_line"]
        for rank, line in enumerate(self.start_lines):
            if exp["kind"] not in line["kind"].lower():
                self.incorrect(f"worker {rank}: device kind {line['kind']!r}, expected {exp['kind']!r}")
            if int(line["devices"]) != exp["devices"]:
                self.incorrect(f"worker {rank}: {line['devices']} devices, expected {exp['devices']}")
            if line["quant"] != exp["quant"]:
                self.incorrect(f"worker {rank}: quant {line['quant']}, expected {exp['quant']}")
            if line["decode"] != exp["decode"]:
                self.incorrect(f"worker {rank}: decode attention {line['decode']}{line['rest'][:120]}, "
                               f"expected {exp['decode']}")

    # -- signals to the launcher --------------------------------------------

    def snapshot(self, k: int, wait_s: float = 10.0) -> None:
        """Ask every worker for ``stats_<rank>_<k>.json``; missing is a note."""
        for w in self.workers:
            w.signal(signal.SIGUSR1)
        deadline = time.monotonic() + wait_s
        for rank in range(self.replicas):
            path = os.path.join(self.out_dir, f"stats_{rank}_{k}.json")
            while not os.path.exists(path) and time.monotonic() < deadline:
                time.sleep(0.05)
            try:
                with open(path) as f:
                    self.stats[f"{rank}.{k}"] = json.load(f)
            except (OSError, ValueError):
                log(f"no stats snapshot {k} from worker {rank}")

    # -- HTTP ---------------------------------------------------------------

    def url(self, what: str, path: str, rank: int = 0) -> str:
        port = self.sys_ports[rank] if what == "worker" else self.ports[what]
        return f"http://127.0.0.1:{port}{path}"

    async def scrape(self, session, when: str) -> None:
        pages = [("frontend", self.url("http", "/metrics"))]
        pages += [(f"worker{r}", self.url("worker", "/metrics", r)) for r in range(self.replicas)]
        for who, url in pages:
            text = await client.get_text(session, url)
            if text is None:
                self.counts["scrape_failures"] += 1
                log(f"scrape of {who} /metrics failed ({when})")
            else:
                self.prom[f"{who}.{when}"] = arith.parse_prom(text)

    async def poll_gauges(self, session, t0: float) -> None:
        url = self.url("exporter", "/metrics")
        while True:
            text = await client.get_text(session, url, timeout_s=2.0)
            if text is None:
                self.counts["scrape_failures"] += 1
            else:
                self.gauges.append({"t": time.monotonic() - t0, **arith.parse_prom(text)})
            await asyncio.sleep(0.5)

    async def wait_listed(self, session) -> None:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            text = await client.get_text(session, self.url("http", "/v1/models"))
            if text and f'"{self.name}"' in text:
                live = await client.get_text(session, self.url("exporter", "/metrics"))
                n = arith.prom_sum(arith.parse_prom(live or ""), "dynamo_tpu_fleet_workers_live")
                if self.replicas == 1 or (n or 0) >= self.replicas:
                    return
            await asyncio.sleep(0.25)
        raise NoResult(EXIT_NO_STACK, "the frontend never listed the model")

    # -- warm-up --------------------------------------------------------------

    async def warm_up(self, session, plan: dict) -> None:
        """One request per prefill program the cell's prompts can dispatch
        (``warm_up_prompts``), one at a time on an idle server; then each
        decode batch bucket at full batch, at both table widths."""
        eargs = engine_args(self.config)
        self.decode_buckets = list(eargs.decode_buckets)
        url = self.url("http", "/v1/completions")
        rng = random.Random(7)
        vocab = self.config["vocab_size"]
        gen = eargs.decode_steps + 2
        narrow = eargs.table_buckets[0] * eargs.block_size   # tokens the narrow table holds
        limit = min(plan["prompt_max"], eargs.max_model_len - gen - 4)

        def ids(n: int) -> list[int]:
            return [rng.randrange(min(1000, vocab // 2), vocab - 1) for _ in range(n)]

        async def one(prompt: list[int], what: str, max_tokens: int = gen) -> None:
            rec = await client.one_shot(session, url, self.name, prompt, max_tokens, WARM_REQUEST_S)
            if rec["status"] != "ok":
                self.counts["warmup_failed"] += 1
                log(f"warm-up request failed ({what}): {rec['error']}")

        # With replicas behind the KV router each shape goes out as a wave of
        # one request a replica: the router spreads a wave by load, and sends
        # a request behind a cached prefix to the replica that holds it.
        cached = 2 * narrow
        prefixes = [ids(cached) for _ in range(self.replicas)]
        shapes = warm_up_prompts(eargs, limit, plan["shares_prefix"], cached)
        t_w = time.monotonic()
        if any(c for c, _ in shapes):
            await asyncio.gather(*(one(p + ids(16), "the cached prefix") for p in prefixes))
        for c, n in shapes:
            what = f"prefill of {n} tokens {'behind a cached prefix' if c else 'fresh'}"
            await asyncio.gather(*(one((p if c else []) + ids(n), what) for p in prefixes))
        for nb in eargs.decode_buckets:
            for plen in (narrow // 4, narrow + 16):
                wave = [one(ids(plen), f"decode B={nb} prompt={plen}", 3 * eargs.decode_steps + 2)
                        for _ in range(nb * self.replicas)]
                await asyncio.gather(*wave)
        self.phases["warm_up_s"] = time.monotonic() - t_w
        log(f"warm-up: {len(shapes)} prefill prompts x {self.replicas} replicas, decode buckets "
            f"{self.decode_buckets} in {self.phases['warm_up_s']:.1f} s, {self.counts['warmup_failed']} failed")

    async def prefill_sessions(self, session, plan: dict) -> None:
        """Closed loop: put every client's starting history into the cache."""
        if plan["mode"] != "closed":
            return
        url = self.url("http", "/v1/completions")
        sem = asyncio.Semaphore(4 * self.replicas)
        t_p = time.monotonic()

        async def one(prompt: list[int]) -> None:
            async with sem:
                rec = await client.one_shot(session, url, self.name, prompt, 1, WARM_REQUEST_S)
            if rec["status"] != "ok":
                self.counts["prefill_failed"] += 1
                log(f"pre-fill request failed: {rec['error']}")

        await asyncio.gather(*(one(c["prefill"]) for c in plan["clients"]))
        self.phases["pre_fill_s"] = time.monotonic() - t_p
        log(f"pre-fill: {len(plan['clients'])} histories, "
            f"{sum(len(c['prefill']) for c in plan['clients'])} tokens in "
            f"{self.phases['pre_fill_s']:.1f} s, {self.counts['prefill_failed']} failed")

    # -- the window -----------------------------------------------------------

    async def tracer(self, t0: float) -> None:
        """Bracket TRACE_S seconds in the middle of the window."""
        seconds = self.opts.seconds
        start = max(0.5, min(0.4 * seconds, seconds - TRACE_S - 1.0))
        await asyncio.sleep(max(0.0, t0 + start - time.monotonic()))
        for w in self.workers:
            w.signal(signal.SIGUSR2)
        self.trace_marks["asked_start"] = time.time()
        await asyncio.sleep(min(TRACE_S, max(0.5, seconds - start - 0.5)))
        for w in self.workers:
            w.signal(signal.SIGUSR2)
        self.trace_marks["asked_stop"] = time.time()

    async def measure(self, plan: dict) -> float:
        import aiohttp

        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:
            await self.wait_listed(session)
            await self.warm_up(session, plan)
            await self.prefill_sessions(session, plan)
            return await self.window(session, plan)

    async def window(self, session, plan: dict, k: int = 0) -> float:
        """The measured window: snapshots ``k`` and ``k + 1`` around it.
        Returns the seconds from the process's start to its opening."""
        self.snapshot(k)
        await self.scrape(session, "before")
        url = self.url("http", "/v1/completions")
        seconds = self.opts.seconds
        t0 = time.monotonic() + 0.05
        self.t0, self.t0_unix = t0, time.time() + 0.05
        side = [asyncio.ensure_future(self.poll_gauges(session, t0))]
        if self.opts.trace:
            side.append(asyncio.ensure_future(self.tracer(t0)))
        log(f"window opens: set-up took {t0 - T_PROCESS_START:.1f} s")
        if plan["mode"] == "open":
            await client.run_open(session, url, self.name, plan["requests"], t0, seconds,
                                  self.records)
        else:
            await client.run_closed(session, url, self.name, plan, t0, seconds, self.records)
        self.t_end = t0 + seconds
        for task in side[:1]:
            task.cancel()
        await asyncio.gather(*side, return_exceptions=True)
        log(f"window closed after {time.monotonic() - t0:.2f} s")
        await self.scrape(session, "after")
        if self.opts.trace:
            self.await_traces()  # the launcher's thread is busy until the trace is written
        self.snapshot(k + 1)
        return t0 - T_PROCESS_START

    def await_traces(self) -> None:
        deadline = time.monotonic() + 90
        for rank in range(self.replicas):
            done = os.path.join(self.out_dir, f"trace_{rank}.done")
            while not os.path.exists(done) and time.monotonic() < deadline:
                time.sleep(0.1)
            if not os.path.exists(done):
                log(f"worker {rank} wrote no trace")

    # -- after the window -------------------------------------------------------

    def reduce_trace(self) -> dict | None:
        """Rank 0's trace through ``trace_reduce.py`` in a child that is held
        to the CPU, after every worker has gone. None when there is none."""
        trace_dir = os.path.join(self.out_dir, "trace_0")
        out = os.path.join(self.out_dir, "trace_reduced.json")
        if not os.path.isdir(trace_dir):
            return None
        try:
            subprocess.run([sys.executable, os.path.join(HERE, "trace_reduce.py"), trace_dir, out],
                           env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=240, check=True,
                           stdout=subprocess.DEVNULL, stderr=open(os.path.join(self.out_dir, "trace_reduce.log"), "w"))
            with open(out) as f:
                trace = json.load(f)
            # The trace's clock starts about when the launcher was asked.
            trace["t_start_unix"] = self.trace_marks.get("asked_start")
            return trace
        except Exception as e:  # noqa: BLE001 - no trace, no trace metrics
            log(f"trace reduction failed: {type(e).__name__}: {e}")
            return None

    def compare(self) -> None:
        """Hold a sample of what the window finished against the
        configuration's reference (``parity.py``), in a child on the freed
        chip. Over a limit, no sample, or a child that fails: not correct."""
        limits = self.spec["parity"]
        if not limits.get("rows") or not self.config.get("reference"):
            self.incorrect(f"parity: configuration {self.name!r} names no reference or no parity rows; "
                           f"the references there are: {lookup.names('references', '.py')}")
            return
        t_p = time.monotonic()
        seqs = parity.sequences(self.records)
        sample, _ = parity.pick_sample(seqs, self.opts.seed, int(limits["rows"]), int(self.config["served"]["max_model_len"]))
        doc, why = parity_child(self.spec["config_file"], {"run": sample}, self.out_dir, self.opts.rehearse,
                                self.opts.parity_weights_seed) if sample else (None, None)
        read = (doc or {}).get("groups", {}).get("run", {"tokens": 0})
        platform = (doc or {}).get("platform")
        if doc and platform != ("cpu" if self.opts.rehearse else "tpu"):
            why = f"the reference ran on {platform!r}, not on the chip the worker left"
        whys = [why] if why else parity.verdict(read, limits)
        self.phases["parity_s"] = time.monotonic() - t_p
        self.parity = {**{k: v for k, v in read.items() if k != "per_row"}, "limits": {k: limits.get(k) for k in parity.JUDGED},
                       "finished": len(seqs), "ok": not whys, "platform": platform,
                       "child_s": (doc or {}).get("seconds"), "weights_s": (doc or {}).get("weights_s")}
        for w in whys:
            self.incorrect(f"parity: {w}")
        log("parity: " + json.dumps(self.parity))

    def judge(self, digests: tuple[str, str]) -> None:
        if self.opts.rehearse:
            self.incorrect("--rehearse: a CPU run at a toy size is never correct")
        if digests[0] != digests[1]:
            self.incorrect("the traffic generator gave two plans for one seed")
        self.check_start_lines()
        for rank, w in enumerate(self.workers):
            text = w.log_text()
            for bad in CONTAINED_ERRORS:
                if bad in text:
                    self.incorrect(f"worker {rank} log says {bad!r}")
            if w.proc.poll() is not None and not self.stack._stopped:
                self.incorrect(f"worker {rank} died (rc={w.proc.poll()})")
        if self.counts["warmup_failed"] or self.counts["prefill_failed"]:
            self.incorrect(f"{self.counts['warmup_failed']} warm-up and "
                           f"{self.counts['prefill_failed']} pre-fill requests failed")

    def device(self, trace: dict | None) -> dict:
        line = self.start_lines[0] if self.start_lines else {}
        stat = self.stats.get("0.1") or self.stats.get("0.0") or {}
        peak = max((held_bytes(mem) for s in self.stats.values() for mem in s.get("memory", [])),
                   default=0)
        dev = {"platform": stat.get("platform") or line.get("platform", "unknown"),
               "kind": stat.get("kind") or line.get("kind", "unknown"),
               "count": self.replicas * int(line.get("devices", 1)),
               "memory_peak_bytes": peak}
        if self.opts.trace and trace:
            dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
        return dev


# -- metrics -------------------------------------------------------------------


def parity_child(config_file: str, groups: dict, out_dir: str, rehearse: bool,
                 weights_seed: int | None = None,
                 timeout_s: float = PARITY_CHILD_S) -> tuple[dict | None, str | None]:
    """``parity.py`` over ``groups`` in a child that owns the chip (the CPU
    under ``--rehearse``). (its document, None), or (None, why there is none):
    never an exception. A child that dies at once may have found the chip
    still held by the worker that just stopped: once more after 5 s.
    ``weights_seed`` (a control) gives the reference other weights than the
    configuration's."""
    sample, out = os.path.join(out_dir, "parity_sample.json"), os.path.join(out_dir, "parity.json")
    with open(sample, "w") as f:
        json.dump({"groups": groups}, f)
    env = {**os.environ, **({"JAX_PLATFORMS": "cpu"} if rehearse else {})}
    cmd = [sys.executable, os.path.join(HERE, "parity.py"), config_file, sample, out]
    if weights_seed is not None:
        cmd += ["--weights-seed", str(weights_seed)]
    why = None
    for attempt in (1, 2):
        if os.path.exists(out):
            os.remove(out)
        t = time.monotonic()
        try:
            with open(os.path.join(out_dir, "parity.log"), "a") as err:
                rc = subprocess.run(cmd, env=env, timeout=timeout_s, stdout=err, stderr=err).returncode
        except subprocess.TimeoutExpired:
            return None, f"the reference's child ran over {timeout_s:g} s"
        if rc == 0 and os.path.exists(out):
            return load_json(out), None
        why = f"the reference's child ended with code {rc}; see parity.log"
        if attempt == 2 or time.monotonic() - t > 60:
            break
        log(f"{why}; once more in 5 s")
        time.sleep(5.0)
    return None, why


def end_to_end(run: Run, setup_s: float) -> dict[str, float]:
    """Every end-to-end number the harness knows, by name; a cell's result
    holds the ones BENCHMARK.json lists for it."""
    recs, seconds = run.records, run.opts.seconds
    ttft = arith.ttft_samples(recs, run.t_end)
    tpot = arith.tpot_samples(recs)
    cap_ms = seconds * 1000.0
    return {
        "ttft_p50_ms": arith.finite_or_cap(arith.pctl(ttft, 50) * 1000.0, cap_ms),
        "ttft_p95_ms": arith.finite_or_cap(arith.pctl(ttft, 95) * 1000.0, cap_ms),
        "tpot_p95_ms": arith.finite_or_cap(arith.pctl(tpot, 95) * 1000.0, cap_ms),
        "out_tok_s": arith.tokens_in_window(recs, run.t0, run.t_end) / seconds,
        "setup_s": setup_s,
    }


def reader_ctx(run: Run, trace: dict | None) -> dict:
    """What a per-layer reader gets (``layer_metrics/__init__.py``)."""
    return {"records": run.records, "prom": run.prom, "gauges": run.gauges, "stats": run.stats,
            "trace": trace, "config": run.config, "traffic": run.traffic, "t0": run.t0,
            "t_end": run.t_end, "seconds": run.opts.seconds, "replicas": run.replicas,
            "worker_logs": [w.log_text() for w in run.workers], "here": HERE,
            "trace_marks": run.trace_marks, "t0_unix": run.t0_unix,
            "decode_buckets": run.decode_buckets}


def unjudged(run: Run, e2e: dict[str, float], ctx: dict) -> dict:
    """What every run, plain or traced, says beside its judged metrics, for
    ``prove.py`` and the reader of a log; the driver ignores it. Set-up's
    parts; where the running batch sat among the decode batch buckets
    (``batch_bucket_main_share``'s own function on this run's polls); every
    end-to-end number the harness knows; requests whose first token took over
    1.5 s (a closed loop's turn that found its history evicted recomputes it);
    and the share of prompt blocks a session had sent before, which is what
    the prefix cache could have held had it evicted nothing."""
    active = gauge_samples(ctx, "dynamo_tpu_fleet_worker_active_slots")
    kv_total = max(gauge_series(ctx, "dynamo_tpu_fleet_worker_kv_total_blocks"), default=0)
    kv_used = [100.0 * v / kv_total
               for v in gauge_series(ctx, "dynamo_tpu_fleet_worker_kv_active_blocks") if kv_total]
    waiting = gauge_series(ctx, "dynamo_tpu_fleet_worker_waiting")
    shares = arith.bucket_shares(active, run.decode_buckets) or {}
    ttft = arith.ttft_samples(run.records, run.t_end)
    bs = int(run.config["served"]["block_size"])
    sent = sum(-(-r["prompt_tokens"] // bs) for r in run.records)
    held = sum(r.get("history_tokens", 0) // bs for r in run.records)
    batch = {"batch_bucket_shares": {f"<={b}": v for b, v in shares.items()},
             "batch_bucket_main_share": arith.main_bucket_share(active, run.decode_buckets),
             "polls": len(active), "active_mean": sum(active) / len(active) if active else None,
             "active_min_max": [min(active), max(active)] if active else None,
             "kv_used_mean_max": [sum(kv_used) / len(kv_used), max(kv_used)] if kv_used else None,
             "waiting_mean": sum(waiting) / len(waiting) if waiting else None,
             "completed": sum(1 for r in run.records if r["status"] == "ok"),
             "ttft_over_1500ms": sum(1 for x in ttft if x > 1.5),
             "resent_block_share": 100.0 * held / sent if sent else None}
    return {"setup_phases": dict(run.phases), "batch": batch,
            "end_to_end_all": {k: v for k, v in e2e.items() if math.isfinite(v)},
            **({"parity": run.parity} if run.parity is not None else {})}


def per_layer(run: Run, names: list[str], ctx: dict) -> dict[str, float]:
    out = {}
    for name in names:
        try:
            reader = importlib.import_module(f"chipbench.layer_metrics.{name.replace('-', '_').replace('.', '_')}")
            value = reader.read(ctx)
        except Exception as e:  # noqa: BLE001 - a reader that fails yields no value
            log(f"per-layer metric {name}: reader failed: {type(e).__name__}: {e}")
            continue
        if value is None or not math.isfinite(value):
            log(f"per-layer metric {name}: nothing to read")
            continue
        out[name] = float(value)
    return out


def breakdown(run: Run, trace: dict) -> dict:
    """The device operations that took most time, and the longest idle gaps,
    each named by the ``sched.*`` phase the scheduler thread spent most of it
    in (``host_phases.py`` on rank 0's trace); ``unattributed`` where the
    trace could not be read for phases."""
    report = host_phases.attribute_dir(os.path.join(run.out_dir, "trace_0"))
    gaps = host_phases.label_gaps(report) if report else [
        ["unattributed", dur_s] for _, dur_s in trace.get("idle_gaps", [])[:10]]
    return {"device_ops": [[n, s] for n, s in trace.get("top_ops", [])[:10]], "idle_gaps": gaps}


def make_result(correct: bool, attempted: int, failed: int, values: dict, units: dict,
                device: dict, breakdown_: dict | None, unjudged_: dict | None = None) -> dict:
    """The result line: the keys the driver's contract names, and under
    ``unjudged`` what the driver ignores and ``prove.py`` reads."""
    result = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": {n: {"value": v, "unit": units[n]} for n, v in values.items()},
        "device": device,
    }
    if breakdown_ is not None:
        result["breakdown"] = breakdown_
    if unjudged_ is not None:
        result["unjudged"] = unjudged_
    return result


# -- main ------------------------------------------------------------------------


def parse(argv: list[str]) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true", help="CPU, toy size, never correct")
    p.add_argument("--parity", type=int, choices=(0, 1), default=1,
                   help="0 (sweep.py only): do not hold the served tokens against the reference")
    p.add_argument("--parity-weights-seed", type=int, default=None,
                   help="a control: the reference makes its weights from another seed than the worker")
    p.add_argument("--param", action="append", default=[], metavar="KEY=JSON",
                   help="sweep.py, prove.py, parity_seeds.py: override one parameter of the mix (rate_rps=6) or, "
                        "as served.KEY, of the configuration's served block (a control: "
                        "'served.extra_flags=[\"--kv-quant\",\"int8\"]')")
    return p.parse_args(argv)


def _leave(signum, _frame) -> None:
    # SIGTERM, SIGINT or SIGHUP to the harness: leave through ``finally`` and
    # ``atexit`` so that no child outlives it.
    raise SystemExit(128 + signum)


def main(argv: list[str]) -> int:
    opts = parse(argv)
    for sig in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
        signal.signal(sig, _leave)
    if not os.path.isdir(os.path.join(ROOT, "dynamo_tpu")):
        print("chipbench: no dynamo_tpu/ beside chipbench/: nothing to measure", file=sys.stderr)
        return EXIT_NO_PROGRAM
    if not opts.rehearse and os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        # Held to the CPU: the worker would only find that out after making
        # 7 GB of weights there. Otherwise its start line is the probe.
        print("chipbench: JAX_PLATFORMS=cpu: no TPU, no numbers (use --rehearse to debug)",
              file=sys.stderr)
        return EXIT_NO_CHIP
    try:
        spec = load_cell(opts.workload, opts.rehearse)
    except NoResult as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return e.code
    if opts.seconds is None:
        opts.seconds = float(load_json(os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"])
    run = Run(opts, spec)
    gen = generators.load(run.traffic["kind"])

    def make_plan() -> tuple[dict, str]:
        plan = gen.generate(run.traffic, opts.seed, opts.seconds, run.config["vocab_size"])
        return plan, hashlib.sha256(json.dumps(plan, sort_keys=True).encode()).hexdigest()

    plan, digest = make_plan()
    digests = (digest, make_plan()[1])
    log(f"cell {opts.workload} seed {opts.seed} seconds {opts.seconds:g} trace {opts.trace}: "
        f"traffic digest {digests[0][:16]} (again: {digests[1][:16]})")
    setup_s, trace, code = float("nan"), None, 0
    try:
        run.start_stack()
        setup_s = asyncio.run(run.measure(plan))
    except NoResult as e:
        print(f"chipbench: {e}", file=sys.stderr)
        code = e.code
    finally:
        if code == 0:
            run.judge(digests)  # before the stop: a worker that died on its own shows
        run.stack.stop()
    if code:
        return code
    if opts.parity:
        run.compare()  # the chip is free now, and the worker's peak memory has been read
    if opts.trace:
        trace = run.reduce_trace()
    e2e = end_to_end(run, setup_s)
    status = {s: sum(1 for r in run.records if r["status"] == s) for s in ("ok", "failed", "cut")}
    for r in [r for r in run.records if r["status"] == "failed"][:5]:
        log(f"failed request: {r['error']}")
    if status["failed"]:
        run.incorrect(f"{status['failed']} of {len(run.records)} requests failed")
    if run.stack.unclean:
        log(f"children that did not stop cleanly: {run.stack.unclean}")
    log(f"requests: {status}; all end-to-end numbers: "
        + json.dumps({k: round(v, 4) for k, v in e2e.items()}))
    log("slo: " + json.dumps(arith.slo_attribution(
        arith.ttft_samples(run.records, run.t_end), arith.tpot_samples(run.records), 1.0, 0.05)))
    ctx = reader_ctx(run, trace)
    extra = unjudged(run, e2e, ctx)
    log("batch buckets: " + json.dumps(extra["batch"]))
    log("set-up phases: " + json.dumps({k: round(v, 2) for k, v in run.phases.items()}))
    if opts.trace:
        names = [m["name"] for m in spec["per_layer"]]
        values = per_layer(run, names, ctx)
        units = {m["name"]: m["unit"] for m in spec["per_layer"]}
    else:
        names = [m["name"] for m in spec["end_to_end"]]
        values = {n: e2e[n] for n in names if n in e2e and math.isfinite(e2e[n])}
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
    result = make_result(not run.notes, len(run.records), status["failed"], values, units,
                         run.device(trace), breakdown(run, trace) if opts.trace and trace else None,
                         extra)
    log("notes: " + json.dumps(run.notes) + " counts: " + json.dumps(
        {**run.counts, **status, "unclean_children": len(run.stack.unclean)}))
    with open(os.path.join(run.out_dir, "result.json"), "w") as f:
        json.dump(result, f)
    # Each number compared beside its limit, as the last lines of the errors too.
    line = "compared: nothing (--parity 0, or a configuration without a reference)"
    if run.parity is not None:
        line = ("compared: " + ", ".join(f"{k} {run.parity.get(k)} (limit {v})"
                                        for k, v in run.parity["limits"].items())
                + f" over {run.parity.get('tokens', 0)} served tokens of {run.parity.get('sequences', 0)} "
                  f"sequences in {run.parity.get('rows', 0)} rows")
    log(line)
    print(f"chipbench: notes {json.dumps(run.notes)}\nchipbench: {line}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
