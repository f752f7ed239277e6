#!/usr/bin/env python3
"""The quickest proof that the serving path still starts on the chip.

    python3 chip_smoke.py

Drives the system once through the entry points a user calls, at the full
width and depth of one supported model (``qwen2-7b``, int8 weights made on
the device from a seed), and checks what comes out by the repo's own
means. Exit code 0 and a last stdout line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

mean every phase passed. Anything else is a failure: no accelerator (a
CPU run is not a pass), a request that ends in anything but ``length``
with its exact token count, a worker that took an XLA or CPU path where
the kernel was expected, an engine error the server contained, a kernel
that disagrees with its XLA reference, a child that does not stop cleanly.

Phases, one after another, because a chip belongs to one process at a
time and this parent must never import JAX:

1. probe    one child asks JAX what it sees; no TPU, no run.
2. serve    store server + worker + frontend (+ metrics exporter), the
            README quick start as four processes; chats over HTTP.
3. kernel   one child: the compiled Pallas kernels (decode, spec, tree,
            prefill) against their XLA references on the same chip,
            ragged lengths; then each kernel alone at the benchmark
            cells' call shapes: device time a call, the decode kernel's
            share of the chip's HBM peak, the prefill kernel's share of
            its bf16 peak beside the XLA form's time.
4. multichip  only when JAX reports four devices: the serve phase with
            ``--tp 4`` and a KV pool larger than one chip's HBM.

Times printed are set-up times (process start, weight init, compilation)
and wall times of a smoke, not a benchmark. Logs of every child land in
``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import http.client
import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

MODEL = "qwen2-7b"
# 7.62 GB of int8 weights + 3.8 GB of bf16 KV on a 16 GB chip.
ENGINE_FLAGS = [
    "--preset", MODEL, "--quant", "int8", "--num-kv-blocks", "4096",
    "--max-model-len", "4096", "--max-num-seqs", "16",
]
# Prompt lengths in byte tokens: several prefill buckets and both table
# widths; the longest exceeds max_prefill_tokens (2048), so chunked
# prefill runs. (prompt length, max_tokens) per concurrent stream.
STREAMS = [(30, 64), (120, 72), (300, 80), (600, 96), (1000, 100),
           (1500, 112), (2200, 120), (3000, 128)]
# The four-chip phase: 22000 blocks x 917,504 B = 20.2 GB of KV, more
# than one 16 GB chip holds, plus the weights, over four chips.
TP4_FLAGS = [
    "--preset", MODEL, "--quant", "int8", "--num-kv-blocks", "22000",
    "--max-model-len", "4096", "--max-num-seqs", "16", "--tp", "4",
]
TP4_STREAMS = [(60, 64), (700, 80), (2500, 96)]
# Errors the engine contains so that a server keeps answering; in a smoke
# each of them is a failure.
CONTAINED_ERRORS = ("engine loop crashed", "prefill dispatch failed",
                    "first-token sampling failed")
START_LINE = re.compile(
    r"engine start: platform=(?P<platform>\S+) device_kind='(?P<kind>[^']*)' "
    r"devices=(?P<devices>\d+) of (?P<visible>\d+) .*?dtype=(?P<dtype>\S+) .*?"
    r"decode=(?P<decode>\S+)(?P<rest>.*)"
)
# The decode kernel's call shapes in the benchmark's cells (PERF.md section
# 4), for the kernel phase's timings: rows x table width at the
# configuration's geometry, a pool of the cell's size, and groups of
# (live rows, fewest, most tokens of context) as the cells' traffic leaves
# them. Lengths and pages are drawn from a fixed seed.
HBM_PEAK_BYTES_PER_S = 819e9  # TPU v5e (Google Cloud documentation)
KERNEL_CALLS = {
    "qwen2.5-7b sessions": dict(B=64, W=256, bs=16, KVH=4, hd=128, G=7, L=28, N=5120,
                                rows=[(46, 1000, 2600)]),
    "qwen2.5-7b chat": dict(B=32, W=256, bs=16, KVH=4, hd=128, G=7, L=28, N=5120,
                            rows=[(17, 150, 700)]),
    "mistral-7b mixed": dict(B=32, W=256, bs=16, KVH=8, hd=128, G=4, L=32, N=2304,
                             rows=[(16, 150, 700), (2, 2100, 3600)]),
    "mistral-7b chat": dict(B=32, W=256, bs=16, KVH=8, hd=128, G=4, L=32, N=2304,
                            rows=[(16, 150, 700)]),
    "lfm2 sessions-conv": dict(B=128, W=128, bs=32, KVH=8, hd=64, G=4, L=2, N=5632,
                               rows=[(61, 1000, 2600)]),
    # the sparse walk: a row a (session, KV head) over its 64 chosen pages
    "sala sessions-long": dict(B=32, W=64, bs=64, KVH=2, hd=128, G=16, L=8, N=4352,
                               rows=[(32, 4033, 4096)]),
    "longcat latent sessions": dict(B=128, W=128, bs=32, Dk=640, H=64, Dv=512, L=8, N=5632,
                                    rows=[(63, 1000, 2600)]),
    # the dots3 full layers' choice: the indexer's scan over a row's index keys, and the
    # latent kernel over the 2,048 rows it chose (gathered ahead of it, outside these times)
    "dots3 index scan": dict(B=32, W=1024, bs=32, di=128, Hi=64, L=3, N=18432, rows=[(18, 16384, 32000)]),
    "dots3 chosen rows": dict(B=32, W=1024, bs=32, Dk=640, H=128, Dv=512, topk=2048, L=3, N=18432,
                              rows=[(18, 16384, 32000)]),
}

# The prefill kernel's call shapes in the dense cells (one row a call: T new
# positions from ``start``, the row ``length`` tokens long over a 256-page
# table), at both geometries: a sessions turn behind 1.7k cached tokens, a
# fresh chat prompt, and the two chunks of a 4k document.
MXU_PEAK_FLOPS = 197e12  # TPU v5e, bf16 (Google Cloud documentation)
PREFILL_GEOMETRIES = {
    "qwen2.5-7b": dict(KVH=4, G=7, hd=128, bs=16, L=28, N=5120),
    "mistral-7b": dict(KVH=8, G=4, hd=128, bs=16, L=32, N=2304),
}
PREFILL_CALLS = {
    "[1,192,ctx 1.9k]": dict(T=192, start=1712, length=1904),
    "[1,256,ctx 220]": dict(T=256, start=0, length=220),
    "[1,2048,start 0]": dict(T=2048, start=0, length=2048),
    "[1,2048,start 2048]": dict(T=2048, start=2048, length=4096),
}

# The kernel phase's bound on |kernel - reference|, fixed from bf16 before
# any run: four units in the last place (eps = 2**-8) at the scale of the
# outputs. A wrong mask or a wrong page is off by tenths.
BF16_TOLERANCE = 4 * 2.0 ** -8
# Any one request may wait for compilation of the shapes it is first to need.
REQUEST_TIMEOUT = 900.0


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """One child process with its output in a log file."""

    def __init__(self, name: str, argv: list[str], phase: str, env: dict | None = None):
        check("jax" not in sys.modules, "the parent imported JAX; it would hold the chip")
        os.makedirs(os.path.join(LOG_DIR, phase), exist_ok=True)
        self.name = name
        self.log_path = os.path.join(LOG_DIR, phase, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, *argv], cwd=ROOT, stdout=self._log,
            stderr=subprocess.STDOUT, env={**os.environ, "PYTHONUNBUFFERED": "1", **(env or {})},
            start_new_session=True,
        )

    def log(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait_for(self, pattern: str, timeout: float) -> re.Match:
        rx = re.compile(pattern)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            m = rx.search(self.log())
            if m:
                return m
            if self.proc.poll() is not None:
                break
            time.sleep(0.2)
        raise SmokeFailure(
            f"{self.name}: no {pattern!r} within {timeout:.0f}s "
            f"(rc={self.proc.poll()}); log tail:\n{self.log()[-3000:]}"
        )

    def wait(self, timeout: float) -> int:
        try:
            return self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{self.name}: still running after {timeout:.0f}s; log tail:\n"
                f"{self.log()[-3000:]}"
            ) from None

    def terminate(self) -> None:
        """SIGTERM; a healthy child drains and exits 0."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        rc = self.wait(60)
        check(rc == 0, f"{self.name}: exit code {rc} on SIGTERM; log tail:\n{self.log()[-2000:]}")

    def kill(self) -> None:
        """Unconditional cleanup of the child's whole process group."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        self._log.close()


# -- HTTP ---------------------------------------------------------------------


def http_get(port: int, path: str, timeout: float = 10.0) -> str:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read().decode()
        check(resp.status == 200, f"GET {path} on :{port} → {resp.status}: {body[:300]}")
        return body
    finally:
        conn.close()


def prompt_text(n: int, seed: int) -> str:
    """``n`` ASCII characters — ``n`` byte tokens — from a seed."""
    rng = random.Random(seed)
    words = []
    size = 0
    while size < n:
        w = "".join(rng.choice("abcdefghijklmnopqrstuvwxyz") for _ in range(rng.randint(2, 9)))
        words.append(w)
        size += len(w) + 1
    return " ".join(words)[:n]


def chat(port: int, prompt: str, max_tokens: int, seed: int, stream: bool,
         timeout: float) -> dict:
    """One chat completion → {"status", "finish_reason", "usage", "first_s",
    "total_s"}; asserts nothing itself."""
    body = json.dumps({
        "model": MODEL, "messages": [{"role": "user", "content": prompt}],
        "max_tokens": max_tokens, "temperature": 0, "seed": seed,
        "ignore_eos": True, "stream": stream,
    })
    t0 = time.monotonic()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    out: dict = {"status": None, "finish_reason": None, "usage": None, "first_s": None}
    try:
        conn.request("POST", "/v1/chat/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out["status"] = resp.status
        if resp.status != 200 or not stream:
            payload = resp.read().decode()
            out["first_s"] = time.monotonic() - t0
            if resp.status == 200:
                doc = json.loads(payload)
                out["finish_reason"] = doc["choices"][0]["finish_reason"]
                out["usage"] = doc.get("usage")
            else:
                out["error"] = payload[:500]
        else:
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data:") or line == "data: [DONE]":
                    continue
                doc = json.loads(line[5:])
                if "error" in doc:
                    out["error"] = json.dumps(doc["error"])[:500]
                    continue
                choice = doc["choices"][0]
                if out["first_s"] is None and choice["delta"].get("content") is not None:
                    out["first_s"] = time.monotonic() - t0
                if choice.get("finish_reason"):
                    out["finish_reason"] = choice["finish_reason"]
                if doc.get("usage"):
                    out["usage"] = doc["usage"]
    finally:
        conn.close()
    out["total_s"] = time.monotonic() - t0
    return out


def check_answer(what: str, got: dict, max_tokens: int) -> None:
    # The byte tokenizer drops ids >= 256 from the text, so the text says
    # nothing; usage and finish_reason do.
    check(got["status"] == 200, f"{what}: HTTP {got['status']}: {got.get('error')}")
    check(got["finish_reason"] == "length",
          f"{what}: finish_reason {got['finish_reason']!r}, not 'length': {got.get('error')}")
    usage = got["usage"] or {}
    check(usage.get("completion_tokens") == max_tokens,
          f"{what}: completion_tokens {usage.get('completion_tokens')} != max_tokens {max_tokens}")


def metric(text: str, name: str, label: str = "") -> float:
    """Sum of the samples of one series in a Prometheus exposition."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(name) and label in line and line[len(name)] in " {":
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    check(seen, f"no series {name}{{{label}}} in /metrics")
    return total


# -- phases -------------------------------------------------------------------


def run_python(name: str, code: str, timeout: float) -> str:
    """Run ``code`` in a child interpreter that may use the chip → its log."""
    child = Child(name, ["-c", code], phase=name)
    try:
        rc = child.wait(timeout)
        check(rc == 0, f"{name}: exit code {rc}; log tail:\n{child.log()[-4000:]}")
        return child.log()
    finally:
        child.kill()


def last_json(log: str, what: str) -> dict:
    for line in reversed(log.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise SmokeFailure(f"{what}: no JSON line in its output:\n{log[-2000:]}")


def probe_phase() -> dict:
    log = run_python("probe", "import chip_smoke; chip_smoke.child_probe()", 180)
    return last_json(log, "probe")


def serve_phase(phase: str, engine_flags: list[str], streams: list[tuple[int, int]],
                expect: dict) -> dict:
    """Store + worker + frontend + metrics exporter through their CLIs;
    chats over HTTP; every child must stop cleanly. ``expect`` holds what
    the worker's start line must say. → timings."""
    t_phase = time.monotonic()
    store_port, http_port, sys_port, exp_port = (free_port() for _ in range(4))
    store_url = f"tcp://127.0.0.1:{store_port}"
    children: list[Child] = []
    try:
        store = Child("store", ["-m", "dynamo_tpu.runtime.store_server",
                                "--host", "127.0.0.1", "--port", str(store_port)], phase)
        children.append(store)
        store.wait_for(r"store server: tcp://", 60)
        worker = Child(
            "worker", ["-m", "dynamo_tpu.worker", "--store-url", store_url, *engine_flags],
            phase, env={"DYNTPU_SYSTEM_ENABLED": "1", "DYNTPU_SYSTEM_PORT": str(sys_port)},
        )
        children.append(worker)
        frontend = Child("frontend", ["-m", "dynamo_tpu.frontend", "--store-url", store_url,
                                      "--router-mode", "kv", "--host", "127.0.0.1",
                                      "--port", str(http_port)], phase)
        children.append(frontend)
        exporter = Child("exporter", ["-m", "dynamo_tpu.metrics_exporter", "--store-url",
                                      store_url, "--host", "127.0.0.1", "--port",
                                      str(exp_port), "--interval", "1"], phase)
        children.append(exporter)

        worker.wait_for(r"dynamo_tpu worker: serving " + MODEL, 600)
        t_ready = time.monotonic() - t_phase
        m = START_LINE.search(worker.log())
        check(m is not None, f"worker logged no start line:\n{worker.log()[-2000:]}")
        print(f"[{phase}] worker {m.group(0).split('engine start: ')[1]}", flush=True)
        check(m["platform"] == expect["platform"],
              f"worker runs on platform {m['platform']!r}, not {expect['platform']!r}")
        check(expect["kind"] in m["kind"].lower(),
              f"device_kind {m['kind']!r} is not a {expect['kind']!r}")
        check(m["dtype"] == expect["dtype"], f"worker dtype {m['dtype']}, not {expect['dtype']}")
        check(int(m["devices"]) == expect["devices"],
              f"worker uses {m['devices']} devices, not {expect['devices']}")
        check(m["decode"] == expect["decode"],
              f"decode attention path {m['decode']}{m['rest']!r}, not {expect['decode']!r}")
        if expect["devices"] > 1:
            # A forced XLA path must say why, and every chip must hold
            # about its share of weights and pool, more than one could.
            check("mesh" in m["rest"], f"start line does not name the mesh: {m['rest']!r}")
            hbm = re.search(r"hbm_in_use_gb=([\d.,]+)", m["rest"])
            check(hbm is not None, "start line reports no per-device HBM use")
            used = [float(x) for x in hbm[1].split(",")]
            total = sum(used)
            check(len(used) == expect["devices"] and total > expect["min_total_gb"],
                  f"HBM in use {used} GB: expected {expect['devices']} devices "
                  f"holding > {expect['min_total_gb']} GB together")
            check(all(abs(u / total - 1 / len(used)) < 0.05 for u in used),
                  f"HBM in use {used} GB is not about a quarter each")

        frontend.wait_for(r"dynamo_tpu frontend: http://", 60)
        exporter.wait_for(r"metrics exporter: http://", 60)
        deadline = time.monotonic() + 60
        while MODEL not in http_get(http_port, "/v1/models"):
            check(time.monotonic() < deadline, "frontend never listed the model")
            time.sleep(0.5)

        # 1. one non-streamed chat: the first answer, compilation included.
        first_prompt, first_max = prompt_text(200, seed=1), 64
        got = chat(http_port, first_prompt, first_max, 11, False, REQUEST_TIMEOUT)
        check_answer("first chat", got, first_max)
        t_first = time.monotonic() - t_phase
        prompt_tokens = got["usage"]["prompt_tokens"]

        # 2. concurrent streamed chats.
        results: list[dict | BaseException | None] = [None] * len(streams)

        def one(i: int, plen: int, mt: int) -> None:
            try:
                results[i] = chat(http_port, prompt_text(plen, seed=100 + i), mt,
                                  200 + i, True, REQUEST_TIMEOUT)
            except BaseException as e:  # noqa: BLE001 — reported by the parent
                results[i] = e

        threads = [threading.Thread(target=one, args=(i, p, mt))
                   for i, (p, mt) in enumerate(streams)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(REQUEST_TIMEOUT + 60)
        t_streams = time.monotonic() - t0
        for (plen, mt), r in zip(streams, results):
            check(isinstance(r, dict), f"stream (prompt {plen}): {r!r}")
            check_answer(f"stream (prompt {plen}, max_tokens {mt})", r, mt)
            check(r["usage"]["prompt_tokens"] >= plen,
                  f"stream (prompt {plen}): usage counts {r['usage']['prompt_tokens']} prompt tokens")

        # The worker's own counters. It reports gpu_prefix_cache_hit_rate
        # on its load_metrics endpoint; the exporter (polling every
        # second) puts it on a /metrics page.
        def hit_rate() -> float:
            return metric(http_get(exp_port, "/metrics"),
                          "dynamo_tpu_fleet_worker_prefix_hit_rate")

        time.sleep(2.5)
        hits_before = hit_rate()

        # 3. the first request again: its prompt is in the prefix cache.
        again = chat(http_port, first_prompt, first_max, 11, False, REQUEST_TIMEOUT)
        check_answer("repeated first chat", again, first_max)
        check(again["usage"]["prompt_tokens"] == prompt_tokens,
              "repeated chat counted a different prompt")
        hits, deadline = hits_before, time.monotonic() + 15
        while hits <= hits_before and time.monotonic() < deadline:
            time.sleep(1.0)
            hits = hit_rate()
        check(hits > hits_before,
              f"worker prefix-cache hit rate stayed at {hits_before:.4f} after a repeated prompt")

        wm = http_get(sys_port, "/metrics")
        n_requests = len(streams) + 2
        decodes = metric(wm, "dynamo_tpu_phase_duration_seconds_count", 'phase="engine.decode"')
        check(decodes >= n_requests, f"worker decoded {decodes:.0f} of {n_requests} requests")
        check(metric(wm, "dynamo_tpu_engine_tokens_per_weight_pass") > 0,
              "engine_tokens_per_weight_pass is zero: no decode step ran")

        worker_log = worker.log()
        for bad in CONTAINED_ERRORS:
            check(bad not in worker_log, f"worker log says {bad!r}:\n{worker_log[-3000:]}")
        for child in (frontend, exporter, worker, store):
            child.terminate()
        return {
            "wall_s": time.monotonic() - t_phase, "worker_ready_s": t_ready,
            "first_answer_s": t_first, "streams_s": t_streams,
            "repeat_s": again["total_s"], "prefix_hit_rate": (hits_before, hits),
            "requests": n_requests, "streams": len(streams),
        }
    finally:
        for child in children:
            child.kill()


def kernel_phase() -> dict:
    log = run_python("kernel", "import chip_smoke; chip_smoke.child_kernel()", 600)
    for line in log.splitlines():
        if line.startswith("[kernel]"):
            print(line, flush=True)
    return last_json(log, "kernel")


# -- children (these run in their own interpreter and may import JAX) ---------


def child_probe() -> None:
    import importlib.metadata as md

    import jax
    import jaxlib

    d = jax.devices()[0]
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    print(json.dumps({
        "platform": d.platform, "kind": d.device_kind, "count": len(jax.devices()),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu,
    }), flush=True)


def child_kernel() -> None:
    """The compiled kernel against the XLA reference on the same device,
    at the smoke model's geometry, ragged lengths."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.compile_cache import configure_compile_cache
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_xla,
        paged_prefill_attention,
        paged_prefill_attention_xla,
        paged_spec_attention,
        paged_spec_attention_xla,
    )

    configure_compile_cache()
    check(jax.default_backend() == "tpu",
          f"kernel phase on {jax.default_backend()!r}, not a TPU")
    bs, L, N, W, T = 16, 2, 520, 64, 4
    # Rows: empty, one token, a page minus one, one page, a page plus one,
    # two ending mid-page, and one longer than a 512-token chunk.
    lengths = np.array([0, 1, 15, 16, 17, 250, 488, 1000], np.int32)
    B = len(lengths)
    rng = np.random.default_rng(0)
    # Each row owns W distinct blocks; block 0 stays the garbage sink.
    tables = rng.permutation(np.arange(1, N))[: B * W].reshape(B, W).astype(np.int32)
    # A four-node draft tree: 0 is the root, 1 and 2 its children, 3 a
    # child of 1. anc[t, s]: query t attends in-flight slot s.
    anc1 = np.array([[1, 0, 0, 0], [1, 1, 0, 0], [1, 0, 1, 0], [1, 1, 0, 1]], np.int8)
    results = {}

    def run(name: str, cfg: ModelConfig, quantized: bool, mode: str) -> None:
        KVH, hd = cfg.num_kv_heads, cfg.head_dim
        G = cfg.num_heads // KVH
        key = jax.random.fold_in(jax.random.PRNGKey(7), len(results))
        kq, kk, kv = jax.random.split(key, 3)
        shape = (L, N, bs, KVH, hd)
        k = jax.random.normal(kk, shape, jnp.float32)
        v = jax.random.normal(kv, shape, jnp.float32)
        ks = vs = None
        if quantized:
            k, ks = M.kv_quantize(k)
            v, vs = M.kv_quantize(v)
        else:
            k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
        pages = M.fuse_kv(k.reshape(L, N, bs, KVH * hd), v.reshape(L, N, bs, KVH * hd))
        layer = jnp.int32(1)
        bt = jnp.asarray(tables)
        if mode == "decode":
            q = jax.random.normal(kq, (B, KVH, G, hd), jnp.float32).astype(jnp.bfloat16)
            ln = jnp.asarray(lengths)
            got = paged_decode_attention(q, pages, layer, bt, ln, ks, vs, interpret=False)
            ref = paged_decode_attention_xla(q, pages, layer, bt, ln, ks, vs)
            live = lengths > 0
        else:
            q = jax.random.normal(kq, (B, T, KVH, G, hd), jnp.float32).astype(jnp.bfloat16)
            if mode == "spec":
                # Query t attends [0, length + t); an empty row stays empty.
                ln2 = np.where(lengths[:, None] > 0, lengths[:, None] + np.arange(T), 0)
                anc = None
            else:
                # Every live query's history horizon is the row length; the
                # four in-flight slots sit on top under the tree mask.
                ln2 = np.repeat(lengths[:, None], T, axis=1)
                anc_np = np.where(lengths[:, None, None] > 0, anc1[None], 0).astype(np.int8)
                anc = jnp.asarray(anc_np)
            ln2 = jnp.asarray(ln2.astype(np.int32))
            got = paged_spec_attention(q, pages, layer, bt, ln2, ks, vs, anc, interpret=False)
            ref = paged_spec_attention_xla(q, pages, layer, bt, ln2, ks, vs, anc=anc)
            live = lengths > 0
        compare(name, got, ref, live)

    def compare(name: str, got, ref, live) -> None:
        got = np.asarray(jax.block_until_ready(got), np.float32)
        ref = np.asarray(ref, np.float32)
        check(np.isfinite(got).all(), f"{name}: kernel output is not finite")
        scale = max(1.0, float(np.abs(ref[live]).max()))
        err = float(np.abs(got[live] - ref[live]).max())
        results[name] = err / scale
        print(f"[kernel] {name}: max |kernel - xla| = {err:.5f} at output scale "
              f"{scale:.2f} (bound {BF16_TOLERANCE * scale:.5f})", flush=True)
        check(err <= BF16_TOLERANCE * scale, f"{name}: kernel and XLA reference disagree by {err}")

    def run_prefill(name: str, cfg: ModelConfig) -> None:
        """Chunks of 256 positions: a fresh prompt, one behind 1,000 cached
        tokens that ends inside a page, one row inactive, one a full chunk
        behind a prefix that is no multiple of the kernel's chunk."""
        KVH, hd = cfg.num_kv_heads, cfg.head_dim
        G, Tp = cfg.num_heads // KVH, 256
        start = np.array([0, 992, 0, 400], np.int32)
        tlen = np.array([220, 992 + 141, 0, 400 + 256], np.int32)
        kq, kk = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(7), len(results)))
        pages = jax.random.normal(kk, (L, N, 2, bs, KVH * hd), jnp.float32).astype(jnp.bfloat16)
        q = jax.random.normal(kq, (4, Tp, KVH, G, hd), jnp.float32).astype(jnp.bfloat16)
        wide = rng.permutation(np.arange(1, N))[: 4 * 96].reshape(4, 96).astype(np.int32)
        bt, layer = jnp.asarray(wide), jnp.int32(1)
        # The chunk's own K and V as its pages hold them (kv_write precedes attn).
        pos = start[:, None] + np.arange(Tp)[None]
        blk = jnp.asarray(np.take_along_axis(wide, pos // bs, axis=1))
        own = lambda pool: pool[1, blk, jnp.asarray(pos % bs)].reshape(4, Tp, KVH, hd)  # noqa: E731
        k, v = M.split_kv(pages)
        got = paged_prefill_attention(q, pages, layer, bt, jnp.asarray(start), jnp.asarray(tlen))
        ref = paged_prefill_attention_xla(
            q, own(k), own(v), pages, layer, bt, jnp.asarray(start), jnp.asarray(tlen))
        compare(name, got, ref, pos < tlen[:, None])

    smoke = ModelConfig.preset(MODEL)
    run("decode bf16", smoke, False, "decode")
    run("decode int8-KV", smoke, True, "decode")
    run("spec T=4", smoke, False, "spec")
    run("tree T=4", smoke, False, "tree")
    # head_dim 64 takes another in-kernel scale layout (llama-1b geometry).
    run("decode int8-KV head_dim=64", ModelConfig.preset("llama-1b"), True, "decode")
    run_prefill("prefill G=7 KVH=4", smoke)
    run_prefill("prefill G=4 KVH=8", ModelConfig.preset("llama-8b"))

    d = jax.devices()[0]
    stats = d.memory_stats() or {}
    if "bytes_in_use" in stats:
        # What an int8 pool's scale array really occupies in HBM: its
        # minor dimension is KVH (4), far under a 128-lane tile.
        before = stats["bytes_in_use"]
        sc = jax.block_until_ready(
            jnp.zeros((smoke.num_layers, 4096, bs, smoke.num_kv_heads), jnp.float32))
        resident = d.memory_stats()["bytes_in_use"] - before
        print(f"[kernel] int8-KV scale array {sc.shape} f32: logical "
              f"{sc.nbytes / 1e6:.1f} MB, resident {resident / 1e6:.1f} MB", flush=True)
    print(json.dumps({"variants": results, "calls": kernel_times(),
                      "prefill_calls": prefill_kernel_times()}), flush=True)


def traced_ns(run, line_name: str, prefix: str) -> list[int]:
    """Device durations (ns) of the events on ``line_name`` ("XLA Ops",
    "XLA Modules") whose name starts with ``prefix``, in one profiled call
    of ``run`` after one that compiles it."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    jax.block_until_ready(run())  # compiles
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        jax.block_until_ready(run())
        jax.profiler.stop_trace()
        data = ProfileData.from_file(
            sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True))[-1])
    return [ev.duration_ns for plane in data.planes if plane.name.startswith("/device:")
            for line in plane.lines if line.name == line_name
            for ev in line.events if ev.name.lstrip("%").startswith(prefix)]


def prefill_kernel_times() -> dict:
    """The prefill kernel alone at ``PREFILL_CALLS``, both geometries: a
    jitted loop over the cell's layers, the kernel's device time a call,
    the causal attention's own operations (4 x heads x head_dim for every
    pair of a live query and a position it attends) over that time as a
    share of the bf16 peak, and the XLA form's time a layer at the same
    call (its whole jitted loop over the layers). Passes or fails nothing."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops.paged_attention import (
        paged_prefill_attention,
        paged_prefill_attention_xla,
    )

    out = {}
    for geom, g in PREFILL_GEOMETRIES.items():
        KVH, G, hd, bs, L, N = (g[k] for k in ("KVH", "G", "hd", "bs", "L", "N"))
        kq, kk, ks = jax.random.split(jax.random.PRNGKey(2), 3)
        pool = jax.random.normal(kk, (L, N, 2, bs, KVH * hd), jnp.bfloat16)
        for shape, c in PREFILL_CALLS.items():
            T, start, length = c["T"], c["start"], c["length"]
            rng = np.random.default_rng(0)
            table = np.zeros((1, 256), np.int32)
            table[0, : -(-length // bs)] = rng.permutation(np.arange(1, N))[: -(-length // bs)]
            table = jnp.asarray(table)
            q = jax.random.normal(kq, (1, T, KVH, G, hd), jnp.bfloat16)
            own = jax.random.normal(ks, (2, 1, T, KVH, hd), jnp.bfloat16)
            s0, n = jnp.full((1,), start, jnp.int32), jnp.full((1,), length, jnp.int32)

            def kernel(i, q, pool):
                return paged_prefill_attention(q, pool, i, table, s0, n)

            def xla(i, q, pool):
                return paged_prefill_attention_xla(q, own[0], own[1], pool, i, table, s0, n)

            def layers(call):
                @jax.jit
                def run(q, pool):
                    def body(i, acc):
                        return acc + call(i, q, pool).astype(jnp.float32)
                    return jax.lax.fori_loop(1, L, body, call(jnp.int32(0), q, pool).astype(jnp.float32))
                return lambda: run(q, pool)

            ns = traced_ns(layers(kernel), "XLA Ops", "paged_prefill_attention")
            name = f"{geom} {shape}"
            check(len(ns) == L, f"{name}: {len(ns)} events of paged_prefill_attention, not {L}")
            us = sum(ns) / len(ns) / 1e3
            xla_us = sum(traced_ns(layers(xla), "XLA Modules", "jit_run")) / L / 1e3
            live = np.arange(start, min(start + T, length))
            flops = 4.0 * KVH * G * hd * float((live + 1).sum())
            share = flops / (us * 1e-6) / MXU_PEAK_FLOPS
            out[name] = {"us_a_call": round(us, 1), "mxu_peak_share": round(share, 4),
                         "xla_us_a_layer": round(xla_us, 1)}
            print(f"[kernel] prefill {name}: {us:.1f} us a call, {flops / 1e9:.2f} GFLOP = "
                  f"{100 * share:.1f}% of {MXU_PEAK_FLOPS / 1e12:.0f} TFLOP/s; the XLA form "
                  f"{xla_us:.1f} us a layer", flush=True)
        del pool
    return out


def kernel_times() -> dict:
    """The decode kernel alone at ``KERNEL_CALLS``: one jitted loop over
    the cell's layers, the kernel's device time a call from a profiler
    trace, and the bytes it must read (live K and V once) over that time
    as a share of the HBM peak. A kernel-alone number without a served
    run; it passes or fails nothing."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import dsa
    from dynamo_tpu.ops.paged_attention import latent_decode_attention, paged_decode_attention

    out = {}
    for name, c in KERNEL_CALLS.items():
        rng = np.random.default_rng(0)
        B, W, bs, L, N = c["B"], c["W"], c["bs"], c["L"], c["N"]
        lens = np.zeros(B, np.int32)
        order, at = rng.permutation(B), 0
        for n, lo, hi in c["rows"]:
            lens[order[at:at + n]] = rng.integers(lo, hi + 1, n)
            at += n
        # Scattered pages; the draw wraps, so rows may share pages as a
        # shared prefix does.
        pages, tables, at = rng.permutation(np.arange(1, N)), np.zeros((B, W), np.int32), 0
        for b in range(B):
            n = -(-int(lens[b]) // bs)
            tables[b, :n] = pages[(at + np.arange(n)) % len(pages)]
            at += n
        tables, lengths = jnp.asarray(tables), jnp.asarray(lens)
        kq, kk = jax.random.split(jax.random.PRNGKey(1))
        if "di" in c:
            pool = jax.random.normal(kk, (L, N, bs, c["di"]), jnp.bfloat16)
            q = jax.random.normal(kq, (B, c["Hi"], c["di"]), jnp.bfloat16)
            kernel = "dsa_index_scores"
            need = float(lens.sum()) * c["di"] * 2

            def call(i, q, pool):
                return dsa.index_scores(q, jnp.ones((B, c["Hi"]), jnp.float32), pool, i, tables, lengths)
        elif "topk" in c:
            pool = jax.random.normal(kk, (L, N, bs, c["Dk"]), jnp.bfloat16)
            q = jax.random.normal(kq, (B, c["H"], c["Dk"]), jnp.bfloat16)
            kernel = "latent_sparse_decode_attention"
            need = float((lens > 0).sum()) * c["topk"] * 576 * 2  # the live values of a chosen row
            picked = jnp.asarray(np.stack([rng.permutation(max(int(n), c["topk"]))[:c["topk"]] for n in lens]), jnp.int32)
            counts = jnp.where(lengths > 0, c["topk"], 0)

            def call(i, q, pool):
                return dsa.sparse_decode_attention(q, pool, i, tables, picked, counts,
                                                   value_dim=c["Dv"], scale=192 ** -0.5)
        elif "Dk" in c:
            pool = jax.random.normal(kk, (L, N, bs, c["Dk"]), jnp.bfloat16)
            q = jax.random.normal(kq, (B, c["H"], c["Dk"]), jnp.bfloat16)
            kernel = "latent_decode_attention"
            need = float(lens.sum()) * c["Dk"] * 2

            def call(i, q, pool):
                return latent_decode_attention(
                    q, pool, i, tables, lengths, value_dim=c["Dv"], scale=192 ** -0.5)
        else:
            D = c["KVH"] * c["hd"]
            pool = jax.random.normal(kk, (L, N, 2, bs, D), jnp.bfloat16)  # a page: K then V
            q = jax.random.normal(kq, (B, c["KVH"], c["G"], c["hd"]), jnp.bfloat16)
            kernel = "paged_decode_attention"
            need = float(lens.sum()) * D * 2 * 2

            def call(i, q, pool):
                return paged_decode_attention(q, pool, i, tables, lengths)

        @jax.jit
        def layers(q, pool):
            def body(i, acc):
                return acc + call(i, q, pool).astype(jnp.float32)
            return jax.lax.fori_loop(1, L, body, call(jnp.int32(0), q, pool).astype(jnp.float32))

        durations = traced_ns(lambda: layers(q, pool), "XLA Ops", kernel)
        check(len(durations) == L, f"{name}: {len(durations)} events of {kernel} in the trace, not {L}")
        us = sum(durations) / len(durations) / 1e3
        share = need / (us * 1e-6) / HBM_PEAK_BYTES_PER_S
        out[name] = {"us_a_call": round(us, 1), "hbm_peak_share": round(share, 4)}
        print(f"[kernel] {name} [{B} rows, {int((lens > 0).sum())} live, {int(lens.sum()):,} tokens, "
              f"{need / 1e6:.1f} MB]: {us:.1f} us a call, {need / us / 1e3:.0f} GB/s = "
              f"{100 * share:.1f}% of {HBM_PEAK_BYTES_PER_S / 1e9:.0f} GB/s", flush=True)
        del pool, q
    return out


# -- main ---------------------------------------------------------------------


def main() -> int:
    if not os.path.isdir(os.path.join(ROOT, "dynamo_tpu")):
        print("chip_smoke: no dynamo_tpu package beside this script; it drives "
              "the repository it sits in", file=sys.stderr)
        return 1
    sys.path.insert(0, ROOT)
    from dynamo_tpu.engine.compile_cache import compile_cache_dir

    cache = compile_cache_dir()

    def cache_entries() -> int:
        return len(os.listdir(cache)) if os.path.isdir(cache) else 0

    # JAX keeps every program that took over a second to compile. A start
    # is warm when the cache already held programs; which shapes a run
    # needs depends on how its requests happen to batch, so even a warm
    # start may compile a few more.
    entries0 = cache_entries()
    start = f"{'warm' if entries0 else 'cold'} start, {entries0} programs in the cache"
    t_start = time.monotonic()
    try:
        dev = probe_phase()
        print(f"[probe] platform={dev['platform']} device_kind={dev['kind']!r} "
              f"devices={dev['count']} jax={dev['jax']} jaxlib={dev['jaxlib']} "
              f"libtpu={dev['libtpu']}", flush=True)
        print(f"[probe] compile cache {cache}: {start}", flush=True)
        check(dev["platform"] == "tpu",
              f"JAX found no accelerator (platform {dev['platform']!r}); "
              "a CPU run is not a pass")

        def serve(phase: str, flags: list[str], streams: list, expect: dict) -> None:
            before = cache_entries()
            r = serve_phase(phase, flags, streams, expect)
            wrote = cache_entries() - before
            print(f"[{phase}] passed: {r['requests']} requests, wall {r['wall_s']:.1f}s; "
                  f"set-up: worker ready {r['worker_ready_s']:.1f}s, first answer "
                  f"{r['first_answer_s']:.1f}s after phase start (compile included: "
                  f"{start}, {wrote} more compiled and cached in this phase); "
                  f"{r['streams']} concurrent streams {r['streams_s']:.1f}s; "
                  f"repeated first chat {r['repeat_s']:.2f}s; worker prefix hit rate "
                  f"{r['prefix_hit_rate'][0]:.4f} -> {r['prefix_hit_rate'][1]:.4f}", flush=True)

        chip = {"platform": "tpu", "kind": "v5 lite", "dtype": "bfloat16"}
        serve("serve", ENGINE_FLAGS, STREAMS, {**chip, "devices": 1, "decode": "pallas"})
        t0 = time.monotonic()
        kernel_phase()
        print(f"[kernel] passed: wall {time.monotonic() - t0:.1f}s", flush=True)
        if dev["count"] == 4:
            serve("multichip", TP4_FLAGS, TP4_STREAMS,
                  {**chip, "devices": 4, "decode": "xla", "min_total_gb": 16.0})
        else:
            print(f"multichip: skipped ({dev['count']} devices)", flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED after {time.monotonic() - t_start:.1f}s: {e}",
              file=sys.stderr, flush=True)
        return 1
    print(f"chip_smoke passed in {time.monotonic() - t_start:.1f}s: {start}, "
          f"{cache_entries() - entries0} more compiled and cached", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
    }}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
