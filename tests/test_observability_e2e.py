"""Observability slice e2e: distributed span tracing, lifecycle ledger,
/debug endpoints, and the new metric series across a real messaging hop.

In-process fleets (mocker workers + frontend over real framed TCP) share
the process-global SpanRecorder, so these tests see the full
frontend→router→worker span nesting that a single-host deployment sees.
"""

import asyncio
import logging

import httpx
import pytest

from dynamo_tpu.kv_router.publisher import KvEventBroadcaster, serve_kv_endpoints
from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
from dynamo_tpu.llm.http_service import HttpService
from dynamo_tpu.llm.model_card import ModelDeploymentCard, register_model
from dynamo_tpu.llm.pipeline import RouterSettings
from dynamo_tpu.llm.tokenizer import ByteTokenizer
from dynamo_tpu.mocker.engine import MockerArgs, MockerEngine
from dynamo_tpu.runtime import tracing
from dynamo_tpu.runtime.chaos import ChaosConfig
from dynamo_tpu.runtime.config import Config
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.push_router import RouterMode

TRACEPARENT = "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01"
TRACE_ID = "0af7651916cd43dd8448eb211c80319c"


@pytest.fixture
def fresh_recorder():
    rec = tracing.SpanRecorder(capacity=4096, ledger_capacity=256)
    prev = tracing.set_recorder(rec)
    yield rec
    tracing.set_recorder(prev)


def fast_config(chaos: ChaosConfig | None = None) -> Config:
    cfg = Config.from_env({})
    cfg.runtime.retry_backoff_base = 0.005
    cfg.runtime.retry_backoff_max = 0.05
    cfg.runtime.circuit_cooldown = 0.2
    if chaos is not None:
        cfg.chaos = chaos
    return cfg


async def start_worker(store_url, namespace="obs", chaos=None, migration_limit=0,
                       mocker: MockerArgs | None = None):
    rt = await DistributedRuntime.create(store_url=store_url, config=fast_config(chaos))
    # delta_max_tokens=0: per-window frames. The chaos/migration assertions
    # need multi-frame streams (a mid-stream cut only exists between
    # frames); emit coalescing would ship a whole fast burst in one frame.
    engine = MockerEngine(
        mocker or MockerArgs(block_size=4, num_kv_blocks=256, speedup=1000.0,
                             delta_max_tokens=0)
    )
    broadcaster = KvEventBroadcaster(engine.pool)
    engine.pool.set_event_sink(broadcaster.publish)
    comp = rt.namespace(namespace).component("backend")

    async def gen_handler(payload, ctx):
        async for item in engine.generate(payload, ctx):
            yield item

    await comp.endpoint("generate").serve(gen_handler)
    await serve_kv_endpoints(comp, broadcaster, engine.metrics)
    card = ModelDeploymentCard(
        name="obs-model", kv_cache_block_size=4,
        eos_token_ids=[ByteTokenizer.EOS], context_length=512,
        migration_limit=migration_limit,
    )
    await register_model(rt, namespace, card)
    return rt, engine


async def start_frontend(store_url):
    rt = await DistributedRuntime.create(store_url=store_url, config=fast_config())
    manager = ModelManager(rt, RouterSettings(mode=RouterMode.ROUND_ROBIN))
    watcher = await ModelWatcher(rt, manager).start()
    http = await HttpService(
        manager, rt.metrics, health=rt.health, host="127.0.0.1", port=0
    ).start()
    return rt, manager, watcher, http


def body(text="observe me", max_tokens=8, **kw):
    out = {
        "model": "obs-model",
        "messages": [{"role": "user", "content": text}],
        "max_tokens": max_tokens,
    }
    out.update(kw)
    return out


async def wait_model(client, base):
    for _ in range(100):
        r = await client.get(f"{base}/v1/models")
        if r.json()["data"]:
            return
        await asyncio.sleep(0.05)
    raise AssertionError("model never appeared")


def span_index(trace_json):
    """Chrome-trace JSON → {span_id: event} for complete events."""
    return {
        e["args"]["span_id"]: e
        for e in trace_json["traceEvents"]
        if e["ph"] == "X"
    }


def ancestors(spans, event):
    """Names of the event's ancestor chain (nearest first)."""
    chain = []
    parent = event["args"]["parent_id"]
    while parent is not None and parent in spans:
        event = spans[parent]
        chain.append(event["name"])
        parent = event["args"]["parent_id"]
    return chain


def test_inbound_traceparent_to_worker_spans_ledger_and_flame(fresh_recorder):
    """A request with an inbound traceparent yields same-trace-id spans on
    both sides of a real messaging hop, a /debug/requests ledger entry with
    non-zero phases, and a /debug/traces flame whose spans nest
    frontend→router→worker."""

    captured = []

    class Capture(logging.Handler):
        def emit(self, record):
            captured.append(record)

    handler = Capture()
    logging.getLogger("dynamo_tpu.ledger").addHandler(handler)

    async def go():
        url = "memory://obs_trace"
        wrt, _eng = await start_worker(url)
        frt, manager, watcher, http = await start_frontend(url)
        base = f"http://127.0.0.1:{http.port}"
        try:
            async with httpx.AsyncClient(timeout=20) as client:
                await wait_model(client, base)
                r = await client.post(
                    f"{base}/v1/chat/completions", json=body(),
                    headers={"traceparent": TRACEPARENT},
                )
                assert r.status_code == 200

                # ledger entry via /debug/requests, filtered by trace id
                r = await client.get(
                    f"{base}/debug/requests", params={"trace_id": TRACE_ID}
                )
                assert r.status_code == 200
                records = r.json()["requests"]
                assert len(records) == 1, records
                rec = records[0]
                assert rec["trace_id"] == TRACE_ID
                assert rec["model"] == "obs-model"
                assert rec["status"] == "200"
                assert rec["completion_tokens"] == 8
                assert rec["ttft_s"] > 0
                for phase in ("admission_wait", "preprocess", "route", "wire",
                              "queue_wait", "prefill", "decode"):
                    assert rec["phases"].get(phase, 0) > 0, (phase, rec["phases"])

                # worker-side spans carry the inbound trace id (the hop is
                # real framed TCP — the id crossed the wire)
                names = {s.name for s in fresh_recorder.spans(TRACE_ID)}
                assert {"wire.serve", "engine.queue", "engine.prefill",
                        "engine.decode"} <= names, names

                # flame export nests frontend→router→worker
                r = await client.get(f"{base}/debug/traces/{TRACE_ID}")
                assert r.status_code == 200
                spans = span_index(r.json())
                decodes = [e for e in spans.values() if e["name"] == "engine.decode"]
                assert decodes, spans
                chain = ancestors(spans, decodes[0])
                assert chain[:4] == ["wire.serve", "wire.call", "router.attempt",
                                     "http.request"], chain
                assert decodes[0]["args"]["tokens"] == 8
                assert decodes[0]["dur"] > 0

                # unknown trace → 404
                r = await client.get(f"{base}/debug/traces/{'0' * 32}")
                assert r.status_code == 404

                # ledger also rode the logging layer with structured fields
                ledger_records = [
                    c for c in captured
                    if getattr(c, "event", None) == "request_ledger"
                    and getattr(c, "trace_id", None) == TRACE_ID
                ]
                assert ledger_records, "no ledger log line"
                assert ledger_records[0].phases["decode"] > 0
        finally:
            logging.getLogger("dynamo_tpu.ledger").removeHandler(handler)
            await http.close()
            await watcher.close()
            await manager.close()
            await frt.shutdown()
            await wrt.shutdown()

    asyncio.run(asyncio.wait_for(go(), timeout=60))


def test_chaos_run_ledger_counts_retries_and_migrations(fresh_recorder):
    """Acceptance: a chaos-run request (mocker path) yields a ledger entry
    with non-zero phase durations and retry/migration counts, plus the new
    metric series in /metrics text exposition."""

    async def go():
        url = "memory://obs_chaos"
        # Frame drops cut the transport mid-stream (after payload flowed),
        # which is what forces Migration re-dispatch; truncation at the
        # final frame alone is absorbed by the over-delivery guard.
        chaos = ChaosConfig(enabled=True, seed=7, frame_drop_p=0.08, truncate_p=0.2)
        w1 = await start_worker(url, chaos=chaos, migration_limit=20)
        w2 = await start_worker(
            url, chaos=ChaosConfig(enabled=True, seed=8, frame_drop_p=0.08, truncate_p=0.2),
            migration_limit=20,
        )
        frt, manager, watcher, http = await start_frontend(url)
        base = f"http://127.0.0.1:{http.port}"
        try:
            async with httpx.AsyncClient(timeout=30) as client:
                await wait_model(client, base)
                migrated = None
                for _ in range(25):
                    r = await client.post(
                        f"{base}/v1/chat/completions", json=body(max_tokens=24),
                        headers={"X-Request-Timeout": "30"},
                    )
                    assert r.status_code == 200, r.text
                    r = await client.get(f"{base}/debug/requests", params={"limit": "1"})
                    rec = r.json()["requests"][0]
                    if rec["migrations"] > 0:
                        migrated = rec
                        break
                assert migrated is not None, "chaos never forced a migration in 25 runs"
                assert migrated["status"] == "200"
                assert migrated["completion_tokens"] == 24
                assert migrated["phases"]["decode"] > 0
                assert migrated["phases"]["prefill"] > 0

                # /metrics text exposition: phase histograms + admission series
                r = await client.get(f"{base}/metrics")
                text = r.text
                assert "dynamo_tpu_phase_duration_seconds_bucket" in text
                assert 'phase="http.request"' in text
                assert 'phase="router.attempt"' in text
                assert "dynamo_tpu_admission_queue_depth" in text
                assert "dynamo_tpu_admission_wait_seconds_bucket" in text
                assert "dynamo_tpu_http_requests_total" in text

                # worker registries: engine phases + chaos injections
                wtext = w1[0].metrics.render() + w2[0].metrics.render()
                assert 'phase="engine.decode"' in wtext
                assert "dynamo_tpu_chaos_injections_total" in wtext
                assert 'kind="frame_drop"' in wtext or 'kind="truncate"' in wtext
        finally:
            await http.close()
            await watcher.close()
            await manager.close()
            await frt.shutdown()
            await w1[0].shutdown()
            await w2[0].shutdown()

    asyncio.run(asyncio.wait_for(go(), timeout=120))


def test_deadline_breaker_retry_series_and_shed_ledger(fresh_recorder):
    """deadline_expired_total / router_retries_total / circuit_breaker_state
    appear once their paths fire; shed requests get ledger entries too."""
    from dynamo_tpu.runtime.admission import AdmissionController

    async def go():
        url = "memory://obs_series"
        wrt, _eng = await start_worker(
            url, mocker=MockerArgs(block_size=4, num_kv_blocks=256, itl_ms=50.0)
        )
        frt = await DistributedRuntime.create(store_url=url, config=fast_config())
        manager = ModelManager(frt, RouterSettings(mode=RouterMode.ROUND_ROBIN))
        watcher = await ModelWatcher(frt, manager).start()
        http = await HttpService(
            manager, frt.metrics, health=frt.health, host="127.0.0.1", port=0,
            admission=AdmissionController(max_inflight=1, retry_after=1.0),
        ).start()
        base = f"http://127.0.0.1:{http.port}"
        try:
            async with httpx.AsyncClient(timeout=30) as client:
                await wait_model(client, base)

                # deadline → 504 + counter
                r = await client.post(
                    f"{base}/v1/chat/completions", json=body(max_tokens=100),
                    headers={"X-Request-Timeout": "0.3"},
                )
                assert r.status_code == 504
                text = (await client.get(f"{base}/metrics")).text
                assert 'dynamo_tpu_deadline_expired_total{' in text
                assert 'scope="http"' in text

                # shed → 429 with its own ledger record
                slow = asyncio.ensure_future(client.post(
                    f"{base}/v1/chat/completions", json=body(max_tokens=30)
                ))
                while http.admission.inflight == 0:
                    await asyncio.sleep(0.01)
                r = await client.post(f"{base}/v1/chat/completions", json=body())
                assert r.status_code == 429
                await slow
                r = await client.get(f"{base}/debug/requests", params={"limit": "10"})
                statuses = [rec["status"] for rec in r.json()["requests"]]
                assert "429" in statuses, statuses

                # breaker: mark the instance down → gauge series appears
                pipe = manager.get("obs-model")
                disc = pipe.discovery
                iid = disc.instances()[0].instance_id
                disc.report_instance_down(iid)
                text = frt.metrics.render()
                assert "dynamo_tpu_circuit_breaker_state" in text
                assert f'instance="{iid:x}"' in text
                disc.report_instance_up(iid)
                assert 'dynamo_tpu_circuit_breaker_state{' in frt.metrics.render()
        finally:
            await http.close()
            await watcher.close()
            await manager.close()
            await frt.shutdown()
            await wrt.shutdown()

    asyncio.run(asyncio.wait_for(go(), timeout=60))


def test_debug_endpoints_when_tracing_disabled():
    prev = tracing.set_recorder(None)

    async def go():
        url = "memory://obs_off"
        wrt, _eng = await start_worker(url)
        frt, manager, watcher, http = await start_frontend(url)
        base = f"http://127.0.0.1:{http.port}"
        try:
            async with httpx.AsyncClient(timeout=20) as client:
                await wait_model(client, base)
                # serving still works with the no-op fast path
                r = await client.post(f"{base}/v1/chat/completions", json=body())
                assert r.status_code == 200
                r = await client.get(f"{base}/debug/requests")
                assert r.json() == {"enabled": False, "requests": []}
                r = await client.get(f"{base}/debug/traces/{'0' * 32}")
                assert r.status_code == 404
        finally:
            await http.close()
            await watcher.close()
            await manager.close()
            await frt.shutdown()
            await wrt.shutdown()

    try:
        asyncio.run(asyncio.wait_for(go(), timeout=60))
    finally:
        tracing.set_recorder(prev)


# ---------------------------------------------------------------------------
# Fleet stitching: store-backed span export, the pure merge, and the
# supervisor's /debug/fleet/traces endpoint (PR 17).
# ---------------------------------------------------------------------------


def _span_dict(span_id, parent_id, name, proc, start_ts, trace_id=TRACE_ID):
    return {
        "name": name, "trace_id": trace_id, "span_id": span_id,
        "parent_id": parent_id, "start_ts": start_ts, "duration_s": 0.01,
        "status": "ok", "proc": proc, "attrs": {}, "events": [],
    }


def test_merge_traces_relabels_dedups_and_renders_byte_stable():
    """The pure fleet stitch (fleet/aggregate.py): scraped child bodies get
    the metrics-merge relabel convention (``<worker_id>/<lane>``),
    store-exported spans keep their own lane, duplicates collapse by
    span_id, and repeated assembly of the same fragment set is
    byte-identical."""
    import json

    from dynamo_tpu.fleet.aggregate import merge_traces

    root = _span_dict("aaaa", None, "http.request", "frontend-0", 1.0)
    child = _span_dict("bbbb", "aaaa", "wire.serve", "decode-1", 1.002)
    # The same worker span arrives twice: scraped from child 1 AND via the
    # store export (its own lane). Exactly one survives.
    exported_child = dict(child)
    exported_only = _span_dict("cccc", "bbbb", "engine.decode", "decode-1", 1.004)
    parts = [("0", {"spans": [root]}), ("1", {"spans": [child]})]
    merged = merge_traces(TRACE_ID, parts,
                          extra_spans=[exported_child, exported_only])

    by_id = {d["span_id"]: d for d in merged["spans"]}
    assert len(by_id) == 3
    assert by_id["aaaa"]["proc"] == "0/frontend-0"  # scraped → relabeled
    assert by_id["bbbb"]["proc"] == "1/decode-1"    # scrape wins the dedup
    assert by_id["cccc"]["proc"] == "decode-1"      # export keeps its lane
    lanes = {e["args"]["name"] for e in merged["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert lanes == {"0/frontend-0", "1/decode-1", "decode-1"}

    again = merge_traces(TRACE_ID, parts,
                         extra_spans=[exported_only, exported_child])
    assert json.dumps(merged, sort_keys=True) == json.dumps(again, sort_keys=True)

    # Bodies without a spans list (older children) reconstruct from the
    # Chrome "X" events — the merge accepts its own output as a part.
    legacy = {k: v for k, v in merged.items() if k != "spans"}
    relegacy = merge_traces(TRACE_ID, [("2", legacy)])
    assert {d["span_id"] for d in relegacy["spans"]} == set(by_id)


def test_trace_exporter_roundtrip_is_bounded_batched_and_lease_scoped(fresh_recorder):
    """TraceExporter ships finished spans to ``fleet/<id>/trace/…`` keys a
    prefix scan reassembles; every key rides the exporter's lease so a dead
    process's fragments age out with it."""
    from dynamo_tpu.runtime.logging import TraceContext
    from dynamo_tpu.runtime.store import connect_store
    from dynamo_tpu.runtime.trace_export import (
        TraceExporter,
        load_fleet_trace,
        trace_prefix,
    )

    async def go():
        store = await connect_store("memory://obs_export")
        exporter = await TraceExporter(
            store, "f1", recorder=fresh_recorder, lane="w0", interval_s=30.0
        ).start()
        trace = TraceContext.parse(TRACEPARENT)
        with tracing.start_span("wire.serve", parent=trace) as outer:
            with tracing.start_span("engine.decode",
                                    parent=outer.trace_context()):
                pass
        assert await exporter.flush() == 2

        entries = await store.get_prefix(trace_prefix("f1"))
        assert [e.key for e in entries] == [
            f"fleet/f1/trace/{TRACE_ID}/w0/00000001"
        ]
        spans = await load_fleet_trace(store, "f1", TRACE_ID)
        assert {d["name"] for d in spans} == {"wire.serve", "engine.decode"}
        assert all(d["trace_id"] == TRACE_ID for d in spans)
        assert await load_fleet_trace(store, "f1", "0" * 32) == []

        # close() revokes the lease → the fragments die with the process.
        await exporter.close()
        assert await load_fleet_trace(store, "f1", TRACE_ID) == []
        await store.close()

    asyncio.run(asyncio.wait_for(go(), timeout=30))


def test_chaos_injection_stamps_victim_trace_into_ledger(fresh_recorder):
    """A chaos fault that fires inside a traced request lands the injection
    kind in that request's ledger record (``chaos_injections``)."""
    from dynamo_tpu.runtime.chaos import ChaosInjector
    from dynamo_tpu.runtime.logging import (
        TraceContext,
        reset_current_trace,
        set_current_trace,
    )

    inj = ChaosInjector(ChaosConfig(enabled=True, seed=3, truncate_p=1.0))
    inj.bind_metrics(__import__("dynamo_tpu.runtime.metrics",
                                fromlist=["MetricsRegistry"]).MetricsRegistry())
    token = set_current_trace(TraceContext.parse(TRACEPARENT))
    try:
        assert inj.should_truncate()
    finally:
        reset_current_trace(token)
    assert fresh_recorder.injections(TRACE_ID) == ["truncate"]

    rec = tracing.build_ledger(
        TRACE_ID, request_id="r1", model="m", endpoint="chat",
        status="200", duration_s=0.5, spans=[],
    )
    assert rec["chaos_injections"] == ["truncate"]


def test_fleet_stitched_trace_for_remote_prefill_plus_live_migration(fresh_recorder):
    """PR 17 acceptance: ONE trace id for a request that prefills remotely
    (disagg) and is then live-migrated between decode engines yields a
    single connected cross-process span tree with a lane per process
    (frontend, source decode, destination decode, prefill — ≥4), served
    byte-stable from the supervisor's ``/debug/fleet/traces`` endpoint via
    BOTH stitch paths (store export and per-child scrape), with the ledger
    record's phase durations decomposing wall TTFT / E2E within tolerance.

    Real TpuEngines on CPU (the mocker has no migration cutover); each
    DistributedRuntime gets its own ``proc_label`` so the in-process fleet
    records the same lanes a multi-process deployment would."""
    import json
    import time

    from aiohttp import ClientSession, ClientTimeout

    from dynamo_tpu.engine.config import EngineArgs, ModelConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.fleet.supervisor import FleetSupervisor, frontends_prefix
    from dynamo_tpu.llm.disagg import (
        DisaggConfig,
        DisaggDecodeHandler,
        PrefillHandler,
    )
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.runtime.store import connect_store
    from dynamo_tpu.runtime.trace_export import TraceExporter
    from dynamo_tpu.worker.migrate import (
        MigrationCoordinator,
        MigrationReceiver,
        register_migration_metrics,
    )

    NS = "obsfleet"
    FLEET = "obsfleet"
    url = "memory://obs_fleet_stitch"

    def engine_args():
        return EngineArgs(
            model=ModelConfig(), block_size=4, num_kv_blocks=128,
            max_num_seqs=4, max_model_len=256, max_prefill_tokens=128,
            dtype="float32", decode_steps=4,
        )

    class DecodeWorker:
        def __init__(self, rt, engine, disagg, receiver, coordinator, iid):
            self.rt = rt
            self.engine = engine
            self.disagg = disagg
            self.receiver = receiver
            self.coordinator = coordinator
            self.instance_id = iid

        async def stop(self):
            await self.receiver.close()
            await self.engine.stop()
            await self.rt.shutdown()

    async def start_decode(label):
        rt = await DistributedRuntime.create(
            store_url=url, config=fast_config(), proc_label=label
        )
        engine = await TpuEngine(engine_args(), seed=0).start()
        metrics = register_migration_metrics(rt.metrics)
        receiver = MigrationReceiver(rt, NS, metrics=metrics)
        pcomp = rt.namespace(NS).component("prefill")
        disagg = DisaggDecodeHandler(
            engine,
            await pcomp.endpoint("generate").router(RouterMode.ROUND_ROBIN),
            await pcomp.endpoint("kv_fetch").router(RouterMode.DIRECT),
            DisaggConfig(max_local_prefill_length=8),
        )
        comp = rt.namespace(NS).component("backend")

        async def gen_handler(payload, ctx):
            if isinstance(payload, dict):
                mr = (payload.get("kv_transfer_params") or {}).get(
                    "migration_resume")
                if isinstance(mr, dict) and mr.get("handle"):
                    staged = receiver.take(mr["handle"])
                    if staged is not None:
                        payload = dict(payload)
                        ktp = dict(payload.get("kv_transfer_params") or {})
                        ktp["inject"] = staged
                        payload["kv_transfer_params"] = ktp
                    # Resume leg: the KV just arrived via migration — no
                    # disagg detour for the carried prompt.
                    async for item in engine.generate(payload, ctx):
                        yield item
                    return
            async for item in disagg.generate(payload, ctx):
                yield item

        gh = await comp.endpoint("generate").serve(gen_handler)
        await comp.endpoint("kv_fetch").serve(PrefillHandler(engine).kv_fetch)

        acomp = rt.namespace(NS).component("workerctl")
        coordinator = MigrationCoordinator(
            engine,
            await acomp.endpoint("admin").router(RouterMode.DIRECT),
            "backend", gh.instance.instance_id, metrics=metrics,
        )

        async def admin(payload, ctx):
            # The roles.py admin verbs this test needs — including the
            # traceparent forward on migrate_in_start that stitches the
            # destination's KV pull into the migrating request's trace.
            payload = payload or {}
            cmd = payload.get("cmd")
            try:
                if cmd == "migrate_out":
                    yield await coordinator.migrate_out(
                        payload.get("request_id", ""),
                        int(payload.get("dest_instance") or 0),
                    )
                elif cmd == "migrate_in_start":
                    yield await receiver.start_pull(
                        payload.get("handle", ""),
                        payload.get("source_component", ""),
                        int(payload.get("source_instance") or 0),
                        traceparent=payload.get("traceparent"),
                    )
                elif cmd == "migrate_in_commit":
                    yield await receiver.commit(
                        payload.get("handle", ""),
                        int(payload.get("kv_blocks") or 0),
                    )
                elif cmd == "migrate_in_abort":
                    yield await receiver.abort(payload.get("handle", ""))
                else:
                    yield {"error": f"unknown admin cmd {cmd!r}"}
            except Exception as e:  # noqa: BLE001 — shim answers typed like roles.py
                yield {"error": f"{type(e).__name__}: {e}"}

        await acomp.endpoint("admin").serve(admin)
        return DecodeWorker(rt, engine, disagg, receiver, coordinator,
                            gh.instance.instance_id)

    async def go():
        w1 = await start_decode("decode-1")
        w2 = await start_decode("decode-2")

        prt = await DistributedRuntime.create(
            store_url=url, config=fast_config(), proc_label="prefill-0"
        )
        pengine = await TpuEngine(engine_args(), seed=0).start()
        ph = PrefillHandler(pengine)
        pcomp = prt.namespace(NS).component("prefill")
        await pcomp.endpoint("generate").serve(ph.generate)
        await pcomp.endpoint("kv_fetch").serve(ph.kv_fetch)

        frt = await DistributedRuntime.create(
            store_url=url, config=fast_config(), proc_label="frontend-0"
        )
        manager = ModelManager(frt, RouterSettings(mode=RouterMode.ROUND_ROBIN))
        watcher = await ModelWatcher(frt, manager).start()
        http = await HttpService(
            manager, frt.metrics, health=frt.health, host="127.0.0.1",
            port=0, proc_label="frontend-0",
        ).start()
        base = f"http://127.0.0.1:{http.port}"

        card = ModelDeploymentCard(
            name="fleet-model", kv_cache_block_size=4,
            eos_token_ids=[ByteTokenizer.EOS], context_length=256,
            migration_limit=3,
        )
        await register_model(frt, NS, card)

        # Store-backed export off the shared recorder: the push half of
        # the supervisor's stitch.
        store = await connect_store(url)
        exporter = await TraceExporter(
            store, FLEET, recorder=fresh_recorder, lane="export",
            interval_s=0.1, max_buffer=8192,
        ).start()

        admin = await frt.namespace(NS).component("workerctl") \
            .endpoint("admin").router(RouterMode.DIRECT)

        async def migrate_running():
            for w, other in ((w1, w2), (w2, w1)):
                running = w.engine.list_running()
                if running:
                    last = {}
                    async for frame in admin.generate(
                        {"cmd": "migrate_out", "request_id": running[0],
                         "dest_instance": other.instance_id},
                        Context(), instance_id=w.instance_id,
                    ):
                        if isinstance(frame, dict):
                            last = frame
                    return last
            return None

        async def one_request(client, attempt):
            """Stream one chat completion; fire migrate_out mid-stream.
            → (trace_id, migrate reply, wall ttft, wall e2e)."""
            tid = f"{0xfeedc0de + attempt:032x}"
            tp = f"00-{tid}-b7ad6b7169203331-01"
            # Fresh prompt text per attempt: a repeated prompt would
            # prefix-hit the decode engine and skip the remote prefill
            # this test must observe.
            text = f"stitch across the fleet please, attempt {attempt}"
            reply = None
            t0 = time.perf_counter()
            t_first = None
            chunks = 0
            async with client.stream(
                "POST", f"{base}/v1/chat/completions",
                json=body(text=text, max_tokens=48, model="fleet-model",
                          stream=True),
                headers={"traceparent": tp},
            ) as resp:
                assert resp.status_code == 200
                async for line in resp.aiter_lines():
                    if not line.startswith("data: ") or "[DONE]" in line:
                        continue
                    if t_first is None:
                        t_first = time.perf_counter()
                    chunks += 1
                    if reply is None and chunks >= 2:
                        reply = await migrate_running()
            assert t_first is not None and chunks > 2
            return tid, reply, t_first - t0, time.perf_counter() - t0

        sup = None
        try:
            async with httpx.AsyncClient(timeout=60) as client:
                for _ in range(200):
                    r = await client.get(f"{base}/v1/models")
                    if r.json()["data"]:
                        break
                    await asyncio.sleep(0.05)

                # The engines race the migrate trigger; retry with a fresh
                # trace id until a migration actually lands (CI timing).
                tid = reply = None
                for attempt in range(4):
                    tid, reply, wall_ttft, wall_e2e = await one_request(
                        client, attempt)
                    if reply is not None and reply.get("ok"):
                        break
                assert reply is not None and reply.get("ok"), reply
                assert (w1.disagg.remote_prefills
                        + w2.disagg.remote_prefills) >= 1

                # -- one CONNECTED span tree, ≥4 process lanes ------------
                spans = fresh_recorder.spans(tid)
                idx = {s.span_id: s for s in spans}
                roots = [s for s in spans if s.parent_id not in idx]
                assert len(roots) == 1, [(s.name, s.proc) for s in roots]
                assert roots[0].name == "http.request"
                assert roots[0].parent_id == "b7ad6b7169203331"  # inbound
                lanes = {s.proc for s in spans}
                assert {"frontend-0", "decode-1", "decode-2",
                        "prefill-0"} <= lanes, lanes
                names = {s.name for s in spans}
                assert {"disagg.remote_prefill", "transfer.kv_pull",
                        "migration.out", "migration.resume",
                        "engine.prefill", "engine.decode"} <= names, names
                # The migration KV pull is distinguishable from the disagg
                # one and runs on the DESTINATION lane.
                mig_pulls = [s for s in spans if s.name == "transfer.kv_pull"
                             and s.attrs.get("kind") == "migration"]
                assert mig_pulls and all(
                    s.proc in ("decode-1", "decode-2") for s in mig_pulls)

                # -- ledger v2: phases decompose wall TTFT / E2E ----------
                r = await client.get(f"{base}/debug/requests",
                                     params={"trace_id": tid})
                recs = r.json()["requests"]
                assert len(recs) == 1
                rec = recs[0]
                assert rec["schema"] == 2
                ph = rec["phases"]
                for key in ("remote_prefill", "transfer", "decode",
                            "migration_freeze"):
                    assert ph.get(key, 0) > 0, (key, ph)
                # TTFT-side serial phases (the disagg window covers the
                # remote prefill dispatch + pull + inject; route is NOT in
                # this set — router.attempt wraps the whole streamed leg)
                # stay bounded by the wall TTFT; generous slack for CPU
                # scheduling noise.
                ttft_side = sum(ph.get(k, 0) for k in
                                ("admission_wait", "preprocess",
                                 "remote_prefill"))
                assert rec["ttft_s"] <= wall_ttft + 0.05
                assert 0.2 * rec["ttft_s"] < ttft_side <= 1.2 * rec["ttft_s"] + 0.25, \
                    (ttft_side, rec["ttft_s"], ph)
                # Decode-budget phases (decode legs + the client-visible
                # freeze gap) account for the post-TTFT window.
                stream_wall = rec["duration_s"] - rec["ttft_s"]
                decode_side = ph["decode"] + ph["migration_freeze"] \
                    + ph.get("redispatch", 0)
                assert 0.3 * stream_wall < decode_side <= 2.0 * stream_wall + 0.25, \
                    (decode_side, stream_wall, ph)
                assert rec["duration_s"] <= wall_e2e + 0.05

                # -- the supervisor endpoint, both stitch paths ----------
                await exporter.flush()
                sup = FleetSupervisor(
                    1, [], "127.0.0.1", 0, fleet_id=FLEET,
                    store_url="tcp://unused:1",
                )
                sup._store = store
                sup._http = ClientSession(timeout=ClientTimeout(total=5.0))
                await sup._start_admin()
                sup_base = f"http://127.0.0.1:{sup.admin_port}"

                # (a) store-export path alone: no children registered yet.
                r = await client.get(f"{sup_base}/debug/fleet/traces/{tid}")
                assert r.status_code == 200
                exported_lanes = {
                    e["args"]["name"] for e in r.json()["traceEvents"]
                    if e.get("ph") == "M" and e.get("name") == "process_name"
                }
                assert {"frontend-0", "decode-1", "decode-2",
                        "prefill-0"} <= exported_lanes, exported_lanes

                # (b) register the frontend as fleet child 0 → the scrape
                # path joins; lanes adopt the <worker_id>/<lane> relabel
                # convention and the body pins byte-stable across GETs.
                await store.put(
                    frontends_prefix(FLEET) + "0",
                    json.dumps({"pid": 0, "admin": base}).encode(),
                )
                r1 = await client.get(f"{sup_base}/debug/fleet/traces/{tid}")
                r2 = await client.get(f"{sup_base}/debug/fleet/traces/{tid}")
                assert r1.status_code == r2.status_code == 200
                assert r1.content == r2.content  # byte-stable
                merged = r1.json()
                merged_lanes = {
                    e["args"]["name"] for e in merged["traceEvents"]
                    if e.get("ph") == "M" and e.get("name") == "process_name"
                }
                assert {"0/frontend-0", "0/decode-1", "0/decode-2",
                        "0/prefill-0"} <= merged_lanes, merged_lanes
                # Complete: every span the recorder holds for this trace
                # made it into the assembled body exactly once.
                assert {d["span_id"] for d in merged["spans"]} == set(idx)

                # unknown trace → 404 from the fleet endpoint too
                r = await client.get(
                    f"{sup_base}/debug/fleet/traces/{'0' * 32}")
                assert r.status_code == 404
        finally:
            if sup is not None:
                if sup._runner is not None:
                    await sup._runner.cleanup()
                await sup._http.close()
            await exporter.close()
            await http.close()
            await watcher.close()
            await manager.close()
            await frt.shutdown()
            await prt.shutdown()
            await pengine.stop()
            await w1.stop()
            await w2.stop()

    asyncio.run(asyncio.wait_for(go(), timeout=300))


# -- /debug/slo: burn state + attribution over the ledger ----------------------


def test_attribution_summary_shares_and_ttft_tail():
    from dynamo_tpu.runtime.slo import attribution_summary

    recs = [
        {"ttft_s": 0.1 * (i + 1), "phases": {"prefill": 0.3, "decode": 0.1,
                                              "route": None}}
        for i in range(10)
    ] + [{"phases": {"decode": 1.0}}, "junk"]
    out = attribution_summary(recs)
    assert out["schema"] == 2 and out["requests"] == 11   # non-dicts dropped
    assert set(out["phases"]) == {"prefill", "decode"}     # a None phase is no phase
    assert out["phases"]["prefill"] == {"total_s": 3.0, "mean_s": 0.3, "share": 0.6}
    assert out["phases"]["decode"] == {"total_s": 2.0, "mean_s": round(2 / 11, 6),
                                        "share": 0.4}
    assert out["ttft"] == {"mean_s": 0.55, "p99_s": 1.0}
    empty = attribution_summary([])
    assert empty["requests"] == 0 and empty["phases"] == {}
    assert empty["ttft"] == {"mean_s": None, "p99_s": None}


def test_debug_slo_attributes_the_recent_ledger_to_phases(fresh_recorder):
    async def go():
        url = "memory://obs-slo"
        wrt, _engine = await start_worker(url)
        frt, manager, watcher, http = await start_frontend(url)
        base = f"http://127.0.0.1:{http.port}"
        try:
            async with httpx.AsyncClient(timeout=30) as client:
                await wait_model(client, base)
                for i in range(6):
                    r = await client.post(f"{base}/v1/chat/completions",
                                          json=body(f"slo {i}", stream=i % 2 == 0))
                    assert r.status_code == 200
                slo = (await client.get(f"{base}/debug/slo")).json()
            assert slo["schema"] == 2
            assert sum(c.get("observed", 0) for c in slo["classes"].values()) == 6
            att = slo["attribution"]
            assert att["schema"] == 2 and att["requests"] == 6
            assert att["phases"], "no phase reached the ledger"
            assert abs(sum(p["share"] for p in att["phases"].values()) - 1.0) < 0.01
            for p in att["phases"].values():
                assert p["total_s"] >= p["mean_s"] >= 0.0
            assert 0.0 < att["ttft"]["mean_s"] <= att["ttft"]["p99_s"]
        finally:
            await http.close()
            await watcher.close()
            await manager.close()
            await frt.shutdown()
            await wrt.shutdown()

    asyncio.run(go())
