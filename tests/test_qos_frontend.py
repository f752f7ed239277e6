"""Frontend QoS e2e: priority/tenant identity through the OpenAI
surface (body fields + x-priority/x-tenant headers, typed 400s on
junk), the wire stamp reaching the worker, per-class admission metrics
and the /debug/admission surface, and the contention headline —
interactive TTFT beats batch TTFT through a saturated gate."""

import asyncio
import json
import time

import httpx
import pytest

from dynamo_tpu.kv_router.publisher import KvEventBroadcaster, serve_kv_endpoints
from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
from dynamo_tpu.llm.http_service import HttpService
from dynamo_tpu.llm.model_card import ModelDeploymentCard, register_model
from dynamo_tpu.llm.pipeline import RouterSettings
from dynamo_tpu.llm.tokenizer import ByteTokenizer
from dynamo_tpu.mocker.engine import MockerArgs, MockerEngine
from dynamo_tpu.runtime.admission import AdmissionController
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.push_router import RouterMode
from dynamo_tpu.runtime.qos import QosPolicy


async def start_worker(store_url, payloads=None, namespace="qos", **mocker_kw):
    """In-process mocker worker; ``payloads`` (if given) captures every
    wire request dict the engine receives."""
    rt = await DistributedRuntime.create(store_url=store_url)
    kw = dict(block_size=4, num_kv_blocks=512, speedup=1000.0)
    kw.update(mocker_kw)
    engine = MockerEngine(MockerArgs(**kw))
    broadcaster = KvEventBroadcaster(engine.pool)
    comp = rt.namespace(namespace).component("backend")

    async def gen_handler(payload, ctx):
        if payloads is not None:
            payloads.append(payload)
        async for item in engine.generate(payload, ctx):
            yield item

    await comp.endpoint("generate").serve(gen_handler)
    await serve_kv_endpoints(comp, broadcaster, engine.metrics)
    card = ModelDeploymentCard(
        name="mock-model", kv_cache_block_size=4,
        eos_token_ids=[ByteTokenizer.EOS], context_length=512,
    )
    await register_model(rt, namespace, card)
    return rt


async def start_frontend(store_url, admission=None):
    rt = await DistributedRuntime.create(store_url=store_url)
    manager = ModelManager(rt, RouterSettings(mode=RouterMode.ROUND_ROBIN))
    watcher = await ModelWatcher(rt, manager).start()
    http = await HttpService(
        manager, rt.metrics, health=rt.health, host="127.0.0.1", port=0,
        admission=admission,
    ).start()
    deadline = time.monotonic() + 20
    while "mock-model" not in manager.list_names():
        assert time.monotonic() < deadline, "model never discovered"
        await asyncio.sleep(0.05)
    return rt, manager, watcher, http


def chat_body(**kw):
    body = {
        "model": "mock-model",
        "messages": [{"role": "user", "content": "hello qos"}],
        "max_tokens": 4,
    }
    body.update(kw)
    return body


def test_qos_junk_is_typed_400_and_identity_reaches_worker():
    async def go():
        url = "memory://qos-e2e-1"
        payloads = []
        wrt = await start_worker(url, payloads=payloads)
        frt, manager, watcher, http = await start_frontend(
            url, admission=AdmissionController(qos=QosPolicy()),
        )
        base = f"http://127.0.0.1:{http.port}"
        try:
            async with httpx.AsyncClient(timeout=30) as client:
                # Junk header: typed 400 BEFORE any admission/parse work.
                r = await client.post(f"{base}/v1/chat/completions",
                                      json=chat_body(),
                                      headers={"x-priority": "urgent"})
                assert r.status_code == 400
                assert "priority" in r.json()["error"]["message"]
                r = await client.post(f"{base}/v1/chat/completions",
                                      json=chat_body(),
                                      headers={"x-tenant": "two words"})
                assert r.status_code == 400
                # Junk body fields: typed 400 from the parser.
                r = await client.post(f"{base}/v1/chat/completions",
                                      json=chat_body(priority="p0"))
                assert r.status_code == 400
                r = await client.post(f"{base}/v1/chat/completions",
                                      json=chat_body(tenant=12))
                assert r.status_code == 400
                # Valid headers: identity stamps through to the worker
                # wire request.
                r = await client.post(
                    f"{base}/v1/chat/completions", json=chat_body(),
                    headers={"x-priority": "batch", "x-tenant": "acme"},
                )
                assert r.status_code == 200
                assert payloads[-1]["priority"] == "batch"
                assert payloads[-1]["tenant"] == "acme"
                # Body wins over header on conflict.
                r = await client.post(
                    f"{base}/v1/chat/completions",
                    json=chat_body(priority="interactive", tenant="corp"),
                    headers={"x-priority": "batch", "x-tenant": "acme"},
                )
                assert r.status_code == 200
                assert payloads[-1]["priority"] == "interactive"
                assert payloads[-1]["tenant"] == "corp"
                # No QoS fields at all: the wire dict omits both keys —
                # byte-identical to the pre-QoS format.
                r = await client.post(f"{base}/v1/chat/completions", json=chat_body())
                assert r.status_code == 200
                assert "priority" not in payloads[-1]
                assert "tenant" not in payloads[-1]
                # /debug/admission surfaces per-class gate state.
                r = await client.get(f"{base}/debug/admission")
                st = r.json()
                assert set(st["classes"]) == {"interactive", "standard", "batch"}
                assert all("retry_after" in c for c in st["classes"].values())
        finally:
            await http.close()
            await watcher.close()
            await manager.close()
            await frt.shutdown()
            await wrt.shutdown()

    asyncio.run(go())


def test_two_class_contention_interactive_ttft_beats_batch():
    """The headline property end to end: under a saturated admission
    gate (2 slots, 12+12 offered), interactive requests' TTFT — queue
    wait included — beats batch p99 vs p99, while EVERY batch request
    still completes (no starvation)."""

    async def go():
        url = "memory://qos-e2e-2"
        # Real service time so the gate actually queues: ~30ms TTFT +
        # 4 x 5ms ITL per request at speedup 1.
        wrt = await start_worker(
            url, speedup=1.0, ttft_ms=30.0, itl_ms=5.0, max_num_seqs=64,
        )
        admission = AdmissionController(
            max_inflight=2, max_queue_depth=64, queue_timeout=60.0,
            qos=QosPolicy(aging_s=30.0),
        )
        frt, manager, watcher, http = await start_frontend(url, admission=admission)
        base = f"http://127.0.0.1:{http.port}"
        ttfts = {"interactive": [], "batch": []}
        statuses = []
        try:
            async with httpx.AsyncClient(timeout=120) as client:
                async def one(cls):
                    t0 = time.perf_counter()
                    first = None
                    async with client.stream(
                        "POST", f"{base}/v1/chat/completions",
                        json=chat_body(stream=True, ignore_eos=True),
                        headers={"x-priority": cls},
                    ) as resp:
                        statuses.append(resp.status_code)
                        if resp.status_code != 200:
                            return
                        async for line in resp.aiter_lines():
                            if line.startswith("data: ") and line != "data: [DONE]":
                                if first is None:
                                    first = time.perf_counter() - t0
                    ttfts[cls].append(first)

                await asyncio.gather(
                    *(one("batch") for _ in range(12)),
                    *(one("interactive") for _ in range(12)),
                )
            assert statuses.count(200) == 24, f"sheds in an unsaturated test: {statuses}"
            assert len(ttfts["batch"]) == 12  # zero starvation
            inter = sorted(x for x in ttfts["interactive"] if x is not None)
            batch = sorted(x for x in ttfts["batch"] if x is not None)
            assert len(inter) == 12 and len(batch) == 12
            # p99 ~ max at n=12; the gate drains 8 interactive per batch.
            assert inter[-1] < batch[-1], (
                f"interactive p99 {inter[-1]:.3f}s !< batch p99 {batch[-1]:.3f}s"
            )
            # Metrics: per-class queue-depth series appeared.
            exposition = frt.metrics.render()
            assert 'dynamo_tpu_admission_queue_depth{class="interactive"' in exposition
        finally:
            await http.close()
            await watcher.close()
            await manager.close()
            await frt.shutdown()
            await wrt.shutdown()

    asyncio.run(go())


def test_overload_sheds_are_labeled_and_retry_after_scales():
    """Queue depth 0 + saturated slots: excess requests 429 with
    admission_rejected_total{class,reason="capacity"} and a Retry-After
    header ≥ the base."""

    async def go():
        url = "memory://qos-e2e-3"
        wrt = await start_worker(url, speedup=1.0, ttft_ms=50.0, itl_ms=5.0,
                                 max_num_seqs=64)
        admission = AdmissionController(
            max_inflight=1, max_queue_depth=0, queue_timeout=5.0,
            qos=QosPolicy(),
        )
        frt, manager, watcher, http = await start_frontend(url, admission=admission)
        base = f"http://127.0.0.1:{http.port}"
        try:
            async with httpx.AsyncClient(timeout=60) as client:
                results = await asyncio.gather(*(
                    client.post(f"{base}/v1/chat/completions",
                                json=chat_body(ignore_eos=True),
                                headers={"x-priority": "batch"})
                    for _ in range(6)
                ))
                codes = sorted(r.status_code for r in results)
                assert 429 in codes and 200 in codes
                shed = next(r for r in results if r.status_code == 429)
                assert int(shed.headers["Retry-After"]) >= 1
                assert shed.json()["error"]["type"] == "overloaded_error"
                exposition = frt.metrics.render()
                assert 'dynamo_tpu_admission_rejected_total{' in exposition
                assert 'class="batch"' in exposition
                assert 'reason="capacity"' in exposition
                r = await client.get(f"{base}/debug/admission")
                assert r.json()["classes"]["batch"]["shed"]["capacity"] >= 1
        finally:
            await http.close()
            await watcher.close()
            await manager.close()
            await frt.shutdown()
            await wrt.shutdown()

    asyncio.run(go())


def test_responses_and_completions_carry_qos_fields():
    """The QoS extension parses on all three OpenAI endpoints."""

    async def go():
        url = "memory://qos-e2e-4"
        payloads = []
        wrt = await start_worker(url, payloads=payloads)
        frt, manager, watcher, http = await start_frontend(
            url, admission=AdmissionController(qos=QosPolicy()),
        )
        base = f"http://127.0.0.1:{http.port}"
        try:
            async with httpx.AsyncClient(timeout=30) as client:
                r = await client.post(f"{base}/v1/completions", json={
                    "model": "mock-model", "prompt": "hi", "max_tokens": 4,
                    "priority": "batch", "tenant": "acme",
                })
                assert r.status_code == 200
                assert payloads[-1]["priority"] == "batch"
                r = await client.post(f"{base}/v1/responses", json={
                    "model": "mock-model", "input": "hi",
                    "max_output_tokens": 4, "priority": "interactive",
                })
                assert r.status_code == 200
                assert payloads[-1]["priority"] == "interactive"
                r = await client.post(f"{base}/v1/responses", json={
                    "model": "mock-model", "input": "hi", "priority": "p9",
                })
                assert r.status_code == 400
        finally:
            await http.close()
            await watcher.close()
            await manager.close()
            await frt.shutdown()
            await wrt.shutdown()

    asyncio.run(go())


@pytest.mark.parametrize("streams", [16, 128])
def test_concurrent_sse_streams_deliver_every_token_and_count_it(streams):
    """N concurrent chat streams through one in-process frontend against
    the mocker (window-burst deltas): none fails, each ends with a finish
    chunk and ``[DONE]``, the content tokens delivered equal streams x
    gen_len, and ``http_output_tokens_total`` counted exactly those."""
    gen_len = 24

    async def go():
        url = f"memory://fe-streams-{streams}"
        wrt = await start_worker(url, max_num_seqs=256, num_kv_blocks=4096,
                                 delta_tokens=4)
        frt, manager, watcher, http = await start_frontend(url)
        base = f"http://127.0.0.1:{http.port}"
        try:
            limits = httpx.Limits(max_connections=streams)
            async with httpx.AsyncClient(timeout=120, limits=limits) as client:
                async def one(i):
                    chars, finish, done = 0, None, False
                    async with client.stream(
                        "POST", f"{base}/v1/chat/completions",
                        json=chat_body(stream=True, ignore_eos=True,
                                       max_tokens=gen_len, seed=i),
                    ) as resp:
                        assert resp.status_code == 200
                        async for line in resp.aiter_lines():
                            if line == "data: [DONE]":
                                done = True
                            elif line.startswith("data: "):
                                choice = json.loads(line[6:])["choices"][0]
                                chars += len(choice["delta"].get("content") or "")
                                finish = choice.get("finish_reason") or finish
                    return chars, finish, done

                results = await asyncio.gather(*(one(i) for i in range(streams)))
            assert all(done and finish == "length" for _c, finish, done in results)
            # the mocker's tokens are single printable bytes
            assert sum(c for c, _f, _d in results) == streams * gen_len
            page = frt.metrics.render()
            counted = sum(
                float(l.rsplit(" ", 1)[1]) for l in page.splitlines()
                if l.startswith("dynamo_tpu_http_output_tokens_total")
            )
            assert counted == streams * gen_len
        finally:
            await http.close()
            await watcher.close()
            await manager.close()
            await frt.shutdown()
            await wrt.shutdown()

    asyncio.run(go())
