"""Cross-worker KV prefix reuse (G4 analogue, llm/peer_kv.py).

Two REAL TpuEngines with host tiers over the runtime: worker A prefills
a prompt (write-through offloads its blocks to A's G2 tier), worker B
then serves the same prefix WITHOUT recomputing it — pages fetched from
A over the response plane and injected as a materialized prefix hit.
Reference behaviour being matched: the KVBM remote blockset tier
(lib/llm/src/block_manager.rs:68-81) — outside the disagg prefill path.
"""

import asyncio

import pytest

from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.kv_router.publisher import KvEventBroadcaster, serve_kv_endpoints
from dynamo_tpu.kv_router.router import KvPushRouter, KvRouterConfig
from dynamo_tpu.llm.peer_kv import (
    KV_PREFIX_ENDPOINT,
    PeerPrefixFetcher,
    make_kv_prefix_handler,
)
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.push_router import RouterMode
from dynamo_tpu.tokens import compute_block_hashes

BS = 4


async def start_tpu_worker(store_url, namespace="peerkv"):
    """Real engine + host tier, serving generate (peer-fetch wrapped),
    kv_prefix, and the KV event/metrics endpoints."""
    rt = await DistributedRuntime.create(store_url=store_url)
    engine = await TpuEngine(EngineArgs(
        model=ModelConfig(), block_size=BS, num_kv_blocks=64, max_num_seqs=4,
        max_model_len=128, dtype="float32", decode_steps=2, host_kv_blocks=32,
    )).start()
    broadcaster = KvEventBroadcaster(engine.pool)
    engine.pool.set_event_sink(broadcaster.publish)
    comp = rt.namespace(namespace).component("backend")
    fetcher = PeerPrefixFetcher(
        engine, await comp.endpoint(KV_PREFIX_ENDPOINT).router(RouterMode.DIRECT)
    )

    async def gen_handler(payload, ctx):
        async for item in fetcher.generate(payload, ctx):
            yield item

    await comp.endpoint("generate").serve(gen_handler)
    await comp.endpoint(KV_PREFIX_ENDPOINT).serve(make_kv_prefix_handler(engine))
    await serve_kv_endpoints(comp, broadcaster, engine.metrics)
    wid = await rt.primary_lease()
    return rt, engine, fetcher, wid


PROMPT = [7 * i % 500 + 1 for i in range(23)]  # 5 matchable blocks + suffix


def make_request(prompt=PROMPT, max_tokens=8, **ktp):
    r = PreprocessedRequest(model="tiny", token_ids=list(prompt))
    r.sampling.temperature = 0.0
    r.sampling.seed = 0  # greedy, but unseeded requests draw global RNG (DT004)
    r.stop.max_tokens = max_tokens
    r.stop.ignore_eos = True
    d = r.to_dict()
    if ktp:
        d["kv_transfer_params"] = ktp
    return d


async def wait_for(cond, timeout=5.0, interval=0.02):
    deadline = asyncio.get_running_loop().time() + timeout
    while not cond():
        assert asyncio.get_running_loop().time() < deadline, "condition timed out"
        await asyncio.sleep(interval)


def tokens_of(items):
    return [t for it in items for t in (it.get("token_ids") or [])]


def test_peer_prefix_fetch_injects_and_matches_tokens():
    """Direct hint path: B told A holds 5 blocks → B fetches+injects,
    prefills only the suffix, and emits exactly A's continuation."""

    async def go():
        url = "memory://peerkv1"
        rt_a, eng_a, _fa, wid_a = await start_tpu_worker(url)
        rt_b, eng_b, fetcher_b, _wid_b = await start_tpu_worker(url)
        try:
            out_a = [x async for x in eng_a.generate(make_request(), Context())]
            toks_a = tokens_of(out_a)
            assert len(toks_a) == 8
            # Write-through offload lands A's prompt blocks in its G2 tier.
            await wait_for(lambda: len(eng_a.tiers.host) >= 5)

            out_b = [
                x async for x in fetcher_b.generate(
                    make_request(peer_prefix={"instance_id": wid_a, "num_blocks": 5}),
                    Context(),
                )
            ]
            assert tokens_of(out_b) == toks_a  # token parity with local prefill
            assert fetcher_b.peer_fetches == 1
            assert fetcher_b.peer_fetch_failures == 0
            # Only the 3-token suffix was computed locally (5 blocks injected).
            assert eng_b.total_prefilled == len(PROMPT) - 5 * BS
        finally:
            await eng_a.stop()
            await eng_b.stop()
            await rt_a.shutdown()
            await rt_b.shutdown()

    asyncio.run(go())


def test_peer_delta_fetch_extends_local_prefix():
    """B already holds the first 2 blocks; only blocks [2, 5) travel
    (block_offset inject), and tokens still match A's full-prefill run."""

    async def go():
        url = "memory://peerkv_delta"
        rt_a, eng_a, _fa, wid_a = await start_tpu_worker(url)
        rt_b, eng_b, fetcher_b, _wid_b = await start_tpu_worker(url)
        try:
            out_a = [x async for x in eng_a.generate(make_request(), Context())]
            toks_a = tokens_of(out_a)
            await wait_for(lambda: len(eng_a.tiers.host) >= 5)

            # Warm B with just the first 2 blocks of the prompt.
            warm = [x async for x in eng_b.generate(
                make_request(PROMPT[:9], max_tokens=2), Context())]
            assert tokens_of(warm)
            prefilled_before = eng_b.total_prefilled

            out_b = [
                x async for x in fetcher_b.generate(
                    make_request(peer_prefix={"instance_id": wid_a, "num_blocks": 5}),
                    Context(),
                )
            ]
            assert tokens_of(out_b) == toks_a
            assert fetcher_b.peer_fetches == 1
            # Local hit covered 2 blocks, the delta injected 3 more: only
            # the 3-token suffix was recomputed.
            assert eng_b.total_prefilled - prefilled_before == len(PROMPT) - 5 * BS
        finally:
            await eng_a.stop()
            await eng_b.stop()
            await rt_a.shutdown()
            await rt_b.shutdown()

    asyncio.run(go())


def test_peer_fetch_skipped_when_local_cache_covers():
    """A worker already holding the prefix must not fetch it again."""

    async def go():
        url = "memory://peerkv2"
        rt_a, eng_a, fetcher_a, wid_a = await start_tpu_worker(url)
        rt_b, eng_b, _fb, wid_b = await start_tpu_worker(url)
        try:
            _ = [x async for x in eng_a.generate(make_request(), Context())]
            # Stale hint pointing at B (which has nothing): local hit wins.
            out = [
                x async for x in fetcher_a.generate(
                    make_request(peer_prefix={"instance_id": wid_b, "num_blocks": 5}),
                    Context(),
                )
            ]
            assert tokens_of(out)
            assert fetcher_a.peer_fetches == 0
        finally:
            await eng_a.stop()
            await eng_b.stop()
            await rt_a.shutdown()
            await rt_b.shutdown()

    asyncio.run(go())


@pytest.mark.e2e
def test_worker_cli_peer_fetch_spawned_processes():
    """The full CLI wiring: two real-engine worker processes (CPU-forced
    via JAX_PLATFORMS), prefix seeded on A through the runtime,
    then B serves the same prompt from a peer_prefix hint — B's log must
    show the fetch and the token streams must match."""
    import socket

    from procutil import ManagedProcess

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        store_port = s.getsockname()[1]
    store_url = f"tcp://127.0.0.1:{store_port}"
    wargs = [
        "-m", "dynamo_tpu.worker", "--store-url", store_url,
        "--engine", "tpu", "--preset", "test-tiny", "--dtype", "float32",
        "--block-size", str(BS), "--num-kv-blocks", "64", "--max-num-seqs", "4",
        "--max-model-len", "128", "--decode-steps", "2", "--host-kv-blocks", "32",
    ]
    env = {"JAX_PLATFORMS": "cpu"}

    with ManagedProcess(
        ["-m", "dynamo_tpu.runtime.store_server", "--host", "127.0.0.1",
         "--port", str(store_port)], name="store",
    ) as store:
        store.wait_for(r"store server: tcp://")
        with ManagedProcess(wargs, name="worker_a", env=env) as wa, \
             ManagedProcess(wargs, name="worker_b", env=env) as wb:
            wa.wait_for(r"serving test-tiny", timeout=90)
            wb.wait_for(r"serving test-tiny", timeout=90)

            async def drive():
                from dynamo_tpu.runtime.distributed import DistributedRuntime

                rt = await DistributedRuntime.create(store_url=store_url)
                try:
                    ep = rt.namespace("dynamo").component("backend").endpoint("generate")
                    push = await ep.router(RouterMode.DIRECT)
                    await push.discovery.wait_for_instances(2)
                    wid_a, wid_b = sorted(push.discovery.instance_ids())
                    req = make_request()
                    out_a = [x async for x in push.generate(req, Context(), instance_id=wid_a)]
                    await asyncio.sleep(1.0)  # A's write-through offload
                    req2 = make_request(
                        peer_prefix={"instance_id": wid_a, "num_blocks": 5}
                    )
                    out_b = [x async for x in push.generate(req2, Context(), instance_id=wid_b)]
                    assert tokens_of(out_b) == tokens_of(out_a)
                finally:
                    await rt.shutdown()

            asyncio.run(drive())
            # One of the two workers logged the peer fetch (id→process
            # mapping is arbitrary, so accept either; logs may lag the
            # stream end slightly). Non-blocking reads of every line there
            # is: a select on the pipe does not see the lines Python has
            # already buffered, and a worker logs several at once.
            import os
            import time

            needle = "peer prefix: fetched 5 blocks"
            deadline = time.monotonic() + 5
            found = False
            for p in (wa, wb):
                os.set_blocking(p.proc.stdout.fileno(), False)
            while not found and time.monotonic() < deadline:
                for p in (wa, wb):
                    while ln := p.proc.stdout.readline():
                        p.lines.append(ln)
                found = any(needle in ln for p in (wa, wb) for ln in p.lines)
                if not found:
                    time.sleep(0.1)
            assert found, "no worker logged the peer prefix fetch"


def test_router_hints_peer_and_cold_worker_reuses():
    """End to end through the KV router: prefix lives on the warm worker;
    load pushes placement to the cold worker; the router's peer_prefix
    hint makes the cold worker onboard instead of recomputing."""

    async def go():
        url = "memory://peerkv3"
        rt_a, eng_a, f_a, wid_a = await start_tpu_worker(url)
        rt_b, eng_b, f_b, wid_b = await start_tpu_worker(url)
        rt_c = await DistributedRuntime.create(store_url=url)
        ep = rt_c.namespace("peerkv").component("backend").endpoint("generate")
        push = await ep.router(RouterMode.DIRECT)
        await push.discovery.wait_for_instances(2)
        router = await KvPushRouter(
            push, KvRouterConfig(block_size=BS, peer_fetch_min_blocks=2)
        ).start()
        by_wid = {wid_a: (eng_a, f_a), wid_b: (eng_b, f_b)}
        try:
            ctx1 = Context()
            out1 = [x async for x in router.generate(make_request(), ctx1)]
            toks1 = tokens_of(out1)
            warm = ctx1.metadata["worker_instance_id"]
            cold = wid_b if warm == wid_a else wid_a
            warm_eng, _ = by_wid[warm]
            cold_eng, cold_fetcher = by_wid[cold]
            # Blocks offloaded + KV events indexed before the second shot.
            await wait_for(lambda: len(warm_eng.tiers.host) >= 5)
            hashes = compute_block_hashes(PROMPT, BS)[:5]
            await wait_for(
                lambda: router.index.find_matches(hashes).scores.get(warm, 0) >= 5
            )

            # Pile synthetic load on the warm worker so the scheduler
            # prefers the cold one despite the prefix affinity.
            for i in range(4):
                router.active.add_request(f"fake{i}", warm, 50, 0, 200)

            ctx2 = Context()
            out2 = [x async for x in router.generate(make_request(), ctx2)]
            assert ctx2.metadata["worker_instance_id"] == cold
            assert tokens_of(out2) == toks1  # parity through the fetched prefix
            assert cold_fetcher.peer_fetches == 1
            # Cold worker computed only the suffix.
            assert cold_eng.total_prefilled == len(PROMPT) - 5 * BS
        finally:
            await router.close()
            await rt_c.shutdown()
            await eng_a.stop()
            await eng_b.stop()
            await rt_a.shutdown()
            await rt_b.shutdown()

    asyncio.run(go())
