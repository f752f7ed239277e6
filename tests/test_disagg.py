"""Disaggregated prefill/decode tests (CPU, virtual devices).

Covers: KV page extract/inject round-trip, the conditional-disagg
decision, engine-level export + inject parity (disagg token streams
identical to aggregated), the multi-process-shaped e2e (prefill worker +
decode worker over the runtime), and the WorkQueue primitive.
"""

import asyncio

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.engine import kv_transfer
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.disagg import (
    DisaggConfig,
    DisaggDecodeHandler,
    PrefillHandler,
    should_prefill_remote,
)
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.push_router import RouterMode
from dynamo_tpu.runtime.queue import WorkQueue
from dynamo_tpu.runtime.store import connect_store

CFG = ModelConfig()  # test-tiny


def make_args(**kw) -> EngineArgs:
    defaults = dict(
        model=CFG, block_size=4, num_kv_blocks=64, max_num_seqs=4,
        max_model_len=128, max_prefill_tokens=64, dtype="float32",
        decode_steps=4,
    )
    defaults.update(kw)
    return EngineArgs(**defaults)


def greedy_request(prompt, max_tokens=8, **ktp) -> PreprocessedRequest:
    req = PreprocessedRequest(model="t", token_ids=list(prompt))
    req.sampling.temperature = 0.0
    req.sampling.seed = 0  # greedy, but unseeded requests draw global RNG (DT004)
    req.stop.max_tokens = max_tokens
    req.stop.ignore_eos = True
    if ktp:
        req.kv_transfer_params = ktp
    return req


async def collect(engine_like, req, ctx=None):
    out = []
    final = None
    async for item in engine_like.generate(
        req.to_dict() if hasattr(req, "to_dict") else req, ctx or Context()
    ):
        out.extend(item.get("token_ids") or [])
        if item.get("finish_reason"):
            final = item
    return out, final


# ---------------------------------------------------------------------------
# Page movement primitives
# ---------------------------------------------------------------------------


def test_extract_inject_roundtrip():
    cache = M.init_kv_cache(CFG, num_blocks=16, block_size=4, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    L, N, _, bs, lanes = cache.kv.shape
    k = rng.normal(size=(L, N, bs, lanes)).astype(np.float32)
    v = rng.normal(size=(L, N, bs, lanes)).astype(np.float32)
    cache = M.KVCache(M.fuse_kv(jnp.asarray(k), jnp.asarray(v)))

    ids = [3, 7, 2]
    pk, pv = kv_transfer.extract_pages(cache, ids)
    assert pk.shape == (CFG.num_layers, 3, 4, CFG.num_kv_heads * CFG.head_dim)
    np.testing.assert_array_equal(pk, k[:, ids])

    # Wire round-trip then inject into different slots of a fresh cache.
    payload = kv_transfer.KvPagePayload(k=pk, v=pv, num_tokens=12)
    wire = payload.to_dict()
    assert isinstance(wire["k"], bytes)
    back = kv_transfer.KvPagePayload.from_dict(wire)
    np.testing.assert_array_equal(back.k, pk)

    cache2 = M.init_kv_cache(CFG, num_blocks=16, block_size=4, dtype=jnp.float32)
    cache2 = kv_transfer.inject_pages(cache2, [5, 1, 9], back.k, back.v)
    got, got_v = (np.asarray(a) for a in M.split_kv(cache2.kv))
    np.testing.assert_array_equal(got[:, [5, 1, 9]], k[:, ids])
    np.testing.assert_array_equal(got_v[:, [5, 1, 9]], v[:, ids])
    assert (got[:, 4] == 0).all()  # untouched block stays zero


def test_bf16_wire_roundtrip():
    import ml_dtypes

    rng = np.random.default_rng(1)
    k = rng.normal(size=(2, 1, 4, 2, 8)).astype(ml_dtypes.bfloat16)
    payload = kv_transfer.KvPagePayload(k=k, v=k.copy(), num_tokens=4)
    back = kv_transfer.KvPagePayload.from_dict(payload.to_dict())
    assert back.k.dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(back.k.view(np.uint16), k.view(np.uint16))


def test_should_prefill_remote():
    assert should_prefill_remote(1000, 0, 512)
    assert not should_prefill_remote(400, 0, 512)
    # A big prefix hit keeps a long prompt local (ref: disagg_router.rs).
    assert not should_prefill_remote(1000, 600, 512)


# ---------------------------------------------------------------------------
# Engine-level export / inject
# ---------------------------------------------------------------------------


def test_engine_export_then_inject_parity():
    """Prefill-only export on engine A, inject into engine B: B's stream
    must equal an aggregated run on a single engine."""

    async def go():
        rng = np.random.default_rng(3)
        prompt = rng.integers(1, CFG.vocab_size - 1, size=22).tolist()
        N = 10

        # Aggregated reference run.
        agg = await TpuEngine(make_args(), seed=0).start()
        ref, _ = await collect(agg, greedy_request(prompt, N))
        await agg.stop()

        # Engine A: prefill-only + export.
        ea = await TpuEngine(make_args(), seed=0).start()
        toks_a, final_a = await collect(
            ea, greedy_request(prompt, 1, do_remote_decode=True)
        )
        meta = final_a.get("kv_transfer_params")
        assert meta and meta["num_blocks"] == (len(prompt) - 1) // 4
        assert toks_a[0] == ref[0]  # same first token (greedy)
        export = ea.take_export(meta["remote_handle"])
        assert export is not None
        assert ea.take_export(meta["remote_handle"]) is None  # one-shot
        await ea.stop()

        # Engine B (different seed → different random weights? No: same
        # seed param init so weights match the aggregated engine).
        eb = await TpuEngine(make_args(), seed=0).start()
        got, _ = await collect(
            eb, greedy_request(prompt, N, inject=export.to_dict())
        )
        await eb.stop()
        assert got == ref
        return True

    assert asyncio.run(go())


def test_engine_export_ttl_reaped():
    async def go():
        rng = np.random.default_rng(4)
        prompt = rng.integers(1, CFG.vocab_size - 1, size=14).tolist()
        e = await TpuEngine(make_args(), seed=0).start()
        e.export_ttl_s = 0.0  # expire immediately
        _, final = await collect(e, greedy_request(prompt, 1, do_remote_decode=True))
        handle = final["kv_transfer_params"]["remote_handle"]
        # Next step reaps; trigger one by running another request.
        await collect(e, greedy_request(prompt[:6], 2))
        gone = e.take_export(handle)
        await e.stop()
        return gone

    assert asyncio.run(go()) is None


def test_stream_export_ttl_refreshes_on_pull():
    """The reap deadline is per-pull, not per-transfer: every
    get_stream_export lookup pushes it out by export_ttl_s, so a healthy
    long pull outlives any fixed total budget — and once the consumer
    stops pulling, the next reap aborts the stream."""
    import time as _time

    from dynamo_tpu.transfer.stream import KvStreamExport

    async def go():
        e = await TpuEngine(make_args(), seed=0).start()
        try:
            exp = KvStreamExport("h-refresh")
            with e._mutex:
                e._exports["h-refresh"] = (
                    exp, _time.monotonic() + e.export_ttl_s
                )
                _, dl0 = e._exports["h-refresh"]
            _time.sleep(0.01)
            assert e.get_stream_export("h-refresh") is exp
            with e._mutex:
                _, dl1 = e._exports["h-refresh"]
            assert dl1 > dl0
            # Consumer goes away: with an immediate TTL the next engine
            # step reaps the export and aborts the unsealed stream.
            e.export_ttl_s = 0.0
            e.get_stream_export("h-refresh")  # re-arm deadline at "now"
            await collect(e, greedy_request(list(range(1, 7)), 2))
            return e.get_stream_export("h-refresh") is None and \
                exp.abort_reason == "expired"
        finally:
            await e.stop()

    assert asyncio.run(go())


# ---------------------------------------------------------------------------
# e2e: prefill worker + decode worker over the runtime
# ---------------------------------------------------------------------------


def test_disagg_e2e_matches_aggregated():
    async def go():
        url = "memory://disagg1"
        rng = np.random.default_rng(5)
        prompt = rng.integers(1, CFG.vocab_size - 1, size=30).tolist()
        N = 12

        # Aggregated reference.
        agg = await TpuEngine(make_args(), seed=0).start()
        ref, _ = await collect(agg, greedy_request(prompt, N))
        await agg.stop()

        # Prefill worker process (in-process here; procutil covers the
        # spawned shape elsewhere).
        prt = await DistributedRuntime.create(store_url=url)
        pengine = await TpuEngine(make_args(), seed=0).start()
        ph = PrefillHandler(pengine)
        pcomp = prt.namespace("dg").component("prefill")
        await pcomp.endpoint("generate").serve(ph.generate)
        await pcomp.endpoint("kv_fetch").serve(ph.kv_fetch)

        # Decode worker with remote prefill (threshold 8 → our 30-token
        # prompt goes remote).
        drt = await DistributedRuntime.create(store_url=url)
        dengine = await TpuEngine(make_args(), seed=0).start()
        pcomp_client = drt.namespace("dg").component("prefill")
        handler = DisaggDecodeHandler(
            dengine,
            await pcomp_client.endpoint("generate").router(RouterMode.ROUND_ROBIN),
            await pcomp_client.endpoint("kv_fetch").router(RouterMode.DIRECT),
            DisaggConfig(max_local_prefill_length=8),
        )
        got, _ = await collect(handler, greedy_request(prompt, N).to_dict())
        assert handler.remote_prefills == 1
        # Short prompt stays local.
        short = rng.integers(1, CFG.vocab_size - 1, size=6).tolist()
        await collect(handler, greedy_request(short, 3).to_dict())
        assert handler.remote_prefills == 1

        # The decode engine registered the injected blocks: a repeat of the
        # long prompt now prefix-hits locally and stays local.
        got2, _ = await collect(handler, greedy_request(prompt, N).to_dict())
        assert handler.remote_prefills == 1  # still 1: local prefix hit
        assert got2 == ref

        await pengine.stop()
        await dengine.stop()
        await drt.shutdown()
        await prt.shutdown()
        return got, ref

    got, ref = asyncio.run(go())
    assert got == ref


def test_disagg_falls_back_when_no_prefill_workers():
    async def go():
        url = "memory://disagg2"
        rng = np.random.default_rng(6)
        prompt = rng.integers(1, CFG.vocab_size - 1, size=26).tolist()

        drt = await DistributedRuntime.create(store_url=url)
        dengine = await TpuEngine(make_args(), seed=0).start()
        pcomp = drt.namespace("dg").component("prefill")
        handler = DisaggDecodeHandler(
            dengine,
            await pcomp.endpoint("generate").router(RouterMode.ROUND_ROBIN),
            await pcomp.endpoint("kv_fetch").router(RouterMode.DIRECT),
            DisaggConfig(max_local_prefill_length=8),
        )
        got, final = await collect(handler, greedy_request(prompt, 6).to_dict())
        await dengine.stop()
        await drt.shutdown()
        return got, final, handler.local_fallbacks

    got, final, fallbacks = asyncio.run(go())
    assert len(got) == 6 and final.get("finish_reason") == "length"
    assert fallbacks == 1


# ---------------------------------------------------------------------------
# WorkQueue
# ---------------------------------------------------------------------------


def test_work_queue_fifo_and_claim():
    async def go():
        store = await connect_store("memory://q1")
        q = WorkQueue(store, "prefill")
        await q.enqueue({"i": 1})
        await q.enqueue({"i": 2})
        await q.enqueue({"i": 3})
        assert await q.depth() == 3
        got = [await q.dequeue(timeout=1) for _ in range(3)]
        assert [g["i"] for g in got] == [1, 2, 3]
        assert await q.dequeue(timeout=0.05) is None
        return True

    assert asyncio.run(go())


def test_work_queue_blocks_until_enqueue():
    async def go():
        store = await connect_store("memory://q2")
        q = WorkQueue(store, "jobs")

        async def producer():
            await asyncio.sleep(0.05)
            await q.enqueue("late")

        task = asyncio.get_running_loop().create_task(producer())
        item = await q.dequeue(timeout=2)
        await task
        return item

    assert asyncio.run(go()) == "late"


def test_work_queue_competing_consumers():
    async def go():
        store = await connect_store("memory://q3")
        q1 = WorkQueue(store, "jobs")
        q2 = WorkQueue(store, "jobs")
        for i in range(20):
            await q1.enqueue(i)

        async def drain(q):
            out = []
            while (item := await q.dequeue(timeout=0.1)) is not None:
                out.append(item)
            return out

        a, b = await asyncio.gather(drain(q1), drain(q2))
        assert sorted(a + b) == list(range(20))  # no dup, no loss
        return True

    assert asyncio.run(go())


# ---------------------------------------------------------------------------
# Chunked KV streaming + queue-fed dispatch (VERDICT r3 next #3)
# ---------------------------------------------------------------------------


def test_kv_payload_frame_roundtrip_large():
    """>256MiB-equivalent geometry (framing.py caps frames at 256MiB, so
    the old single-frame path would hard-fail): chunked frames must
    round-trip exactly and each stay under the chunk limit."""
    rng = np.random.default_rng(9)
    # 512 MiB per array (1 GiB total): 8 layers x 64 blocks x 128 tokens
    # x 2048 lane-dim, f32
    shape = (8, 64, 128, 2048)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    payload = kv_transfer.KvPagePayload(k=k, v=v, num_tokens=64 * 128)
    assert k.nbytes + v.nbytes > (256 << 20)

    frames = list(payload.to_frames(max_bytes=64 << 20))
    assert frames[0]["kind"] == "kv_header"
    data_frames = frames[1:]
    assert all(len(f["data"]) <= (64 << 20) for f in data_frames)
    assert len(data_frames) == 16  # 8 k-chunks + 8 v-chunks

    back = kv_transfer.KvPagePayload.from_frames(frames)
    np.testing.assert_array_equal(back.k, k)
    np.testing.assert_array_equal(back.v, v)
    assert back.num_tokens == payload.num_tokens


def test_kv_payload_frame_truncation_detected():
    rng = np.random.default_rng(10)
    payload = kv_transfer.KvPagePayload(
        k=rng.standard_normal((2, 3, 4, 8)).astype(np.float32),
        v=rng.standard_normal((2, 3, 4, 8)).astype(np.float32),
        num_tokens=12,
    )
    frames = list(payload.to_frames(max_bytes=64))
    with pytest.raises(ValueError, match="truncated"):
        kv_transfer.KvPagePayload.from_frames(frames[:-1])


def test_disagg_queue_dispatch_matches_aggregated():
    """Queue-fed disagg: decode enqueues, a PrefillPuller consumes, pages
    stream back in multiple small frames — token parity with aggregated."""

    async def go():
        from dynamo_tpu.llm.disagg import PrefillPuller
        from dynamo_tpu.runtime.queue import WorkQueue

        url = "memory://disagg3"
        rng = np.random.default_rng(11)
        prompt = rng.integers(1, CFG.vocab_size - 1, size=30).tolist()
        N = 10

        agg = await TpuEngine(make_args(), seed=0).start()
        ref, _ = await collect(agg, greedy_request(prompt, N))
        await agg.stop()

        prt = await DistributedRuntime.create(store_url=url)
        pengine = await TpuEngine(make_args(), seed=0).start()
        ph = PrefillHandler(pengine, frame_bytes=256)  # force many frames
        pcomp = prt.namespace("dg").component("prefill")
        gen_handle = await pcomp.endpoint("generate").serve(ph.generate)
        await pcomp.endpoint("kv_fetch").serve(ph.kv_fetch)
        puller = PrefillPuller(
            pengine, WorkQueue(prt.store, "prefill"), prt.store,
            gen_handle.instance.instance_id,
        ).start()

        drt = await DistributedRuntime.create(store_url=url)
        dengine = await TpuEngine(make_args(), seed=0).start()
        pclient = drt.namespace("dg").component("prefill")
        handler = DisaggDecodeHandler(
            dengine,
            await pclient.endpoint("generate").router(RouterMode.ROUND_ROBIN),
            await pclient.endpoint("kv_fetch").router(RouterMode.DIRECT),
            DisaggConfig(max_local_prefill_length=8, queue_timeout_s=30),
            queue=WorkQueue(drt.store, "prefill"),
            store=drt.store,
        )
        got, _ = await collect(handler, greedy_request(prompt, N).to_dict())
        assert handler.remote_prefills == 1
        assert puller.jobs_done == 1

        await puller.stop()
        await pengine.stop()
        await dengine.stop()
        await drt.shutdown()
        await prt.shutdown()
        return got, ref

    got, ref = asyncio.run(go())
    assert got == ref


def test_disagg_queue_timeout_falls_back_local():
    """No puller consuming the queue → decode times out and prefills
    locally (disagg is never a correctness dependency)."""

    async def go():
        from dynamo_tpu.runtime.queue import WorkQueue

        url = "memory://disagg4"
        rng = np.random.default_rng(12)
        prompt = rng.integers(1, CFG.vocab_size - 1, size=26).tolist()

        drt = await DistributedRuntime.create(store_url=url)
        dengine = await TpuEngine(make_args(), seed=0).start()
        pcomp = drt.namespace("dg").component("prefill")
        handler = DisaggDecodeHandler(
            dengine,
            await pcomp.endpoint("generate").router(RouterMode.ROUND_ROBIN),
            await pcomp.endpoint("kv_fetch").router(RouterMode.DIRECT),
            DisaggConfig(max_local_prefill_length=8, queue_timeout_s=0.5),
            queue=WorkQueue(drt.store, "prefill"),
            store=drt.store,
        )
        got, final = await collect(handler, greedy_request(prompt, 5).to_dict())
        fallbacks = handler.local_fallbacks
        await dengine.stop()
        await drt.shutdown()
        return got, final, fallbacks

    got, final, fallbacks = asyncio.run(go())
    assert len(got) == 5 and final.get("finish_reason") == "length"
    assert fallbacks == 1


# ---------------------------------------------------------------------------
# Streaming KV data plane (dynamo_tpu/transfer)
# ---------------------------------------------------------------------------


def test_chunk_frame_roundtrip_and_truncation():
    from dynamo_tpu.transfer.stream import (
        KvChunk,
        KvChunkAssembler,
        TransferError,
        chunk_to_frames,
    )

    rng = np.random.default_rng(13)
    pages = (
        rng.standard_normal((2, 3, 4, 8)).astype(np.float32),
        rng.standard_normal((2, 3, 4, 8)).astype(np.float32),
    )
    chunk = KvChunk(block_offset=5, pages=pages, num_tokens=12)
    frames = list(chunk_to_frames(7, chunk, max_bytes=64))
    assert frames[0]["kind"] == "kv_chunk"
    assert frames[0]["idx"] == 7 and frames[0]["block_offset"] == 5
    assert all(len(f["data"]) <= 64 for f in frames[1:])

    asm = KvChunkAssembler()
    out = None
    for f in frames:
        got = asm.feed(f)
        if got is not None:
            assert out is None  # exactly one completion
            out = got
    assert out is not None and out.block_offset == 5 and out.num_tokens == 12
    np.testing.assert_array_equal(out.pages[0], pages[0])
    np.testing.assert_array_equal(out.pages[1], pages[1])

    # A second chunk header while one is mid-assembly is a protocol error.
    asm2 = KvChunkAssembler()
    asm2.feed(frames[0])
    assert asm2.mid_chunk
    with pytest.raises(TransferError):
        asm2.feed(frames[0])
    # Data before any header is too.
    with pytest.raises(TransferError):
        KvChunkAssembler().feed(frames[1])


def test_stream_export_flow_control():
    """ack frees publisher memory; an unacked consumer hits the budget
    and the stream aborts (overrun) instead of growing the heap."""
    from dynamo_tpu.transfer.stream import KvChunk, KvStreamExport

    def chunk(off):
        z = np.zeros((1, 1, 4, 8), np.float32)  # 128 bytes/page
        return KvChunk(block_offset=off, pages=(z, z), num_tokens=4)

    exp = KvStreamExport("h", max_buffer_bytes=3 * 256)
    assert exp.publish(chunk(0)) and exp.publish(chunk(1)) and exp.publish(chunk(2))
    assert not exp.publish(chunk(3))  # over budget -> abort
    assert exp.abort_reason == "overrun"
    # The overrun frees the buffered pages immediately — nobody will
    # pull them, and holding max_buffer_bytes until the TTL reap is the
    # heap pressure the budget exists to prevent.
    assert exp._buffered_bytes == 0
    assert all(c is None for c in exp._chunks)

    exp2 = KvStreamExport("h2", max_buffer_bytes=3 * 256)
    for i in range(3):
        assert exp2.publish(chunk(i))
    exp2.ack(2)  # consumer took chunks 0-1 -> credit returns
    assert exp2.publish(chunk(3))
    assert exp2.abort_reason is None
    got = exp2.chunks_since(2, 10 << 20)
    assert [i for i, _ in got] == [2, 3]
    exp2.seal(num_blocks=4, num_tokens=16)
    assert exp2.state() == (4, True, None)
    # Re-requesting an acked chunk is a protocol error, not silent junk.
    from dynamo_tpu.transfer.stream import TransferError

    with pytest.raises(TransferError):
        exp2.chunks_since(0, 10 << 20)


def test_pull_kv_stream_stall_times_out():
    """A window that never progresses trips the stall deadline -> typed
    timeout (the disagg handler's 'timeout' fallback reason)."""
    from dynamo_tpu.transfer.stream import TransferTimeoutError, pull_kv_stream

    async def go():
        def window_call(cursor, credit, wait_s):
            async def gen():
                yield {"kind": "kv_more", "cursor": cursor}
            return gen()

        with pytest.raises(TransferTimeoutError):
            await pull_kv_stream(window_call, stall_timeout_s=0.3, window_wait_s=0.05)
        return True

    assert asyncio.run(go())


def test_pull_kv_stream_failed_signal_aborts_fast():
    """A prefill that dies before registering its export never aborts on
    the wire (the server just answers kv_more forever) -- the ``failed``
    signal must end the pull immediately, not after the stall budget."""
    import time as _time

    from dynamo_tpu.transfer.stream import TransferAbortedError, pull_kv_stream

    async def go():
        def window_call(cursor, credit, wait_s):
            async def gen():
                yield {"kind": "kv_more", "cursor": cursor}
            return gen()

        t0 = _time.monotonic()
        with pytest.raises(TransferAbortedError):
            await pull_kv_stream(
                window_call, stall_timeout_s=30.0, window_wait_s=0.05,
                failed=lambda: True,
            )
        # One window round-trip, not the 30s stall budget.
        assert _time.monotonic() - t0 < 5.0
        return True

    assert asyncio.run(go())


def _streamed_e2e(url, make_engine_args_prefill, make_engine_args_decode,
                  prompt, N, *, frame_bytes=16 << 20, chaos=None,
                  max_local=8):
    """Run one streamed disagg e2e (push dispatch) -> (tokens, handler,
    prefill_handler)."""

    async def go():
        from dynamo_tpu.llm.disagg import DisaggConfig

        prt = await DistributedRuntime.create(store_url=url)
        pengine = await TpuEngine(make_engine_args_prefill, seed=0).start()
        ph = PrefillHandler(pengine, frame_bytes=frame_bytes, chaos=chaos)
        pcomp = prt.namespace("dg").component("prefill")
        await pcomp.endpoint("generate").serve(ph.generate)
        await pcomp.endpoint("kv_fetch").serve(ph.kv_fetch)

        drt = await DistributedRuntime.create(store_url=url)
        dengine = await TpuEngine(make_engine_args_decode, seed=0).start()
        pclient = drt.namespace("dg").component("prefill")
        handler = DisaggDecodeHandler(
            dengine,
            await pclient.endpoint("generate").router(RouterMode.ROUND_ROBIN),
            await pclient.endpoint("kv_fetch").router(RouterMode.DIRECT),
            DisaggConfig(max_local_prefill_length=max_local,
                         pull_stall_timeout_s=10.0),
        )
        got, _ = await collect(handler, greedy_request(prompt, N).to_dict())
        stats = dict(
            remote=handler.remote_prefills,
            fallbacks=handler.local_fallbacks,
            reasons=dict(handler.fallback_reasons),
            last=dict(handler.last_transfer),
            bytes=handler.transfer_bytes_total,
        )
        await pengine.stop()
        await dengine.stop()
        await drt.shutdown()
        await prt.shutdown()
        return got, stats

    return asyncio.run(go())


@pytest.mark.parametrize("max_prefill", [16, 32])
def test_streamed_disagg_parity_across_chunk_sizes(max_prefill):
    """Chunked streaming (several chunks per prefill) must be
    byte-identical to aggregated serving regardless of chunk size."""
    rng = np.random.default_rng(21)
    prompt = rng.integers(1, CFG.vocab_size - 1, size=60).tolist()
    N = 10

    ref, _ = asyncio.run(_aggregated_run(make_args(), prompt, N))
    got, stats = _streamed_e2e(
        f"memory://sdg_{max_prefill}",
        make_args(max_prefill_tokens=max_prefill),
        make_args(max_prefill_tokens=max_prefill),
        prompt, N,
    )
    assert got == ref
    assert stats["remote"] == 1 and stats["fallbacks"] == 0
    # 60-token prompt, chunked prefill -> several streamed chunks.
    assert stats["last"]["chunks"] >= 2
    assert stats["bytes"] > 0


async def _aggregated_run(args, prompt, N):
    agg = await TpuEngine(args, seed=0).start()
    ref, _ = await collect(agg, greedy_request(prompt, N))
    await agg.stop()
    return ref, None


@pytest.mark.parametrize(
    "p_quant,d_quant",
    [("int8", "int8"), ("none", "int8"), ("int8", "none")],
)
def test_streamed_disagg_kv_quant_parity(p_quant, d_quant):
    """Streamed chunks in the publisher's storage format bridge to the
    decode engine's format per chunk (adapt_pages): output must equal
    the DECODE engine's own aggregated run for every combination."""
    rng = np.random.default_rng(22)
    prompt = rng.integers(1, CFG.vocab_size - 1, size=44).tolist()
    N = 8

    ref, _ = asyncio.run(_aggregated_run(make_args(kv_quant=d_quant), prompt, N))
    got, stats = _streamed_e2e(
        f"memory://sdgq_{p_quant}_{d_quant}",
        make_args(kv_quant=p_quant, max_prefill_tokens=16),
        make_args(kv_quant=d_quant, max_prefill_tokens=16),
        prompt, N,
    )
    assert got == ref
    assert stats["remote"] == 1 and stats["fallbacks"] == 0


def test_chaos_kill_mid_transfer_falls_back_byte_identical():
    """transfer_cut_p=1.0 cuts the wire after the FIRST chunk of every
    pull window (kill-mid-transfer): decode must fall back to local
    prefill and still produce the aggregated stream byte-for-byte."""
    from dynamo_tpu.runtime.chaos import ChaosInjector

    rng = np.random.default_rng(23)
    prompt = rng.integers(1, CFG.vocab_size - 1, size=52).tolist()
    N = 8

    ref, _ = asyncio.run(_aggregated_run(make_args(), prompt, N))
    chaos = ChaosInjector(transfer_cut_p=1.0, seed=3)
    got, stats = _streamed_e2e(
        "memory://sdg_chaos",
        make_args(max_prefill_tokens=16),
        make_args(max_prefill_tokens=16),
        prompt, N, chaos=chaos,
    )
    assert got == ref
    assert stats["remote"] == 0 and stats["fallbacks"] == 1
    assert stats["reasons"].get("transfer") == 1
    assert chaos.stats.transfer_cuts >= 1  # a chunk WAS mid-flight


def test_streamed_disagg_no_workers_reason():
    """Empty prefill fleet: the default-on handler costs one lookup and
    records the no_workers fallback reason."""

    async def go():
        url = "memory://sdg_nofleet"
        rng = np.random.default_rng(24)
        prompt = rng.integers(1, CFG.vocab_size - 1, size=26).tolist()
        drt = await DistributedRuntime.create(store_url=url)
        dengine = await TpuEngine(make_args(), seed=0).start()
        pcomp = drt.namespace("dg").component("prefill")
        handler = DisaggDecodeHandler(
            dengine,
            await pcomp.endpoint("generate").router(RouterMode.ROUND_ROBIN),
            await pcomp.endpoint("kv_fetch").router(RouterMode.DIRECT),
            DisaggConfig(max_local_prefill_length=8),
        )
        t0 = asyncio.get_running_loop().time()
        got, _ = await collect(handler, greedy_request(prompt, 4).to_dict())
        dt = asyncio.get_running_loop().time() - t0
        reasons = dict(handler.fallback_reasons)
        await dengine.stop()
        await drt.shutdown()
        return got, reasons, dt

    got, reasons, dt = asyncio.run(go())
    assert len(got) == 4
    assert reasons == {"no_workers": 1}
    assert dt < 5.0  # fail-fast, not a queue/router timeout


def test_streamed_disagg_queue_dispatch_with_claim():
    """Queue mode: the puller's early CLAIM reply lets the decode worker
    pull chunks while the queued prefill runs -> parity + one job."""

    async def go():
        from dynamo_tpu.llm.disagg import PrefillPuller
        from dynamo_tpu.runtime.queue import WorkQueue

        url = "memory://sdg_queue"
        rng = np.random.default_rng(25)
        prompt = rng.integers(1, CFG.vocab_size - 1, size=50).tolist()
        N = 8

        agg = await TpuEngine(make_args(max_prefill_tokens=16), seed=0).start()
        ref, _ = await collect(agg, greedy_request(prompt, N))
        await agg.stop()

        prt = await DistributedRuntime.create(store_url=url)
        pengine = await TpuEngine(make_args(max_prefill_tokens=16), seed=0).start()
        ph = PrefillHandler(pengine, frame_bytes=512)
        pcomp = prt.namespace("dg").component("prefill")
        gen_handle = await pcomp.endpoint("generate").serve(ph.generate)
        await pcomp.endpoint("kv_fetch").serve(ph.kv_fetch)
        puller = PrefillPuller(
            pengine, WorkQueue(prt.store, "prefill"), prt.store,
            gen_handle.instance.instance_id,
        ).start()

        drt = await DistributedRuntime.create(store_url=url)
        dengine = await TpuEngine(make_args(max_prefill_tokens=16), seed=0).start()
        pclient = drt.namespace("dg").component("prefill")
        handler = DisaggDecodeHandler(
            dengine,
            await pclient.endpoint("generate").router(RouterMode.ROUND_ROBIN),
            await pclient.endpoint("kv_fetch").router(RouterMode.DIRECT),
            DisaggConfig(max_local_prefill_length=8, queue_timeout_s=30),
            queue=WorkQueue(drt.store, "prefill"),
            store=drt.store,
        )
        got, _ = await collect(handler, greedy_request(prompt, N).to_dict())
        stats = (handler.remote_prefills, puller.jobs_done,
                 dict(handler.last_transfer))
        await puller.stop()
        await pengine.stop()
        await dengine.stop()
        await drt.shutdown()
        await prt.shutdown()
        return got, ref, stats

    got, ref, (remote, jobs, last) = asyncio.run(go())
    assert got == ref
    assert remote == 1 and jobs == 1
    assert last["chunks"] >= 2
