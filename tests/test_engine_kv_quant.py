"""int8 paged-KV-cache suite: capacity math, golden stream stability,
tier/transfer propagation.

The quantized cache changes VALUES (logits move by the KV rounding
error) but must never change DISCIPLINE: greedy streams under
kv_quant=int8 are deterministic and byte-identical across pipeline
depths and spec modes, because a token's stored int8 bytes depend only
on its own K/V vector (per-position-per-head scales, model.kv_quantize)
— never on which path wrote it or what else shares its block. Capacity:
kv_bytes_per_block derives from the STORAGE dtype plus scale overhead,
so auto_kv_blocks sizes the pool ~2x larger under int8 for the same HBM
budget (ROADMAP open item 3; PagedAttention 2309.06180 + KIVI
2402.02750 establish the quality headroom).

CPU, test-tiny, every request explicitly seeded (DT004).
"""

import asyncio

import numpy as np
import pytest
from engine_waves import one_wave

import jax.numpy as jnp

from dynamo_tpu.engine import kv_transfer
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.engine import Context

CFG = ModelConfig()  # test-tiny


# ---------------------------------------------------------------------------
# Capacity math (satellite: kv_bytes_per_block must derive from storage)
# ---------------------------------------------------------------------------


def test_kv_bytes_per_block_pins_storage_math():
    m8 = ModelConfig.preset("llama-8b")
    # bf16: 2 (k+v) x L x bs x KVH x hd x 2 bytes.
    dense = EngineArgs(model=m8, block_size=16)
    assert dense.kv_bytes_per_block() == 2 * 32 * 16 * 8 * 128 * 2
    # int8: 1 byte/elem + fp32 scale per (position, kv head).
    quant = EngineArgs(model=m8, block_size=16, kv_quant="int8")
    assert quant.kv_bytes_per_block() == 2 * 32 * (16 * 8 * 128 + 16 * 8 * 4)
    # fp32 dev dtype doubles the dense cost but not the int8 cost.
    dense32 = EngineArgs(model=CFG, block_size=4, dtype="float32")
    assert dense32.kv_bytes_per_block() == 2 * 2 * 4 * 2 * 32 * 4
    quant32 = EngineArgs(model=CFG, block_size=4, dtype="float32", kv_quant="int8")
    assert quant32.kv_bytes_per_block() == 2 * 2 * (4 * 2 * 32 + 4 * 2 * 4)


def test_auto_kv_blocks_doubles_under_int8():
    """The acceptance number: >= 1.9x blocks from the same HBM budget at
    the llama-8b/v5e geometry (head_dim=128 → scale overhead ~3%)."""
    m8 = ModelConfig.preset("llama-8b")
    free = 8 << 30  # ~what int8 weights leave on a 16GB v5e
    dense = EngineArgs.auto_kv_blocks(free, EngineArgs(model=m8))
    quant = EngineArgs.auto_kv_blocks(free, EngineArgs(model=m8, kv_quant="int8"))
    assert quant / dense >= 1.9
    # And the pool cannot silently be mis-sized: blocks x per-block
    # bytes must fit the utilization-scaled budget for BOTH formats.
    for args, n in ((EngineArgs(model=m8), dense),
                    (EngineArgs(model=m8, kv_quant="int8"), quant)):
        assert n * args.kv_bytes_per_block() <= int(free * 0.9)


def test_kv_quant_validated_at_construction():
    with pytest.raises(ValueError):
        EngineArgs(model=CFG, kv_quant="fp8")


# ---------------------------------------------------------------------------
# Quantization scheme consistency (host adapter == device write path)
# ---------------------------------------------------------------------------


def test_host_quantize_matches_device_kv_quantize():
    rng = np.random.default_rng(0)
    L, n, bs, KVH, hd = 2, 3, 4, 2, 32
    k = rng.standard_normal((L, n, bs, KVH * hd)).astype(np.float32)
    v = rng.standard_normal((L, n, bs, KVH * hd)).astype(np.float32)
    kq, vq, ks, vs = kv_transfer.quantize_pages_np(k, v, KVH)
    dq, ds = M.kv_quantize(jnp.asarray(k).reshape(L, n, bs, KVH, hd))
    np.testing.assert_array_equal(kq, np.asarray(dq).reshape(k.shape))
    np.testing.assert_allclose(ks, np.asarray(ds), rtol=1e-6)
    # Round trip bound: |x - q*s| <= s/2 per element.
    back, _ = kv_transfer.dequantize_pages_np(kq, vq, ks, vs, KVH, np.float32)
    err = np.abs(k.reshape(L, n, bs, KVH, hd) - kq.reshape(L, n, bs, KVH, hd) * ks[..., None])
    assert np.all(err <= ks[..., None] / 2 + 1e-7)
    assert back.shape == k.shape


def test_extract_inject_roundtrip_with_scales():
    cache = M.init_kv_cache(CFG, 16, 4, jnp.float32, kv_quant="int8")
    rng = np.random.default_rng(1)
    L, N, _, bs, lanes = cache.kv.shape
    shape = (L, N, bs, lanes)
    sshape = cache.k_scale.shape
    cache = M.KVCache(
        M.fuse_kv(jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                  jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)),
        jnp.asarray(np.abs(rng.standard_normal(sshape)) + 1e-3, jnp.float32),
        jnp.asarray(np.abs(rng.standard_normal(sshape)) + 1e-3, jnp.float32),
    )
    ids = [5, 1, 9]
    pages = kv_transfer.extract_pages(cache, ids)
    assert len(pages) == 4 and pages[0].dtype == np.int8
    assert pages[2].shape == (CFG.num_layers, 3, 4, CFG.num_kv_heads)

    # Wire roundtrip: dict AND chunked frames carry the scale sidecars.
    payload = kv_transfer.KvPagePayload(
        k=pages[0], v=pages[1], num_tokens=12,
        k_scale=pages[2], v_scale=pages[3],
    )
    back = kv_transfer.KvPagePayload.from_dict(payload.to_dict())
    np.testing.assert_array_equal(back.k_scale, pages[2])
    framed = kv_transfer.KvPagePayload.from_frames(list(payload.to_frames(64)))
    np.testing.assert_array_equal(framed.v_scale, pages[3])
    np.testing.assert_array_equal(framed.k, pages[0])

    cache2 = M.init_kv_cache(CFG, 16, 4, jnp.float32, kv_quant="int8")
    cache2 = kv_transfer.inject_pages(cache2, ids, *back.pages())
    np.testing.assert_array_equal(np.asarray(cache2.kv[:, 5]), np.asarray(cache.kv[:, 5]))
    np.testing.assert_array_equal(
        np.asarray(cache2.k_scale[:, 9]), np.asarray(cache.k_scale[:, 9])
    )


def test_adapt_pages_bridges_formats():
    """Heterogeneous fleets: a float payload injects into an int8 cache
    (quantized host-side) and an int8 payload into a float cache
    (dequantized) — arity mismatches never reach the device scatter."""
    rng = np.random.default_rng(2)
    L, bs, KVH, hd = CFG.num_layers, 4, CFG.num_kv_heads, CFG.head_dim
    kf = rng.standard_normal((L, 2, bs, KVH * hd)).astype(np.float32)
    vf = rng.standard_normal((L, 2, bs, KVH * hd)).astype(np.float32)

    quant_cache = M.init_kv_cache(CFG, 8, bs, jnp.float32, kv_quant="int8")
    adapted = kv_transfer.adapt_pages((kf, vf), quant_cache, KVH)
    assert len(adapted) == 4 and adapted[0].dtype == np.int8
    out = kv_transfer.inject_pages(quant_cache, [1, 2], *adapted)
    assert out.kv.dtype == jnp.int8

    float_cache = M.init_kv_cache(CFG, 8, bs, jnp.float32)
    back = kv_transfer.adapt_pages(tuple(adapted), float_cache, KVH)
    assert len(back) == 2
    # Quantize→dequantize stays within the absmax bound of the original.
    err = np.abs(back[0].astype(np.float32) - kf)
    bound = np.abs(kf).reshape(L, 2, bs, KVH, hd).max(-1, keepdims=True) / 127.0
    assert np.all(err.reshape(L, 2, bs, KVH, hd) <= bound / 2 + 1e-6)


def test_concat_page_run_bridges_mixed_arities():
    """A persistent disk tier written under one kv_quant setting and
    reused under another puts BOTH arities in a single leading run — the
    onboard/peer-serve concat must bridge every block to the engine's
    current format, in either order, instead of IndexError-ing (dense
    block last) or silently concatenating int8 bytes as floats (dense
    block first)."""
    rng = np.random.default_rng(3)
    L, bs, KVH, hd = CFG.num_layers, 4, CFG.num_kv_heads, CFG.head_dim
    mk = lambda: rng.standard_normal((L, 1, bs, KVH * hd)).astype(np.float32)
    dense_blk = (mk(), mk())
    kf, vf = mk(), mk()
    quant_blk = kv_transfer.quantize_pages_np(kf, vf, KVH)

    for run in ([dense_blk, quant_blk], [quant_blk, dense_blk]):
        q = kv_transfer.concat_page_run(
            run, quantized=True, num_kv_heads=KVH, dtype="float32")
        assert len(q) == 4 and q[0].dtype == np.int8
        assert q[0].shape[1] == 2 and q[2].dtype == np.float32
        d = kv_transfer.concat_page_run(
            run, quantized=False, num_kv_heads=KVH, dtype="float32")
        assert len(d) == 2 and d[0].dtype == np.float32
    # Blocks already in the target format pass through bit-exact; the
    # foreign block lands within the quantization round-trip bound.
    d = kv_transfer.concat_page_run(
        [quant_blk, dense_blk], quantized=False, num_kv_heads=KVH,
        dtype="float32")
    np.testing.assert_array_equal(d[0][:, 1], dense_blk[0][:, 0])
    err = np.abs(d[0][:, :1] - kf)
    bound = np.abs(kf).reshape(L, 1, bs, KVH, hd).max(-1, keepdims=True) / 127.0
    assert np.all(err.reshape(L, 1, bs, KVH, hd) <= bound / 2 + 1e-6)
    q = kv_transfer.concat_page_run(
        [dense_blk, quant_blk], quantized=True, num_kv_heads=KVH,
        dtype="float32")
    np.testing.assert_array_equal(q[0][:, 1], quant_blk[0][:, 0])
    np.testing.assert_array_equal(q[2][:, 1], quant_blk[2][:, 0])


# ---------------------------------------------------------------------------
# Golden stream stability on the real engine
# ---------------------------------------------------------------------------


def kv_args(depth: int = 2, spec: int = 0, fused: bool = False, **kw) -> EngineArgs:
    defaults = dict(
        model=CFG, block_size=4, num_kv_blocks=256, max_num_seqs=8,
        max_model_len=128, max_prefill_tokens=64, dtype="float32",
        decode_steps=4, kv_quant="int8",
        spec_tokens=spec, spec_gate=0.0, spec_fused=fused,
        pipeline_depth=depth, pipeline_windows=depth > 0,
    )
    defaults.update(kw)
    return EngineArgs(**defaults)


def request(prompt, max_tokens, temperature=0.0, seed=0, logprobs=False,
            top_logprobs=0) -> PreprocessedRequest:
    req = PreprocessedRequest(model="t", token_ids=list(prompt))
    req.sampling.temperature = temperature
    req.sampling.seed = seed
    req.sampling.logprobs = logprobs
    req.sampling.top_logprobs = top_logprobs
    req.stop.max_tokens = max_tokens
    req.stop.ignore_eos = True
    return req


def workload():
    return [
        request([1, 2, 3] * 6, 24),
        request([7, 8, 9, 4] * 4, 17, logprobs=True),
        request([11, 13, 17, 19, 23, 29, 31, 37], 20, logprobs=True, top_logprobs=3),
        request([2, 4, 8], 1),                       # prefill-only
        request(list(range(40, 70)), 9, temperature=0.8, seed=5),  # sampled row
    ]


async def run_stream(engine, req):
    toks, lps, tops = [], [], []
    finish = None
    async for item in engine.generate(req, Context()):
        toks.extend(item.get("token_ids") or [])
        lps.extend(item.get("log_probs") or [])
        tops.extend(item.get("top_log_probs") or [])
        if item.get("finish_reason"):
            finish = item["finish_reason"]
    return toks, lps, tops, finish


async def run_workload(eargs: EngineArgs):
    engine = await TpuEngine(eargs).start()
    try:
        # One admission wave in every engine compared: which prefills share a
        # packed dispatch is then the workload's, not the machine's timing.
        return await one_wave(engine, [run_stream(engine, r) for r in workload()])
    finally:
        await engine.stop()


def test_int8_streams_deterministic_and_depth_invariant():
    """The ISSUE's token-stability gate: greedy (and seeded-sampled)
    streams under kv_quant=int8 are identical run-to-run and across
    pipeline depths — quantized writes are window/batch-composition
    independent."""
    a = asyncio.run(run_workload(kv_args(depth=2)))
    b = asyncio.run(run_workload(kv_args(depth=2)))
    assert a == b
    c = asyncio.run(run_workload(kv_args(depth=0)))
    assert a == c


def test_int8_spec_stepwise_matches_dense():
    """Stepwise spec verify is the byte-identity anchor (same compiled
    decode body as the dense path) — it must stay exact under int8 KV:
    rejected-draft junk is rolled back and rewritten through the SAME
    per-position quantization the dense path would have used."""
    dense = asyncio.run(run_workload(kv_args(spec=0)))
    spec = asyncio.run(run_workload(kv_args(spec=4, fused=False)))
    assert dense == spec


def test_int8_spec_fused_tokens_match_dense():
    """The fused single-pass verify keeps greedy TOKEN streams identical
    under int8 KV (logprob values may move at the last ulp, as on the
    dense/f32 path — see test_engine_spec's fused caveat)."""
    dense = asyncio.run(run_workload(kv_args(spec=0)))
    fused = asyncio.run(run_workload(kv_args(spec=4, fused=True)))
    assert [r[0] for r in dense] == [r[0] for r in fused]
    assert [r[3] for r in dense] == [r[3] for r in fused]


def test_int8_tier_onboard_and_reuse(tmp_path):
    """The whole block economy at int8: write-through offload fills G2
    with int8+scale pages, eviction churn drops the prompt from G1, and
    re-admission onboards the quantized blocks instead of recomputing —
    prefilling only the suffix. (Streams are not asserted byte-equal to
    the first run: the suffix prefill attends the prefix through
    quantized pages where the original prefill attended its own exact
    registers — the int8 KV caveat.)"""

    async def go():
        args = kv_args(
            depth=0, num_kv_blocks=20, max_num_seqs=2, max_model_len=64,
            max_prefill_tokens=32, decode_steps=2,
            host_kv_blocks=64, disk_kv_dir=str(tmp_path),
        )
        engine = await TpuEngine(args).start()
        rng = np.random.default_rng(0)
        try:
            async def run(prompt, n=4, seed=0):
                req = request(list(prompt), n, seed=seed)
                out = []
                async for item in engine.generate(req, Context()):
                    out.extend(item.get("token_ids") or [])
                return out

            A = rng.integers(1, CFG.vocab_size - 1, size=25).tolist()
            first = await run(A)
            assert len(first) == 4
            assert engine.tiers.offloaded_blocks >= 6
            # G2 holds int8 pages + scale sidecars, so the same block
            # budget stores ~half the bytes per block.
            pages = engine.tiers.host.get(
                next(iter(engine.tiers.host._pages))
            )
            assert len(pages) == 4 and pages[0].dtype == np.int8
            assert pages[2].dtype == np.float32

            for _ in range(6):  # churn A out of the tiny G1 pool
                await run(rng.integers(1, CFG.vocab_size - 1, size=25).tolist())
            assert engine.prefix_hit_length(A) == 0

            prefilled0 = engine.total_prefilled
            onboarded0 = engine.tiers.onboarded_blocks
            second = await run(A)
            assert len(second) == 4
            assert engine.tiers.onboarded_blocks - onboarded0 == 6
            assert engine.total_prefilled - prefilled0 == 25 - 24  # suffix only
            return True
        finally:
            await engine.stop()

    assert asyncio.run(go())


def test_int8_disagg_export_inject():
    """Disagg handoff at int8: the prefill engine exports int8 pages +
    scale sidecars (half the bf16 wire bytes), and the decode engine
    injects them as a materialized prefix hit — prefilling only the
    suffix. (Token streams are asserted for shape, not byte-parity with
    a from-scratch run: the suffix recompute attends the prefix through
    quantized pages where a full local prefill attends exact registers —
    the documented int8 caveat.)"""

    async def go():
        prompt = list(range(1, 22))  # 21 tokens → 5 exportable blocks
        engA = await TpuEngine(kv_args(depth=0)).start()
        try:
            reqA = request(prompt, 1)
            reqA.kv_transfer_params = {"do_remote_decode": True}
            meta = None
            async for item in engA.generate(reqA, Context()):
                meta = item.get("kv_transfer_params") or meta
            assert meta and meta["num_blocks"] == 5
            payload = engA.take_export(meta["remote_handle"])
            assert payload is not None
            assert payload.k.dtype == np.int8 and payload.k_scale is not None
            assert payload.k_scale.shape == (CFG.num_layers, 5, 4, CFG.num_kv_heads)
        finally:
            await engA.stop()

        engB = await TpuEngine(kv_args(depth=0)).start()
        try:
            reqB = request(prompt, 8)
            reqB.kv_transfer_params = {"inject": payload.to_dict()}
            outB = []
            async for item in engB.generate(reqB, Context()):
                outB.extend(item.get("token_ids") or [])
            # Injected 5 blocks = 20 positions; only the 1-token suffix
            # was prefilled locally.
            assert len(outB) == 8
            assert engB.total_prefilled == len(prompt) - 20
            return True
        finally:
            await engB.stop()

    assert asyncio.run(go())
