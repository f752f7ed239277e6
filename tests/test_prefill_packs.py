"""A wave's prefills as one packed dispatch (ISSUE 38), on the CPU: the limit
the runner derives from a model's own bytes and operations, on the published
configurations' shapes; the planner that packs across T buckets; and the
engine serving a packed wave, behind prefix hits, the tokens the singles
serve, for each of the three blocks; a shape whose program is not there yet
goes as singles and builds nothing inside a request."""

import asyncio
import json
import os

import engine_waves
import jax
import jax.numpy as jnp
import pytest

from chipbench import run as chipbench_run
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import PACK_ROWS, EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.runner import _OPS_PER_BYTE, LocalRunner, pack_limit
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.metrics import MetricsRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
V5E = _OPS_PER_BYTE["TPU v5 lite"]


def published(name: str) -> tuple[EngineArgs, int]:
    """A cell's EngineArgs as the benchmark's worker gets them, and the limit
    the runner derives for it on a v5e: the weights from shapes alone."""
    with open(os.path.join(HERE, "..", "chipbench", "configs", f"{name}.json")) as f:
        eargs = chipbench_run.engine_args(json.load(f))
    cfg = eargs.model
    if eargs.quant == "int8":
        from dynamo_tpu.engine.quant import random_int8_params_device

        shapes = jax.eval_shape(lambda: random_int8_params_device(cfg, 0, eargs.dtype))
    else:
        block = M.block_module(cfg)
        shapes = jax.eval_shape(
            lambda: block.init_params(cfg, jax.random.PRNGKey(0), jnp.dtype(eargs.dtype)))
    weight_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(shapes))
    return eargs, pack_limit(cfg, weight_bytes, V5E)


LONGCAT_SHAPES = ((2, 128), (2, 192), (4, 64), (4, 96))


@pytest.mark.parametrize("name,lo,hi,shapes", [
    ("qwen2.5-7b-int8", 110, 127, ()),
    ("mistral-7b-v0.3-int8", 110, 127, ()),
    ("longcat-flash-omni-ep32", 400, 500, LONGCAT_SHAPES),
    ("lfm2-24b-a2b-pp4", 1500, 1900, ((2, 384), (2, 512), (4, 256), (4, 384))),
])
def test_the_limit_comes_from_the_models_bytes_and_operations(name, lo, hi, shapes):
    """Dense int8 weights reach the chip's ridge at ~120 padded tokens, under
    four rows of the smallest bucket: every suffix goes alone as it always
    did and no program is compiled; 64 small experts all held stay bound by
    their bytes to ~1,700, so four session turns share one weight stream; the
    latent block's limit of ~445 holds four rows of its two smallest buckets
    and pairs up to 192 tokens since its prefill attends out of the pages in a
    kernel that walks only what a row can see (until PR 44 a row cost the
    table's 4,096 latents multiplied out, ~99 tokens' worth, and four of those
    did not fit). No block has a cost a row beside its tokens now. No program
    holds more than ``max_prefill_tokens``."""
    eargs, limit = published(name)
    assert lo <= limit <= hi
    assert eargs.pack_shapes(limit) == shapes
    assert all(rows * t <= limit and rows * t <= eargs.max_prefill_tokens for rows, t in shapes)
    turns = [150, 190, 170, 130]   # four session turns: the 192 bucket
    packs = eargs.plan_prefill_packs(turns, shapes)
    if name.startswith("lfm2"):
        assert packs == [([1, 2, 0, 3], 4, 256)]
    elif name.startswith("longcat"):
        # four rows of 192 pass the limit: two pairs, each one weight stream
        assert packs == [([1, 2], 2, 192), ([0, 3], 2, 192)]
        # a wave of that cell's suffixes (64-256 new tokens): the longest alone
        # at its own bucket, the rest in pairs at the smallest T that holds them
        assert eargs.plan_prefill_packs([100, 250, 180, 70, 140], shapes) == [
            ([1], 1, 256), ([2, 4], 2, 192), ([0, 3], 2, 128)]
        assert eargs.plan_prefill_packs([60, 90, 64, 33], shapes) == [([1, 2, 0, 3], 4, 96)]
    else:
        assert packs == [([i], 1, 192) for i in (1, 2, 0, 3)]
        assert [rows for _, rows, _ in eargs.plan_prefill_packs([40, 30], shapes)] == [1, 1]
        # The same weights in bf16 double the limit, and short suffixes pack.
        assert (4, 32) in eargs.pack_shapes(2 * limit)


def test_the_chip_is_looked_up_by_kind_and_an_unknown_one_is_a_v5e_and_says_so():
    """The table holds the one chip the cells were measured on; another
    device (the tests' CPU) takes its ratio and the start line names that."""
    cfg = ModelConfig()
    assert list(_OPS_PER_BYTE) == ["TPU v5 lite"] and 235 < V5E < 245  # 197 TFLOP/s over 819 GB/s
    assert pack_limit(cfg, 4 * cfg.param_count()) == pack_limit(cfg, 4 * cfg.param_count(), V5E)
    assert pack_limit(cfg, 4 * cfg.param_count(), 2 * V5E) > pack_limit(cfg, 4 * cfg.param_count(), V5E)
    args = EngineArgs(model=cfg, block_size=8, num_kv_blocks=32, max_model_len=64,
                      max_prefill_tokens=64, dtype="float32")
    runner = LocalRunner(args)
    runner.start()
    try:
        assert f" kv_page_bytes={2 * 8 * cfg.kv_size * 4} attention: " in runner._start_line("")
        assert runner._start_line("").endswith(
            f" prefill_pack<={runner.pack_limit_tokens} tok"
            " (at the v5e's operations a byte: this device_kind has no entry)")
    finally:
        runner.stop()


ARGS = EngineArgs(block_size=32, max_model_len=4096, max_prefill_tokens=2048)
LFM2_SHAPES = ((2, 384), (2, 512), (4, 256), (4, 384))


@pytest.mark.parametrize("suffixes,shapes,want", [
    # a wave of five over four T buckets: 4 + 1, the pack at its longest member's bucket
    ([100, 290, 130, 200, 70], LFM2_SHAPES, [([1, 3, 2, 0], 4, 384), ([4], 1, 96)]),
    # what no shape holds goes alone and the rest still packs
    ([600, 100, 90], LFM2_SHAPES, [([0], 1, 1024), ([1, 2], 2, 384)]),
    # three fill more than half a program of four: one dispatch, one row inactive
    ([300, 280, 90], ((4, 256), (4, 384)), [([0, 1, 2], 4, 384)]),
    ([64, 65, 66], LFM2_SHAPES, [([2, 1, 0], 4, 256)]),
    # two do not: the program of two; six: 4 + 2; seven: 4 + 3 in a program of four
    ([64, 65], LFM2_SHAPES, [([1, 0], 2, 384)]),
    ([64, 65], ((4, 256), (4, 384)), [([1], 1, 96), ([0], 1, 64)]),
    ([200] * 6, LFM2_SHAPES, [([0, 1, 2, 3], 4, 256), ([4, 5], 2, 384)]),
    ([200] * 7, LFM2_SHAPES, [([0, 1, 2, 3], 4, 256), ([4, 5, 6], 4, 256)]),
    # no program yet: singles at their own buckets, as before
    ([100, 290, 130], (), [([1], 1, 384), ([2], 1, 192), ([0], 1, 128)]),
    ([], LFM2_SHAPES, []),
])
def test_a_wave_packs_across_t_buckets_into_the_programs_there_are(suffixes, shapes, want):
    assert ARGS.plan_prefill_packs(suffixes, shapes) == want
    flat = sorted(i for idx, _, _ in want for i in idx)
    assert flat == list(range(len(suffixes)))  # every suffix once, none dropped
    assert all(rows // 2 < len(idx) <= rows for idx, rows, _ in want)


@pytest.mark.parametrize("chunk", [2048, 512])
@pytest.mark.parametrize("limit", [0, 63, 120, 445, 1718, 5000])
def test_no_pack_shape_passes_the_limit_or_the_largest_single(limit, chunk):
    args = ARGS.replace(max_prefill_tokens=chunk)
    shapes = args.pack_shapes(limit)
    assert all(rows in PACK_ROWS and rows * t <= limit and rows * t <= chunk
               and t in args.prefill_buckets for rows, t in shapes)
    assert len(shapes) <= 2 * len(PACK_ROWS)
    assert (limit < PACK_ROWS[-1] * args.prefill_buckets[0]) == (shapes == ())


# -- through the engine ----------------------------------------------------------


def greedy(prompt_ids, max_tokens=5) -> PreprocessedRequest:
    req = PreprocessedRequest(model="t", token_ids=list(prompt_ids))
    req.sampling.temperature = 0.0
    req.sampling.seed = 0
    req.stop.max_tokens = max_tokens
    req.stop.ignore_eos = True
    return req


def engine_args(preset: str) -> EngineArgs:
    return EngineArgs(model=ModelConfig.preset(preset), block_size=8, num_kv_blocks=160, max_num_seqs=8,
                      max_model_len=256, max_prefill_tokens=256, dtype="float32")


async def tokens(engine, req) -> list[int]:
    return [t async for o in engine.generate(req, Context()) for t in o.get("token_ids", [])]


async def one_wave(engine, prompts) -> list[list[int]]:
    return await engine_waves.one_wave(engine, [tokens(engine, greedy(p)) for p in prompts])


def toks(n: int, seed: int, vocab: int) -> list[int]:
    return [(seed * 7919 + 31 * j * (seed + 3)) % (vocab - 2) + 1 for j in range(n)]


def without_packs(engine: TpuEngine) -> TpuEngine:
    """The runner as it is before any packed program exists."""
    engine._runner._start_pack_compiles = lambda: None
    return engine


HISTORIES = (17, 33, 9, 41, 26, 12, 20)   # 2, 4, 1, 5, 3, 1 and 2 whole blocks cached
TURNS = (10, 28, 45, 60, 7, 30, 18)       # suffixes of 8 to 61 tokens: the 32, 48 and 64 buckets


@pytest.mark.parametrize("preset", ["test-tiny", "longcat-tiny", "lfm2-tiny"])
def test_a_packed_wave_behind_prefix_hits_serves_the_singles_tokens(preset):
    """Seven sessions whose histories are cached to different depths send
    turns of different lengths at once: packed (4 + 3 in a program of four
    with an inactive row; each row from its own ``start_pos``: its K and V,
    its latents, its conv state in the block before) they are served the
    greedy tokens they are served one by one."""
    vocab = ModelConfig.preset(preset).vocab_size

    async def serve(engine: TpuEngine):
        await engine.start()
        try:
            hist = [toks(n, s + 1, vocab) for s, n in enumerate(HISTORIES)]
            for h in hist:
                await tokens(engine, greedy(h, 2))
            hits0, rows0 = engine.pool.hit_blocks, dict(engine.prefill_dispatch_rows)
            wave = [h + toks(n, 50 + s, vocab) for s, (h, n) in enumerate(zip(hist, TURNS))]
            out = await one_wave(engine, wave)
            rows = {r: n - rows0.get(r, 0) for r, n in engine.prefill_dispatch_rows.items()}
            rows = {r: n for r, n in rows.items() if n}
            resumes = dict(engine.conv_resumes) if engine.conv_resumes is not None else None
            return out, rows, engine.pool.hit_blocks - hits0, resumes, engine._runner.packed_ready
        finally:
            await engine.stop()

    packed, rows, hits, resumes, ready = asyncio.run(serve(TpuEngine(engine_args(preset))))
    singles, rows1, hits1, resumes1, ready1 = asyncio.run(serve(without_packs(TpuEngine(engine_args(preset)))))
    assert ready == {(2, 96), (2, 128), (4, 48), (4, 64)} and ready1 == frozenset()
    assert packed == singles and all(len(t) == 5 for t in packed)
    assert hits == hits1 == sum((n - 1) // 8 for n in HISTORIES)
    assert rows == {4: 2}, rows   # seven real rows and one inactive
    assert rows1 == {1: 7}
    if preset == "lfm2-tiny":
        assert resumes == resumes1 and resumes["recompute"] == 0 and resumes["cache"] == 7


def test_a_shape_whose_program_is_not_ready_goes_as_singles_and_compiles_nothing_in_a_request():
    """Until the runner's own thread has a packed program, the wave goes as
    the one-row dispatches it always was; once it has, the pack runs the
    program compiled from shapes at start, and the jitted entry point, which
    would compile inside the request, is not asked for a new shape."""
    args = engine_args("test-tiny")
    vocab = args.model.vocab_size
    seen, built, picked = [], [], []
    # Every program JAX builds from here on: picking a sequence's row out of a
    # pack's logits (runner.stack_rows) is an eager one, by the logits' shape.
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **kw: built.append(name) if name.endswith("backend_compile_duration") else None)

    async def go():
        engine = without_packs(TpuEngine(args))
        runner = engine._runner
        registry = MetricsRegistry()
        engine.bind_metrics(registry)
        await engine.start()
        try:
            inner = runner.prefill_batch
            runner.prefill_batch = lambda toks, *a, **kw: (seen.append(toks.shape), inner(toks, *a, **kw))[1]
            wave = [toks(n, s, vocab) for s, n in enumerate((20, 30, 40, 50))]
            first = await one_wave(engine, wave)
            before, seen[:] = list(seen), []
            assert runner.packed_ready == frozenset() and runner._pack_thread is None
            del runner._start_pack_compiles   # the class's own again (conftest.py: it waits)
            runner._start_pack_compiles()
            assert runner.packed_ready == frozenset(args.pack_shapes(runner.pack_limit_tokens))
            jitted = M.prefill_batch._cache_size()
            wave2 = [toks(n, 10 + s, vocab) for s, n in enumerate((20, 30, 40, 50))]
            stack = runner.stack_rows
            runner.stack_rows = lambda srcs: (built.clear(), stack(srcs), picked.append(len(built)))[1]
            second = await one_wave(engine, wave2)
            del runner.stack_rows
            grew = M.prefill_batch._cache_size() - jitted + sum(picked)
            # The cache a pack leaves is the cache the jitted programs were
            # compiled for: the same singles and decode windows again, behind
            # the pack, find their programs and compile none anew.
            jitted = [f._cache_size() for f in (M.prefill_batch, M.prefill, M.multi_decode)]
            runner._packed.clear()
            wave3 = [toks(n, 20 + s, vocab) for s, n in enumerate((20, 30, 40, 50))]
            third = await one_wave(engine, wave3)
            assert all(len(t) == 5 for t in third)
            assert [f._cache_size() for f in (M.prefill_batch, M.prefill, M.multi_decode)] == jitted
            await engine.run_on_engine_thread(engine._update_gauges)
            return (first, second, before, list(seen), grew, registry.render(), runner._start_line(""),
                    runner.pack_limit_tokens)
        finally:
            await engine.stop()

    first, second, before, after, grew, page, line, limit = asyncio.run(go())
    assert all(len(t) == 5 for t in first + second)
    assert before and all(shape[0] == 1 for shape in before)
    assert any(shape[0] > 1 for shape in after) and grew == 0
    assert 'dynamo_tpu_engine_prefill_dispatch_rows_total{rows="1"}' in page
    assert 'dynamo_tpu_engine_prefill_dispatch_rows_total{rows="4"} 1' in page
    assert "dynamo_tpu_engine_prefill_rows_total 12" in page
    assert 470 < limit < 490 and f" prefill_pack<={limit} tok" in line  # float32: 4 B x 240.5 / 2


def test_a_row_with_an_adapter_and_a_mesh_keep_to_one_row():
    """The packed programs take no adapter bank and are compiled for one
    device: under a mesh the limit is 0 and nothing is compiled."""
    from dynamo_tpu.parallel.mesh import ModelSharding, build_mesh

    args = EngineArgs(model=ModelConfig(), block_size=8, num_kv_blocks=32, max_model_len=64,
                      max_prefill_tokens=64, dtype="float32", tp=2)
    runner = LocalRunner(args, sharding=ModelSharding(build_mesh(tp=2, cfg=args.model), args.model))
    runner.start()
    try:
        assert runner.pack_limit_tokens == 0 and runner._pack_thread is None
        assert runner._start_line("").endswith(" prefill_pack<=0 tok")   # no limit, no ratio assumed
    finally:
        runner.stop()
