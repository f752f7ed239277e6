"""The attention kernel meets the TPU compiler in tier-1, on the CPU.

Interpret mode (tests/test_ops_attention.py) checks the kernel's numbers
and none of the compiler's rules: PR 7's ``(1, H)`` block of ``[B, H]``
passed every test and could not be lowered for a TPU at all. Two levels:

- ``jax.export`` for ``platforms=["tpu"]`` runs Pallas's own TPU lowering
  (BlockSpec legality) and needs nothing but JAX.
- Where libtpu can describe a v5e topology without a chip, the real
  XLA:TPU and Mosaic compilers run: vector layouts, VMEM and semaphore
  budgets. It executes nothing, so numbers stay the chip's business
  (``chip_smoke.py``, kernel phase).
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import json
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.ops.paged_attention import (
    kernel_unsupported,
    paged_decode_attention,
    paged_spec_attention,
)

GEOMETRIES = ("llama-8b", "qwen2-7b")
VARIANTS = ("decode", "int8", "spec", "tree")
BS = 16


def _kernel_case(cfg: ModelConfig, variant: str, *, ctx: int = 4096, B: int = 16,
                 sharding=None):
    """→ (fn, abstract args) for one kernel variant at ``cfg``'s geometry."""
    S = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    KVH, hd = cfg.num_kv_heads, cfg.head_dim
    G = cfg.num_heads // KVH
    W, L = ctx // BS, 2
    N = 2 * W
    quant = variant == "int8"
    pages = S((L, N, 2, BS, KVH * hd), jnp.int8 if quant else jnp.bfloat16)  # a page: K then V
    scales = S((L, N, BS, KVH), jnp.float32) if quant else None
    layer, tables = S((), jnp.int32), S((B, W), jnp.int32)
    if variant in ("decode", "int8"):
        q, lengths = S((B, KVH, G, hd), jnp.bfloat16), S((B,), jnp.int32)
        return paged_decode_attention, (q, pages, layer, tables, lengths,
                                        scales, scales)
    T = 4
    q, lengths = S((B, T, KVH, G, hd), jnp.bfloat16), S((B, T), jnp.int32)
    anc = S((B, T, T), jnp.int8) if variant == "tree" else None
    return paged_spec_attention, (q, pages, layer, tables, lengths,
                                  None, None, anc)


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("preset", GEOMETRIES)
def test_kernel_lowers_for_tpu(preset, variant):
    fn, args = _kernel_case(ModelConfig.preset(preset), variant)
    exported = jax.export.export(fn, platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module()


@pytest.fixture(scope="module")
def v5e():
    """One abstract v5e device to compile for, or skip with the reason."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or one that wants a chip
        pytest.skip(f"no v5e topology without a chip: {type(e).__name__}: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("preset", GEOMETRIES)
def test_kernel_compiles_for_v5e(v5e, preset, variant):
    fn, args = _kernel_case(ModelConfig.preset(preset), variant, sharding=v5e)
    fn.lower(*args).compile()


# The benchmark's cells call the decode kernel at these shapes (PERF.md
# section 4): decode bucket x table width, at the configuration's attention
# geometry. llama-8b's (32 query heads on 8 KV heads of 128) is Mistral-7B's.
CELL_CALLS = {
    "qwen2.5-7b-int8.sessions": ("qwen2-7b", 64, 256),
    "qwen2.5-7b-int8.chat": ("qwen2-7b", 32, 256),
    "mistral-7b-v0.3-int8.mixed": ("llama-8b", 32, 256),
    "mistral-7b-v0.3-int8.chat.bucket8": ("llama-8b", 8, 256),
}


@pytest.mark.parametrize("cell", list(CELL_CALLS))
def test_decode_kernel_compiles_at_the_cells_call_shapes(v5e, cell):
    preset, B, W = CELL_CALLS[cell]
    fn, args = _kernel_case(ModelConfig.preset(preset), "decode", ctx=W * BS, B=B, sharding=v5e)
    fn.lower(*args).compile()


# The geometries the benchmark serves, with their block sizes (PR 46: a page is
# a block's K then its V, ``[2, bs, KVH*hd]``, one DMA descriptor): the page's
# bytes go 32 KB (Qwen) to 64 KB, its rows 512 to 1,024 lanes.
SERVED_GEOMETRIES = {
    # name: (KVH, G, hd, bs, rows of a decode call, table width)
    "qwen_G7_KVH4_bs16": (4, 7, 128, 16, 64, 256),
    "mistral_G4_KVH8_bs16": (8, 4, 128, 16, 32, 256),
    "lfm2_hd64_bs32": (8, 4, 64, 32, 128, 128),
    "sala_KVH2_bs64": (2, 16, 128, 64, 32, 64),
}


@pytest.mark.parametrize("kernel", ["decode", "decode_int8", "prefill"])
@pytest.mark.parametrize("geometry", list(SERVED_GEOMETRIES))
def test_fused_page_kernels_compile_for_v5e(v5e, geometry, kernel):
    """Mosaic takes the page ``[2, bs, KVH*hd]`` as one copy into its place in
    the chunk buffer at every served geometry, bf16 and int8 (whose tile is
    32 sublanes: a 16-token part is half of one), in the decode kernel's
    unrolled starts and in the prefill kernel's loops."""
    from dynamo_tpu.ops.paged_attention import paged_prefill_attention

    KVH, G, hd, bs, B, W = SERVED_GEOMETRIES[geometry]
    S = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    L, N = 2, 2 * W
    quant = kernel == "decode_int8"
    pages = S((L, N, 2, bs, KVH * hd), jnp.int8 if quant else jnp.bfloat16)
    scales = S((L, N, bs, KVH), jnp.float32) if quant else None
    if kernel == "prefill":
        T = 256
        lowered = jax.jit(paged_prefill_attention).lower(
            S((1, T, KVH, G, hd), jnp.bfloat16), pages, S((), jnp.int32), S((1, W), jnp.int32),
            S((1,), jnp.int32), S((1,), jnp.int32))
    else:
        lowered = paged_decode_attention.lower(
            S((B, KVH, G, hd), jnp.bfloat16), pages, S((), jnp.int32), S((B, W), jnp.int32),
            S((B,), jnp.int32), scales, scales)
    assert "tpu_custom_call" in lowered.compile().as_text()


_IN_PLACE = ("scatter", "dynamic-update-slice", "fusion", "custom-call", "while", "conditional", "call")
_NO_RESULT = ("parameter", "get-tuple-element", "bitcast", "tuple")


def _produced(compiled, shapes, skip: tuple[str, ...]) -> list[str]:
    """Instructions of a compiled program, other than ``skip``'s kinds, whose
    result has one of ``shapes``."""
    want = {"[" + ",".join(map(str, shape)) + "]" for shape in shapes}
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?\S+ = \S*?(\[[\d,]*\])\S* ([\w-]+)\(", line)
        if m and m.group(2) not in skip and m.group(1) in want:
            found.append(line.strip()[:140])
    return found


def _pool_copies(compiled, cache: M.KVCache) -> list[str]:
    """Instructions of a compiled program whose result has the shape of one of
    ``cache``'s pools, of one layer of it, or of the K or V part of either,
    other than what changes a pool in place (the scatters and the fusions
    that wrap them, the kernels that alias it through, the loop that carries
    it): a temporary the size of a pool, as the token scatter of
    ``[2, KVH*hd]`` windows first made (a copy of the whole pool into a
    token-major layout at every decode call; PERF.md section 6, PR 46)."""
    def shapes(full):
        lay = full[1:]
        return {full, lay, (1, *lay), (full[0] * full[1], *full[2:])}

    big = set()
    for name, pool in cache._asdict().items():
        if pool is None:
            continue
        big |= shapes(tuple(pool.shape))
        if name == "kv" and pool.ndim == 5:
            big |= shapes(tuple(pool.shape[:2] + pool.shape[3:]))
    return _produced(compiled, big, _NO_RESULT + _IN_PLACE)


def _period_copies(compiled, params) -> list[str]:
    """Instructions of a compiled dots3 program whose result is one period's
    share, ``[n_win, ...]`` and over a megabyte, of a tensor of
    ``params["swa"]`` (``[P, n_win, ...]``): a loop's operand is a buffer, so a
    period's slice handed to the inner scan as its ``xs`` is written anew
    every period (fourteen such fusions, 694 MB, twice a decode step:
    PERF.md section 6, PR 50). Whatever its kind: the copy is a fusion."""
    stacks = {tuple(t.shape[1:]) for t in jax.tree.leaves(params["swa"])
              if math.prod(t.shape[1:]) * t.dtype.itemsize > 1 << 20}
    return _produced(compiled, stacks, _NO_RESULT)


def _a_layer_of_the_pages(cache: M.KVCache) -> int:
    """Bytes of one layer of the K and V pages."""
    return cache.kv.size // cache.kv.shape[0] * cache.kv.dtype.itemsize


def _eqns(jaxpr, primitive: str) -> list:
    """Every equation of ``primitive`` under ``jaxpr``, nested programs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found.append(eqn)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _eqns(inner, primitive)
    return found


def _latent_case(B: int = 128, W: int = 128, sharding=None):
    """The LongCat cell's call: 64 query heads against one shared 576-wide
    key padded to 640 lanes, value = its first 512, pages of 32 tokens."""
    from dynamo_tpu.ops.paged_attention import latent_decode_attention

    S = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    fn = functools.partial(latent_decode_attention, value_dim=512, scale=192 ** -0.5)
    return jax.jit(fn), (S((B, 64, 640), jnp.bfloat16), S((8, 2 * W, 32, 640), jnp.bfloat16),
                         S((), jnp.int32), S((B, W), jnp.int32), S((B,), jnp.int32))


@pytest.mark.parametrize("variant", VARIANTS + ("latent",))
def test_the_walk_is_one_grid_step_a_row(variant):
    """The structural pin of PR 31. The grid is the rows alone: a row's
    chunks are a loop inside its step, so the table's width adds no grid
    steps (before, ``(B, W // P)``: 512 steps for 46 live rows of the
    sessions cell, 321 of them dead). int8 pages keep the chunk axis, for
    their scale blocks, and nothing else may. And no product takes a
    transposed LEFT operand: the old ``v^T p`` made Mosaic transpose the
    whole ``[512, 512]`` chunk of V every chunk (``tpu.transpose`` in its
    output); ``q k^T`` and ``p v`` contract the left operand's last
    dimension."""
    B, W = 16, 256
    if variant == "latent":
        fn, args = _latent_case(B, W)
    else:
        fn, args = _kernel_case(ModelConfig.preset("qwen2-7b"), variant, ctx=W * BS, B=B)
    (call,) = _eqns(jax.make_jaxpr(fn)(*args).jaxpr, "pallas_call")
    grid = tuple(call.params["grid_mapping"].grid)
    if variant == "int8":
        assert grid[0] == B and len(grid) == 2
    else:
        assert grid == (B,)
    dots = _eqns(call.params["jaxpr"], "dot_general")
    assert dots
    for eqn in dots:
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        assert tuple(lhs_contract) == (eqn.invars[0].aval.ndim - 1,), eqn


def test_int8_kv_kernel_limits_repaired(v5e):
    """The two int8-KV limits the compiler used to enforce and the code
    did not: head_dim 64 (the in-kernel scale broadcast) and a scale block
    that grew with the context until VMEM ran out (from 16k tokens)."""
    fn, args = _kernel_case(ModelConfig.preset("llama-1b"), "int8", sharding=v5e)
    fn.lower(*args).compile()
    fn, args = _kernel_case(
        ModelConfig.preset("qwen2-7b"), "int8", ctx=131072, B=8, sharding=v5e
    )
    fn.lower(*args).compile()


def test_kernel_unsupported_agrees_with_the_compiler(v5e):
    """A geometry ``kernel_unsupported`` names is one Mosaic refuses: the
    engine turns it away at start instead of inside a request."""
    tiny = ModelConfig.preset("test-tiny")
    assert "128" in kernel_unsupported(tiny, BS)
    fn, args = _kernel_case(tiny, "decode", ctx=512, sharding=v5e)
    with pytest.raises(Exception, match="aligned to tiling"):
        fn.lower(*args).compile()
    fn, args = _prefill_kernel_case(tiny, 32, W=32, sharding=v5e)  # the same page DMAs
    with pytest.raises(Exception, match="aligned to tiling"):
        fn.lower(*args).compile()
    for preset in GEOMETRIES + ("llama-1b", "llama-70b"):
        assert kernel_unsupported(ModelConfig.preset(preset), BS) is None


def _int8_params(cfg: ModelConfig, S):
    """The shapes of ``cfg``'s int8 weights, as ``S`` makes them."""
    from dynamo_tpu.engine.quant import random_int8_params_device

    return jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda: random_int8_params_device(cfg, 0)),
    )


def test_multi_decode_window_compiles_for_v5e(v5e):
    """One decode window of the smoke model (chip_smoke.py: qwen2-7b int8,
    full width and depth, 16 rows over a 4096-token table) through the
    compiled kernel: the whole jitted step, not the kernel alone."""
    cfg = ModelConfig.preset("qwen2-7b")
    S = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    params = _int8_params(cfg, S)
    B, K, W, N = 16, 8, 4096 // BS, 4096
    cache = M.KVCache(S((cfg.num_layers, N, 2, BS, cfg.kv_size), jnp.bfloat16))
    i32, f32 = (lambda *s: S(s, jnp.int32)), (lambda *s: S(s, jnp.float32))
    flags = S((B,), jnp.bool_)
    compiled = M.multi_decode.lower(
        cfg, K, "greedy", 0, params, cache,
        i32(B), i32(B), i32(B, W), flags,            # tokens, positions, tables, active
        f32(B), S((B,), jnp.uint32), i32(B),         # temperature, seeds, steps0
        i32(B), f32(B), f32(B), f32(B), i32(B, 1),   # top_k, top_p, penalties
        flags, i32(B), i32(B + 1),                   # chain mask/src, last_toks
        None, None, attn_impl="pallas",
    ).compile()
    # Weights + pool are arguments; what the step adds must leave room in
    # a 16 GB chip beside them.
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    # The pool changes in place: a token's K and V go in as rows of lanes
    # (``write_kv_tokens``), and nothing the window adds is as large as one
    # layer of the pages (3.7 MB against 134; the first form of the write
    # added 4.4 GB, the whole pool laid out token-major).
    assert mem.temp_size_in_bytes < _a_layer_of_the_pages(cache) / 4
    assert not _pool_copies(compiled, cache)


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_prefill_forms_no_layer_of_the_pool_on_v5e(v5e, kv_quant):
    """What the chip's compiler makes of prefill's prefix read (PR 28):
    at the sessions cell's widths (qwen2-7b int8, 5,120 blocks, 4 rows of
    128 tokens over a 256-page table) no instruction's result is one layer
    of the KV pool, or of its scales, and nothing but the in-place scatters
    has the pool's own shape. The parent's layer slice compiled to
    ``dynamic-slice_bitcast_fusion bf16[5120,16,512]``, an 84 MB copy twice
    a layer; a flat view of the int8 scales compiles to a copy of the whole
    scale pool every layer. The jaxpr (tests/test_prefill_page_gather.py)
    shows neither."""
    cfg = ModelConfig.preset("qwen2-7b")
    S = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    N, Bp, T, W = 5120, 4, 128, 256
    cache = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda: M.init_kv_cache(cfg, N, BS, kv_quant=kv_quant)),
    )
    i32 = lambda *s: S(s, jnp.int32)  # noqa: E731
    hlo = M.prefill_batch.lower(
        cfg, _int8_params(cfg, S), cache, i32(Bp, T), i32(Bp, W), i32(Bp), i32(Bp)
    ).compile().as_text()

    L, kv, KVH = cfg.num_layers, cfg.kv_size, cfg.num_kv_heads
    # (a page is K then V since PR 46: the pool's layer, and its K or V part)
    layer = {f"[{N},2,{BS},{kv}]", f"[1,{N},2,{BS},{kv}]", f"[{N},{BS},{kv}]", f"[1,{N},{BS},{kv}]",
             f"[{N},{BS},{KVH}]", f"[1,{N},{BS},{KVH}]"}
    pool = {f"[{L},{N},2,{BS},{kv}]", f"[{L * N},2,{BS},{kv}]", f"[{L},{N},{BS},{kv}]",
            f"[{L * N},{BS},{kv}]", f"[{L},{N},{BS},{KVH}]", f"[{L * N},{BS},{KVH}]"}
    found, entry = [], False
    for line in hlo.splitlines():
        if re.match(r"(ENTRY )?%?\S+ \(.*\) -> .* \{$", line):  # a computation's header
            entry = line.startswith("ENTRY")
            continue
        m = re.match(r"\s*(?:ROOT )?%?\S+ = \S*?(\[[\d,]*\])\S* ([\w-]+)\(", line)
        if not m or m.group(2) in ("parameter", "get-tuple-element", "bitcast", "tuple"):
            continue
        shape, opcode = m.groups()
        # Inside the layer loop the pool's shape belongs to the in-place
        # scatters (and the fusions that wrap them) alone. The entry may
        # re-lay the int8 scale pools out once a call, as the parent's does.
        if shape in layer or (shape in pool and not entry and opcode not in ("scatter", "fusion")):
            found.append(line.strip()[:120])
    assert not found, "\n".join(found)


def _prefill_kernel_case(cfg: ModelConfig, T: int, *, Bp: int = 1, W: int = 256, sharding=None):
    from dynamo_tpu.ops.paged_attention import paged_prefill_attention

    S = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    KVH, hd = cfg.num_kv_heads, cfg.head_dim
    pages = S((2, 512, 2, BS, KVH * hd), jnp.bfloat16)
    return jax.jit(paged_prefill_attention), (
        S((Bp, T, KVH, cfg.num_heads // KVH, hd), jnp.bfloat16), pages,
        S((), jnp.int32), S((Bp, W), jnp.int32), S((Bp,), jnp.int32), S((Bp,), jnp.int32))


@pytest.mark.parametrize("T", [48, 192, 1024])
@pytest.mark.parametrize("preset", GEOMETRIES)
def test_prefill_kernel_compiles_for_v5e(v5e, preset, T):
    """One kernel body for both dense geometries, the query tile from T
    (48, 96 and 128 here): a packed wave of short prompts, one row of a
    long one. The dense cells' whole "fine" ladder (32 .. 2,048) compiled
    in PR 34's scratch run; the prefill program below holds 256 and 2,048."""
    fn, args = _prefill_kernel_case(
        ModelConfig.preset(preset), T, Bp=4 if T <= 256 else 1, sharding=v5e)
    fn.lower(*args).compile()


def test_prefill_kernel_walks_tiles_not_the_table():
    """The structural pin: the grid is rows x query tiles, whatever the
    table's width, and neither product takes a transposed left operand."""
    cfg = ModelConfig.preset("qwen2-7b")
    calls = []
    for W in (8, 256):
        fn, args = _prefill_kernel_case(cfg, 256, Bp=2, W=W)
        (call,) = _eqns(jax.make_jaxpr(fn)(*args).jaxpr, "pallas_call")
        assert tuple(call.params["grid_mapping"].grid) == (2, 2)
        calls.append(call)
    # Both table buckets call ONE kernel: the table is padded ahead of the
    # jitted call, so a worker traces it once a T and not once a (T, W).
    assert str(calls[0].params["jaxpr"]) == str(calls[1].params["jaxpr"])
    assert [v.aval for v in calls[0].invars] == [v.aval for v in calls[1].invars]
    dots = _eqns(call.params["jaxpr"], "dot_general")
    # q k^T and p v, once for plain and once for masked chunks: the heads
    # are a loop (unrolled, the kernel's text took four times as long to compile)
    assert len(dots) == 4
    for eqn in dots:
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        assert tuple(lhs_contract) == (eqn.invars[0].aval.ndim - 1,), eqn


@pytest.mark.parametrize("program,Bp,T", [("prefill_batch", 1, 256), ("prefill_batch", 1, 2048),
                                          ("prefill", 1, 2048), ("prefill_batch", 4, 128)],
                         ids=["256", "2048", "prefill-2048", "packed-4x128"])
def test_prefill_attends_out_of_the_pages_on_v5e(v5e, program, Bp, T):
    """The prefill program at the sessions cell's widths (qwen2-7b int8,
    5,120 blocks, a 256-page table), one row of a 256-token turn and of a
    2,048-token chunk, packed and through the single-row program a long
    prompt's chunks take, and a pack of four 128-token rows: the kernel is in it, nothing in it has the table's
    ``W*bs`` rows (the two gathers) or ``W*bs + T`` columns (the float32
    scores and their softmax), and what the program adds to its arguments
    stays under 100 MB (the XLA form: 77 MB and 2.1 GB of temporaries,
    the scores 125 MB and 1.4 GB of them)."""
    cfg = ModelConfig.preset("qwen2-7b")
    S = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    N, W = 5120, 256
    cache = jax.tree.map(
        lambda a: S(a.shape, a.dtype), jax.eval_shape(lambda: M.init_kv_cache(cfg, N, BS)))
    i32 = lambda *s: S(s, jnp.int32)  # noqa: E731
    if program == "prefill":
        compiled = M.prefill.lower(
            cfg, _int8_params(cfg, S), cache, i32(T), i32(W), i32(), i32(), attn_impl="pallas",
        ).compile()
    else:
        compiled = M.prefill_batch.lower(
            cfg, _int8_params(cfg, S), cache, i32(Bp, T), i32(Bp, W), i32(Bp), i32(Bp),
            attn_impl="pallas",
        ).compile()
    hlo = compiled.as_text()
    # Whole pages, K and V, in one scatter a layer (``write_kv_pages``), in place.
    assert not _pool_copies(compiled, cache)
    assert "paged_prefill_attention" in hlo
    assert "paged_decode_attention" not in hlo
    wide = [ln.strip()[:120] for ln in hlo.splitlines()
            if re.search(rf"[\[,]({W * BS}|{W * BS + T})[\],]", ln)]
    assert not wide, "\n".join(wide[:10])
    assert compiled.memory_analysis().temp_size_in_bytes < 100e6


# -- the LongCat block: latent pages and the grouped expert product ---------------


def _longcat(num_layers: int = 1) -> ModelConfig:
    """The benchmark's LongCat configuration at its published widths (a
    chip's share: 16 of 512 experts, 16,384 vocabulary rows)."""
    return ModelConfig(
        name="longcat-share", block="longcat", vocab_size=16384, published_vocab_size=131072,
        hidden_size=6144, intermediate_size=12288, num_layers=num_layers, num_heads=64,
        num_kv_heads=1, head_dim=192, rope_theta=1e7, tie_embeddings=False,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, mla_scale_q_lora=True, mla_scale_kv_lora=True,
        num_experts=16, num_routed_experts=512, expert_offset=0, zero_expert_num=256,
        num_experts_per_token=12, moe_intermediate_size=2048, routed_scaling_factor=6.0,
    )


LBS = 32  # the latent configuration's block size


def _abstract(tree, S):
    return jax.tree.map(lambda a: S(a.shape, a.dtype), tree)


def test_latent_decode_kernel_compiles_for_v5e(v5e):
    """The LongCat cell's own call: 128 rows over a 4,096-token table."""
    from dynamo_tpu.ops.paged_attention import latent_kernel_unsupported

    cfg = _longcat()
    assert cfg.latent_page_width == 640 and latent_kernel_unsupported(cfg, LBS) is None
    fn, args = _latent_case(128, 4096 // LBS, sharding=v5e)
    fn.lower(*args).compile()


# The LongCat cell's prefill calls (rows, T): singles at the turns' buckets and
# a 2,048-token chunk, and the packs ``EngineArgs.pack_shapes`` gives its limit.
LATENT_PREFILL_CALLS = [(1, 64), (1, 128), (1, 192), (1, 256), (1, 2048), (2, 128), (2, 192), (4, 64), (4, 96)]
LW = 136  # the cell's table: 4,096 positions and a chunk's overhang, in blocks of 32


def _latent_prefill_case(rows: int, T: int, W: int, sharding=None):
    """Head-major absorbed queries (the latent lanes, and the rope lanes padded
    to a lane tile) against the cell's pool of 640-lane rows."""
    from dynamo_tpu.ops.paged_attention import latent_prefill_attention

    S = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    fn = jax.jit(functools.partial(latent_prefill_attention, scale=192 ** -0.5))
    return fn, (S((rows, 64, T, 512), jnp.bfloat16), S((rows, 64, T, 128), jnp.bfloat16),
                S((8, 5632, LBS, 640), jnp.bfloat16), S((), jnp.int32),
                S((rows, W), jnp.int32), S((rows,), jnp.int32), S((rows,), jnp.int32))


@pytest.mark.parametrize("rows,T", LATENT_PREFILL_CALLS)
def test_latent_prefill_kernel_compiles_for_v5e(v5e, rows, T):
    """64 heads x 32 positions are the one left operand of a tile (2,048 rows
    against a 512-token chunk: 4 MiB of float32 scores in VMEM, where the
    dense kernel's 128 positions and 1,024 tokens would be 32), behind the
    cell's table."""
    from dynamo_tpu.ops.paged_attention import _latent_prefill_tile

    assert _latent_prefill_tile(T, 64, 512) == 32
    fn, args = _latent_prefill_case(rows, T, LW, sharding=v5e)
    fn.lower(*args).compile()


def test_latent_prefill_kernel_walks_tiles_not_the_table():
    """The structural pin, as the dense kernel's: rows x query tiles whatever
    the table's width, one kernel for both widths, and two products a chunk
    (plain and masked), neither with a transposed left operand: no head loop."""
    calls = []
    for W in (8, LW):
        fn, args = _latent_prefill_case(2, 64, W)
        (call,) = _eqns(jax.make_jaxpr(fn)(*args).jaxpr, "pallas_call")
        assert tuple(call.params["grid_mapping"].grid) == (2, 2)
        calls.append(call)
    assert str(calls[0].params["jaxpr"]) == str(calls[1].params["jaxpr"])
    dots = _eqns(call.params["jaxpr"], "dot_general")
    assert len(dots) == 4
    for eqn in dots:
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        assert tuple(lhs_contract) == (eqn.invars[0].aval.ndim - 1,), eqn


# The grouped product's calls in the two expert cells: (assignment rows, K, N,
# groups in the stack): LongCat's decode window (128 rows x 12) and a 512-token
# part of a chunk, 16 experts of 4 layers in one stack; LFM2's decode window
# (128 rows x 4) and a four-row pack or a 1,024-token part, a layer's 64 experts.
GROUPED_CALLS = [(1536, 6144, 2048, 64), (6144, 2048, 6144, 64),
                 (512, 2048, 1536, 64), (512, 1536, 2048, 64), (4096, 2048, 1536, 64), (4096, 1536, 2048, 64)]


@pytest.mark.parametrize("rows,k,n,groups", GROUPED_CALLS)
def test_grouped_expert_matmul_compiles_for_v5e(v5e, rows, k, n, groups):
    from dynamo_tpu.engine.longcat import grouped_expert_matmul

    S = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    grouped_expert_matmul.lower(
        S((rows, k), jnp.bfloat16), S((groups, k, n), jnp.bfloat16), S((groups,), jnp.int32),
        impl="gmm",
    ).compile()


EXPERT_CONFIGS = ["lfm2-24b-a2b-pp4", "longcat-flash-omni-ep32", "rehearse-lfm2-tiny", "rehearse-longcat-tiny"]


@functools.cache
def _expert_widths() -> dict[str, tuple[int, int]]:
    """(hidden, expert intermediate) of every benchmark configuration that has experts."""
    from chipbench import model_maps

    out = {}
    for path in sorted(glob.glob(os.path.join(os.path.dirname(__file__), "..", "chipbench", "configs", "*.json"))):
        with open(path) as f:
            cfg = model_maps.model_config(json.load(f))
        if cfg.block in ("longcat", "lfm2"):
            out[os.path.basename(path)[:-5]] = (cfg.hidden_size, cfg.moe_intermediate_size)
    return out


@pytest.mark.parametrize("matrix", ["gate_up", "down"])
@pytest.mark.parametrize("config", EXPERT_CONFIGS)
def test_the_grouped_products_tiles_come_from_its_operands(config, matrix):
    """``longcat.gmm_tiling`` at every (K, N) a benchmark configuration runs,
    in the bf16 the cells serve: one K tile beside an N tile that divides N,
    is whole lane tiles and is at least the stated fetch wide (or N itself),
    else whole rows of N beside a K tile that divides K; no remainder anywhere,
    the row tile ``_moe_tokens`` pads to, and the buffers' bytes under the
    stated budget."""
    from dynamo_tpu.engine import longcat

    widths = _expert_widths()
    assert sorted(widths) == EXPERT_CONFIGS
    D, ie = widths[config]
    K, N = (D, ie) if matrix == "gate_up" else (ie, D)
    tm, tk, tn = longcat.gmm_tiling(K, N, 2)
    assert tm == longcat._GMM_ROW_TILE == 128
    assert K % tk == 0 and N % tn == 0 and (tn % 128 == 0 or tn == N)
    assert (tk == K and (tn == N or tn * 2 >= longcat._GMM_MIN_FETCH_BYTES)) or (tn == N and tk % 128 == 0)
    assert longcat._gmm_buffer_bytes(tm, tk, tn, 2) <= longcat._GMM_VMEM_BUDGET < 16 << 20
    want = {(2048, 1536): (2048, 768), (1536, 2048): (1536, 1024), (6144, 2048): (1024, 2048), (2048, 6144): (2048, 1024)}
    assert (tk, tn) == want.get((K, N), (K, N))


@pytest.mark.parametrize("k,n,itemsize,want", [
    (16384, 2048, 2, (128, 1024, 2048)),   # no [K, 128] tile fits: whole rows of N, the deepest K tile that divides K
    (6144, 2048, 4, (128, 512, 2048)),     # float32 weights: half the elements a tile
    (2048, 1536, 1, (128, 2048, 1536)),    # int8 weights: the whole matrix is one tile
    (96, 40, 4, (128, 96, 40)),            # narrower than a lane tile: the dimension itself
])
def test_the_tiling_rule_off_the_cells_shapes(k, n, itemsize, want):
    from dynamo_tpu.engine import longcat

    tm, tk, tn = got = longcat.gmm_tiling(k, n, itemsize)
    assert got == want and k % tk == 0 and n % tn == 0
    assert longcat._gmm_buffer_bytes(tm, tk, tn, itemsize) <= longcat._GMM_VMEM_BUDGET


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunk_2048", "prefill_packed_256"])
def test_longcat_programs_compile_for_v5e(v5e, program):
    """The jitted programs the cell runs, at the published widths (one
    layer of the four; the layer is one scan body): the decode window at
    128 rows, a 2,048-token chunk and a 256-token packed prefill behind a
    4,096-token table. What a program adds to its arguments must leave
    room in 16 GB beside 10.4 GB of weights and a 3 GB pool."""
    cfg = _longcat()
    S = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    from dynamo_tpu.engine import longcat

    params = _abstract(jax.eval_shape(lambda: longcat.init_params(cfg, jax.random.PRNGKey(0))), S)
    W, N = LW, 2048
    cache = _abstract(jax.eval_shape(lambda: longcat.init_kv_cache(cfg, N, LBS)), S)
    i32, f32 = (lambda *s: S(s, jnp.int32)), (lambda *s: S(s, jnp.float32))
    if program == "decode_window":
        B = 128
        flags = S((B,), jnp.bool_)
        compiled = longcat.multi_decode.lower(
            cfg, 8, "greedy", 0, params, cache,
            i32(B), i32(B), i32(B, W), flags, f32(B), S((B,), jnp.uint32), i32(B),
            i32(B), f32(B), f32(B), f32(B), i32(B, 1), flags, i32(B), i32(B + 1),
            None, None, attn_impl="pallas", experts="gmm",
        ).compile()
    else:
        T = int(program.rsplit("_", 1)[1])
        compiled = longcat.prefill_batch.lower(
            cfg, params, cache, i32(1, T), i32(1, W), i32(1), i32(1), attn_impl="pallas", experts="gmm"
        ).compile()
        # The prefill attends out of the pages in the kernel: nothing in the
        # program has the table's ``W*bs`` rows (the gather of the latents) or
        # columns (the float32 scores, which the XLA form wrote to HBM: 0.54 GB
        # of temporaries at T 2,048). What is left, 0.476 GB at T 2,048 and
        # 0.188 at 256, is the expert layer's 512-token part (at 256 tokens a
        # part it reads 0.390, the absorbed queries and attended latents).
        hlo = compiled.as_text()
        assert "latent_prefill_attention" in hlo
        wide = [ln.strip()[:120] for ln in hlo.splitlines()
                if re.search(rf"[\[,]({W * LBS}|{W * LBS + T})[\],]", ln)]
        assert not wide, "\n".join(wide[:10])
        assert compiled.memory_analysis().temp_size_in_bytes < {2048: 0.5e9, 256: 0.2e9}[T]
    assert compiled.memory_analysis().temp_size_in_bytes < 2.0e9


# -- the LFM2 block: head size 64, a conv-state pool beside K and V, 64 experts held --


def _lfm2(num_layers: int = 10) -> ModelConfig:
    """The benchmark's LFM2-24B-A2B configuration at its published widths: the
    first ``num_layers`` of its 40 layers (conv, conv, attention, conv, ...)."""
    pattern = ("conv", "conv", "full_attention", "conv") * 10
    return ModelConfig(
        name="lfm2-cut", block="lfm2", vocab_size=65536, hidden_size=2048, intermediate_size=11776,
        num_layers=num_layers, num_heads=32, num_kv_heads=8, head_dim=64, rope_theta=1e6,
        tie_embeddings=True, layer_types=pattern[:num_layers], num_dense_layers=2, conv_L_cache=3,
        num_experts=64, num_routed_experts=64, num_experts_per_token=4, moe_intermediate_size=1536,
        routed_scaling_factor=1.0, router_scoring="sigmoid", use_expert_bias=True, norm_topk_prob=True,
    )


@pytest.mark.parametrize("kernel", ["decode", "prefill_256", "prefill_2048"])
def test_paged_kernels_compile_for_v5e_at_head_size_64(v5e, kernel):
    """A geometry no other cell has: G 4, KVH 8, hd 64, bs 32, so two heads
    share a 128-lane tile of the page row. ``kernel_unsupported`` passes it
    (a row of 512 lanes) and the chip's compiler has to agree."""
    cfg = _lfm2()
    assert kernel_unsupported(cfg, LBS) is None and (cfg.kv_size, cfg.num_heads // cfg.num_kv_heads) == (512, 4)
    S = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    KVH, G, hd, W = 8, 4, 64, 4096 // LBS
    pages, layer = S((2, 2048, 2, LBS, KVH * hd), jnp.bfloat16), S((), jnp.int32)
    if kernel == "decode":
        B = 128
        paged_decode_attention.lower(S((B, KVH, G, hd), jnp.bfloat16), pages, layer,
                                     S((B, W), jnp.int32), S((B,), jnp.int32)).compile()
        return
    from dynamo_tpu.ops.paged_attention import paged_prefill_attention

    T = int(kernel.rsplit("_", 1)[1])
    jax.jit(paged_prefill_attention).lower(
        S((1, T, KVH, G, hd), jnp.bfloat16), pages, layer, S((1, W), jnp.int32),
        S((1,), jnp.int32), S((1,), jnp.int32)).compile()


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunk_2048", "prefill_packed_256",
                                     "prefill_single_2048"])
def test_lfm2_programs_copy_no_pool_and_no_expert_stack_on_v5e(v5e, program):
    """The jitted programs the LFM2 cell runs, at the published widths and the
    cell's pool (four layers: a dense conv layer and three expert layers, one
    of them attention): the decode window at 128 rows, a 2,048-token chunk and
    a 256-token packed prefill behind a 4,096-token table. No instruction's
    result is a pool, a layer of one, or a layer's expert stack: the pools
    change in place and the stacks reach the grouped product as they lie."""
    from dynamo_tpu.engine import lfm2

    cfg = _lfm2(4)
    cfg = dataclasses.replace(cfg, num_dense_layers=1)
    S = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    params = _abstract(jax.eval_shape(lambda: lfm2.init_params(cfg, jax.random.PRNGKey(0))), S)
    W, N = 4096 // LBS, 5632
    cache = _abstract(jax.eval_shape(lambda: lfm2.init_kv_cache(cfg, N, LBS)), S)
    i32, f32 = (lambda *s: S(s, jnp.int32)), (lambda *s: S(s, jnp.float32))
    if program == "decode_window":
        B = 128
        flags = S((B,), jnp.bool_)
        compiled = lfm2.multi_decode.lower(
            cfg, 8, "greedy", 0, params, cache,
            i32(B), i32(B), i32(B, W), flags, f32(B), S((B,), jnp.uint32), i32(B),
            i32(B), f32(B), f32(B), f32(B), i32(B, 1), flags, i32(B), i32(B + 1),
            None, None, attn_impl="pallas", experts="gmm",
        ).compile()
    elif program == "prefill_single_2048":  # the program a long prompt's chunks take
        compiled = lfm2.prefill.lower(
            cfg, params, cache, i32(2048), i32(W), i32(), i32(), attn_impl="pallas", experts="gmm"
        ).compile()
    else:
        T = int(program.rsplit("_", 1)[1])
        compiled = lfm2.prefill_batch.lower(
            cfg, params, cache, i32(1, T), i32(1, W), i32(1), i32(1), attn_impl="pallas", experts="gmm"
        ).compile()
    # Nothing a program adds is as large as the attention layer's pages (369 MB;
    # the decode window adds 12 MB, the 2,048-token chunk 125).
    assert compiled.memory_analysis().temp_size_in_bytes < _a_layer_of_the_pages(cache) / 2
    assert not _pool_copies(compiled, cache)
    hlo = compiled.as_text()
    assert hlo.count("paged_prefill_attention" if program != "decode_window" else "tpu_custom_call") >= 1
    D, E, ie, kv = cfg.hidden_size, cfg.num_experts, cfg.moe_intermediate_size, cfg.kv_size
    La, Lc, K = len(cfg.attn_layers), len(cfg.conv_layers), cfg.conv_state_slots
    big = {f"[{La},{N},2,{LBS},{kv}]", f"[{N},2,{LBS},{kv}]", f"[1,{N},2,{LBS},{kv}]",    # the pages, a layer
           f"[{La},{N},{LBS},{kv}]", f"[{N},{LBS},{kv}]", f"[1,{N},{LBS},{kv}]",          # their K or V part
           f"[{Lc},{K},{N},{D}]", f"[{K},{N},{D}]", f"[{N},{D}]", f"[1,1,{N},{D}]",        # conv state, a layer, a slot
           f"[{E},{D},{ie}]", f"[{E},{ie},{D}]"}                                          # a layer's expert stack
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?\S+ = \S*?(\[[\d,]*\])\S* ([\w-]+)\(", line)
        if not m or m.group(2) in ("parameter", "get-tuple-element", "bitcast", "tuple"):
            continue
        shape, opcode = m.groups()
        # a pool's own shape belongs to the in-place scatters, the fusions that wrap them, the
        # kernels that alias it through and the loop that carries it
        if shape in big and opcode not in ("scatter", "fusion", "custom-call", "while", "conditional", "call"):
            found.append(line.strip()[:140])
    assert not found, "\n".join(found)


@pytest.mark.parametrize("block,rows,t", [("lfm2", 4, 384), ("longcat", 4, 96)])
def test_a_packed_wave_is_one_grouped_product_a_layer_and_no_larger_than_a_single(v5e, block, rows, t):
    """A four-row prefill program of each expert block (the largest the LFM2
    cell's limit allows, ``EngineArgs.pack_shapes``; the LongCat block packs
    behind a narrower table than its cell's; ISSUE 38), behind a 4,096-token table: every
    grouped product takes the whole pack's assignment rows (tokens x choices
    in one call a layer and matrix, not one a 512-token part, which would
    stream the layer's experts again), and the program's temporaries are no
    larger than the 2,048-token single's, so ``hbm_peak_gb`` has no cause to
    rise."""
    from dynamo_tpu.engine import lfm2, longcat

    S = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    if block == "lfm2":
        cfg, mod, kw, N = dataclasses.replace(_lfm2(4), num_dense_layers=1), lfm2, {"attn_impl": "pallas"}, 5632
        expert_layers = 3
    else:
        cfg, mod, kw, N, expert_layers = _longcat(), longcat, {"attn_impl": "pallas"}, 2048, 1
    params = _abstract(jax.eval_shape(lambda: mod.init_params(cfg, jax.random.PRNGKey(0))), S)
    cache = _abstract(jax.eval_shape(lambda: mod.init_kv_cache(cfg, N, LBS)), S)
    W = 4096 // LBS

    def i32(*s):
        return S(s, jnp.int32)

    def compiled(b, tt):
        return mod.prefill_batch.lower(
            cfg, params, cache, i32(b, tt), i32(b, W), i32(b), i32(b), experts="gmm", **kw).compile()

    pack, single = compiled(rows, t), compiled(1, 2048)
    products = re.findall(r"%gmm[.\d]* = f32\[(\d+),\d+\]", pack.as_text())
    assignment_rows = rows * t * min(cfg.num_experts_per_token, cfg.num_experts)
    assert products == [str(assignment_rows)] * (3 * expert_layers), products
    assert pack.memory_analysis().temp_size_in_bytes <= single.memory_analysis().temp_size_in_bytes
    assert not _pool_copies(pack, cache)  # a pack's pages go in place as a single row's do


def _sala(mixers: tuple[str, ...]) -> ModelConfig:
    """MiniCPM-SALA's published widths over ``mixers`` (the cell's has all 32 layers)."""
    return ModelConfig(
        name="sala", block="sala", vocab_size=73448, hidden_size=4096, intermediate_size=16384,
        num_layers=len(mixers), num_heads=32, num_kv_heads=2, head_dim=128, rms_norm_eps=1e-6,
        tie_embeddings=False, mixer_types=mixers, lightning_heads=32, lightning_head_dim=128,
        scale_emb=12.0, scale_depth=1.4, dim_model_base=256, max_position=24576)


@pytest.mark.parametrize("program", ["step_kernel", "decode_window", "prefill_chunk_2048",
                                     "prefill_packed_2x256", "prefill_packed_4x128"])
def test_sala_programs_compile_for_v5e(v5e, program):
    """What the MiniCPM-SALA cell runs, at the published widths, pages of 64
    tokens, the cell's pools (4,096 blocks, 41 state slots) and its 24,576-token
    table, over a sparse layer, two lightning layers and a sparse layer: the
    lightning step kernel alone (16 rows), the decode window of 16 rows and a
    2,048-token prefill chunk, and packs of two and four rows (PR 46: with more
    than one row the read of K's tail behind each chunk, as a window over part
    of the page's token axis, copied the whole pool token-major; a pack adds
    210 and 478 MB, the parent's 277 and 478). The pools change in place: a decode window's
    temporaries stay far under one pool's size, and a chunk's under the room
    the configuration's memory arithmetic leaves (its float32 scores are a
    tile of 64 queries over the table's width)."""
    from dynamo_tpu.engine import sala
    from dynamo_tpu.ops.lightning import lightning_decode

    S = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    i32, f32 = (lambda *s: S(s, jnp.int32)), (lambda *s: S(s, jnp.float32))
    B, W, N, slots = 16, 24576 // 64, 4096, 88
    if program == "step_kernel":
        vec = S((B, 32, 128), jnp.bfloat16)
        compiled = lightning_decode.lower(vec, vec, vec, S((24, slots, 32, 128, 128), jnp.bfloat16),
                                          i32(), i32(B), i32(B)).compile()
        assert "lightning_decode" in compiled.as_text()
        return
    cfg = _sala(("minicpm4", "lightning-attn", "lightning-attn", "minicpm4"))
    params = _abstract(jax.eval_shape(
        lambda: sala.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16, quant="int8")), S)
    cache = _abstract(jax.eval_shape(lambda: sala.init_kv_cache(cfg, N, 64, state_slots=slots)), S)
    if program == "decode_window":
        flags = S((B,), jnp.bool_)
        compiled = sala.multi_decode.lower(
            cfg, 8, "greedy", 0, params, cache,
            i32(B), i32(B), i32(B, W), flags, f32(B), S((B,), jnp.uint32), i32(B),
            i32(B), f32(B), f32(B), f32(B), i32(B, 1), flags, i32(B), i32(B + 1),
            None, None, attn_impl="pallas", state_slots=i32(B, 3),
        ).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < _a_layer_of_the_pages(cache) / 4  # 3.7 MB of 268
        assert not _pool_copies(compiled, cache)
        hlo = compiled.as_text()
        assert "lightning_decode" in hlo and "paged_decode_attention" in hlo or hlo.count("tpu_custom_call") >= 2
    elif program.startswith("prefill_packed"):
        rows, t = map(int, program.rsplit("_", 1)[1].split("x"))
        compiled = sala.prefill_batch.lower(
            cfg, params, cache, i32(rows, t), i32(rows, W), i32(rows), i32(rows),
            attn_impl="pallas", state_slots=i32(rows, 6)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
        assert not _pool_copies(compiled, cache)
    else:
        compiled = sala.prefill.lower(
            cfg, params, cache, i32(2048), i32(W), i32(), i32(), None, None,
            attn_impl="pallas", state_slots=i32(6)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 2.0e9
        assert not _pool_copies(compiled, cache)  # 239 MB of scores and chunked scans, no pool among them
        assert "paged_prefill_attention" in compiled.as_text()  # the dense branch, under dense_len


# -- the dots3 block: two latent geometries, a chosen set, a window pool of its own --


def _dots3():
    """The benchmark's dots3 configuration at its published widths (a chip's
    share: 9 of 46 layers, 16 of 256 experts, 19,008 vocabulary rows)."""
    from chipbench import model_maps

    with open(os.path.join(os.path.dirname(__file__), "..", "chipbench", "configs", "dots3-note-prev-ep16.json")) as f:
        doc = json.load(f)
    from chipbench.run import engine_args

    return model_maps.model_config(doc), engine_args(doc)


def _computations(hlo: str) -> dict[str, list[str]]:
    """A compiled program's text by computation: name -> its lines."""
    out, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.-]+) \(.*\{$", line)
        if m:
            cur = out.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    return out


@pytest.mark.parametrize("program", ["index_scores", "chosen_rows", "masked_walk", "window_decode", "window_prefill",
                                     "keep_prefill", "decode_window", "prefill_chunk_2048", "prefill_packed_2x256",
                                     "prefill_packed_4x128"])
def test_dots3_programs_compile_for_v5e(v5e, program):
    """What the dots3 cell runs, at the published widths, the cell's pools
    (18,432 blocks of latents and index keys, the window pool) and its
    32,768-token table: the two new kernels at the decode window's call (32
    rows), the chosen set as a mask over the decode kernel's walk, the latent
    kernels at the second geometry under the window's mask and under a chosen
    set's, then the decode window (both forms of the chosen-rows attend, a
    branch each: the walk's side holds no ``[B, topk, Dk]`` rows), a
    2,048-token chunk and the cell's packs of two and of four rows. No program copies a pool (the chosen rows are gathered as
    rows of lanes, each with its own block and slot) or a period's window
    layers' weights (the inner scan indexes the whole stack), nothing in a
    prefill has the 128 heads' absorbed queries of a whole chunk, and a chunk's
    temporaries leave room in 16 GB beside 9.2 GB of weights and 3.4 GB of pools."""
    from dynamo_tpu.engine import dots3
    from dynamo_tpu.ops import dsa
    from dynamo_tpu.ops.paged_attention import latent_decode_attention, latent_prefill_attention

    cfg, args = _dots3()
    S = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    i32, f32, bf16 = (lambda *s: S(s, jnp.int32)), (lambda *s: S(s, jnp.float32)), (lambda *s: S(s, jnp.bfloat16))
    B, W, N, Nw = 32, args.blocks_per_seq, args.num_kv_blocks, args.window_blocks
    assert (W, Nw, args.window_table_width, args.window_prefill_width) == (1024, 1477, 18, 81)
    kernels = {
        "index_scores": lambda: dsa.index_scores.lower(bf16(B, 64, 128), f32(B, 64), bf16(3, N, LBS, 128), i32(), i32(B, W), i32(B)),
        "chosen_rows": lambda: dsa.sparse_decode_attention.lower(
            bf16(B, 128, 640), bf16(3, N, LBS, 640), i32(), i32(B, W), i32(B, 2048), i32(B), value_dim=512, scale=0.07),
        "masked_walk": lambda: dsa.masked_decode_attention.lower(
            bf16(B, 128, 640), bf16(3, N, LBS, 640), i32(), i32(B, W), i32(B), S((B, W * LBS), jnp.bool_),
            value_dim=512, scale=0.07),
        "window_decode": lambda: latent_decode_attention.lower(
            bf16(B, 64, 1152), bf16(6, Nw, LBS, 1152), i32(), i32(B, 18), i32(B), value_dim=1024, scale=0.06, window=513),
        "window_prefill": lambda: jax.jit(functools.partial(latent_prefill_attention, scale=0.06, window=513)).lower(
            bf16(1, 64, 512, 1024), bf16(1, 64, 512, 128), bf16(6, Nw, LBS, 1152), i32(), i32(1, 81), i32(1), i32(1)),
        "keep_prefill": lambda: jax.jit(functools.partial(latent_prefill_attention, scale=0.07)).lower(
            bf16(1, 128, 512, 512), bf16(1, 128, 512, 128), bf16(3, N, LBS, 640), i32(), i32(1, W), i32(1), i32(1),
            keep=bf16(1, 512, W * LBS)),
    }
    if program in kernels:
        assert "tpu_custom_call" in kernels[program]().compile().as_text()
        return
    params = _abstract(jax.eval_shape(lambda: dots3.init_params(cfg, jax.random.PRNGKey(0))), S)
    cache = _abstract(jax.eval_shape(lambda: dots3.init_kv_cache(cfg, N, LBS, window_blocks=Nw)), S)
    if program == "decode_window":
        flags = S((B,), jnp.bool_)
        compiled = dots3.multi_decode.lower(
            cfg, 8, "greedy", 0, params, cache,
            i32(B), i32(B), i32(B, W), flags, f32(B), S((B,), jnp.uint32), i32(B),
            i32(B), f32(B), f32(B), f32(B), i32(B, 1), flags, i32(B), i32(B + 1),
            None, None, attn_impl="pallas", experts="gmm", state_slots=i32(B, 1 + args.window_table_width),
        ).compile()
        hlo = compiled.as_text()
        assert "dsa_index_scores" in hlo
        # The chosen-rows attend is one kernel call a branch of the rule's
        # ``cond``, in layer 0 and in the scanned full layer: the gather form's
        # branch holds the gathered rows, the walk's nothing of their size.
        sides = [lines for lines in _computations(hlo).values()
                 if any("custom-call(" in ln and "latent_sparse_decode_attention" in ln for ln in lines)]
        gathered = re.compile(rf"\[{B},{cfg.index_topk},640\]|\[{B * cfg.index_topk},640\]")
        assert sorted(any(gathered.search(ln) for ln in lines) for lines in sides) == [False, False, True, True]
        limit = 0.2e9  # 0.115 GB by the compiler's analysis (0.81 with the period's copy, 0.69 GB of it)
    else:
        rows, t = {"prefill_chunk_2048": (1, 2048), "prefill_packed_2x256": (2, 256), "prefill_packed_4x128": (4, 128)}[program]
        compiled = dots3.prefill_batch.lower(
            cfg, params, cache, i32(rows, t), i32(rows, W), i32(rows), i32(rows),
            attn_impl="pallas", experts="gmm", state_slots=i32(rows, args.state_operand_width)).compile()
        assert "latent_prefill_attention" in compiled.as_text()
        # 1.04 GB at T 2,048 (1.29 with the copy): a query block's scores, keys and mask over 32,768 positions,
        # the experts' parts; the packs 0.31 GB at 2 x 256 and 0.36 at 4 x 128 (0.86 both with it)
        limit = 1.25e9 if t == 2048 else 0.45e9
    assert compiled.memory_analysis().temp_size_in_bytes < limit
    assert not _pool_copies(compiled, cache)
    assert not _period_copies(compiled, params)


# -- the DeepSeek block: whole layers over a four-chip host, the kernels inside shard_map --


def _deepseek():
    """The benchmark's DeepSeek-V2 configuration at its published widths (whole
    layers: 160 experts, 128 heads, 102,400 vocabulary rows; 6 of 60 layers)."""
    from chipbench import model_maps
    from chipbench.run import engine_args

    with open(os.path.join(os.path.dirname(__file__), "..", "chipbench", "configs", "deepseek-v2-tp4.json")) as f:
        doc = json.load(f)
    return model_maps.model_config(doc), engine_args(doc)


@pytest.fixture(scope="module")
def v5e_host():
    """The four chips of one described v5e host as the mesh ``--tp 4`` builds."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from dynamo_tpu.parallel.mesh import DP_AXIS, EP_AXIS, TP_KV_AXIS, TP_REP_AXIS

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or one that wants a chip
        pytest.skip(f"no v5e topology without a chip: {type(e).__name__}: {e}")
    return Mesh(np.array(topo.devices).reshape(1, 1, 1, 4), (DP_AXIS, EP_AXIS, TP_KV_AXIS, TP_REP_AXIS))


# A chip's calls under --tp 4: 32 of the 128 heads against the whole latent row;
# the grouped product over a chip's 40 experts of 5 layers in one stack, at the
# decode window's 128 x 6 assignment rows and a 1,024-token part's.
@pytest.mark.parametrize("kernel", ["latent_decode", "latent_prefill_1x256", "latent_prefill_1x2048", "latent_prefill_4x128",
                                    "gmm_gate_768", "gmm_down_768", "gmm_gate_6144", "gmm_down_6144"])
def test_deepseek_kernels_compile_for_v5e_at_a_chips_share(v5e, kernel):
    from dynamo_tpu.engine import longcat
    from dynamo_tpu.ops.paged_attention import latent_decode_attention, latent_kernel_unsupported, latent_prefill_attention

    cfg, args = _deepseek()
    assert latent_kernel_unsupported(cfg, args.block_size) is None and cfg.latent_page_width == 640
    S = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    i32, bf16 = (lambda *s: S(s, jnp.int32)), (lambda *s: S(s, jnp.bfloat16))
    pool = bf16(cfg.cache_layers, args.num_kv_blocks, LBS, 640)
    H = cfg.num_heads // 4
    if kernel == "latent_decode":
        lowered = latent_decode_attention.lower(bf16(128, H, 640), pool, i32(), i32(128, 128), i32(128),
                                                value_dim=512, scale=0.11)
    elif kernel.startswith("latent_prefill"):
        rows, T = map(int, kernel.rsplit("_", 1)[1].split("x"))
        lowered = jax.jit(functools.partial(latent_prefill_attention, scale=0.11)).lower(
            bf16(rows, H, T, 512), bf16(rows, H, T, 128), pool, i32(), i32(rows, LW), i32(rows), i32(rows))
    else:
        _, matrix, rows = kernel.split("_")
        k, n = (5120, 1536) if matrix == "gate" else (1536, 5120)
        # K 5120 by N 1536: whole rows of N beside a quarter of K; N 5120: one K tile beside a quarter of N
        assert longcat.gmm_tiling(k, n, 2) == ((128, 1280, 1536) if matrix == "gate" else (128, 1536, 1280))
        lowered = longcat.grouped_expert_matmul.lower(bf16(int(rows), k), bf16(200, k, n), i32(200), impl="gmm")
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunk_2048", "prefill_packed_4x128", "init"])
def test_deepseek_programs_compile_for_four_v5e_chips(v5e_host, program):
    """The cell's programs at the published widths, ``shard_map``ped over the
    host's four chips as ``--tp 4`` places them: a chip's arguments are its
    share of the weights and the whole latent pool (the file's arithmetic:
    10.73 + 1.38 GB), the kernels are inside (per-device ``tpu_custom_call``s:
    the latent walk in layer 0 and in the scanned layer, the three grouped
    products), the chips exchange (``all-reduce``, ``all-gather``), and what a
    program adds leaves room in 16 GB."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.engine import deepseek
    from dynamo_tpu.parallel.mesh import ModelSharding

    cfg, args = _deepseek()
    sh = ModelSharding(v5e_host, cfg)
    if program == "init":
        build = functools.partial(deepseek.init_params, cfg, jax.random.PRNGKey(0), jnp.bfloat16, mesh=v5e_host)
        compiled = jax.jit(build, out_shardings=sh.param_shardings()).lower().compile()
        mem = compiled.memory_analysis()
        assert 10.70e9 < mem.output_size_in_bytes < 10.76e9 and mem.temp_size_in_bytes < 1.0e9
        return
    S = functools.partial(jax.ShapeDtypeStruct, sharding=NamedSharding(v5e_host, P()))
    params = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                          jax.eval_shape(lambda: deepseek.init_params(cfg, jax.random.PRNGKey(0))), sh.param_shardings())
    cache = _abstract(jax.eval_shape(lambda: deepseek.init_kv_cache(cfg, args.num_kv_blocks, LBS)), S)
    i32, f32 = (lambda *s: S(s, jnp.int32)), (lambda *s: S(s, jnp.float32))
    W = args.blocks_per_seq
    if program == "decode_window":
        B = 128
        flags = S((B,), jnp.bool_)
        compiled = deepseek.multi_decode.lower(
            cfg, args.decode_steps, "greedy", 0, params, cache,
            i32(B), i32(B), i32(B, W), flags, f32(B), S((B,), jnp.uint32), i32(B),
            i32(B), f32(B), f32(B), f32(B), i32(B, 1), flags, i32(B), i32(B + 1),
            None, None, attn_impl="pallas", experts="gmm", mesh=v5e_host).compile()
        limit = 0.4e9   # 0.20 GB by the compiler's analysis
    else:
        rows, t = {"prefill_chunk_2048": (1, 2048), "prefill_packed_4x128": (4, 128)}[program]
        compiled = deepseek.prefill_batch.lower(
            cfg, params, cache, i32(rows, t), i32(rows, W), i32(rows), i32(rows),
            attn_impl="pallas", experts="gmm", mesh=v5e_host).compile()
        assert "latent_prefill_attention" in compiled.as_text()
        limit = 0.7e9   # 0.41 GB at T 2,048, 0.16 at 4 x 128
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    assert hlo.count("tpu_custom_call") == 5 and "all-reduce" in hlo and "all-gather" in hlo
    assert 12.05e9 < mem.argument_size_in_bytes < 12.20e9
    assert mem.temp_size_in_bytes < limit
