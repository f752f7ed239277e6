"""ModelRunner: every device dispatch the engine makes, behind one seam.

Single-process serving uses ``LocalRunner`` directly (zero overhead).
Multi-host serving mirrors the JAX SPMD model: ONE logical worker spans H
processes (one per host), every process must issue the SAME jitted calls
on the SAME global mesh, and only process 0 (the leader) looks at
results. The leader's engine drives a ``LeaderRunner`` that broadcasts a
compact descriptor of each dispatch over TCP before executing it locally;
follower processes run ``follower_loop`` which replays the descriptors
against their own ``LocalRunner``. Host inputs are small (tokens, tables,
sampling knobs), so the step stream is cheap; results chain on-device
(windows reference the previous window's output by id, never by value).

Reference analogue: the role the NCCL/MPI launch scripts play for
multi-node engines (reference: components/backends/sglang/slurm_jobs/
submit_job_script.py, components/backends/vllm/launch/dsr1_dep.sh:86-105)
— but TPU-native: jax.distributed + a mirrored dispatch stream instead of
torchrun per-rank processes.

Failure model: a dead follower stalls the collectives; the leader's lease
expires and the cluster routes around the whole worker (same blast radius
as a dead NCCL rank in the reference).
"""

from __future__ import annotations

import functools
import os
import socket
import struct
import threading
import time
from collections import OrderedDict
from typing import Any

import msgpack
import numpy as np

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import kv_transfer
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs, spec_verify_widths
from dynamo_tpu.engine.sampler import (
    sample_full,
    sample_simple,
    token_logprobs,
)
from dynamo_tpu.runtime.logging import get_logger

log = get_logger("runner")

_RETAIN = 128  # refs kept for chaining/sampling (identical on all hosts)

# A chip's peak operations over its peak bytes a second, by ``device_kind``
# (the v5e's published 197 TFLOP/s in bf16 over 819 GB/s of HBM, the chip every
# cell is measured on): the one constant of ``pack_limit``. A device the table
# lacks is taken as a v5e, and the start line says so.
_OPS_PER_BYTE = {"TPU v5 lite": 197e12 / 819e9}
_OPS_PER_BYTE_DEFAULT = _OPS_PER_BYTE["TPU v5 lite"]


def pack_limit(cfg, weight_bytes: int, ops_per_byte: float = _OPS_PER_BYTE_DEFAULT) -> int:
    """How many padded tokens one prefill dispatch may hold before their
    operations take longer than the weights take to stream: ``weight_bytes``
    (every leaf the call holds, experts included) x the chip's operations a
    byte over 2 x the parameters a token is multiplied by. Under it a second
    row rides the same weight stream; over it the rows are cheaper apart,
    each padded to its own bucket. ~120 for a dense int8 7B, ~1,700 for a
    model of many small experts all held."""
    return int(weight_bytes * ops_per_byte / (2 * cfg.active_param_count()))


@jax.jit
def _fold_tokens(last_toks, toks, slots):
    """Scatter sampled tokens into the persistent per-slot buffer (one
    tiny compiled variant per batch bucket). ``slots`` names each row's
    stable sequence slot; padding rows point at the dummy tail slot."""
    return last_toks.at[slots].set(toks)


@functools.partial(jax.jit, donate_argnums=(0,))
def _bank_write(bank_arr, update, slot):
    """Write one adapter's factor array into bank slot ``slot`` (traced
    scalar — ONE compile per array shape, not per slot; the bank is
    donated so the update is in-place)."""
    return jax.lax.dynamic_update_slice_in_dim(
        bank_arr, update[:, None], slot, axis=1
    )


class StepRef:
    """Opaque handle to a dispatch's device-side results."""

    __slots__ = ("rid", "arrs", "hist")

    def __init__(self, rid: int, arrs: tuple, hist=None):
        self.rid = rid
        self.arrs = arrs
        # The dispatch's expert-routing histogram (engine/longcat.py), which
        # rides the fetch of ``arrs``; None for a block that routes nothing.
        self.hist = hist


def start_host_fetch(arrs) -> None:
    """Begin async device→host transfers for a dispatch's result arrays.
    Called by the engine at dispatch time so the D2H copy runs while the
    device executes subsequent work; the later ``np.asarray`` then
    completes from the host-side buffer instead of blocking on a device
    round trip. No-op for host-resident arrays."""
    for a in arrs:
        fn = getattr(a, "copy_to_host_async", None)
        if fn is not None:
            fn()


def host_ready(arrs) -> bool:
    """True when every array's device computation (and any started host
    copy) has completed — fetching now will not block the caller on
    device work. Arrays without ``is_ready`` (numpy) are always ready."""
    for a in arrs:
        fn = getattr(a, "is_ready", None)
        if fn is not None and not fn():
            return False
    return True


def _pack_np(a: np.ndarray) -> dict:
    a = np.ascontiguousarray(a)
    return {"b": a.tobytes(), "d": str(a.dtype), "s": list(a.shape)}


def _unpack_np(d: dict) -> np.ndarray:
    return np.frombuffer(d["b"], np.dtype(d["d"])).reshape(d["s"])


def _with_histogram(block, program):
    """``program``'s results and then a routing histogram in the last place:
    None from the dense block, which has none."""
    if block is not M:
        return program
    return lambda *a, **kw: (*program(*a, **kw), None)


def _block_programs(cfg):
    """→ (the module that runs ``cfg.block``, its jitted prefill_batch,
    prefill, multi_decode and decode_step). The one place the block is
    chosen. Every program returns its results and then a routing histogram
    in the last place (``_with_histogram``)."""
    block = M.block_module(cfg)
    programs = (block.prefill_batch, block.prefill, block.multi_decode, block.decode_step)
    return (block, *(_with_histogram(block, fn) for fn in programs))


class LocalRunner:
    """Owns device state (params, KV cache, sharding) and executes
    dispatches. Thread-affinity: engine/scheduler thread only."""

    def __init__(self, args: EngineArgs, params: Any | None = None,
                 seed: int = 0, sharding=None):
        self.args = args
        self.cfg = args.model
        (self._block, self._prefill_batch, self._prefill, self._multi_decode,
         self._decode_step) = _block_programs(self.cfg)
        self._seed = seed
        self.sharding = sharding
        self.params = params
        self.cache: M.KVCache | None = None
        self.attn_impl = "xla"
        self.prefill_attn_impl = "xla"
        # What the block's prefill programs take beside their operands: the
        # dense block's attention path (the latent block's has one).
        self._prefill_kw: dict = {}
        # What a block whose programs are ``shard_map``ped takes beside: the mesh;
        # and where a dispatch's host operands go under it (``_dev``).
        self._mesh_kw: dict = {}
        self._operand_sharding = None
        self.prefill_dispatches = 0  # engine_prefill_attn_dispatch_total
        # Packed prefill: the padded tokens a dispatch may hold (pack_limit;
        # 0 under a mesh) and the (rows, T) programs compiled so far, by a
        # thread of this runner's own, off the request path.
        self.pack_limit_tokens = 0
        self._packed: dict[tuple[int, int], Any] = {}
        self._pack_thread: threading.Thread | None = None
        self._pack_stop = threading.Event()
        self._rid = 0
        self._refs: OrderedDict[int, StepRef] = OrderedDict()
        # Per-SLOT latest sampled token [max_num_seqs + 1], kept on
        # device: decode windows chain their input from it (no host
        # sync), and it is fed by both window folds and admission-time
        # first-token samples (async admission — the engine keeps
        # dispatching while first tokens are still in flight). The extra
        # tail slot is the scatter sink for padding rows.
        self._last_toks: jax.Array | None = None
        # Multi-LoRA adapter bank (engine/lora.py): per-target A/B factor
        # stacks [L, lora_slots, ...] in HBM. Dispatches whose batch has
        # at least one adapter row pass (bank, adapter_slots) into the
        # jitted impls; base-only batches pass None and trace the exact
        # pre-LoRA variant. None when lora_slots == 0.
        self.lora_bank: dict[str, jax.Array] | None = None
        # Routing histograms of the prefill dispatches since the engine last
        # took them: they ride its next first-token fetch.
        self._routed: list[jax.Array] = []

    def take_routed(self) -> list:
        out, self._routed = self._routed, []
        return out

    # -- lifecycle --------------------------------------------------------

    def start(self) -> None:
        if self.sharding is None and self.args.tp > 1:
            from dynamo_tpu.parallel.mesh import ModelSharding, build_mesh

            self.sharding = ModelSharding(build_mesh(tp=self.args.tp, cfg=self.cfg), self.cfg)
        sh = self.sharding
        if sh is not None and getattr(self._block, "SHARD_MAPPED", False):
            from jax.sharding import NamedSharding, PartitionSpec

            self._mesh_kw = {"mesh": sh.mesh}
            self._operand_sharding = NamedSharding(sh.mesh, PartitionSpec())
        # Before anything is allocated: a refused configuration fails fast.
        self.attn_impl, attn_note = self._resolve_attention()
        self.prefill_attn_impl, prefill_note = self._resolve_prefill_attention(attn_note)
        self._prefill_kw = {"attn_impl": self.prefill_attn_impl, **self._mesh_kw}
        dtype = jnp.dtype(self.args.dtype)
        # Seeded params and the KV pool are BORN sharded (jit with
        # out_shardings): a model or pool sized for the mesh never has to
        # fit device 0 first.
        if self.params is not None:
            if self.args.quant == "int8" and not any(
                leaf.dtype == jnp.int8 for leaf in jax.tree.leaves(self.params)
            ):
                if not isinstance(jax.tree.leaves(self.params)[0], np.ndarray):
                    # Device-resident float params: the loader should have
                    # quantized host-side (load_model(quant="int8")); pulling
                    # them back would defeat the memory savings.
                    raise ValueError(
                        "quant='int8' with unquantized device params — pass "
                        "quant='int8' to load_model/load_params instead"
                    )
                from dynamo_tpu.engine.quant import quantize_params_np

                self.params = quantize_params_np(self.params)
            if sh is not None:
                self.params = sh.shard_params(self.params)
            elif isinstance(jax.tree.leaves(self.params)[0], np.ndarray):
                self.params = jax.tree.map(jnp.asarray, self.params)
        elif self.args.quant == "int8":
            from dynamo_tpu.engine.quant import random_int8_params_device

            self.params = random_int8_params_device(
                self.cfg, self._seed, self.args.dtype, sharding=sh
            )
        else:
            build = functools.partial(
                self._block.init_params, self.cfg, jax.random.PRNGKey(self._seed), dtype, **self._mesh_kw
            )
            self.params = build() if sh is None else sh.born_sharded(build)
        # Scale arrays shard over the same kv-head axis as the cache
        # lanes, so the (mesh-forced) XLA attention paths dequantize
        # with co-sharded scales — int8 KV composes with tp.
        self.cache = self._block.init_kv_cache(
            self.cfg, self.args.num_kv_blocks, self.args.block_size, dtype,
            kv_quant=self.args.kv_quant,
            sharding=None if sh is None else sh.cache_sharding,
            **({"state_slots": self.args.state_slots} if self.args.state_slots else {}),
            **({"window_blocks": self.args.window_blocks} if self.args.window_blocks else {}),
        )
        if self.args.lora_slots > 0:
            from dynamo_tpu.engine.lora import bank_shapes

            # Replicated under tp (GSPMD reshards the skinny deltas);
            # zero-initialized — a slot is garbage until its first
            # upload, and the engine never dispatches a row pointing at
            # an unuploaded slot.
            self.lora_bank = {
                k: jnp.zeros(shape, dtype)
                for k, shape in bank_shapes(
                    self.cfg, self.args.lora_slots, self.args.lora_rank
                ).items()
            }
        if sh is None or self._mesh_kw:  # a mesh's shares of bytes and of operations are the same share
            weight_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(self.params))
            self.pack_limit_tokens = pack_limit(
                self.cfg, weight_bytes,
                _OPS_PER_BYTE.get(jax.devices()[0].device_kind, _OPS_PER_BYTE_DEFAULT))
        log.info("engine start: %s", self._start_line(attn_note, prefill_note))
        self._start_pack_compiles()

    # -- packed prefill programs -------------------------------------------

    def _start_pack_compiles(self) -> None:
        """Compile the packed prefill programs the limit allows
        (``EngineArgs.pack_shapes``) on a thread of their own, from shapes
        alone: the engine dispatches a pack only once its program is in
        ``packed_ready``, so none is ever built inside a request, and the
        worker serves singles meanwhile."""
        shapes = self.args.pack_shapes(self.pack_limit_tokens)
        if not shapes:
            return

        def spec(x):
            # No sharding: a program lowered for a named device commits its
            # results to it, and the jitted programs, compiled for the
            # uncommitted cache they hand each other, would each compile
            # again when they met the cache a pack left. Under a mesh every
            # program's parameters and cache are committed to it already.
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding if self._mesh_kw else None)

        params, cache = jax.tree.map(spec, (self.params, self.cache))
        W = self.args.blocks_per_seq  # the wide table only
        i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)

        def state_kw(rows):  # a block with a second cache takes a row's slots (or window table) beside its table
            width = self.args.state_operand_width
            return {"state_slots": i32((rows, width))} if width else {}

        def work():
            t0 = time.monotonic()
            for rows, t in shapes:
                if self._pack_stop.is_set():
                    return
                try:
                    program = self._block.prefill_batch.lower(
                        self.cfg, params, cache, i32((rows, t)), i32((rows, W)), i32((rows,)),
                        i32((rows,)), None, None, **self._prefill_kw, **state_kw(rows)).compile()
                    # stack_rows picks a sequence's row out of the pack's
                    # logits with an eager index, a small program a
                    # [rows, V] shape: that one too exists before a request
                    # meets the shape.
                    logits = program.out_info[0]
                    jnp.zeros(logits.shape, logits.dtype)[0].block_until_ready()
                except Exception:  # noqa: BLE001 - the worker serves singles without it
                    log.exception("packed prefill %dx%d did not compile", rows, t)
                    continue
                self._packed[(rows, t)] = _with_histogram(self._block, program)
            log.info("packed prefill programs ready: %s (%.1f s on their thread)",
                     " ".join(f"{r}x{t}" for r, t in sorted(self._packed)), time.monotonic() - t0)

        self._pack_thread = threading.Thread(target=work, name="prefill-pack-compile", daemon=True)
        self._pack_thread.start()

    @property
    def packed_ready(self) -> frozenset:
        """The (rows, T) packed prefill programs that exist by now."""
        return frozenset(self._packed)

    def _resolve_attention(self) -> tuple[str, str]:
        """→ (decode attention path, why it is not the one asked for).
        The path is chosen here, once, from the platform, the mesh and the
        geometry, so a path the compiler would refuse never reaches a
        request and a path that gives way says so in the start line."""
        from dynamo_tpu.ops.paged_attention import (
            kernel_unsupported,
            latent_kernel_unsupported,
            resolve_attn_impl,
        )

        if self.sharding is not None and not self._mesh_kw:
            # block='deepseek' shard_maps its programs, so its kernels are
            # per-device code and stay; the dense block's are GSPMD's to cut.
            return "xla", (f"mesh: pallas_call is opaque to GSPMD partitioning, and block={self.cfg.block!r} "
                           f"programs are not shard_mapped")
        impl = resolve_attn_impl(self.args.attn_impl)
        if impl != "pallas":
            return impl, ""
        if self.cfg.kv_lora_rank:  # latent pages: both geometries of a model that has two
            geometries = (self.cfg, self.cfg.swa) if self.cfg.block == "dots3" else (self.cfg,)
            limit = next(filter(None, (latent_kernel_unsupported(g, self.args.block_size) for g in geometries)), None)
        else:
            limit = kernel_unsupported(self.cfg, self.args.block_size)
        if limit is None:
            return impl, ""
        if self.args.attn_impl == "pallas":
            raise ValueError(f"attn_impl='pallas' cannot serve {self.cfg.name}: {limit}")
        return "xla", limit

    def _resolve_prefill_attention(self, attn_note: str) -> tuple[str, str]:
        """→ (prefill's attention path, why it is the XLA form), from what
        decode resolved to (platform, mesh, geometry) and what prefill's own
        kernel adds: bf16 pages."""
        from dynamo_tpu.ops.paged_attention import resolve_prefill_impl

        if self.attn_impl == "xla":
            return "xla", attn_note
        return resolve_prefill_impl(
            self.attn_impl, self.cfg, self.args.block_size, self.args.kv_quant == "int8"
        )

    def _start_line(self, attn_note: str, prefill_note: str = "") -> str:
        """One line naming what this engine really runs on: platform,
        device kind and count, dtype, and the attention path of each op.
        ``chip_smoke.py`` reads it, and so should anyone who suspects a
        CPU or XLA path was taken in place of the chip or the kernel."""
        from dynamo_tpu.ops.paged_attention import spec_kernel_fits

        a = self.args
        devs = (
            list(self.sharding.mesh.devices.flat) if self.sharding is not None
            else [jax.devices()[0]]
        )
        decode = self.attn_impl + (f" ({attn_note})" if attn_note else "")
        prefill = self.prefill_attn_impl + (f" ({prefill_note})" if prefill_note else "")
        if a.spec_tokens <= 0:
            spec = "off"
        else:
            def verify_path(t: int) -> str:
                if self.attn_impl == "xla":
                    return "xla"
                if spec_kernel_fits(self.cfg.num_heads, t):
                    return self.attn_impl
                return f"xla ({self.cfg.num_heads}*{t} query columns > 128 lanes)"

            spec = ",".join(
                f"T={t}:{verify_path(t)}"
                for t in spec_verify_widths(a.spec_tokens, a.spec_budget_adaptive)
            )
        # Weights + pool actually resident on each of this process's
        # devices (the CPU backend reports no memory stats).
        jax.block_until_ready((self.params, self.cache))
        used = [
            (d.memory_stats() or {}).get("bytes_in_use")
            for d in devs if d.process_index == jax.process_index()
        ]
        hbm = (
            "" if None in used
            else " hbm_in_use_gb=" + ",".join(f"{u / 1e9:.2f}" for u in used)
        )
        # The grouped expert product's path, where the block has one.
        experts = f" experts={self._block.expert_impl()}" if hasattr(self._block, "expert_impl") else ""
        # A block added after the lines above were pinned names itself, and
        # one that spans a mesh inside one shard_map says over how many chips.
        block = getattr(self._block, "START_LINE", "") + (f" tp={a.tp}" if self._mesh_kw else "")
        # A dp rank is pinned to its chips by the spawner; inside its own
        # TPU world every rank's device ids start at 0 again.
        pinned = os.environ.get("TPU_VISIBLE_CHIPS", "all")
        # The limit's one constant is a chip's: say when it is not this one's.
        assumed = ("" if devs[0].device_kind in _OPS_PER_BYTE or not self.pack_limit_tokens
                   else " (at the v5e's operations a byte: this device_kind has no entry)")
        return (
            f"platform={devs[0].platform} device_kind={devs[0].device_kind!r} "
            f"devices={len(devs)} of {jax.device_count()} "
            f"ids={','.join(str(d.id) for d in devs)} visible_chips={pinned} "
            f"dtype={a.dtype} quant={a.quant} kv_quant={a.kv_quant} "
            f"kv_page_bytes={a.kv_page_bytes()} "
            f"attention: prefill={prefill} decode={decode} spec_verify={spec}{block}{experts}{hbm}"
            f" prefill_pack<={self.pack_limit_tokens} tok{assumed}"
        )

    def stop(self) -> None:
        self._pack_stop.set()
        if self._pack_thread is not None:
            self._pack_thread.join()  # at most the compile it is in
        self._refs.clear()

    # -- ref bookkeeping (must stay deterministic across hosts) -----------

    def _new_ref(self, arrs: tuple, rid: int | None = None, hist=None,
                 prefill: bool = False) -> StepRef:
        if prefill and hist is not None:
            self._routed.append(hist)
        if rid is None:
            rid = self._rid
        self._rid = rid + 1
        ref = StepRef(rid, arrs, hist)
        self._refs[rid] = ref
        while len(self._refs) > _RETAIN:
            self._refs.popitem(last=False)
        return ref

    def ref_by_id(self, rid: int) -> StepRef:
        return self._refs[rid]

    # -- dispatches -------------------------------------------------------

    @staticmethod
    def _state_kw(state) -> dict:
        """``state``: a dispatch's state-pool slots a row (engine/sala.py's
        ``state_slots``), None for a block without such a pool."""
        return {} if state is None else {"state_slots": jnp.asarray(state, jnp.int32)}

    def _dev(self, *operands):
        """A dispatch's host operands as device arrays, in order. Under a
        block's own mesh they go to every chip straight from the host in one
        transfer: an array made on chip 0 first is copied on from there behind
        whatever chip 0 is running, and the dispatch waits a window for it
        (44 ms a decode dispatch with the chips idle: PERF.md section 6, PR 52)."""
        if self._operand_sharding is None:
            return tuple(jnp.asarray(x) for x in operands)
        return tuple(jax.device_put([np.asarray(x) for x in operands], self._operand_sharding))

    def _lora_operands(self, adapter_slots):
        """(bank, slots-array) for a dispatch, or (None, None) for the
        exact base-variant trace."""
        if adapter_slots is None:
            return None, None
        if self.lora_bank is None:
            raise ValueError("adapter_slots passed but lora_slots == 0")
        return self.lora_bank, jnp.asarray(adapter_slots, jnp.int32)

    def upload_adapter(self, slot: int, pages) -> None:
        """Scatter one adapter's packed factor pages (engine/lora.py
        LORA_PAGE_KEYS order) into bank slot ``slot``. Device-stream
        ordering makes this safe while windows are in flight: the upload
        is dispatched AFTER them, so already-issued work reads the old
        occupant."""
        from dynamo_tpu.engine.lora import LORA_PAGE_KEYS

        assert self.lora_bank is not None, "lora_slots == 0"
        for key, arr in zip(LORA_PAGE_KEYS, pages):
            bank = self.lora_bank[key]
            self.lora_bank[key] = _bank_write(
                bank, jnp.asarray(arr, bank.dtype), jnp.int32(slot)
            )

    def prefill_batch(self, toks, tables, starts, tlens, adapter_slots=None,
                      *, rid=None, state=None) -> StepRef:
        bank, slots = self._lora_operands(adapter_slots)
        self.prefill_dispatches += 1
        # A pack runs the program compiled at start from its shape
        # (_start_pack_compiles: the wide table, no adapter bank); anything
        # else the jitted entry point.
        run = None
        if bank is None and np.shape(tables)[1] == self.args.blocks_per_seq:
            run = self._packed.get(np.shape(toks))
        if run is None:
            run = functools.partial(self._prefill_batch, self.cfg, **self._prefill_kw)
        logits, self.cache, hist = run(
            self.params, self.cache, *self._dev(toks, tables, starts, tlens), bank, slots, **self._state_kw(state))
        return self._new_ref((logits,), rid, hist, prefill=True)

    def prefill_chunk(self, toks, table, pos, tlen, adapter_slot=None,
                      *, rid=None, state=None) -> StepRef:
        bank = slot = None
        if adapter_slot is not None and adapter_slot >= 0:
            bank, slot = self.lora_bank, jnp.int32(adapter_slot)
        self.prefill_dispatches += 1
        logits, self.cache, hist = self._prefill(
            self.cfg, self.params, self.cache,
            *self._dev(toks, table, np.int32(pos), np.int32(tlen)),
            bank, slot,
            **self._prefill_kw, **self._state_kw(state),
        )
        return self._new_ref((logits,), rid, hist, prefill=True)

    def _ensure_last_toks(self) -> None:
        if self._last_toks is None:
            self._last_toks = jnp.zeros((self.args.max_num_seqs + 1,), jnp.int32)

    def multi_decode(self, K, mode, tokens, chain, positions, tables, active,
                     temps, seeds, steps0, tks, tps, freqs, press, pen,
                     fold_slots=None, top_n=0, adapter_slots=None,
                     *, rid=None, state=None) -> StepRef:
        """chain: None | (dst rows, src slots) — rows of this window whose
        input token is the latest on-device sample for that sequence SLOT
        (previous window fold or admission first-token fold; no host
        sync). Shapes stay fixed per batch bucket: chaining is expressed
        as a [B] mask + slot map inside the jit. ``fold_slots`` [B] names
        each row's slot so the window's final tokens land back in the
        buffer (padding rows → dummy tail slot). ``top_n`` (static) adds
        ranked alternative logprobs to the ref. ``adapter_slots`` = None
        (base variant) or [B] int32 per-row LoRA bank slots (-1 = base
        row) — the bank rides the dispatch as one more operand."""
        B = len(tokens)
        self._ensure_last_toks()
        mask = np.zeros((B,), bool)
        srcmap = np.zeros((B,), np.int32)
        if chain is not None:
            dst, src = chain
            mask[np.asarray(dst, np.int64)] = True
            srcmap[np.asarray(dst, np.int64)] = src
        bank, aslots = self._lora_operands(adapter_slots)
        if fold_slots is None:
            fold_slots = np.full((B,), self.args.max_num_seqs, np.int32)
        *operands, mask_d, src_d, fold_d = self._dev(
            tokens, positions, tables, active, temps, seeds, steps0, tks, tps, freqs, press, pen,
            mask, srcmap, np.asarray(fold_slots, np.int32))
        toks_d, logps_d, tvals_d, tids_d, self.cache, hist = self._multi_decode(
            self.cfg, K, mode, int(top_n), self.params, self.cache,
            *operands, mask_d, src_d, self._last_toks,
            bank, aslots,
            attn_impl=self.attn_impl, **self._mesh_kw, **self._state_kw(state),
        )
        self._last_toks = _fold_tokens(self._last_toks, toks_d[-1], fold_d)
        return self._new_ref((toks_d, logps_d, tvals_d, tids_d), rid, hist)

    def decode_step(self, tokens, positions, tables, active,
                    adapter_slots=None, *, rid=None, state=None) -> StepRef:
        bank, aslots = self._lora_operands(adapter_slots)
        logits, self.cache, hist = self._decode_step(
            self.cfg, self.params, self.cache,
            *self._dev(tokens, positions, tables, active),
            bank, aslots,
            attn_impl=self.attn_impl, **self._mesh_kw, **self._state_kw(state),
        )
        return self._new_ref((logits,), rid, hist)

    def spec_verify(self, S1, mode, tokens, positions0, draft_len, tables,
                    active, temps, seeds, steps0, fold_slots=None, top_n=0,
                    tree=None, masks=None, adapter_slots=None,
                    *, rid=None) -> StepRef:
        """One speculative verify pass: a single forward over ``S1``
        positions per row (one weight stream) with on-device acceptance.
        ``tree`` = None for a linear draft, or (parents [B, S1],
        anc [B, S1, S1], depth [B, S1]) numpy arrays for a SpecInfer
        token tree — the topology mask rides the same fused gather and
        the accepted root path is compacted on device. ``masks`` = None
        or [B, S1, W32] uint32 packed per-node grammar bitsets (tree
        dispatches only — a constrained batch always upgrades to the
        tree op); acceptance then renormalizes over each node's legal
        vocabulary. The pass's FINAL emitted token folds into the
        per-slot chain buffer like a window's last sample. Ref arrays:
        (out [B, S1], n_emit [B], logps [B, S1], cand [B, S1],
        top_vals, top_ids)."""
        self._ensure_last_toks()
        tp = ta = td = None
        if tree is not None:
            parents, anc, depth = tree
            tp = jnp.asarray(parents, jnp.int32)
            ta = jnp.asarray(anc, jnp.int8)
            td = jnp.asarray(depth, jnp.int32)
        mb = None if masks is None else jnp.asarray(masks, jnp.uint32)
        bank, aslots = self._lora_operands(adapter_slots)
        out, n_emit, logps, cand, tvals, tids, last_tok, self.cache = M.spec_verify(
            self.cfg, int(S1), mode, int(top_n), self.params, self.cache,
            jnp.asarray(tokens), jnp.asarray(positions0),
            jnp.asarray(draft_len), jnp.asarray(tables), jnp.asarray(active),
            jnp.asarray(temps), jnp.asarray(seeds), jnp.asarray(steps0),
            tp, ta, td, mb, bank, aslots,
            fused=self.args.spec_fused, attn_impl=self.attn_impl,
        )
        if fold_slots is None:
            fold_slots = np.full((len(tokens),), self.args.max_num_seqs, np.int32)
        self._last_toks = _fold_tokens(
            self._last_toks, last_tok, jnp.asarray(fold_slots, jnp.int32)
        )
        return self._new_ref((out, n_emit, logps, cand, tvals, tids), rid)

    def stack_rows(self, srcs) -> jax.Array:
        """srcs: list of (StepRef-or-rid, row|None); row None → arr is [V]."""
        rows = []
        for ref, row in srcs:
            if not isinstance(ref, StepRef):
                ref = self.ref_by_id(ref)
            arr = ref.arrs[0]
            rows.append(arr if row is None else arr[row])
        return jnp.stack(rows)

    def sample_rows(self, srcs, temps, tks, tps, pen, freqs, press, seeds,
                    steps, full: bool, fold_slots=None, top_n: int = 0,
                    masks=None):
        """→ (tokens [B], logprobs [B], top_ref|None) as device arrays
        (leader fetches). With ``fold_slots``, the sampled tokens also
        land in the per-slot chain buffer so the next decode window can
        consume them without a host sync (async admission). ``top_n``
        adds ranked alternatives computed from the SAME stacked logits
        (one gather, one logsumexp — not a second pass). ``masks`` =
        None or [B, W32] packed grammar bitsets — the dense-row masked
        sampling path (admission first tokens + single-step decode)."""
        from dynamo_tpu.engine.sampler import top_k_logprobs

        logits = self.stack_rows(srcs)
        mb = None if masks is None else jnp.asarray(masks, jnp.uint32)
        if full:
            out = sample_full(logits, *self._dev(temps, tks, tps, pen, freqs, press, seeds, steps), mb)
        else:
            out = sample_simple(logits, *self._dev(temps, seeds, steps), mb)
        if fold_slots is not None:
            self._ensure_last_toks()
            self._last_toks = _fold_tokens(self._last_toks, out, *self._dev(np.asarray(fold_slots, np.int32)))
        top_ref = None
        if top_n > 0:
            vals, ids = top_k_logprobs(logits, int(top_n))
            top_ref = self._new_ref((vals, ids))
        return out, token_logprobs(logits, out), top_ref

    def embed(self, toks, tlen, *, rid=None) -> StepRef:
        emb = M.embed(self.cfg, self.params, jnp.asarray(toks), jnp.int32(tlen))
        return self._new_ref((emb,), rid)

    def extract_pages(self, block_ids: list[int]) -> tuple:
        """→ (k, v) page arrays, plus (k_scale, v_scale) under int8 KV."""
        M.refuse_block(self.cfg, "KV page export (transfer/, tiers, migration)")
        return kv_transfer.extract_pages(
            self.cache, block_ids, replicate=self.sharding
        )

    def start_extract_pages(self, block_ids: list[int]) -> tuple:
        """Dispatch a page gather without syncing → (device arrays, n).
        The streaming KV exporter starts the D2H copy on these
        (start_host_fetch) and harvests with ``finish_extract_pages``
        once host_ready — page copies overlap remaining prefill chunks
        instead of blocking the scheduler per chunk."""
        M.refuse_block(self.cfg, "KV page export (transfer/, tiers, migration)")
        return kv_transfer.start_extract(
            self.cache, block_ids, replicate=self.sharding
        )

    @staticmethod
    def finish_extract_pages(device_pages: tuple, n: int) -> tuple:
        return kv_transfer.finish_extract(device_pages, n)

    def inject_pages(self, block_ids: list[int], *pages) -> None:
        M.refuse_block(self.cfg, "KV page injection (transfer/, tiers, migration)")
        pages = kv_transfer.adapt_pages(pages, self.cache, self.cfg.num_kv_heads)
        self.cache = kv_transfer.inject_pages(self.cache, block_ids, *pages)


# ---------------------------------------------------------------------------
# Multi-host: leader broadcast + follower replay
# ---------------------------------------------------------------------------


def _send_msg(sock: socket.socket, obj: dict) -> None:
    body = msgpack.packb(obj, use_bin_type=True)
    sock.sendall(struct.pack(">I", len(body)) + body)


def _recv_msg(sock: socket.socket) -> dict | None:
    hdr = _recv_exact(sock, 4)
    if hdr is None:
        return None
    (n,) = struct.unpack(">I", hdr)
    body = _recv_exact(sock, n)
    return None if body is None else msgpack.unpackb(body, raw=False)


def _recv_exact(sock: socket.socket, n: int) -> bytes | None:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


class LeaderRunner(LocalRunner):
    """LocalRunner that mirrors every dispatch to follower processes.

    ``bind`` accepts ``num_followers`` TCP connections before serving;
    descriptors are pushed in dispatch order (TCP preserves it)."""

    def __init__(self, args, params=None, seed=0, sharding=None,
                 *, listen_addr: str = "0.0.0.0:7411", num_followers: int = 0):
        super().__init__(args, params, seed, sharding)
        self.num_followers = num_followers
        self._listen_addr = listen_addr
        self._socks: list[socket.socket] = []

    def start(self) -> None:
        host, port = self._listen_addr.rsplit(":", 1)
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((host, int(port)))
        srv.listen(self.num_followers)
        log.info("leader waiting for %d followers on %s", self.num_followers, self._listen_addr)
        for _ in range(self.num_followers):
            s, peer = srv.accept()
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._socks.append(s)
            log.info("follower connected from %s", peer)
        srv.close()
        self._cast({"op": "start"})
        super().start()

    def stop(self) -> None:
        self._cast({"op": "stop"})
        for s in self._socks:
            s.close()
        self._socks.clear()
        super().stop()

    def _cast(self, desc: dict) -> None:
        for s in self._socks:
            _send_msg(s, desc)

    # Each dispatch: broadcast first (followers start immediately), then
    # run locally. rid assignment is deterministic on both sides.

    def prefill_batch(self, toks, tables, starts, tlens, adapter_slots=None,
                      *, rid=None) -> StepRef:
        rid = self._rid
        self._cast({"op": "prefill_batch", "rid": rid,
                    "toks": _pack_np(toks), "tables": _pack_np(tables),
                    "starts": _pack_np(starts), "tlens": _pack_np(tlens),
                    "aslots": None if adapter_slots is None
                    else _pack_np(np.asarray(adapter_slots, np.int32))})
        return super().prefill_batch(toks, tables, starts, tlens,
                                     adapter_slots, rid=rid)

    def prefill_chunk(self, toks, table, pos, tlen, adapter_slot=None,
                      *, rid=None) -> StepRef:
        rid = self._rid
        self._cast({"op": "prefill_chunk", "rid": rid,
                    "toks": _pack_np(toks), "table": _pack_np(table),
                    "pos": int(pos), "tlen": int(tlen),
                    "aslot": None if adapter_slot is None else int(adapter_slot)})
        return super().prefill_chunk(toks, table, pos, tlen, adapter_slot,
                                     rid=rid)

    def upload_adapter(self, slot: int, pages) -> None:
        self._cast({"op": "upload_adapter", "slot": int(slot),
                    "pages": [_pack_np(np.asarray(p)) for p in pages]})
        super().upload_adapter(slot, pages)

    def multi_decode(self, K, mode, tokens, chain, positions, tables, active,
                     temps, seeds, steps0, tks, tps, freqs, press, pen,
                     fold_slots=None, top_n=0, adapter_slots=None,
                     *, rid=None) -> StepRef:
        rid = self._rid
        wire_chain = None
        if chain is not None:
            dst, src = chain
            wire_chain = [list(map(int, dst)), list(map(int, src))]
        self._cast({"op": "multi_decode", "rid": rid, "K": int(K), "mode": mode,
                    "tokens": _pack_np(tokens), "chain": wire_chain,
                    "positions": _pack_np(positions), "tables": _pack_np(tables),
                    "active": _pack_np(active), "temps": _pack_np(temps),
                    "seeds": _pack_np(seeds), "steps0": _pack_np(steps0),
                    "tks": _pack_np(tks), "tps": _pack_np(tps),
                    "freqs": _pack_np(freqs), "press": _pack_np(press),
                    "pen": _pack_np(pen), "top_n": int(top_n),
                    "aslots": None if adapter_slots is None
                    else _pack_np(np.asarray(adapter_slots, np.int32)),
                    "fold": None if fold_slots is None else _pack_np(np.asarray(fold_slots, np.int32))})
        return super().multi_decode(K, mode, tokens, chain, positions, tables,
                                    active, temps, seeds, steps0, tks, tps,
                                    freqs, press, pen, fold_slots, top_n,
                                    adapter_slots, rid=rid)

    def decode_step(self, tokens, positions, tables, active,
                    adapter_slots=None, *, rid=None) -> StepRef:
        rid = self._rid
        self._cast({"op": "decode_step", "rid": rid,
                    "tokens": _pack_np(tokens), "positions": _pack_np(positions),
                    "tables": _pack_np(tables), "active": _pack_np(active),
                    "aslots": None if adapter_slots is None
                    else _pack_np(np.asarray(adapter_slots, np.int32))})
        return super().decode_step(tokens, positions, tables, active,
                                   adapter_slots, rid=rid)

    def spec_verify(self, S1, mode, tokens, positions0, draft_len, tables,
                    active, temps, seeds, steps0, fold_slots=None, top_n=0,
                    tree=None, masks=None, adapter_slots=None,
                    *, rid=None) -> StepRef:
        rid = self._rid
        self._cast({"op": "spec_verify", "rid": rid, "S1": int(S1), "mode": mode,
                    "tokens": _pack_np(tokens), "positions0": _pack_np(positions0),
                    "draft_len": _pack_np(draft_len), "tables": _pack_np(tables),
                    "active": _pack_np(active), "temps": _pack_np(temps),
                    "seeds": _pack_np(seeds), "steps0": _pack_np(steps0),
                    "top_n": int(top_n),
                    "tree": None if tree is None else [
                        _pack_np(np.asarray(a)) for a in tree
                    ],
                    "masks": None if masks is None else _pack_np(
                        np.asarray(masks, np.uint32)
                    ),
                    "aslots": None if adapter_slots is None
                    else _pack_np(np.asarray(adapter_slots, np.int32)),
                    "fold": None if fold_slots is None else _pack_np(np.asarray(fold_slots, np.int32))})
        return super().spec_verify(S1, mode, tokens, positions0, draft_len,
                                   tables, active, temps, seeds, steps0,
                                   fold_slots, top_n, tree, masks,
                                   adapter_slots, rid=rid)

    def sample_rows(self, srcs, temps, tks, tps, pen, freqs, press, seeds,
                    steps, full: bool, fold_slots=None, top_n: int = 0,
                    masks=None):
        wire_srcs = [
            [ref.rid if isinstance(ref, StepRef) else ref,
             None if row is None else int(row)]
            for ref, row in srcs
        ]
        self._cast({"op": "sample_rows", "srcs": wire_srcs,
                    "temps": _pack_np(temps), "tks": _pack_np(tks),
                    "tps": _pack_np(tps), "pen": _pack_np(pen),
                    "freqs": _pack_np(freqs), "press": _pack_np(press),
                    "seeds": _pack_np(seeds), "steps": _pack_np(steps),
                    "full": bool(full), "top_n": int(top_n),
                    "masks": None if masks is None else _pack_np(
                        np.asarray(masks, np.uint32)
                    ),
                    "fold": None if fold_slots is None else _pack_np(np.asarray(fold_slots, np.int32))})
        return super().sample_rows(srcs, temps, tks, tps, pen, freqs, press,
                                   seeds, steps, full, fold_slots, top_n, masks)

    def embed(self, toks, tlen, *, rid=None) -> StepRef:
        rid = self._rid
        self._cast({"op": "embed", "rid": rid, "toks": _pack_np(np.asarray(toks, np.int32)),
                    "tlen": int(tlen)})
        return super().embed(toks, tlen, rid=rid)

    def extract_pages(self, block_ids: list[int]):
        self._cast({"op": "extract_pages", "ids": list(map(int, block_ids))})
        return super().extract_pages(block_ids)

    def start_extract_pages(self, block_ids: list[int]):
        # Followers replay the same gather dispatch (and discard the
        # result) so the SPMD dispatch streams stay aligned.
        self._cast({"op": "start_extract_pages", "ids": list(map(int, block_ids))})
        return super().start_extract_pages(block_ids)

    def inject_pages(self, block_ids: list[int], *pages) -> None:
        def pack(a):
            a = np.asarray(a)
            return _pack_np(a.view(np.uint16) if str(a.dtype) == "bfloat16" else a)

        self._cast({"op": "inject_pages", "ids": list(map(int, block_ids)),
                    "pages": [pack(p) for p in pages],
                    "bf16": str(np.asarray(pages[0]).dtype) == "bfloat16"})
        super().inject_pages(block_ids, *pages)


def follower_loop(args: EngineArgs, leader_addr: str, params=None, seed: int = 0,
                  sharding=None) -> None:
    """Replay the leader's dispatch stream forever (until EOF / stop).

    Every process in the multi-host group must construct the same mesh
    (jax.distributed must already be initialized); this loop performs the
    same jit calls as the leader's engine, keeping the SPMD program
    aligned. Never fetches results."""
    import ml_dtypes

    import time

    host, port = leader_addr.rsplit(":", 1)
    deadline = time.monotonic() + 120.0
    while True:  # leader may still be binding its listener
        try:
            sock = socket.create_connection((host, int(port)), timeout=5.0)
            break
        except OSError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.5)
    sock.settimeout(None)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    runner = LocalRunner(args, params=params, seed=seed, sharding=sharding)
    log.info("follower connected to leader at %s", leader_addr)
    while True:
        desc = _recv_msg(sock)
        if desc is None or desc["op"] == "stop":
            log.info("follower: leader stream closed")
            return
        op = desc["op"]
        if op == "start":
            runner.start()
        elif op == "prefill_batch":
            aslots = desc.get("aslots")
            runner.prefill_batch(
                _unpack_np(desc["toks"]), _unpack_np(desc["tables"]),
                _unpack_np(desc["starts"]), _unpack_np(desc["tlens"]),
                None if aslots is None else _unpack_np(aslots),
                rid=desc["rid"])
        elif op == "prefill_chunk":
            runner.prefill_chunk(
                _unpack_np(desc["toks"]), _unpack_np(desc["table"]),
                desc["pos"], desc["tlen"], desc.get("aslot"), rid=desc["rid"])
        elif op == "upload_adapter":
            runner.upload_adapter(
                desc["slot"], [_unpack_np(p) for p in desc["pages"]])
        elif op == "multi_decode":
            chain = desc["chain"]
            if chain is not None:
                chain = (chain[0], chain[1])
            fold = desc.get("fold")
            aslots = desc.get("aslots")
            runner.multi_decode(
                desc["K"], desc["mode"], _unpack_np(desc["tokens"]), chain,
                _unpack_np(desc["positions"]), _unpack_np(desc["tables"]),
                _unpack_np(desc["active"]), _unpack_np(desc["temps"]),
                _unpack_np(desc["seeds"]), _unpack_np(desc["steps0"]),
                _unpack_np(desc["tks"]), _unpack_np(desc["tps"]),
                _unpack_np(desc["freqs"]), _unpack_np(desc["press"]),
                _unpack_np(desc["pen"]),
                None if fold is None else _unpack_np(fold),
                desc.get("top_n", 0),
                None if aslots is None else _unpack_np(aslots),
                rid=desc["rid"])
        elif op == "decode_step":
            aslots = desc.get("aslots")
            runner.decode_step(
                _unpack_np(desc["tokens"]), _unpack_np(desc["positions"]),
                _unpack_np(desc["tables"]), _unpack_np(desc["active"]),
                None if aslots is None else _unpack_np(aslots),
                rid=desc["rid"])
        elif op == "spec_verify":
            fold = desc.get("fold")
            tree = desc.get("tree")
            wire_masks = desc.get("masks")
            aslots = desc.get("aslots")
            runner.spec_verify(
                desc["S1"], desc["mode"], _unpack_np(desc["tokens"]),
                _unpack_np(desc["positions0"]), _unpack_np(desc["draft_len"]),
                _unpack_np(desc["tables"]), _unpack_np(desc["active"]),
                _unpack_np(desc["temps"]), _unpack_np(desc["seeds"]),
                _unpack_np(desc["steps0"]),
                None if fold is None else _unpack_np(fold),
                desc.get("top_n", 0),
                None if tree is None else tuple(_unpack_np(a) for a in tree),
                None if wire_masks is None else _unpack_np(wire_masks),
                None if aslots is None else _unpack_np(aslots),
                rid=desc["rid"])
        elif op == "sample_rows":
            fold = desc.get("fold")
            wire_masks = desc.get("masks")
            runner.sample_rows(
                [(s[0], s[1]) for s in desc["srcs"]],
                _unpack_np(desc["temps"]), _unpack_np(desc["tks"]),
                _unpack_np(desc["tps"]), _unpack_np(desc["pen"]),
                _unpack_np(desc["freqs"]), _unpack_np(desc["press"]),
                _unpack_np(desc["seeds"]), _unpack_np(desc["steps"]),
                desc["full"], None if fold is None else _unpack_np(fold),
                desc.get("top_n", 0),
                None if wire_masks is None else _unpack_np(wire_masks))
        elif op == "embed":
            runner.embed(_unpack_np(desc["toks"]), desc["tlen"], rid=desc["rid"])
        elif op == "extract_pages":
            runner.extract_pages(desc["ids"])
        elif op == "start_extract_pages":
            runner.start_extract_pages(desc["ids"])
        elif op == "inject_pages":
            pages = [_unpack_np(d) for d in desc["pages"]]
            if desc["bf16"]:
                # Only the k/v pages travel as uint16 views; scale
                # sidecars (if present) are fp32 and pass through.
                pages[0] = pages[0].view(ml_dtypes.bfloat16)
                pages[1] = pages[1].view(ml_dtypes.bfloat16)
            runner.inject_pages(desc["ids"], *pages)
        else:
            raise RuntimeError(f"unknown dispatch op {op!r}")
