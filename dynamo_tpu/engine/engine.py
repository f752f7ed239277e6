"""TpuEngine: continuous-batching inference engine over the jitted model.

Replaces what the reference delegates to vLLM's ``AsyncLLM``
(reference: components/backends/vllm/src/dynamo/vllm/main.py:90,
handlers.py:113): admission, paged-KV allocation with prefix caching,
prefill (chunked, prefix-skipping), batched decode, on-device sampling,
per-request streaming, cancellation, preemption-by-recompute, KV events
and load metrics.

Threading model: JAX dispatch is blocking, so the scheduler loop runs in a
dedicated thread; asyncio callers submit requests through a lock-guarded
queue and receive ``LLMEngineOutput`` dicts on per-request asyncio queues
via ``loop.call_soon_threadsafe``.

Host↔device sync budget (the latency cost model): one *fetch* per
``decode_steps``-token fused window (model.multi_decode feeds sampled
tokens back on device) and one per admission wave (all first tokens
sampled together) — and the host starts every fetch asynchronously at
dispatch time (``copy_to_host_async``), harvesting results from a FIFO
completion queue by readiness polling. The scheduler therefore blocks on
a fetch only when the window pipeline is full (``pipeline_depth``
windows in flight) or a consumer needs host-visible tokens (full
sampler, per-step path, preemption); admission, prefill dispatch and the
next window dispatch all proceed while fetches are in flight. Per-step
syncing (decode_steps=1) is the fallback for full-sampler batches and
near-max_model_len sequences.
"""

from __future__ import annotations

import asyncio
import collections
import functools
import random
import threading
import time
from typing import Any, AsyncIterator

import numpy as np
from jax.profiler import TraceAnnotation

from dynamo_tpu.block_manager.adapters import AdapterSlotPool
from dynamo_tpu.block_manager.pool import BlockPool, NoFreeBlocksError
from dynamo_tpu.engine import kv_transfer
from dynamo_tpu.engine.config import (
    SPEC_BUDGET_MAX_MULT,
    EngineArgs,
    spec_verify_widths,
)
from dynamo_tpu.engine.lora import (
    LoraAdapterSpec,
    adapter_tier_hash,
    make_adapter_pages,
)
from dynamo_tpu.engine.drafter import (
    DraftConstraint,
    TreeDraft,
    build_drafter,
    constrain_chain,
)
from dynamo_tpu.engine.grammar import (
    GrammarError,
    build_compiler,
    mask_words,
    pack_token_ids,
)
from dynamo_tpu.engine.model import block_module, refuse_block
from dynamo_tpu.engine.runner import host_ready, start_host_fetch
from dynamo_tpu.engine.sampler import needs_full, row_needs_full
from dynamo_tpu.engine.side import KINDS as SIDE_KINDS
from dynamo_tpu.kv_router.protocols import ForwardPassMetrics, KvCacheEvent, KvStats, WorkerStats
from dynamo_tpu.llm.protocols import (
    FinishReason,
    LLMEngineOutput,
    PreprocessedRequest,
    coalesce_delta,
)
from dynamo_tpu.runtime import tracing
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.runtime.metrics import PREFIX
from dynamo_tpu.runtime.qos import DEFAULT_CLASS, QOS_CLASSES, qos_rank
from dynamo_tpu.tokens import (
    TokenBlockSequence,
    adapter_hash_seed,
    compute_block_hashes,
)
from dynamo_tpu.transfer.stream import KvChunk, KvStreamExport

log = get_logger("engine")

_SENTINEL_DONE = object()


class RequestValidationError(Exception):
    """Client error (clean rejection, no stack trace)."""


def trim_spec_budgets(rows: list[tuple[float, int]], S: int) -> list[int]:
    """Batch-level draft-node reallocation (ROADMAP 6 fold-in), the trim
    half: rows drafted OPTIMISTICALLY (each up to min(cap, 2S) nodes —
    drafting is host dict probes, cheap), and this decides how many
    nodes each row KEEPS so the batch stays under the fixed uniform
    budget ``len(rows) * S``. ``rows`` = per-row (spec_ema,
    drafted_len).

    Rows that drafted short (no index/pool hit, cooldown, near model
    end) implicitly donate their unused allowance; when the total still
    exceeds the budget, EMA-cold rows are trimmed back toward their
    EMA-desired length — the SAME shrink the uniform path applies
    (S * ema / 0.5, floor 1) — coldest first.

    Invariants (pinned by tests):
    - sum(keep) <= len(rows) * S (never exceeds the uniform total);
    - keep_i >= min(drafted_i, 1) (a drafting row is never starved —
      its probe survives, so its EMA can re-heat);
    - keep_i >= min(drafted_i, desired_i) (no row keeps fewer nodes
      than the uniform path's EMA shrink would have drafted — per-row
      drafts dominate uniform's, so greedy batch tokens-per-weight-pass
      can only go up at equal total node budget);
    - keep_i <= drafted_i.

    Feasibility: sum(min(drafted, desired)) <= len(rows) * S always
    (desired <= S per row), so trimming to desired always lands under
    budget. Hot rows — grammar-constrained rows above all (near-perfect
    drafts: forced JSON structure runs past S) — keep their full 2S
    drafts whenever cold rows leave room, which is where the
    reallocation pays."""
    n = len(rows)
    keep = [d for _, d in rows]
    if n == 0 or S <= 0:
        return [0] * n
    total = sum(keep)
    limit = n * S
    if total <= limit:
        return keep
    order = sorted(range(n), key=lambda i: (rows[i][0], i))  # coldest first
    for i in order:
        if total <= limit:
            break
        desired = max(1, round(S * min(1.0, rows[i][0] / 0.5)))
        cut = min(keep[i] - min(keep[i], desired), total - limit)
        keep[i] -= cut
        total -= cut
    return keep


class _Seq:
    __slots__ = (
        "request_id", "tokens", "prompt_len", "sampling", "stop", "eos_ids",
        "block_ids", "block_seq", "registered_blocks", "queue", "emitted",
        "cancelled", "preempted", "prefix_hit_blocks", "sample_seed",
        "kv_written", "export", "export_meta", "inject", "dead",
        "slot", "first_pend", "t_admit",
        "t_blocked", "blocked_why", "chunks", "t_dispatched", "wave",
        "t_first", "first_waited", "first_unread_s",
        "spec_ema", "spec_cool", "draft_state",
        "export_handle", "export_stream", "export_pub_blocks",
        "grammar", "grammar_state", "grammar_eos_bits",
        "adapter_id", "adapter_slot", "hash_seed",
        "qos", "qos_rank", "arrival",
        "step_base", "mig", "offer_deadline", "traceparent",
        "side",
    )

    def __init__(self, request_id: str, req: PreprocessedRequest, queue: asyncio.Queue):
        self.request_id = request_id
        self.tokens: list[int] = list(req.token_ids)
        self.prompt_len = len(req.token_ids)
        self.sampling = req.sampling
        self.stop = req.stop
        self.eos_ids = set(req.eos_token_ids) | set(req.stop.stop_token_ids)
        self.block_ids: list[int] = []
        self.block_seq: TokenBlockSequence | None = None
        self.registered_blocks = 0
        self.queue = queue
        self.emitted = 0
        self.cancelled = False
        self.preempted = False
        self.prefix_hit_blocks = 0
        # What the block's second cache holds for this sequence while it runs
        # (engine/side.py: the kind's own record); None off a residence.
        self.side: Any = None
        # Seeded requests are reproducible; others get a per-request seed.
        self.sample_seed = (
            req.sampling.seed if req.sampling.seed is not None else random.getrandbits(31)
        ) & 0x7FFFFFFF
        # Number of positions whose KV is actually in the cache. Blocks may
        # only be registered for prefix reuse once fully *written* — a
        # just-sampled token's KV lands on the NEXT step (it is that step's
        # input), so sealing a block lags writing it.
        self.kv_written = 0
        # Tracing stamp (perf_counter, set by the scheduler thread when the
        # request wins admission): splits queue-wait from prefill in the
        # consumer coroutine's retroactive spans.
        self.t_admit: float | None = None
        # The first token's timeline, stamped by the scheduler thread
        # like t_admit and read by the consumer coroutine only at the
        # first delta: the first admission attempt that failed for want
        # of a slot or of blocks (t_blocked, blocked_why), the prefill
        # programs this sequence dispatched (chunks), the return of
        # _dispatch_prefills for its wave (t_dispatched) with
        # wave = (sequences in the wave, decode windows in flight then),
        # and the first-token sample's arrival on the host (t_first;
        # first_waited = the host had to wait for the fetch; otherwise
        # first_unread_s is how long the sample had lain ready by then).
        self.t_blocked: float | None = None
        self.blocked_why = ""
        self.chunks = 0
        self.t_dispatched: float | None = None
        self.wave = (0, 0)
        self.t_first: float | None = None
        self.first_waited = False
        self.first_unread_s = 0.0
        # Finished/cancelled (set by _finish). In-flight decode windows
        # drain after the fact; dead rows' outputs are discarded.
        self.dead = False
        # Stable device chain slot (runner._last_toks index) while
        # running; first_pend = first token sampled on device but not yet
        # fetched/emitted (async admission).
        self.slot: int | None = None
        self.first_pend = False
        # Speculative decoding: per-sequence acceptance-rate EMA (starts
        # optimistic so new sequences get full drafts; a few rejected
        # passes decay it below the disable threshold), cooldown counter
        # of decode iterations before a disabled/draft-less row proposes
        # again, and the drafter's incremental n-gram index (built lazily
        # on the first draft call).
        self.spec_ema = 1.0
        self.spec_cool = 0
        self.draft_state = None
        # Grammar-constrained decoding (engine/grammar.py): the compiled
        # token-FSM shared by every request using the same schema, this
        # sequence's FSM state (advanced host-side per EMITTED token —
        # the prompt is unconstrained), and the packed EOS bitset OR-ed
        # into terminal-state masks. Attached by generate() before
        # submission; None = unconstrained.
        self.grammar = None
        self.grammar_state = 0
        self.grammar_eos_bits: np.ndarray | None = None
        # Multi-LoRA: the request's adapter identity (None = base), its
        # resident bank slot while admitted (-1 = none/base; the pin is
        # released at finish/preempt), and the adapter-salted hash seed
        # that partitions KV identity — block hashes, tier keys, KV
        # events and router stickiness all derive from it, so an
        # adapter's KV can never prefix-hit another identity's.
        self.adapter_id = getattr(req, "adapter_id", None)
        self.adapter_slot = -1
        self.hash_seed = adapter_hash_seed(self.adapter_id)
        # Multi-tenant QoS: the request's priority class name (metrics
        # label; unknown wire values fall back to the default class),
        # its scheduling rank (generate() zeroes it when
        # args.qos_scheduling is off), and the engine-assigned arrival
        # number — the (class, age) sort key for admission order and
        # preemption victim selection.
        self.qos = (
            getattr(req, "priority", None)
            if getattr(req, "priority", None) in QOS_CLASSES
            else DEFAULT_CLASS
        )
        self.qos_rank = qos_rank(getattr(req, "priority", None))
        self.arrival = 0
        # Disaggregation (engine side of llm/disagg.py):
        ktp = req.kv_transfer_params or {}
        self.export = bool(ktp.get("do_remote_decode"))  # prefill-only + export KV
        self.export_meta: dict | None = None             # filled at prefill time
        self.inject = ktp.get("inject")                  # KvPagePayload dict to pre-load
        # Streaming export (dynamo_tpu/transfer): with a decode-worker-
        # minted stream_handle, KV chunks publish DURING prefill instead
        # of one payload after it. export_pub_blocks tracks contiguous
        # published coverage.
        self.export_handle = ktp.get("stream_handle") if self.export else None
        self.export_stream: KvStreamExport | None = None
        self.export_pub_blocks = 0
        # Live migration: sampler step offset (a resumed sequence keeps
        # drawing the SOURCE's gumbel index sequence: same seed, steps
        # continue at step_base + emitted) and the outbound migration
        # state while this sequence is being relocated (engine-thread
        # owned, via _migrations).
        self.step_base = 0
        self.mig = None
        # W3C traceparent of the client request this sequence serves
        # (stamped by generate() from the wire context). Rides the
        # migration protocol so the source coordinator's admin RPCs and
        # the destination's resume leg all join the ORIGINAL trace.
        self.traceparent: str | None = None
        # Preemption-offer grace: when a migration offer hook fires for
        # this sequence as a preemption victim, the kill waits until
        # this deadline for the relocation to free the blocks instead.
        self.offer_deadline = 0.0
        # Resume identity (live migration / re-dispatch): the original
        # prompt boundary survives worker changes — penalties and grammar
        # replay key off it — and seed/step/EMA continue the source's.
        resume = ktp.get("resume")
        if isinstance(resume, dict):
            pl = resume.get("prompt_len")
            if isinstance(pl, int) and 1 <= pl <= len(self.tokens):
                self.prompt_len = pl
            if resume.get("sample_seed") is not None:
                self.sample_seed = int(resume["sample_seed"]) & 0x7FFFFFFF
            self.step_base = int(resume.get("sample_step") or 0)
            if resume.get("spec_ema") is not None:
                self.spec_ema = float(resume["spec_ema"])

    @property
    def next_write_pos(self) -> int:
        return len(self.tokens) - 1


class _Window:
    """One dispatched multi-step decode window (results not yet fetched)."""

    __slots__ = ("rows", "pos0", "K", "ref", "row_of", "top_n")

    def __init__(self, rows: list[_Seq], pos0: list[int], K: int, ref, top_n: int = 0):
        self.rows = rows
        self.pos0 = pos0
        self.K = K
        # StepRef: arrs = (toks [K,B], logps [K,B], tvals [K,B,top_n], tids)
        self.ref = ref
        self.row_of = {s: i for i, s in enumerate(rows)}
        self.top_n = top_n

    def fetch_arrays(self) -> list:
        a = [self.ref.arrs[0], self.ref.arrs[1]]
        if self.top_n:
            a += [self.ref.arrs[2], self.ref.arrs[3]]
        # a block="longcat" model's routing histogram rides the same fetch
        return a if self.ref.hist is None else a + [self.ref.hist]


class _MigSt:
    """Engine-thread state of one outbound live migration: the sequence
    keeps decoding while its sealed KV blocks publish as stream chunks
    (``pump``), until the coordinator freezes it for the bounded cutover
    window. ``fetches`` are this migration's in-flight page extracts
    (lo, hi, device arrays, bucket n), harvested strictly in dispatch
    order so the consumer's chunk coverage stays contiguous."""

    __slots__ = ("seq", "handle", "stream", "pub_blocks", "frozen",
                 "freeze_deadline", "fetches")

    def __init__(self, seq: "_Seq", handle: str, stream: KvStreamExport):
        self.seq = seq
        self.handle = handle
        self.stream = stream
        self.pub_blocks = 0
        self.frozen = False
        self.freeze_deadline = 0.0
        self.fetches: list = []


class _Spec:
    """One dispatched speculative verify pass (results not yet fetched).
    Unlike a _Window, the number of tokens a row will emit (1 + accepted
    drafts) is unknown until the fetch lands, so the scheduler never
    plans further decode work for these rows while a _Spec is queued —
    _decode_iteration force-drains any queued _Spec before planning.

    ``draft_lens`` counts proposed draft NODES per row (the token budget
    spent); ``potentials`` the max accepted run each proposal could
    yield — equal for a linear draft, the deepest path for a tree (the
    honest EMA denominator). ``node_tokens``/``node_parents`` keep the
    host-side tree views so the drain can feed the drafter's Jacobi
    pool without re-fetching anything."""

    __slots__ = ("rows", "pos0", "draft_lens", "potentials", "ref",
                 "top_n", "tree", "node_tokens", "node_parents")

    def __init__(self, rows: list[_Seq], pos0: list[int],
                 draft_lens: list[int], ref, top_n: int = 0,
                 potentials: list[int] | None = None, tree: bool = False,
                 node_tokens: list[list[int]] | None = None,
                 node_parents: list[list[int]] | None = None):
        self.rows = rows
        self.pos0 = pos0
        self.draft_lens = draft_lens
        self.potentials = potentials or draft_lens
        # StepRef: arrs = (out [B, S1], n_emit [B], logps [B, S1],
        # cand [B, S1], top_vals [B, S1, n], top_ids [B, S1, n])
        self.ref = ref
        self.top_n = top_n
        self.tree = tree
        self.node_tokens = node_tokens
        self.node_parents = node_parents

    def fetch_arrays(self) -> list:
        a = [self.ref.arrs[0], self.ref.arrs[1], self.ref.arrs[2],
             self.ref.arrs[3]]
        if self.top_n:
            a += [self.ref.arrs[4], self.ref.arrs[5]]
        return a


class _First:
    """One dispatched admission wave's first-token sample (not yet
    fetched). Entries: (seq, row) into the wave's padded sample batch."""

    __slots__ = ("entries", "out_d", "lps_d", "top_ref", "routed", "t_ready")

    def __init__(self, entries: list[tuple[_Seq, int]], out_d, lps_d, top_ref,
                 routed: list | None = None):
        self.entries = entries
        self.out_d = out_d
        self.lps_d = lps_d
        self.top_ref = top_ref
        # When the scheduler thread first saw the sample ready on the device
        # (_probe); None until then, so still None at a fetch that blocks.
        self.t_ready: float | None = None
        # Routing histograms of the prefill dispatches since the last wave
        # (engine/longcat.py; they ride this wave's fetch).
        self.routed = routed or []

    def fetch_arrays(self) -> list:
        a = [self.out_d, self.lps_d]
        if self.top_ref is not None:
            a += [self.top_ref.arrs[0], self.top_ref.arrs[1]]
        return a + self.routed


def register_engine_metrics(registry) -> dict:
    """Register the engine gauges/counters on a MetricsRegistry →
    {name without the registry's prefix: metric}. Shared by the worker
    (bind_metrics) and the tools/check_metrics.py catalog guard."""
    metrics = (
        registry.gauge(
            "engine_prefill_pad_ratio",
            "Cumulative dispatched/true prefill token ratio (bucket padding waste)",
        ),
        registry.gauge(
            "engine_spec_accept_rate",
            "Cumulative accepted/proposed draft-token ratio",
        ),
        registry.gauge(
            "engine_tokens_per_weight_pass",
            "Decode tokens sampled per per-sequence weight stream "
            "(1.0 = dense; >1.0 = speculation paying off)",
        ),
        registry.gauge(
            "engine_kv_cache_bytes",
            "HBM bytes of the G1 paged KV pool (pages + quantization "
            "scales, num_kv_blocks x kv_bytes_per_block)",
        ),
        registry.gauge(
            "engine_kv_quant_enabled",
            "1 when the paged KV cache stores int8 pages (kv_quant), "
            "0 for full-precision storage",
        ),
        registry.counter(
            "engine_spec_tree_passes_total",
            "Speculative verify passes dispatched with a branched "
            "(non-chain) draft tree",
        ),
        registry.gauge(
            "engine_spec_tree_accept_depth",
            "Cumulative mean accepted root-path depth of tree verify "
            "passes (0 = every tree pass rejected at the root)",
        ),
        registry.counter(
            "tier_protected_evictions_total",
            "Host/disk KV tier eviction scans that SPARED a protected "
            "block (high prefix fan-out or recent hits) and evicted a "
            "colder one instead",
        ),
        registry.gauge(
            "tier_hit_rate",
            "Cumulative G2+G3 tier lookup hit rate (hits / (hits + "
            "misses)) — the churn-resistance signal for the "
            "frequency-aware eviction policy",
        ),
        registry.gauge(
            "engine_grammar_active_seqs",
            "Running sequences decoding under a grammar constraint "
            "(response_format token-mask FSMs)",
        ),
        registry.gauge(
            "engine_grammar_mask_seconds",
            "Cumulative host seconds spent building/packing grammar "
            "token masks (FSM walks + bitset gathers per verify slot)",
        ),
        registry.counter(
            "engine_spec_budget_reallocs_total",
            "Speculative verify passes whose batch-level draft-node "
            "budget was reallocated away from the uniform per-row split "
            "(EMA-hot rows drafting past spec_tokens)",
        ),
        registry.gauge(
            "engine_lora_resident_adapters",
            "LoRA adapters currently resident in the device (G1) bank "
            "slots (engine/lora.py; 0 when lora_slots is 0)",
        ),
        registry.counter(
            "engine_lora_swap_total",
            "LoRA adapter page-ins: uploads of adapter factor pages into "
            "a device bank slot (cold fetch through the G2/G3 tier "
            "economy; when slots are full each one evicts a colder "
            "resident)",
        ),
        registry.gauge(
            "engine_lora_gather_seconds",
            "Cumulative host seconds spent on LoRA multiplexing — "
            "resolving adapter slots at admission, uploading factor "
            "pages, and building per-dispatch adapter_slot operands",
        ),
        registry.counter(
            "engine_preemptions_total",
            "Recompute-preemptions under KV pressure by victim QoS "
            "class (victims are lowest-class/newest-first; a preempted "
            "request requeues and re-prefills, so its stream stays "
            "byte-identical under greedy sampling)",
        ),
        registry.counter(
            "engine_step_phase_seconds_total",
            "Scheduler-thread wall seconds by step-loop phase (idle, "
            "housekeeping, admission, admit_alloc, prefill_dispatch, "
            "first_dispatch, stack_rows, plan, decode_dispatch, drain_sync, "
            "drain_ready, first_sample, emit, gauges, ...). The thread is in "
            "exactly one phase from its first line to its last, so they sum "
            "to engine_sched_wall_seconds_total; drain_sync and first_sample "
            "wait on a device fetch, and a dispatch phase blocks while the "
            "device's queue is full",
        ),
        registry.counter(
            "engine_step_phase_cpu_seconds_total",
            "CPU seconds (time.thread_time) the scheduler thread burned in "
            "each step-loop phase; they sum to engine_sched_cpu_seconds_total, "
            "and a phase's wall seconds less these is what it waited",
        ),
        registry.counter(
            "engine_step_phase_total",
            "Times the scheduler thread left each step-loop phase "
            "(decode_dispatch: one a decode window dispatched)",
        ),
        registry.counter(
            "engine_sched_wall_seconds_total",
            "Wall seconds (time.perf_counter) since the scheduler thread began",
        ),
        registry.counter(
            "engine_admission_stops_total",
            "Steps whose admission pass began with requests waiting, by why it "
            "stopped: empty = the queue was drained, budget = "
            "admission_budget_tokens ran out with slots and blocks to spare, "
            "slots = max_num_seqs, blocks = the KV pool",
        ),
        registry.counter(
            "engine_prefill_waves_total",
            "Admission waves whose prefills were dispatched",
        ),
        registry.counter(
            "engine_wave_windows_ahead_total",
            "Decode windows dispatched and not yet drained when a wave's "
            "prefills had gone out, summed over the waves: what a first token "
            "waits behind on the device",
        ),
        registry.counter(
            "engine_first_ready_unread_seconds_total",
            "Seconds first-token samples lay ready on the device before the "
            "scheduler thread had fetched them: from the first probe that saw "
            "one ready to the end of its fetch (0 for a fetch that blocked)",
        ),
        registry.counter(
            "engine_first_fetch_total",
            "First-token sample fetches by who waited: device = the fetch "
            "blocked on the device, host = the sample was ready before the "
            "scheduler thread came for it",
        ),
        registry.counter(
            "engine_device_dry_seconds_total",
            "Seconds, while requests ran or waited, in which the device had "
            "nothing dispatched left to run, bounded from both sides by a "
            "probe of the last dispatched program's outputs: floor = from a "
            "probe that found them ready to the next dispatch call, ceiling = "
            "every interval between probes that did not end on a probe "
            "finding them unready. The truth lies between; their gap is the "
            "probes' spacing",
        ),
        registry.counter(
            "engine_sched_cpu_seconds_total",
            "CPU seconds the scheduler thread itself burned "
            "(time.thread_time): host work, as against waiting on the "
            "device or for the GIL",
        ),
        registry.counter(
            "engine_decode_row_steps_total",
            "Decode rows x steps by kind: dispatched = batch-bucket rows x "
            "steps of every decode window dispatched, emitted = tokens "
            "those windows delivered to a live sequence (no padding row, "
            "nothing past a stop, no finished, cancelled or preempted row)",
        ),
        registry.counter(
            "engine_prefill_attn_dispatch_total",
            "Prefill dispatches by attention path: pallas = the kernel that "
            "attends out of the pages and walks only the context a chunk "
            "can see, xla = the gather form over the table's whole width "
            "(off the TPU, under a mesh, int8 KV pages, a refused geometry)",
        ),
        registry.counter(
            "engine_prefill_dispatch_rows_total",
            "Prefill dispatches by the rows of their program: 1 = a prompt "
            "or a chunk alone, 2 and 4 = an admission wave's single-chunk "
            "suffixes packed into one program, which streams the weights "
            "once for all of them (the start line's prefill_pack<=N tok is "
            "the padded tokens a pack may hold)",
        ),
        registry.counter(
            "engine_prefill_rows_total",
            "Real rows (sequences) the prefill dispatches carried; three in "
            "a program of four leave one row inactive. Over the sum of "
            "engine_prefill_dispatch_rows_total it is the mean rows a dispatch",
        ),
        registry.counter(
            "kv_pool_hit_blocks_total",
            "Prompt blocks an admission found in the G1 prefix cache",
        ),
        registry.counter(
            "kv_pool_miss_blocks_total",
            "Matchable prompt blocks an admission did not find in the G1 "
            "prefix cache (hit + miss = (prompt_len - 1) // block_size)",
        ),
        registry.counter(
            "engine_conv_state_resumes_total",
            "Prefill rows of a model with convolution layers, by where their "
            "conv state came from: cache = the block before the row's first "
            "(a prefix hit, a returning preempted sequence), zero = position "
            "0, recompute = K and V were cached further than the conv state "
            "and the conv layers ran over those positions again",
        ),
        registry.gauge(
            "kv_pool_bytes",
            "HBM bytes of the G1 pool by kind of page: kv = K and V (or "
            "latent) pages, conv = the convolution layers' state under the "
            "same block ids (block='lfm2' models), ckeys = the sparse layers' "
            "compressed keys under them (block='sala'); their sum is "
            "engine_kv_cache_bytes. state = the lightning layers' state pool "
            "(block='sala'), slots beside the blocks and outside that sum",
        ),
        registry.gauge(
            "kv_page_bytes",
            "Bytes of one page of one cache layer, K and V side by side (a "
            "latent page for block='longcat'): what one DMA descriptor of "
            "the paged attention kernels moves",
        ),
        registry.counter(
            "moe_assignments_total",
            "Expert assignments (token x top-k x layer) the expert layer "
            "routed, by kind: held = to a routed expert this chip holds "
            "(the grouped product), zero = to a zero-compute expert (adds "
            "w*h, no weights), absent = to a routed expert another chip "
            "holds (left out here). Models whose block routes only",
        ),
        registry.counter(
            "moe_expert_tokens_total",
            "Assignments to each routed expert held here, by layer and "
            "published expert index: the load of the grouped product",
        ),
        registry.counter(
            "moe_tokens_routed_total",
            "Tokens x layers the router ran over (padding rows excluded)",
        ),
        registry.counter(
            "moe_expert_calls_total",
            "Calls of the grouped expert product, by program: one a layer "
            "of a decode step (program=decode), one a layer of a prefill "
            "part of up to 512 tokens (program=prefill)",
        ),
        registry.counter(
            "moe_experts_touched_total",
            "Held experts with at least one token, summed over the calls "
            "of the grouped expert product, by program (and by chip where "
            "the expert layers span a mesh): the expert weight reads those "
            "calls needed",
        ),
        registry.counter(
            "moe_token_groups_total",
            "Routing groups the experts chosen for a token span, summed over "
            "tokens x layers (a group-limited router: never over topk_group a "
            "token). Models whose block routes by groups only",
        ),
    )
    for kind in SIDE_KINDS:  # one catalog, whichever block runs
        metrics += tuple(registry.counter(name, text) for name, text in kind.COUNTERS.items())
        metrics += tuple(registry.gauge(name, text) for name, text in kind.GAUGES.items())
    return {m.name.removeprefix(PREFIX + "_"): m for m in metrics}


class TpuEngine:
    # Scheduler-state ownership manifest, machine-checked by DT001
    # (tools/analysis — keep the mirror in checkers/dt001 in sync). Every
    # attribute named here is owned by the scheduler thread (_run/_step):
    # async-side code may touch one ONLY under `with self._wakeup:` (the
    # handoff protocol for _submissions/_embed_jobs/_host_jobs and the
    # cancel flag) or by shipping a closure via run_on_engine_thread.
    # Deliberately NOT owned: spec_tokens + spec_budget_adaptive
    # (documented idle-engine toggles, read once per scheduler
    # iteration), the total_* counters incl. total_grammar_mask_s
    # (monotonic values read racily by the metrics page — stale reads are
    # harmless, total_lora_s included), _stopping (always mutex-guarded),
    # pool/tiers/_lora_pool (internally consistent; acquire/release on
    # the scheduler thread, cross-thread readers get point-in-time
    # values), _lora_registry (always _lora_lock-guarded; registration
    # runs from setup/async contexts), and _grammar_compiler (built
    # under _grammar_lock from
    # generate() coroutines; the compiled FSMs it hands out are
    # internally locked, so scheduler-thread mask lookups race async
    # compiles safely).
    _SCHED_OWNED = frozenset({
        "_submissions", "_waiting", "_running", "_fetchq", "_free_slots",
        "_embed_jobs", "_host_jobs", "_offload_pending", "_exports",
        "_export_fetches", "_drafter", "_step_no", "_spec_ticked",
        "phase_s", "phase_n", "_ctr_pushed", "_spec_depth_hist",
        "_migrations", "_anno", "_slots_blocked_sig",
        "phase_cpu_s", "_cur", "_cur_t", "_cur_cpu", "_t_run",
        "admission_stops", "prefill_waves", "wave_windows_ahead",
        "first_ready_unread_s", "first_fetches", "device_dry_s",
        "_dev_last", "_dev_open", "_dev_done", "_probe_t", "_probe_work",
    })

    def __init__(
        self,
        args: EngineArgs,
        params: Any | None = None,
        seed: int = 0,
        event_sink=None,
        sharding=None,  # dynamo_tpu.parallel.ModelSharding | None
        runner=None,    # engine.runner.ModelRunner | None (multi-host leader)
    ):
        from dynamo_tpu.engine.runner import LocalRunner

        self.args = args
        self.cfg = args.model
        self._runner = runner or LocalRunner(args, params=params, seed=seed, sharding=sharding)
        self._external_events = event_sink
        module = block_module(self.cfg)
        kind = getattr(module, "side_cache", None)
        self.pool = BlockPool(
            args.num_kv_blocks,
            args.block_size,
            event_sink=self._on_pool_event,
            enable_prefix_caching=args.prefix_caching,
            **(kind.pool_options(args) if kind else {}),
        )
        # What the block keeps beside its pages (engine/side.py), if anything.
        self.side = kind(args, self.pool) if kind else None
        # G2/G3 KV tiers: sealed blocks write through to host (batched per
        # step); prefix misses in HBM onboard from the tiers instead of
        # recomputing (block_manager/tiers.py).
        self.tiers = self._build_tiers(args)
        self._offload_pending: list[tuple[int, int]] = []  # (block_id, seq_hash)

        self._loop: asyncio.AbstractEventLoop | None = None
        self._thread: threading.Thread | None = None
        self._mutex = threading.Lock()
        self._wakeup = threading.Condition(self._mutex)
        self._submissions: collections.deque[_Seq] = collections.deque()
        self._waiting: collections.deque[_Seq] = collections.deque()
        self._running: list[_Seq] = []
        self._stopping = False
        # FIFO completion queue of dispatched-but-unfetched device work:
        # _First admission samples and _Window decode windows, in
        # dispatch order. Every item's D2H fetch was started async at
        # dispatch (start_host_fetch); _drain_completed harvests ready
        # items from the front, and force-drains only when the pipeline
        # is full or host-visible tokens are required. FIFO order is the
        # per-sequence emission-order invariant: a seq's first sample is
        # always queued before any window containing it.
        self._fetchq: collections.deque[_First | _Window | _Spec] = collections.deque()
        self._free_slots: list[int] = list(range(args.max_num_seqs))
        # (tokens, future, loop) embedding jobs; served between scheduler
        # steps on the engine thread (device dispatch affinity).
        self._embed_jobs: collections.deque = collections.deque()
        # (fn, future, loop) host jobs run on the engine thread between
        # steps — the device-dispatch-affinity seam for out-of-band work
        # (migration's freeze/export, worker/migrate.py).
        self._host_jobs: collections.deque = collections.deque()
        # Disagg exports: handle → (KvPagePayload | KvStreamExport,
        # deadline). Host copies, so they survive cache donation; reaped
        # after export_ttl_s (unsealed streams abort at reap time).
        self._exports: dict[str, tuple[Any, float]] = {}
        self.export_ttl_s = 60.0
        # Outbound live migrations: request_id → _MigSt. The scheduler
        # pumps each unfrozen migration's KV delta once per step; frozen
        # ones are auto-unfrozen (and the migration aborted) when the
        # coordinator misses the cutover deadline — a dead coordinator
        # can never wedge a stream.
        self._migrations: dict[str, _MigSt] = {}
        self.migration_freeze_ttl_s = 10.0
        # QoS defrag: when set (worker/roles.py wires it to the
        # migration coordinator), preemption under KV pressure OFFERS
        # the victim a relocation first — called from the scheduler
        # thread with the victim's request id, must be thread-safe —
        # and the kill waits a bounded grace for the offer to land.
        self.migration_offer = None
        self.preempt_offer_grace_s = 0.75
        # Proactive defrag (args.kv_pressure_offer): once pool usage
        # crosses the threshold, the offer hook fires for the cheapest
        # running sequence AHEAD of the preemption boundary, rate-limited
        # so one sustained pressure plateau yields one offer per window
        # rather than one per scheduler step.
        self.kv_pressure_offer = float(getattr(args, "kv_pressure_offer", 0.0) or 0.0)
        self.kv_pressure_offer_window_s = 2.0
        self._pressure_offer_next = 0.0
        self.pressure_offers = 0  # observability: proactive offers fired
        # Streaming-export page fetches in flight: (seq, lo, hi, device
        # arrays, bucket n). Dispatched per prefill chunk with async D2H
        # (start_host_fetch); harvested opportunistically between chunk
        # dispatches and in _step, forced at seal — so page copies and
        # wire sends overlap the remaining prefill chunks.
        self._export_fetches: list = []
        # Speculative decoding: host-side drafter + a runtime-togglable
        # draft length (initialized from args; tests flip it on an
        # idle engine to compare dense vs speculative on one warmed
        # engine — it is read once per scheduler iteration, never mid-
        # dispatch).
        self._drafter = build_drafter(args)
        self.spec_tokens = args.spec_tokens
        # Batch-budget mode toggle: like spec_tokens, a documented
        # idle-engine runtime switch (tests compare adaptive with uniform
        # on one warmed engine); read once per _try_speculative call.
        self.spec_budget_adaptive = args.spec_budget_adaptive
        # Grammar-constrained decoding: the compiler (vocab + schema
        # cache) is built lazily on the first constrained request, OFF
        # the scheduler thread (generate() compiles via to_thread; the
        # compiled FSMs are internally locked, so scheduler-thread mask
        # lookups race compiles safely). Not scheduler-owned.
        self._grammar_compiler = None
        self._grammar_lock = threading.Lock()
        # Scheduler-step counter + last-ticked stamp: _decode_iteration
        # can re-enter _try_speculative within one step (drain → replan),
        # and probe cooldowns must tick once per STEP, not per attempt.
        self._step_no = 0
        self._spec_ticked = -1
        # Spec counters: proposed/accepted draft tokens, verify
        # dispatches, live row-passes and tokens they emitted — the
        # numerators/denominators for accept-rate and tokens-per-pass.
        self.total_spec_proposed = 0
        self.total_spec_accepted = 0
        self.total_spec_passes = 0
        self.total_spec_rows = 0
        self.total_spec_emitted = 0
        # Tree speculation: branched-pass dispatches, per-row accepted
        # depth sum + row count (mean accept depth), and a small
        # accepted-depth histogram {depth: rows} for the profiler.
        self.total_spec_tree_passes = 0
        self.total_spec_tree_rows = 0
        self.total_spec_tree_depth = 0
        self._spec_depth_hist: collections.Counter = collections.Counter()
        # Grammar + budget accounting (same racy-read contract as the
        # other total_* counters: monotonic, stale reads harmless).
        # total_grammar_mask_s: host seconds building/packing masks;
        # total_spec_budget_reallocs: passes dispatched with a
        # non-uniform node split; total_grammar_seqs: constrained
        # sequences admitted.
        self.total_grammar_mask_s = 0.0
        self.total_spec_budget_reallocs = 0
        self.total_grammar_seqs = 0
        # Multi-LoRA multiplexing (engine/lora.py): the G1 slot pool
        # (block_manager/adapters.py; acquire/release on the scheduler
        # thread, stats read racily — same contract as pool/tiers, so
        # deliberately NOT scheduler-owned) and the adapter registry
        # (adapter_id → LoraAdapterSpec; registered from setup/async
        # contexts under _lora_lock, read at admission). total_lora_s is
        # the engine_lora_gather_seconds feed (racy-total contract).
        self._lora_pool = (
            AdapterSlotPool(args.lora_slots) if args.lora_slots > 0 else None
        )
        self._lora_registry: dict[str, tuple[LoraAdapterSpec, tuple | None]] = {}
        self._lora_lock = threading.Lock()
        self.total_lora_s = 0.0
        # Tokens-per-weight-pass accounting: every (row, substep) of a
        # drained window or single step is one per-sequence weight pass
        # yielding one token; a spec row-pass is one weight pass yielding
        # n_emit tokens. Dense-only traffic sits at exactly 1.0.
        self.total_row_passes = 0
        self.total_row_tokens = 0
        # Multi-tenant QoS: monotone submission counter (the age half of
        # the (class, age) scheduling key; assigned under _wakeup at
        # submission, read by the scheduler thread afterwards) and
        # recompute-preemption counts by victim class (racy-total
        # contract like the other total_* counters).
        self._arrival_no = 0
        self.total_preemptions_by: collections.Counter = collections.Counter()
        # Cumulative counters (the worker's /metrics page reads them).
        self.total_generated = 0
        self.total_prefilled = 0
        # Token-rows actually DISPATCHED for prefill (bucket padding and
        # padded rows included) — the numerator of engine_prefill_pad_ratio.
        self.total_prefill_padded = 0
        self.total_decode_steps = 0  # device substeps incl. padded/zombie work
        # Decode rows x steps: what every dense decode dispatch paid for
        # (batch-bucket rows x steps, padding and zombie rows included) and
        # the tokens those dispatches delivered to a live sequence.
        self.total_decode_rows_dispatched = 0
        self.total_decode_rows_emitted = 0
        # Host-side phase accounting: the scheduler thread is in exactly one
        # phase (_enter) from _run's first line to its last, so phase_s sums
        # to the thread's wall time and phase_cpu_s to its CPU time
        # (engine_step_phase_{seconds,cpu_seconds}_total{phase}). Keys: idle /
        # housekeeping / admission / admit_alloc / prefill_dispatch /
        # first_dispatch / stack_rows / plan / decode_dispatch / drain_sync /
        # drain_ready / first_sample / emit / gauges, and draft /
        # spec_dispatch / single_step where those paths run.
        self.phase_s: dict[str, float] = collections.defaultdict(float)
        self.phase_cpu_s: dict[str, float] = collections.defaultdict(float)
        self.phase_n: dict[str, int] = collections.defaultdict(int)
        self._cur: str | None = None  # the open phase; None off the thread's run
        self._cur_t = self._cur_cpu = self._t_run = 0.0
        # Why an admission pass that began with requests waiting stopped.
        self.admission_stops: dict[str, int] = dict.fromkeys(
            ("empty", "budget", "slots", "blocks"), 0)
        self.prefill_waves = 0
        self.wave_windows_ahead = 0
        # First-token samples: seconds they lay ready before their fetch
        # ended, and fetches by who waited for whom.
        self.first_ready_unread_s = 0.0
        self.first_fetches: dict[str, int] = {"device": 0, "host": 0}
        # The device's dry time (_probe): the outputs of the last program
        # dispatched, whether a dispatch call is open (nothing reads as done
        # until its outputs are here), and the last probe's time and findings.
        self.device_dry_s: dict[str, float] = {"floor": 0.0, "ceiling": 0.0}
        self._dev_last: Any = ()
        self._dev_open = False
        self._dev_done = False
        self._probe_t = 0.0
        self._probe_work = False
        # runner.stack_rows, the eager slices that pick a wave's rows out of
        # its prefills' logits, each behind the prefill just queued, runs
        # inside runner.sample_rows: this engine's runner calls it in a phase.
        self._runner.stack_rows = self._in_phase("stack_rows", self._runner.stack_rows)
        # Optional Prometheus series (worker bind_metrics), by name.
        # The engine keeps plain running totals; _ctr_pushed remembers
        # what each registry counter series has been fed, so _feed gives
        # it the growth once per step.
        self._gauges: dict | None = None
        self._anno: TraceAnnotation | None = None  # the open phase's
        self._ctr_pushed: dict[tuple, float] = {}
        # _waiting as it was when its head was last stamped blocked for a slot
        self._slots_blocked_sig: tuple | None = None
        # A routing block's histograms [routed layers, E + HIST_EXTRA]
        # (engine/longcat.py) by program, summed from the arrays that ride the
        # token fetches; None for a block that routes nothing. The block's
        # module says which layers route (``routed_layers``).
        self.moe_hist: dict[str, np.ndarray] | None = None
        routed = getattr(module, "routed_layers", None)
        self._routed_layers: tuple[int, ...] = routed(self.cfg) if routed else ()
        if self._routed_layers:
            from dynamo_tpu.engine.longcat import HIST_EXTRA

            # A block whose expert layers span a mesh counts each chip's touched
            # experts, and the routing groups its tokens span, after those.
            extra = module.hist_extra(self.args.tp) if hasattr(module, "hist_extra") else HIST_EXTRA
            shape = (len(self._routed_layers), self.cfg.num_experts + extra)
            self.moe_hist = {p: np.zeros(shape, np.int64) for p in ("prefill", "decode")}
        # Prefill rows of a model with conv layers, by where their conv state
        # came from (engine_conv_state_resumes_total); None without such layers.
        self.conv_resumes: dict[str, int] | None = (
            {"cache": 0, "zero": 0, "recompute": 0} if self.cfg.conv_layers else None)
        # None: pages of per-head K and V, which KV transfer and live migration carry.
        self._uncarried: tuple[str, str] | None = getattr(module, "UNCARRIED", None)
        # Prefill dispatches by their program's rows (a chunk of a chunked
        # prefill is a dispatch of one row): engine_prefill_dispatch_rows_total.
        self.prefill_dispatch_rows: dict[int, int] = collections.defaultdict(int)
        self.prefill_rows_carried = 0   # real rows: engine_prefill_rows_total

    def bind_metrics(self, registry) -> None:
        """Attach the engine gauges to a MetricsRegistry; updated once
        per scheduler step (never per token)."""
        self._gauges = register_engine_metrics(registry)
        for source in self.conv_resumes or ():  # every source is a series from the start, at 0
            self._gauges["engine_conv_state_resumes_total"].inc(0, source=source)
        if self.side is not None:
            self.side.bind_metrics(self._gauges)

    def _feed(self, name: str, total: float, **labels: str) -> None:
        """Give counter ``name`` what its running total grew by since it
        was last fed."""
        key = (name, *labels.values())
        grown = total - self._ctr_pushed.get(key, 0)
        if grown > 0:
            self._gauges[name].inc(grown, **labels)
            self._ctr_pushed[key] = total

    def _update_gauges(self) -> None:
        g = self._gauges
        if g is None:
            return
        feed = self._feed
        g["engine_kv_cache_bytes"].set(self.args.kv_bytes_per_block() * self.args.num_kv_blocks)
        for kind, per_block in self.args.pool_bytes_per_block().items():
            g["kv_pool_bytes"].set(per_block * self.args.num_kv_blocks, kind=kind)
        g["kv_page_bytes"].set(self.args.kv_page_bytes())
        if self.side is not None:
            self.side.feed(g, feed)
        g["engine_kv_quant_enabled"].set(1 if self.args.kv_quant == "int8" else 0)
        g["engine_prefill_pad_ratio"].set(
            self.total_prefill_padded / max(1, self.total_prefilled))
        g["engine_spec_accept_rate"].set(
            self.total_spec_accepted / max(1, self.total_spec_proposed))
        g["engine_tokens_per_weight_pass"].set(
            self.total_row_tokens / max(1, self.total_row_passes))
        feed("engine_spec_tree_passes_total", self.total_spec_tree_passes)
        g["engine_spec_tree_accept_depth"].set(
            self.total_spec_tree_depth / max(1, self.total_spec_tree_rows)
        )
        feed("tier_protected_evictions_total", self.tiers.protected_evictions)
        g["tier_hit_rate"].set(self.tiers.hit_rate)
        g["engine_grammar_active_seqs"].set(
            sum(1 for s in self._running if s.grammar is not None))
        g["engine_grammar_mask_seconds"].set(self.total_grammar_mask_s)
        feed("engine_spec_budget_reallocs_total", self.total_spec_budget_reallocs)
        if self._lora_pool is not None:
            g["engine_lora_resident_adapters"].set(self._lora_pool.resident)
            feed("engine_lora_swap_total", self._lora_pool.pageins)
        g["engine_lora_gather_seconds"].set(self.total_lora_s)
        for cls, n in self.total_preemptions_by.items():
            feed("engine_preemptions_total", n, **{"class": cls})
        # What the step loop did with its time, and the decode rows and
        # prompt blocks it paid for: counters, so that a scrape before and
        # one after give a window's own figures.
        for phase, secs in self.phase_s.items():
            feed("engine_step_phase_seconds_total", secs, phase=phase)
            feed("engine_step_phase_cpu_seconds_total", self.phase_cpu_s[phase], phase=phase)
            feed("engine_step_phase_total", self.phase_n[phase], phase=phase)
        feed("engine_sched_cpu_seconds_total", time.thread_time())
        feed("engine_sched_wall_seconds_total", time.perf_counter() - self._t_run)
        for reason, n in self.admission_stops.items():
            feed("engine_admission_stops_total", n, reason=reason)
        feed("engine_prefill_waves_total", self.prefill_waves)
        feed("engine_wave_windows_ahead_total", self.wave_windows_ahead)
        feed("engine_first_ready_unread_seconds_total", self.first_ready_unread_s)
        for waited, n in self.first_fetches.items():
            feed("engine_first_fetch_total", n, waited=waited)
        for bound, secs in self.device_dry_s.items():
            feed("engine_device_dry_seconds_total", secs, bound=bound)
        feed("engine_decode_row_steps_total", self.total_decode_rows_dispatched,
             kind="dispatched")
        feed("engine_decode_row_steps_total", self.total_decode_rows_emitted,
             kind="emitted")
        feed("engine_prefill_attn_dispatch_total", self._runner.prefill_dispatches,
             path="xla" if self._runner.prefill_attn_impl == "xla" else "pallas")
        for rows, n in self.prefill_dispatch_rows.items():
            feed("engine_prefill_dispatch_rows_total", n, rows=str(rows))
        feed("engine_prefill_rows_total", self.prefill_rows_carried)
        feed("kv_pool_hit_blocks_total", self.pool.hit_blocks)
        feed("kv_pool_miss_blocks_total", self.pool.miss_blocks)
        for source, n in (self.conv_resumes or {}).items():
            feed("engine_conv_state_resumes_total", n, source=source)
        if self.moe_hist is not None:
            E, off = self.cfg.num_experts, self.cfg.expert_offset
            both = sum(self.moe_hist.values())
            zero, absent, routed = both[:, E:E + 3].sum(axis=0)
            feed("moe_assignments_total", int(both[:, :E].sum()), kind="held")
            feed("moe_assignments_total", int(zero), kind="zero")
            feed("moe_assignments_total", int(absent), kind="absent")
            feed("moe_tokens_routed_total", int(routed))
            for program, hist in self.moe_hist.items():
                touched, calls, *more = hist[:, E + 3:].sum(axis=0)
                feed("moe_expert_calls_total", int(calls), program=program)
                if not more:
                    feed("moe_experts_touched_total", int(touched), program=program)
                for chip, n in enumerate(more[:-1]):  # engine/deepseek.py:hist_extra
                    feed("moe_experts_touched_total", int(n), program=program, chip=str(chip))
            if both.shape[1] > E + 5:
                feed("moe_token_groups_total", int(both[:, -1].sum()))
            for (l, e), n in np.ndenumerate(both[:, :E]):
                feed("moe_expert_tokens_total", int(n), layer=str(self._routed_layers[l]),
                     expert=str(off + e))

    def _enter(self, key: str | None) -> float:
        """The scheduler thread leaves its phase for `key` (None at _run's
        last line) → now. Adds what the two clocks, perf_counter and the
        thread's own CPU time, gained since the phase opened to phase_s and
        phase_cpu_s, closes the profiler annotation ``sched.<phase>`` and
        opens the next, so that a profiler trace holds the thread's phases on
        the clock of the device's operations (one check and no record while
        no trace is taken), and probes the device. Something that interrupts
        a phase (a drain, _admit_alloc) enters the phase it found when done."""
        now, cpu = time.perf_counter(), time.thread_time()
        cur = self._cur
        if cur is not None:
            self.phase_s[cur] += now - self._cur_t
            self.phase_cpu_s[cur] += cpu - self._cur_cpu
            self.phase_n[cur] += 1
            self._anno.__exit__(None, None, None)
        self._cur, self._cur_t, self._cur_cpu = key, now, cpu
        self._anno = TraceAnnotation("sched." + key) if key else None
        self._probe(now)
        return now

    def _in_phase(self, key: str, fn):
        """`fn`, run in phase `key` between the caller's phase and itself."""
        def run(*args):
            back = self._cur
            self._enter(key)
            try:
                return fn(*args)
            finally:
                self._enter(back)
        return run

    def _probe(self, now: float) -> None:
        """Ask the device whether it has anything left to run. It runs what it
        is sent in order, so it is done when the outputs of the last program
        dispatched are ready, and busy while they are not. While requests run
        or wait, the time since the last probe counts towards the dry time's
        ceiling unless that program is still running now (then the device was
        busy throughout: _dispatched asks before it takes the next program's
        outputs), and towards its floor only if that probe found it done and
        nothing was dispatched since: never an instant at which a dispatched
        program could still run. Also stamps the first-token samples seen ready."""
        ready = host_ready(self._dev_last)
        work = bool(self._running or self._waiting)
        if self._probe_work:
            if ready:
                self.device_dry_s["ceiling"] += now - self._probe_t
            if self._dev_done and work:
                self.device_dry_s["floor"] += now - self._probe_t
        self._dev_done = ready and not self._dev_open
        self._probe_t, self._probe_work = now, work
        for item in self._fetchq:
            if type(item) is _First and item.t_ready is None:
                if not host_ready(item.fetch_arrays()):
                    break
                item.t_ready = now

    def _dispatching(self) -> None:
        """A program is about to go to the device: what was dry ends here, and
        until _dispatched has its outputs no probe reads the device as done."""
        self._probe(time.perf_counter())
        self._dev_open, self._dev_done = True, False

    def _dispatched(self, outputs) -> None:
        """The dispatch call has returned with the program's `outputs` (a
        dispatch that keeps none leaves the call open until the next that
        does: the floor waits). One more probe of what was dispatched before
        it: if that has finished by now, the device may have been dry for the
        whole call, waiting for the new program, and the call goes to the
        ceiling; if not, it ran throughout."""
        self._probe(time.perf_counter())
        self._dev_last, self._dev_open = outputs, False

    @staticmethod
    def _build_tiers(args: EngineArgs):
        from dynamo_tpu.block_manager.tiers import (
            DiskBlockPool,
            FleetBlockPool,
            HostBlockPool,
            TierStack,
        )

        host = HostBlockPool(args.host_kv_blocks) if args.host_kv_blocks > 0 else None
        disk = (
            DiskBlockPool(args.disk_kv_dir, args.disk_kv_blocks)
            if args.disk_kv_dir
            else None
        )
        fleet = (
            FleetBlockPool(args.fleet_kv_dir, args.fleet_kv_blocks)
            if args.fleet_kv_dir
            else None
        )
        # unit_bytes makes NON-KV paged objects (LoRA adapters) charge
        # the blocks-denominated capacity by their byte size — a 34 MB
        # 8B-geometry adapter costs ~50 block units, not 1, so the
        # host/disk byte budget the capacity was sized for holds.
        return TierStack(host, disk, fleet, unit_bytes=args.kv_bytes_per_block())

    # -- lifecycle --------------------------------------------------------

    async def start(self) -> "TpuEngine":
        self._loop = asyncio.get_running_loop()
        await asyncio.to_thread(self._runner.start)
        self._thread = threading.Thread(target=self._run, name="tpu-engine", daemon=True)
        self._thread.start()
        return self

    async def stop(self) -> None:
        with self._wakeup:
            self._stopping = True
            self._wakeup.notify()
        if self._thread is not None:
            await asyncio.to_thread(self._thread.join, 10.0)
        # Release runner resources (multi-host: sends followers the stop
        # op and closes the step-stream sockets).
        self._runner.stop()

    # -- events / metrics -------------------------------------------------

    def _on_pool_event(self, event: KvCacheEvent) -> None:
        if self._external_events is not None:
            self._external_events(event)

    def metrics(self) -> ForwardPassMetrics:
        with self._mutex:
            running, waiting = len(self._running), len(self._waiting) + len(self._submissions)
        return ForwardPassMetrics(
            worker=WorkerStats(
                request_active_slots=running,
                request_total_slots=self.args.max_num_seqs,
                num_requests_waiting=waiting,
            ),
            kv=KvStats(
                kv_active_blocks=self.pool.num_active,
                kv_total_blocks=self.pool.num_blocks - 1,
                gpu_cache_usage_perc=self.pool.usage,
                gpu_prefix_cache_hit_rate=self.pool.hit_rate,
            ),
        )

    # -- grammar-constrained decoding -------------------------------------

    def _compile_grammar(self, rf: dict):
        """response_format dict → CompiledGrammar (None = unconstrained).
        Called via to_thread from generate(); the compiler is built once
        per engine over the serving tokenizer's vocabulary and caches by
        schema hash, so structured traffic sharing a schema pays the DFA
        construction exactly once."""
        comp = self._grammar_compiler
        if comp is None:
            with self._grammar_lock:
                comp = self._grammar_compiler
                if comp is None:
                    comp = build_compiler(
                        self.args.grammar_tokenizer, self.cfg.vocab_size
                    )
                    self._grammar_compiler = comp
        return comp.compile(rf)

    def _grammar_row_masks(self, seqs: list[_Seq], B: int) -> np.ndarray | None:
        """Per-row packed grammar masks for a dense sampling dispatch
        (admission first tokens / single-step decode) → [B, W32] uint32,
        or None when no row is constrained (the unmasked jit variant —
        unconstrained traffic never pays the where()). Unconstrained
        rows in a mixed batch ride all-ones masks (bitwise identity)."""
        if not any(s.grammar is not None for s in seqs):
            return None
        t0 = time.perf_counter()
        masks = np.full(
            (B, mask_words(self.cfg.vocab_size)), 0xFFFFFFFF, np.uint32
        )
        for i, s in enumerate(seqs):
            if s.grammar is not None:
                masks[i] = s.grammar.mask(s.grammar_state, s.grammar_eos_bits)
        self.total_grammar_mask_s += time.perf_counter() - t0
        return masks

    # -- multi-LoRA adapter multiplexing ----------------------------------
    #
    # Serving shape (Punica BGMV + S-LoRA unified paging, engine/lora.py):
    # MANY per-tenant low-rank fine-tunes of the one base model share this
    # engine. The device bank holds args.lora_slots resident adapters;
    # the registry may hold far more — a cold adapter pages in at
    # admission (blocking only that request's admission, never the
    # running batch: in-flight windows keep executing and the upload is
    # device-ordered after them), its factor pages living in the SAME
    # G2/G3 tier pools as KV blocks under adapter_tier_hash keys, and a
    # cold resident pages out under the slot pool's second-chance
    # pressure. Batch rows carry adapter_slot (-1 = base) into every
    # prefill/decode/spec dispatch; base-only batches pass None and run
    # the exact pre-LoRA jit variant.

    def register_adapter(
        self,
        name: str,
        rank: int | None = None,
        seed: int = 0,
        scaling: float = 1.0,
        targets: str = "qkvo",
        pages: tuple | None = None,
    ) -> None:
        """Register one serveable adapter. ``pages`` = pre-materialized
        factor pages (checkpoint loaders); None = deterministic random
        factors from (name, seed) — what the tests and the worker's
        ``--lora NAME:RANK:SEED`` use. Write-through: pages land in the
        tier economy now, so later slot eviction is free and a cold
        re-page-in is a tier read, not a reload.
        Thread-safe; callable while serving (new tenants onboard live)."""
        if self._lora_pool is None:
            raise RequestValidationError(
                "engine has no adapter bank (lora_slots=0)"
            )
        spec = LoraAdapterSpec(
            name=name, rank=rank if rank is not None else self.args.lora_rank,
            seed=seed, scaling=scaling, targets=targets,
        )
        if spec.rank > self.args.lora_rank:
            raise RequestValidationError(
                f"adapter {name!r} rank {spec.rank} exceeds lora_rank="
                f"{self.args.lora_rank}"
            )
        if self.tiers.enabled:
            tier_pages = (
                pages if pages is not None
                else make_adapter_pages(self.cfg, spec, self.args.lora_rank)
            )
            self.tiers.put_object(adapter_tier_hash(name), *tier_pages)
        # Caller-provided pages (real checkpoints) are NOT rematerializable
        # from the spec, so they stay pinned in the registry even with
        # tiers enabled — the tiers are a cache (adapter objects compete
        # with KV blocks and CAN be evicted end to end), never the only
        # copy. Seed-generated adapters pin nothing (a tier miss
        # regenerates bit-identically).
        with self._lora_lock:
            self._lora_registry[name] = (spec, pages)

    def adapters(self) -> list[str]:
        """Registered adapter names (thread-safe)."""
        with self._lora_lock:
            return sorted(self._lora_registry)

    def lora_stats(self) -> dict:
        """Slot-pool residency/swap counters (racy snapshot)."""
        if self._lora_pool is None:
            return {}
        return self._lora_pool.stats()

    def _adapter_pages(self, spec: LoraAdapterSpec,
                       pinned: tuple | None) -> tuple:
        """Fetch one adapter's factor pages: tier hit (G2, promoting a G3
        hit — the unified-paging path), registry-pinned pages (real
        checkpoints — always retained), or rematerialize from the spec's
        seed source and write back through. Tier hit/miss counts feed
        tier_hit_rate, so adapter churn shows in the same signal KV
        churn does."""
        h = adapter_tier_hash(spec.name)
        if self.tiers.enabled:
            pages = self.tiers.get_object(h)
            if pages is not None:
                return pages
        if pinned is not None:
            if self.tiers.enabled:  # re-warm the cache for the next miss
                self.tiers.put_object(h, *pinned)
            return pinned
        pages = make_adapter_pages(self.cfg, spec, self.args.lora_rank)
        if self.tiers.enabled:
            self.tiers.put_object(h, *pages)
        return pages

    def _acquire_adapter(self, seq: _Seq) -> None:
        """Resolve seq.adapter_id → pinned bank slot, uploading on a cold
        miss. Raises RequestValidationError (unknown adapter) or
        NoFreeAdapterSlotsError (every slot pinned — admission requeues
        and retries when running sequences release pins)."""
        if self._lora_pool is None:
            raise RequestValidationError(
                f"request names adapter {seq.adapter_id!r} but this engine "
                "has no adapter bank (lora_slots=0)"
            )
        with self._lora_lock:
            entry = self._lora_registry.get(seq.adapter_id)
        if entry is None:
            raise RequestValidationError(f"unknown adapter {seq.adapter_id!r}")
        spec, pinned = entry
        t0 = time.perf_counter()
        slot, needs_upload, _evicted = self._lora_pool.acquire(seq.adapter_id)
        if needs_upload:
            try:
                self._dispatching()
                self._runner.upload_adapter(
                    slot, self._adapter_pages(spec, pinned)
                )
            except BaseException:
                # The upload never landed: DROP the residency entry (not
                # just the pin) or the next acquire would skip the upload
                # and decode against a zero/partial bank slot.
                self._lora_pool.drop(seq.adapter_id)
                raise
        seq.adapter_slot = slot
        self.total_lora_s += time.perf_counter() - t0

    def _release_adapter(self, seq: _Seq) -> None:
        if seq.adapter_slot >= 0 and self._lora_pool is not None:
            self._lora_pool.release(seq.adapter_id)
        seq.adapter_slot = -1

    def _adapter_row_slots(self, seqs: list[_Seq], B: int) -> np.ndarray | None:
        """Per-row adapter_slot operand for one dispatch → [B] int32, or
        None when no row carries an adapter (the unadapted jit variant —
        base-only traffic pays nothing, byte-identical to a lora-disabled
        engine). Base rows in a mixed batch ride -1 (where-masked in
        model._lora_apply, bit-identical)."""
        if not any(s.adapter_slot >= 0 for s in seqs):
            return None
        t0 = time.perf_counter()
        slots = np.full((B,), -1, np.int32)
        for i, s in enumerate(seqs):
            slots[i] = s.adapter_slot
        self.total_lora_s += time.perf_counter() - t0
        return slots

    # -- async API --------------------------------------------------------

    async def generate(self, request: Any, context: Context) -> AsyncIterator[dict]:
        """AsyncEngine shape: PreprocessedRequest (or its dict) in →
        LLMEngineOutput dicts out (token deltas; no text — Backend's job)."""
        req = request if isinstance(request, PreprocessedRequest) else PreprocessedRequest.from_dict(request)
        # Validate wire input here (caller's coroutine) so malformed requests
        # error this stream instead of reaching the shared scheduler thread.
        if not req.token_ids:
            yield LLMEngineOutput(
                finish_reason=FinishReason.ERROR, error="empty prompt"
            ).to_dict()
            return
        ktp = req.kv_transfer_params or {}
        if self._uncarried is not None and any(k in ktp for k in (
                "do_remote_decode", "peer_prefix", "stream_handle", "handle", "pages")):
            yield LLMEngineOutput(
                finish_reason=FinishReason.ERROR,
                error="KV transfer (transfer/: disaggregated prefill, peer prefix "
                      f"fetch) cannot carry a block={self.cfg.block!r} model's {self._uncarried[0]}",
            ).to_dict()
            return
        vocab = self.cfg.vocab_size
        if any(not (0 <= int(t) < vocab) for t in req.token_ids):
            yield LLMEngineOutput(
                finish_reason=FinishReason.ERROR,
                error=f"token id out of range [0, {vocab})",
            ).to_dict()
            return
        # One static alternative-logprob width (compile-matrix bound);
        # requests beyond it are clamped, not rejected. top_logprobs
        # without logprobs would pay the top-k and emit nothing — zero it.
        if req.sampling.top_logprobs:
            req.sampling.top_logprobs = (
                min(req.sampling.top_logprobs, self.args.top_logprobs_max)
                if req.sampling.logprobs else 0
            )
        # Grammar-constrained decoding: compile (or cache-hit) the
        # token-mask FSM for this request's response_format OFF the
        # event loop and the scheduler thread. Malformed specs error
        # this stream only (the frontend already 400s them; engine-
        # direct callers get the typed message).
        grammar = None
        if req.response_format:
            try:
                grammar = await asyncio.to_thread(
                    self._compile_grammar, req.response_format
                )
            except GrammarError as e:
                yield LLMEngineOutput(
                    finish_reason=FinishReason.ERROR,
                    error=f"invalid response_format: {e}",
                ).to_dict()
                return
        queue: asyncio.Queue = asyncio.Queue()
        t_submit = time.perf_counter()
        seq = _Seq(context.id, req, queue)
        # Span lineage across relocation: a resume leg that arrives
        # without a live trace (engine-direct dispatch, staged-inject
        # claim path) re-anchors on the traceparent the cutover identity
        # carried, so destination spans join the original request trace
        # instead of minting a fresh root.
        resume_tp = ((req.kv_transfer_params or {}).get("resume") or {}).get("traceparent")
        if context.trace is None and resume_tp:
            from dynamo_tpu.runtime.logging import TraceContext

            try:
                context.trace = TraceContext.parse(str(resume_tp))
            except Exception:  # noqa: BLE001 — a malformed carried traceparent must never fail the resume leg
                pass
        seq.traceparent = (
            context.trace.traceparent() if context.trace is not None else None
        )
        if grammar is not None:
            seq.grammar = grammar
            seq.grammar_state = grammar.start
            seq.grammar_eos_bits = pack_token_ids(
                seq.eos_ids, self.cfg.vocab_size
            )
            self.total_grammar_seqs += 1
            if seq.prompt_len < len(seq.tokens):
                # Resumed (migrated/re-dispatched) constrained request:
                # the carried tokens past the original prompt boundary
                # were GENERATED under this grammar on the previous leg —
                # replay the FSM over them so masking continues from the
                # exact state the source reached (deterministic: the FSM
                # is a pure function of the emitted tokens).
                st = grammar.start
                for t in seq.tokens[seq.prompt_len:]:
                    if t in seq.eos_ids:
                        break
                    ns = grammar.advance(st, t)
                    if ns is None:
                        break  # desync-defensive, same stance as _emit_tokens
                    st = ns
                seq.grammar_state = st
        if not self.args.qos_scheduling:
            seq.qos_rank = 0  # one class: FIFO admission, newest-first preempt
        with self._wakeup:
            if self._stopping:
                raise RuntimeError("engine is stopping")
            self._arrival_no += 1
            seq.arrival = self._arrival_no
            self._submissions.append(seq)
            self._wakeup.notify()

        async def watch_cancel():
            await context.wait_cancelled()
            with self._wakeup:
                seq.cancelled = True
                self._wakeup.notify()

        watcher = asyncio.get_running_loop().create_task(watch_cancel())
        dspan = tracing.NOOP_SPAN
        first = True
        # Emit coalescing: merge the backlog of decode-window deltas
        # already sitting in the queue into one frame (bounded by
        # delta_max_tokens; optional delta_max_ms gather wait). The first
        # delta is never delayed (TTFT), and a finish delta terminates the
        # merge so it rides the same frame as its tokens.
        cap = self.args.delta_max_tokens
        gather_s = self.args.delta_max_ms / 1000.0
        pending: Any = None
        try:
            while True:
                item = pending if pending is not None else await queue.get()
                pending = None
                if cap > 0 and isinstance(item, dict) and not item.get("finish_reason"):
                    # Backlog merge first (free — deltas already queued),
                    # then the opt-in bounded gather to fill the frame
                    # further toward the cap (costs ≤ delta_max_ms of ITL;
                    # default 0 never waits; the first delta never waits).
                    deadline = (
                        time.monotonic() + gather_s
                        if gather_s > 0.0 and not first else None
                    )
                    while (
                        pending is None
                        and len(item.get("token_ids") or ()) < cap
                        and not item.get("finish_reason")
                    ):
                        if not queue.empty():
                            nxt = queue.get_nowait()
                        elif deadline is not None:
                            wait = deadline - time.monotonic()
                            if wait <= 0:
                                break
                            try:
                                nxt = await asyncio.wait_for(queue.get(), wait)
                            except asyncio.TimeoutError:
                                break
                        else:
                            break
                        if not isinstance(nxt, dict):
                            pending = nxt  # _SENTINEL_DONE: deliver after item
                            break
                        if (
                            len(item.get("token_ids") or ())
                            + len(nxt.get("token_ids") or ())
                        ) > cap:
                            pending = nxt  # merging would exceed the cap
                            break
                        merged = coalesce_delta(item, nxt)
                        if merged is None:
                            pending = nxt
                            break
                        item = merged
                if item is _SENTINEL_DONE:
                    return
                if first:
                    first = False
                    if tracing.enabled() and context.trace is not None:
                        # The first token's timeline from the scheduler
                        # thread's stamps, recorded retroactively at first
                        # delta; decode is live from here. engine.prefill
                        # is admission to first delta: the sum of
                        # engine.dispatch, engine.first_wait and
                        # engine.deliver, most of it waiting, not compute.
                        now = time.perf_counter()
                        t_admit = seq.t_admit or now
                        span = functools.partial(tracing.record_interval, parent=context.trace)
                        span("engine.queue", start=t_submit, end=t_admit)
                        if seq.t_blocked is not None:
                            span("engine.blocked", start=seq.t_blocked, end=t_admit,
                                 reason=seq.blocked_why)
                        if seq.t_dispatched is not None and seq.t_first is not None:
                            span("engine.dispatch", start=t_admit, end=seq.t_dispatched,
                                 chunks=seq.chunks, wave=seq.wave[0],
                                 windows_in_flight=seq.wave[1])
                            span("engine.first_wait", start=seq.t_dispatched,
                                 end=seq.t_first, blocked=seq.first_waited,
                                 ready_unread_ms=round(1e3 * seq.first_unread_s, 3))
                            span("engine.deliver", start=seq.t_first, end=now)
                        span("engine.prefill", start=t_admit, end=now,
                             prompt_tokens=seq.prompt_len,
                             cached_blocks=seq.prefix_hit_blocks)
                        dspan = tracing.start_span(
                            "engine.decode", parent=context.trace
                        )
                yield item
                if isinstance(item, dict) and item.get("finish_reason"):
                    return
        finally:
            dspan.set_attrs(tokens=seq.emitted)
            dspan.end(status="cancelled" if seq.cancelled else None)
            watcher.cancel()
            with self._wakeup:
                seq.cancelled = True  # no-op if already finished

    # -- scheduler loop (engine thread) -----------------------------------

    def _run(self) -> None:
        crashed = False
        self._t_run = self._enter("idle")
        try:
            while True:
                with self._wakeup:
                    while (
                        not self._stopping
                        and not self._submissions
                        and not self._waiting
                        and not self._running
                        and not self._embed_jobs
                        and not self._host_jobs
                    ):
                        if self._migrations:
                            # A frozen cutover must still observe its
                            # deadline even on an otherwise-idle engine:
                            # bounded sleep, then run a (cheap) step.
                            self._wakeup.wait(timeout=0.02)
                            break
                        self._wakeup.wait()
                    if self._stopping:
                        break
                    while self._submissions:
                        self._waiting.append(self._submissions.popleft())
                self._step()
                self._enter("idle")
        except Exception:  # noqa: BLE001 — engine death must not be silent
            crashed = True
            log.exception("engine loop crashed")
        finally:
            # Flip stopping FIRST so late generate() calls are rejected
            # instead of queueing onto a dead thread.
            self._enter("housekeeping")
            self._fetchq.clear()  # drop; leftovers get terminal posts below
            self._export_fetches.clear()
            with self._mutex:
                exports = [item for item, _dl in self._exports.values()]
            for item in exports:
                if isinstance(item, KvStreamExport):
                    item.abort("engine_stopped")  # no-op when sealed
            with self._wakeup:
                self._stopping = True
                leftovers = list(self._running) + list(self._waiting) + list(self._submissions)
                # Frozen mid-cutover sequences live in no queue; without a
                # terminal post their client streams would hang forever.
                leftovers += [
                    m.seq for m in self._migrations.values()
                    if m.frozen and not m.seq.dead
                ]
                for m in self._migrations.values():
                    m.stream.abort("engine_stopped")
                    m.seq.mig = None
                self._migrations.clear()
                self._running.clear()
                self._waiting.clear()
                self._submissions.clear()
            reason = FinishReason.ERROR if crashed else FinishReason.CANCELLED
            err = "engine loop crashed" if crashed else None
            for seq in leftovers:
                self._post(seq, LLMEngineOutput(finish_reason=reason, error=err).to_dict())
                self._post_done(seq)
            # Pending embed/host-job futures must resolve too, or their
            # awaiters hang forever.
            while self._embed_jobs or self._host_jobs:
                if self._embed_jobs:
                    _toks, fut, floop = self._embed_jobs.popleft()
                else:
                    _fn, fut, floop = self._host_jobs.popleft()
                exc = RuntimeError(err or "engine stopped")
                floop.call_soon_threadsafe(
                    lambda f=fut, e=exc: f.set_exception(e) if not f.cancelled() else None
                )
            self._enter(None)

    def _step(self) -> None:
        self._enter("housekeeping")
        self._step_no += 1
        # Harvest whatever fetches completed while the host was away:
        # frees slots/KV and discovers stops as early as possible, and
        # costs nothing when the head of the queue is still in flight.
        self._drain_completed()
        if self._export_fetches:
            self._drain_export_fetches()
        self._reap_cancelled()
        while self._embed_jobs:
            self._serve_embed(*self._embed_jobs.popleft())
        while self._host_jobs:
            self._serve_host_job(*self._host_jobs.popleft())
        if self._exports:
            self._reap_exports()
        if self._migrations:
            self._service_migrations()
        if self.kv_pressure_offer > 0.0:
            self._maybe_pressure_offer()
        # Prefill-priority admission, two phases: (1) allocate KV for the
        # whole wave, (2) dispatch prefills PACKED by suffix bucket
        # (model.prefill_batch) — one-at-a-time prefill was the r3 TTFT
        # killer. The wave shares ONE first-token sampling fetch, and the
        # whole wave is dispatched while previously-dispatched decode
        # windows are still executing (prefill interleave: arrivals no
        # longer inherit a blocking drain's worth of queueing delay).
        # The wave is budgeted to ~one max_prefill_tokens chunk so running
        # decodes are not starved by a long burst of arrivals.
        self._enter("admission")
        waited, stopped = bool(self._waiting), None
        allocated: list[tuple[_Seq, int]] = []  # (seq, suffix start)
        wave_budget = self.args.admission_budget_tokens or (1 << 62)
        # Frozen mid-cutover sequences are out of _running but still hold
        # their chain slot (and KV) until the handoff resolves — admission
        # must not oversubscribe the slot pool past them.
        frozen = sum(1 for m in self._migrations.values() if m.frozen)
        while (
            self._waiting
            and len(self._running) + len(allocated) + frozen < self.args.max_num_seqs
            and (wave_budget > 0 or not allocated)
        ):
            seq = self._pop_next_waiting()
            if seq.cancelled:
                self._post_done(seq)
                continue
            wave_budget -= len(seq.tokens)
            try:
                start = self._admit_alloc(seq)
            except NoFreeBlocksError:
                self._waiting.appendleft(seq)  # try again when blocks free up
                self._stamp_blocked(seq, "blocks")
                stopped = "blocks"
                if not self._running and not allocated and not self._migrations:
                    # Deadlock: nothing to free. Fail the request.
                    # (A frozen migration is NOT a deadlock — its blocks
                    # free within the bounded cutover window either way.)
                    self._waiting.remove(seq)
                    self._finish(seq, FinishReason.ERROR,
                                 error="prompt does not fit in KV cache")
                break
            except RequestValidationError as e:
                self._finish(seq, FinishReason.ERROR, error=str(e))
                continue
            except Exception as e:  # noqa: BLE001 — contain per-request faults
                log.exception("admission failed for %s", seq.request_id)
                if seq.block_ids:
                    self.pool.free_sequence(seq.block_ids)
                    seq.block_ids = []
                self._finish(seq, FinishReason.ERROR, error=f"admission failed: {e}")
                continue
            seq.t_admit = time.perf_counter()
            allocated.append((seq, start))
        if self._waiting and (
            len(self._running) + len(allocated) + frozen >= self.args.max_num_seqs
        ):
            # The head of the queue waits for a slot. Finding it is a pass
            # over _waiting, so look again only when the queue has changed
            # (its length or either end: a backlog adds nothing to a step).
            stopped = stopped or "slots"
            sig = (len(self._waiting), self._waiting[0], self._waiting[-1])
            if sig != self._slots_blocked_sig:
                self._slots_blocked_sig = sig
                self._stamp_blocked(self._next_waiting(), "slots")
        if waited:
            # The loop's own conditions: what ended it when neither the
            # blocks, the slots nor the queue did is the wave's budget.
            self.admission_stops[stopped or ("budget" if self._waiting else "empty")] += 1
        admitted: list[tuple[_Seq, Any, int]] = []  # (seq, logits array, row)
        if allocated:
            self._enter("prefill_dispatch")
            try:
                admitted = self._dispatch_prefills(allocated)
            except Exception as e:  # noqa: BLE001 — contain wave faults
                log.exception("prefill dispatch failed")
                for seq, _ in allocated:
                    self.pool.free_sequence(seq.block_ids)
                    seq.block_ids = []
                    self._finish(seq, FinishReason.ERROR, error=f"prefill failed: {e}")
            t0 = self._enter("first_dispatch")
            # Every chunk of the wave is on the device's queue from here.
            wave = (len(allocated), self._inflight_windows())
            self.prefill_waves += 1
            self.wave_windows_ahead += wave[1]
            for seq, _ in allocated:
                seq.t_dispatched, seq.wave = t0, wave
        if admitted:
            # Async admission: sample first tokens ON DEVICE, fold them
            # into each sequence's chain slot, and enqueue the host fetch
            # on the completion queue (transfer started immediately) —
            # the fetch roundtrip overlaps window execution instead of
            # idling the device (r4 bench: these syncs were 68% of the
            # timed section). Waves padded to a decode bucket so sampling
            # compiles once per bucket.
            seqs = [s for s, _, _ in admitted]
            try:
                B = self.args.bucket_decode(len(admitted))
                srcs = [(ref, row) for _, ref, row in admitted]
                srcs += [srcs[0]] * (B - len(srcs))
                for s in seqs:
                    s.slot = self._free_slots.pop()
                slots = np.full((B,), self.args.max_num_seqs, np.int32)
                slots[: len(seqs)] = [s.slot for s in seqs]
                out_d, lps_d, top_ref = self._sample_rows_device(
                    srcs, seqs, slots,
                    top_n=(self.args.top_logprobs_max
                           if any(s.sampling.top_logprobs for s in seqs) else 0),
                )
            except Exception as e:  # noqa: BLE001 — admitted seqs are in no
                # collection yet; orphaning them would hang their streams.
                log.exception("first-token sampling failed")
                for seq in seqs:
                    self.pool.free_sequence(seq.block_ids)
                    seq.block_ids = []
                    self._finish(seq, FinishReason.ERROR, error=f"sampling failed: {e}")
                seqs = []
            if seqs:
                for seq in seqs:
                    seq.first_pend = True
                    self._running.append(seq)
                first = _First(
                    [(s, i) for i, s in enumerate(seqs)], out_d, lps_d, top_ref,
                    self._runner.take_routed(),
                )
                start_host_fetch(first.fetch_arrays())
                self._fetchq.append(first)
                # Prefill-only requests (disagg export, max_tokens=1)
                # finish at the first token — resolve JUST this wave's
                # sample now (its seqs ride no earlier queued item, so
                # draining it out of FIFO order is safe) so they never
                # ride a decode window as instant zombies and the rest of
                # the pipeline stays in flight.
                if any(s.stop.max_tokens == 1 for s in seqs):
                    self._fetchq.pop()  # == first, just appended
                    self._drain_one(first)
        if self._running:
            self._enter("plan")
            self._decode_iteration()
            self._enter("housekeeping")
            self._flush_offloads()
        elif self._fetchq:
            # Every owner of the queued fetches died during a drain:
            # release them all (zombie rows; keeps StepRef/device arrays
            # from idling forever — the idle predicate ignores _fetchq —
            # and total_decode_steps honest).
            self._enter("housekeeping")
            self._drain_completed(force=True)
        self._enter("gauges")
        self._update_gauges()

    # -- embeddings (reference: http/service/openai.rs:302) ----------------

    async def embed(self, token_ids: list[int]) -> list[float]:
        """Mean-pooled final hidden state; serialized through the
        scheduler thread (device dispatch affinity)."""
        if not token_ids:
            raise RequestValidationError("empty input")
        refuse_block(self.cfg, "embeddings (embed_impl)")
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        with self._wakeup:
            if self._stopping:
                raise RuntimeError("engine is stopping")
            self._embed_jobs.append((list(token_ids), fut, loop))
            self._wakeup.notify()
        return await fut

    async def run_on_engine_thread(self, fn):
        """Run ``fn()`` on the scheduler thread between steps (device
        dispatch affinity) and await its result. Migration's freeze/export
        seam (worker/migrate.py) — not a request-path API."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        with self._wakeup:
            if self._stopping:
                raise RuntimeError("engine is stopping")
            self._host_jobs.append((fn, fut, loop))
            self._wakeup.notify()
        return await fut

    def _serve_host_job(self, fn, fut, loop) -> None:
        try:
            result = fn()
            loop.call_soon_threadsafe(
                lambda: fut.set_result(result) if not fut.cancelled() else None
            )
        except Exception as e:  # noqa: BLE001 — surface to the caller
            err = e
            loop.call_soon_threadsafe(
                lambda: fut.set_exception(err) if not fut.cancelled() else None
            )

    def _serve_embed(self, token_ids: list[int], fut, loop) -> None:
        try:
            if len(token_ids) > self.args.max_model_len:
                raise RequestValidationError(
                    f"input of {len(token_ids)} tokens exceeds max_model_len "
                    f"of {self.args.max_model_len}"
                )
            # Long inputs chunk-pool (VERDICT r4 weak #8): each
            # max_prefill_tokens chunk embeds independently and the
            # results token-weight-average — the standard long-input
            # recipe for mean-pooled embeddings (cross-chunk attention is
            # traded away; within-chunk context is exact).
            chunks = [
                token_ids[i : i + self.args.max_prefill_tokens]
                for i in range(0, len(token_ids), self.args.max_prefill_tokens)
            ]
            refs = []
            for chunk in chunks:
                t_pad = self.args.bucket_prefill(len(chunk))
                toks = np.zeros((t_pad,), np.int32)
                toks[: len(chunk)] = chunk
                self._dispatching()
                refs.append(self._runner.embed(toks, len(chunk)))
            acc: np.ndarray | None = None
            for chunk, ref in zip(chunks, refs):
                v = np.asarray(ref.arrs[0], dtype=np.float64) * len(chunk)
                acc = v if acc is None else acc + v
            vec = [float(x) for x in acc / len(token_ids)]
            loop.call_soon_threadsafe(
                lambda: fut.set_result(vec) if not fut.cancelled() else None
            )
        except Exception as e:  # noqa: BLE001 — surface to the caller
            err = e
            loop.call_soon_threadsafe(
                lambda: fut.set_exception(err) if not fut.cancelled() else None
            )

    def clear_kv_blocks(self) -> int:
        """Admin: drop all idle cached blocks (reference:
        http/service/clear_kv_blocks.rs). → number of blocks dropped."""
        return self.pool.clear()

    def _flush_offloads(self) -> None:
        """Batch-extract queued sealed blocks to the host tiers: one DMA
        per step, bounded. Runs on the engine thread before the next
        donation can recycle the pages (blocks are referenced or at worst
        LRU-cached until the next allocation, which happens after)."""
        if not self._offload_pending:
            return
        batch = self._offload_pending[: self.tiers.MAX_OFFLOAD_PER_STEP]
        del self._offload_pending[: len(batch)]
        self._dispatching()
        pages = self._runner.extract_pages([b for b, _ in batch])
        self._dispatched(pages)  # on the host: the device is through with them
        self.tiers.offload(
            [
                (h, *(a[:, i : i + 1] for a in pages))
                for i, (_, h) in enumerate(batch)
            ],
            # Radix protection hint: branch points / live-shared blocks
            # get eviction credit in the tiers so one-off prompt bursts
            # can't flush the hot shared system-prefix blocks.
            protected=[self.pool.hash_protected(h) for _, h in batch],
        )

    def _reap_cancelled(self) -> None:
        for seq in [s for s in self._running if s.cancelled]:
            self._finish(seq, FinishReason.CANCELLED)
        for seq in [s for s in self._waiting if s.cancelled]:
            self._waiting.remove(seq)
            self._post_done(seq)

    # -- admission / prefill ----------------------------------------------

    def _pop_next_waiting(self) -> _Seq:
        """(class, age)-ordered admission: the highest-rank class first,
        oldest arrival within it — a waiting interactive request admits
        ahead of queued batch work, including into blocks a batch
        preemption just freed. Uniform-rank traffic (no-QoS, or
        qos_scheduling off) reduces to EXACT FIFO: _waiting is
        arrival-ordered (appendleft re-queues — preempted or
        blocks-starved seqs — are always the oldest arrivals, since
        admission itself drains oldest-first), so min arrival IS the
        leftmost element and this selection is byte-identical to the
        popleft it replaces."""
        best = self._next_waiting()
        self._waiting.remove(best)
        return best

    def _next_waiting(self) -> _Seq:
        return max(self._waiting, key=lambda s: (s.qos_rank, -s.arrival))

    @staticmethod
    def _stamp_blocked(seq: _Seq, why: str) -> None:
        """The first admission attempt of `seq` that failed for want of a
        slot or of blocks: where its ``engine.blocked`` span starts."""
        if seq.t_blocked is None:
            seq.t_blocked, seq.blocked_why = time.perf_counter(), why

    def _admit_alloc(self, seq: _Seq) -> int:
        """Phase 1 of admission: allocate KV blocks, resolve prefix hits
        (local cache, disagg inject, tier onboard). Returns the suffix
        start position. Raises on resource/validation failure; no model
        dispatch happens here."""
        self._enter("admit_alloc")
        acquired = False
        try:
            # Flush queued offloads BEFORE allocating: allocation may evict and
            # recycle exactly the pages still waiting to be copied out.
            self._flush_offloads()
            # Adapter residency first (before any block allocation, so a
            # failure here has nothing to unwind): resolve adapter_id → a
            # pinned bank slot, paging the adapter in on a cold miss. Only
            # THIS request's admission blocks on the fetch — decode windows
            # already in flight keep executing, and the upload is device-
            # stream-ordered after them.
            if seq.adapter_id is not None and seq.adapter_slot < 0:
                self._acquire_adapter(seq)
                acquired = True
            return self._admit_alloc_blocks(seq)
        except BaseException:
            if acquired:
                self._release_adapter(seq)
            raise
        finally:
            self._enter("admission")

    def _admit_alloc_blocks(self, seq: _Seq) -> int:
        bs = self.args.block_size
        prompt = seq.tokens
        plen = len(prompt)
        if plen > self.args.max_model_len - 1:
            raise RequestValidationError("prompt exceeds max_model_len")
        # KV identity is (tokens, adapter): the hash seed is salted by
        # the adapter id (tokens.adapter_hash_seed), so adapter KV never
        # prefix-hits base/other-adapter blocks — in the G1 radix tree,
        # the G2/G3 tiers, KV events, and peer fetches alike.
        hashes = compute_block_hashes(prompt, bs, seq.hash_seed)
        # Never reuse the *entire* prompt: at least one suffix token must be
        # computed to produce logits (vLLM rule).
        max_hit = (plen - 1) // bs
        hashes_matchable = hashes[:max_hit]
        total_blocks = (plen + bs - 1) // bs
        depth = found = None
        if self.side is not None:
            depth, found = self.side.max_hit(hashes_matchable)
        block_ids, n_hit = self.pool.allocate_sequence(hashes_matchable, total_blocks, max_hit=depth)
        if self.side is not None:
            try:
                self.side.admit(seq, found, hashes_matchable, n_hit)
            except NoFreeBlocksError:
                self.pool.free_sequence(block_ids)
                raise
        seq.block_ids = block_ids
        seq.prefix_hit_blocks = n_hit
        seq.block_seq = TokenBlockSequence(prompt, bs, seq.hash_seed)
        start = n_hit * bs

        # G2/G3 onboard: blocks evicted from HBM but still host-resident
        # re-enter as a prefix hit instead of being recomputed
        # (reference: block_manager/offload.rs onboard path). Runs BEFORE
        # a remote inject: a peer payload may start past the local tiers'
        # coverage (llm/peer_kv.py delta fetch).
        if self.tiers.enabled and n_hit < max_hit:
            run = self.tiers.lookup_run(hashes_matchable[n_hit:])
            if run:
                # Per-block page tuples → one batched inject; int8 pages
                # carry their scale sidecars through the same stack, and
                # blocks a persistent disk dir stored under a different
                # kv_quant setting are bridged to the current format.
                pages = kv_transfer.concat_page_run(
                    run,
                    quantized=self.args.kv_quant == "int8",
                    num_kv_heads=self.args.model.num_kv_heads,
                    dtype=self.args.dtype,
                )
                n_onb = n_hit + len(run)
                self._dispatching()
                self._runner.inject_pages(seq.block_ids[n_hit:n_onb], *pages)
                n_hit = n_onb
                start = n_hit * bs
                seq.prefix_hit_blocks = n_hit

        # Disagg / peer fetch: pre-load remotely-prefilled pages as a
        # materialized prefix hit — the suffix (< 2 blocks) is recomputed
        # locally, which also regenerates the first-token logits (no logit
        # shipping).
        if seq.inject is not None:
            start, n_hit = self._inject_kv(seq, n_hit, max_hit)
            seq.prefix_hit_blocks = n_hit

        # Streaming disagg export: register the stream at ADMISSION so
        # the decode worker's kv_fetch can start pulling while prefill
        # is still running; locally prefix-hit blocks are already in
        # cache, so they publish as chunk 0 right now.
        if seq.export and seq.export_handle:
            exp = KvStreamExport(
                seq.export_handle,
                max_buffer_bytes=self.args.transfer_buffer_bytes,
            )
            seq.export_stream = exp
            with self._mutex:
                self._exports[seq.export_handle] = (
                    exp, time.monotonic() + self.export_ttl_s
                )
            n_exp = (plen - 1) // bs  # full blocks only, like _export_kv
            hit = min(start // bs, n_exp)
            if hit > 0:
                self._start_export_extract(seq, 0, hit)
        return start

    def _dispatch_prefills(
        self, allocated: list[tuple[_Seq, int]]
    ) -> list[tuple[_Seq, Any, int]]:
        """Phase 2 of admission: run the wave's prefills. Suffixes that fit
        one chunk go out as rows of prefill_batch dispatches, several to a
        dispatch where the model's weight stream pays for it and the packed
        program exists (EngineArgs.plan_prefill_packs over the runner's
        packed_ready), alone otherwise; longer prompts fall back to
        per-sequence chunked prefill, and a suffix left alone whose bucket
        pad is large splits into [bucket chunk, re-bucketed tail] chunked
        dispatches (plan_prefill_chunks). Returns (seq, logits array, row
        index) triples (logits not synced)."""
        out: list[tuple[_Seq, Any, int]] = []
        # Rows that may share a dispatch: one chunk, and no adapter (the
        # program with a bank operand is compiled for one row only).
        rows = [(seq, start) for seq, start in allocated
                if len(seq.tokens) - start <= self.args.max_prefill_tokens and seq.adapter_slot < 0]
        packs = [([rows[j] for j in idx], n_rows, t_pad)
                 for idx, n_rows, t_pad in self.args.plan_prefill_packs(
                     [len(seq.tokens) - start for seq, start in rows], self._runner.packed_ready)
                 if n_rows > 1]
        in_pack = {id(seq) for members, _, _ in packs for seq, _ in members}
        chunked: list[tuple[_Seq, int, list[int] | None]] = []
        singles: dict[int, list[tuple[_Seq, int]]] = {}
        for seq, start in allocated:
            if self.conv_resumes is not None:
                # The state rides the pages, so a row starts where its K and V
                # hits end; "recompute" counts if that ever stops being so.
                cached_kv = seq.prefix_hit_blocks * self.args.block_size
                self.conv_resumes[
                    "recompute" if start < cached_kv else "cache" if start else "zero"] += 1
            if id(seq) in in_pack:
                continue
            sfx = len(seq.tokens) - start
            # Alone, a suffix is padded at its own cost: split the tail off
            # where that saves two blocks of padding.
            plan = None if sfx > self.args.max_prefill_tokens else self.args.plan_prefill_chunks(sfx)
            if plan is None or len(plan) > 1:
                chunked.append((seq, start, plan))
            else:
                singles.setdefault(self.args.bucket_prefill(sfx), []).append((seq, start))

        for seq, start, plan in chunked:
            # row=None: chunked prefill yields [V] logits, not a batch row.
            out.append((seq, self._prefill_chunked(seq, start, plan), None))
        # What goes alone goes as it went before there were packs: by T
        # bucket, in arrival order within one.
        packs += [([one], 1, t_pad) for t_pad, members in sorted(singles.items()) for one in members]
        for members, n_rows, t_pad in packs:
            arr = self._prefill_packed(members, n_rows, t_pad)
            for row, (seq, start) in enumerate(members):
                out.append((seq, arr, row))
        if self.side is not None:
            self.side.end_wave()
        return out

    def _side_kw(self, operand) -> dict:
        """How a dispatch hands the runner what the block's second cache gave
        for it: a runner of a block without one is called as it was."""
        return {} if operand is None else {"state": operand}

    def _release_side(self, seq: _Seq) -> None:
        """``seq`` stops running (finished, failed or preempted): what it
        holds of the block's second cache goes back, the blocks its last
        window sealed registered first (its drain registered before it
        emitted, and what stays cached there is worth what their pages are)."""
        if seq.side is not None:
            self._register_written_blocks(seq)
            self.side.release(seq)

    def _prefill_packed(
        self, members: list[tuple[_Seq, int]], Bp: int, t_pad: int
    ) -> Any:
        """One prefill_batch dispatch of ``Bp`` rows, the first
        ``len(members)`` of them real (a row left over is inactive: true_len
        0, it writes only to garbage block 0). A pack runs at the wide
        table, the only one its programs are compiled for. Returns logits
        [Bp, V] (not synced)."""
        W = (self.args.blocks_per_seq if Bp > 1
             else self.args.bucket_table(len(members[0][0].block_ids)))
        toks = np.zeros((Bp, t_pad), np.int32)
        tables = np.zeros((Bp, W), np.int32)
        starts = np.zeros((Bp,), np.int32)
        tlens = np.zeros((Bp,), np.int32)
        for r, (seq, start) in enumerate(members):
            sfx = seq.tokens[start:]
            toks[r, : len(sfx)] = sfx
            tables[r, : len(seq.block_ids)] = seq.block_ids
            starts[r] = start
            tlens[r] = len(seq.tokens)
        aslots = self._adapter_row_slots([s for s, _ in members], Bp)
        side = self.side and self.side.prefill_rows([(s, a, len(s.tokens)) for s, a in members], Bp, W)
        self._dispatching()
        ref = self._runner.prefill_batch(toks, tables, starts, tlens, aslots, **self._side_kw(side))
        self._dispatched(ref.arrs)
        self.total_prefill_padded += Bp * t_pad
        self.prefill_dispatch_rows[Bp] += 1
        self.prefill_rows_carried += len(members)
        for seq, start in members:
            seq.chunks = 1
            self._finish_prefill_bookkeeping(seq, start)
        return ref

    def _prefill_chunked(self, seq: _Seq, start: int,
                         chunks: list[int] | None = None) -> Any:
        """Per-sequence chunked prefill: suffix > max_prefill_tokens, or
        an explicit tail-split ``chunks`` plan (true lengths; every chunk
        but the last is bucket-sized, hence block-aligned, so each chunk
        starts on a block boundary). Returns last-token logits [V] (not
        synced)."""
        prompt = seq.tokens
        plen = len(prompt)
        W = self.args.bucket_table(len(seq.block_ids))
        table = np.zeros((W,), np.int32)
        table[: len(seq.block_ids)] = seq.block_ids
        logits = None
        pos = start
        max_chunk = self.args.max_prefill_tokens
        ci = n_chunks = 0
        while pos < plen:
            if chunks is not None:
                n = chunks[ci]
                ci += 1
            else:
                n = min(max_chunk, plen - pos)
            chunk = prompt[pos : pos + n]
            t_pad = self.args.bucket_prefill(len(chunk))
            toks = np.zeros((t_pad,), np.int32)
            toks[: len(chunk)] = chunk
            side = self.side and self.side.prefill_rows([(seq, pos, pos + len(chunk))], 1, W)
            self._dispatching()
            logits = self._runner.prefill_chunk(
                toks, table, pos, min(pos + len(chunk), plen),
                seq.adapter_slot if seq.adapter_slot >= 0 else None,
                **self._side_kw(None if side is None else side[0]),
            )
            self._dispatched(logits.arrs)
            self.total_prefill_padded += t_pad
            self.prefill_dispatch_rows[1] += 1
            self.prefill_rows_carried += 1
            pos += len(chunk)
            n_chunks += 1
            # Streaming export: the blocks this chunk completed can ship
            # while the NEXT chunks compute — dispatch their gather with
            # an async D2H now, and harvest whatever earlier gathers
            # already landed (non-blocking), so the data plane overlaps
            # the remaining prefill instead of serializing after it.
            if (seq.export_stream is not None
                    and seq.export_stream.abort_reason is None):
                bs = self.args.block_size
                done = min(pos // bs, (plen - 1) // bs)
                if done > seq.export_pub_blocks:
                    self._start_export_extract(seq, seq.export_pub_blocks, done)
                self._drain_export_fetches()
        seq.chunks = n_chunks
        self._finish_prefill_bookkeeping(seq, start)
        assert logits is not None  # plen >= 1 → at least one chunk ran
        return logits

    def _finish_prefill_bookkeeping(self, seq: _Seq, start: int) -> None:
        plen = len(seq.tokens)
        self.total_prefilled += plen - start
        # Prompt positions are now resident in HBM; register their blocks.
        seq.kv_written = plen
        self._register_written_blocks(seq)
        # Disagg: copy the full prompt blocks to host for the decode
        # worker to fetch (reference: prefill returning kv_transfer_params,
        # handlers.py:149-158 — here device→host DMA replaces NIXL).
        if seq.export:
            self._export_kv(seq, plen)

    def _inject_kv(self, seq: _Seq, n_hit: int, max_hit: int) -> tuple[int, int]:
        """Scatter fetched pages into this sequence's blocks beyond the
        locally-hit prefix. The payload's first page corresponds to prompt
        block ``block_offset`` (0 for disagg exports; >0 for peer delta
        fetches, llm/peer_kv.py). → (new start position, new hit count)."""
        payload = seq.inject
        if isinstance(payload, dict) and payload.get("chunks") is not None:
            return self._inject_kv_chunks(seq, payload["chunks"], n_hit, max_hit)
        off = 0
        if isinstance(payload, dict):
            off = int(payload.get("block_offset") or 0)
            payload = kv_transfer.KvPagePayload.from_dict(payload)
        bs = self.args.block_size
        n_inj = min(off + payload.num_tokens // bs, max_hit, off + payload.k.shape[1])
        if n_inj <= n_hit or off > n_hit:
            # Already covered locally, or the payload starts past what the
            # cache holds (blocks evicted between fetch and admission) —
            # injecting would leave a KV gap, so recompute instead.
            return n_hit * bs, n_hit
        self._dispatching()
        self._runner.inject_pages(
            seq.block_ids[n_hit:n_inj],
            *(a[:, n_hit - off : n_inj - off] for a in payload.pages()),
        )
        seq.inject = None  # free host pages promptly
        return n_inj * bs, n_inj

    def _inject_kv_chunks(
        self, seq: _Seq, chunks: list, n_hit: int, max_hit: int
    ) -> tuple[int, int]:
        """Incremental inject of a streamed chunk list (dynamo_tpu/
        transfer): each contiguous page run scatters separately — no
        monolithic host concat — and format bridging (adapt_pages)
        happens per chunk, so a float-prefill → int8-decode stream
        quantizes run by run. Coverage must stay contiguous from the
        local hit boundary; a gap stops injection (the rest recomputes)."""
        bs = self.args.block_size
        n_cur = n_hit
        for ch in chunks:
            off = int(ch.get("block_offset") or 0)
            payload = kv_transfer.KvPagePayload.from_dict(ch)
            end = min(off + payload.k.shape[1], max_hit)
            if end <= n_cur:
                continue  # fully covered locally already
            if off > n_cur:
                break  # gap — injecting past it would leave a KV hole
            self._dispatching()
            self._runner.inject_pages(
                seq.block_ids[n_cur:end],
                *(a[:, n_cur - off : end - off] for a in payload.pages()),
            )
            n_cur = end
        seq.inject = None  # free host chunk buffers promptly
        return n_cur * bs, n_cur

    def _export_kv(self, seq: _Seq, plen: int) -> None:
        bs = self.args.block_size
        n_exp = (plen - 1) // bs  # full blocks only; suffix recomputed remotely
        if seq.export_stream is not None:
            # Streaming export: publish the remainder (everything for a
            # single-dispatch packed prefill; the final partial run for a
            # chunked one), drain this stream's in-flight page fetches
            # (blocking is fine — prefill is done, nothing left to
            # overlap) and seal.
            meta = {"remote_handle": seq.export_handle, "stream": True,
                    "num_tokens": n_exp * bs, "num_blocks": n_exp}
            if (n_exp > seq.export_pub_blocks
                    and seq.export_stream.abort_reason is None):
                self._start_export_extract(seq, seq.export_pub_blocks, n_exp)
            self._drain_export_fetches(force_seq=seq)
            seq.export_stream.seal(num_blocks=n_exp, num_tokens=n_exp * bs)
            seq.export_meta = meta
            return
        meta = {"remote_handle": seq.request_id, "num_tokens": n_exp * bs, "num_blocks": n_exp}
        if n_exp > 0:
            self._dispatching()
            pages = self._runner.extract_pages(seq.block_ids[:n_exp])
            self._dispatched(pages)
            # int8 KV: scale sidecars ride the same payload.
            payload = kv_transfer.KvPagePayload.from_pages(pages, n_exp * bs)
            with self._mutex:
                self._exports[seq.request_id] = (payload, time.monotonic() + self.export_ttl_s)
        seq.export_meta = meta

    def _start_export_extract(self, seq: _Seq, lo: int, hi: int) -> None:
        """Dispatch the gather for blocks [lo, hi) of a streaming export
        and start its async D2H copy; harvested by _drain_export_fetches."""
        self._dispatching()
        arrs, n = self._runner.start_extract_pages(seq.block_ids[lo:hi])
        start_host_fetch(arrs)
        self._export_fetches.append((seq, lo, hi, arrs, n))
        seq.export_pub_blocks = hi

    def _drain_export_fetches(self, force_seq: _Seq | None = None) -> None:
        """Harvest streaming-export page fetches whose D2H copy landed
        (never blocking), publishing each as one chunk. ``force_seq``
        additionally block-drains THAT sequence's fetches (seal time).
        Fetches whose stream died (abort/preempt) are dropped."""
        keep: list = []
        blocked: set[int] = set()
        bs = self.args.block_size
        for item in self._export_fetches:
            seq, lo, hi, arrs, n = item
            exp = seq.export_stream
            if exp is None or exp.abort_reason is not None:
                continue  # stream gone — release the device arrays
            # Publish strictly in dispatch order per sequence: host_ready
            # is per-array, and a later run landing before an earlier one
            # would punch a gap in the consumer's contiguous chunk stream
            # (its injector stops at the first gap and recomputes).
            if id(seq) in blocked or (
                seq is not force_seq and not host_ready(arrs)
            ):
                keep.append(item)
                blocked.add(id(seq))
                continue
            pages = self._runner.finish_extract_pages(arrs, n)
            exp.publish(KvChunk(
                block_offset=lo, pages=pages, num_tokens=(hi - lo) * bs,
            ))
        self._export_fetches = keep

    def prefix_hit_length(self, token_ids: list[int],
                          adapter_id: str | None = None) -> int:
        """Tokens of this prompt already resident in the local prefix
        cache (whole blocks), probed in the request's (model, adapter)
        identity domain. Used by the disagg decision: a locally-cached
        prompt should not prefill remotely. Thread-safe."""
        bs = self.args.block_size
        max_hit = (len(token_ids) - 1) // bs
        hashes = compute_block_hashes(
            token_ids, bs, adapter_hash_seed(adapter_id)
        )[:max_hit]
        return len(self.pool.match_prefix(hashes)) * bs

    def take_export(self, handle: str):
        """→ KvPagePayload | None. One-shot: the caller owns the pages.
        Streaming exports are not served here (get_stream_export)."""
        with self._mutex:
            item = self._exports.get(handle)
            if item is not None and isinstance(item[0], KvStreamExport):
                return None
            item = self._exports.pop(handle, None)
        return item[0] if item else None

    def get_stream_export(self, handle: str) -> KvStreamExport | None:
        """→ the live streaming export for ``handle`` (non-popping — the
        consumer pulls windows against it), or None. Each lookup refreshes
        the reap deadline: the TTL bounds time since the consumer LAST
        pulled, not the whole transfer — a healthy long prefill + many-GB
        pull must outlive any fixed total budget (mirrors the puller's
        stall-not-total timeout). Thread-safe."""
        with self._mutex:
            item = self._exports.get(handle)
            if item is not None and isinstance(item[0], KvStreamExport):
                exp = item[0]
                self._exports[handle] = (exp, time.monotonic() + self.export_ttl_s)
                return exp
        return None

    def release_stream_export(self, handle: str) -> None:
        """Drop a fully-delivered streaming export (the consumer saw
        kv_eos); frees any remaining host pages. Thread-safe."""
        with self._mutex:
            item = self._exports.pop(handle, None)
        if item is not None and isinstance(item[0], KvStreamExport):
            item[0].ack(item[0].chunk_count())

    def _reap_exports(self) -> None:
        now = time.monotonic()
        with self._mutex:
            dead = [h for h, (_, dl) in self._exports.items() if dl < now]
            reaped = [self._exports.pop(h) for h in dead]
        for item, _dl in reaped:
            if isinstance(item, KvStreamExport):
                # An unsealed reaped stream means the consumer never
                # finished pulling — tell any late puller it is gone,
                # and free whatever pages are still buffered.
                item.abort("expired")
                item.ack(item.chunk_count())

    # -- live migration (engine side) --------------------------------------
    #
    # Protocol (worker/migrate.py drives it; every entry point below runs
    # on the scheduler thread via run_on_engine_thread):
    #   begin    — register a KvStreamExport for a RUNNING decode; the
    #              sequence keeps decoding while each step's newly-sealed
    #              full blocks publish as chunks (the PR 8 credit-flow
    #              plane serves them to the destination, int8 scales
    #              riding along).
    #   cutover  — force-drain pending device tokens, FREEZE the sequence
    #              (out of _running; slot/KV retained), publish the delta
    #              blocks since the stream cursor, seal, and return the
    #              full resume identity (tokens, seed, sampler step,
    #              spec EMA, grammar state, adapter, next_write_pos).
    #   finish   — the destination committed: release resources and post
    #              a {"migration": marker} frame; the Migration operator
    #              consumes it and re-dispatches the SAME client stream
    #              pinned to the destination. Byte-identity: after the
    #              force-drain, kv_written == len(tokens)-1, so the sealed
    #              full blocks equal the destination's admission hit
    #              ceiling exactly — it recomputes only the <block_size
    #              suffix and continues sampling at (seed, step_base).
    #   abort    — any failure (or the freeze deadline passing with no
    #              coordinator): unfreeze, re-enter _running, keep
    #              decoding locally. The client never notices.

    def migration_begin(self, request_id: str) -> dict:
        """Start streaming a running decode's KV. → {"ok", "handle",
        "published"} or {"error"}. Scheduler thread only."""
        if self._uncarried is not None:
            return {"error": f"live migration cannot carry {self._uncarried[1]}"}
        seq = next(
            (s for s in self._running if s.request_id == request_id), None
        )
        if seq is None or seq.dead or seq.cancelled:
            return {"error": "not_running"}
        if seq.mig is not None:
            return {"error": "already_migrating"}
        if seq.export or seq.export_stream is not None:
            return {"error": "exporting"}  # disagg export seqs finish at token 1
        handle = f"mig-{request_id}-{self._step_no}"
        stream = KvStreamExport(handle)
        with self._mutex:
            self._exports[handle] = (stream, time.monotonic() + self.export_ttl_s)
        mig = _MigSt(seq, handle, stream)
        seq.mig = mig
        self._migrations[request_id] = mig
        self._pump_migration(mig)
        return {"ok": True, "handle": handle, "published": mig.pub_blocks,
                "traceparent": seq.traceparent}

    def migration_status(self, request_id: str) -> dict:
        """Cutover-lag probe: how far the stream cursor trails the KV
        actually written. Scheduler thread only."""
        mig = self._migrations.get(request_id)
        if mig is None:
            return {"error": "no_migration"}
        return {
            "ok": True,
            "published": mig.pub_blocks,
            "written": mig.seq.kv_written // self.args.block_size,
            "frozen": mig.frozen,
            "sealed": mig.stream.sealed,
            "aborted": mig.stream.abort_reason,
        }

    def migration_cutover(self, request_id: str) -> dict:
        """Freeze the sequence, ship the delta, seal the stream, and
        return the resume identity. Scheduler thread only."""
        mig = self._migrations.get(request_id)
        if mig is None:
            return {"error": "no_migration"}
        seq = mig.seq
        if mig.stream.abort_reason is not None:
            reason = mig.stream.abort_reason
            self._abort_migration(mig, reason)
            return {"error": f"stream_aborted:{reason}"}
        # Every device-pending token must be host-visible before the
        # identity snapshots: the handoff carries exactly the tokens the
        # client will have seen. The drain may FINISH the sequence (stop
        # condition in flight) or a preemption may have raced us — both
        # tear the migration down via the _finish/_preempt hooks.
        self._drain_completed(force=True)
        if self._migrations.get(request_id) is not mig:
            return {"error": "done" if seq.dead else "preempted"}
        if seq.dead or seq not in self._running:
            self._abort_migration(mig, "finished")
            return {"error": "done"}
        self._running.remove(seq)
        mig.frozen = True
        mig.freeze_deadline = time.monotonic() + self.migration_freeze_ttl_s
        self._pump_migration(mig, force=True)
        if mig.stream.abort_reason is not None:
            reason = mig.stream.abort_reason
            self._abort_migration(mig, reason)
            return {"error": f"stream_aborted:{reason}"}
        bs = self.args.block_size
        mig.stream.seal(
            num_blocks=mig.pub_blocks, num_tokens=mig.pub_blocks * bs
        )
        return {
            "ok": True,
            "handle": mig.handle,
            "kv_blocks": mig.pub_blocks,
            "emitted": seq.emitted,
            "adapter_id": seq.adapter_id,
            "request": {
                "token_ids": list(seq.tokens),
                "resume": {
                    "prompt_len": seq.prompt_len,
                    "sample_seed": seq.sample_seed,
                    "sample_step": seq.step_base + seq.emitted,
                    "spec_ema": seq.spec_ema,
                    "grammar_state": seq.grammar_state,
                    "next_write_pos": seq.next_write_pos,
                    "traceparent": seq.traceparent,
                },
            },
        }

    def migration_finish(self, request_id: str, marker: dict) -> dict:
        """Destination committed: hand the client stream off by posting
        the migration marker, then release this side's resources. The KV
        already lives in the sealed stream's host pages (and the
        destination's staged inject), so freeing device blocks is safe.
        Scheduler thread only."""
        mig = self._migrations.get(request_id)
        if mig is None or not mig.frozen:
            return {"error": "not_frozen"}
        seq = mig.seq
        self._migrations.pop(request_id, None)
        seq.mig = None
        seq.dead = True
        if seq.slot is not None:
            self._free_slots.append(seq.slot)
            seq.slot = None
        self._release_adapter(seq)
        if self._offload_pending:
            freed = set(seq.block_ids)
            self._offload_pending = [
                (b, h) for b, h in self._offload_pending if b not in freed
            ]
        self.pool.free_sequence(seq.block_ids)
        seq.block_ids = []
        self._post(seq, {"token_ids": [], "migration": marker})
        self._post_done(seq)
        return {"ok": True}

    def migration_abort(self, request_id: str, reason: str) -> dict:
        """Coordinator-initiated teardown: the sequence resumes decoding
        locally (if frozen) and the stream aborts. Scheduler thread only."""
        mig = self._migrations.get(request_id)
        if mig is None:
            return {"error": "no_migration"}
        self._abort_migration(mig, reason)
        return {"ok": True}

    def _abort_migration(self, mig: _MigSt, reason: str) -> None:
        seq = mig.seq
        self._migrations.pop(seq.request_id, None)
        seq.mig = None
        mig.fetches = []  # drop in-flight extracts (device arrays released)
        mig.stream.abort(reason)  # no-op when sealed
        mig.stream.ack(mig.stream.chunk_count())  # free buffered host pages
        with self._mutex:
            self._exports.pop(mig.handle, None)
        if mig.frozen and not seq.dead:
            # Unfreeze: re-enter the running batch exactly where it left
            # off (slot and KV were retained) — zero client impact.
            mig.frozen = False
            self._running.append(seq)

    def _service_migrations(self) -> None:
        """Once per step: pump streaming migrations, reap finished ones,
        and enforce the cutover freeze deadline (a dead coordinator must
        never wedge a frozen stream)."""
        now = time.monotonic()
        for rid in list(self._migrations):
            mig = self._migrations.get(rid)
            if mig is None:
                continue
            seq = mig.seq
            if seq.dead or seq.cancelled:
                self._abort_migration(mig, "finished")
                continue
            if mig.stream.abort_reason is not None:
                # Overrun (slow consumer) or TTL reap ("expired" — the
                # consumer/store died). Either way the source just keeps
                # the stream: unfreeze if needed and decode on.
                self._abort_migration(mig, mig.stream.abort_reason)
                continue
            if mig.frozen:
                if now >= mig.freeze_deadline:
                    log.warning(
                        "migration %s cutover deadline exceeded; resuming locally",
                        rid,
                    )
                    self._abort_migration(mig, "cutover_deadline")
                continue
            self._pump_migration(mig)

    def _pump_migration(self, mig: _MigSt, force: bool = False) -> None:
        """Publish the KV block delta written since the stream cursor.
        Extract dispatch is async (start_host_fetch) and harvested
        strictly in dispatch order; ``force`` block-drains everything
        (cutover's final delta)."""
        if mig.stream.abort_reason is not None:
            return
        seq = mig.seq
        bs = self.args.block_size
        lo, hi = kv_transfer.delta_blocks(
            seq.kv_written, bs, mig.pub_blocks, len(seq.block_ids)
        )
        if hi > lo:
            self._dispatching()
            arrs, n = self._runner.start_extract_pages(seq.block_ids[lo:hi])
            start_host_fetch(arrs)
            mig.fetches.append((lo, hi, arrs, n))
            mig.pub_blocks = hi
        keep: list = []
        for item in mig.fetches:
            flo, fhi, arrs, n = item
            if keep or (not force and not host_ready(arrs)):
                keep.append(item)
                continue
            pages = self._runner.finish_extract_pages(arrs, n)
            if not mig.stream.publish(KvChunk(
                block_offset=flo, pages=pages, num_tokens=(fhi - flo) * bs,
            )):
                break  # overrun — stream aborted; _service tears it down
        mig.fetches = keep

    def list_running(self) -> list[str]:
        """Request ids currently in the running batch — the relocation
        candidate set for pool moves/retirement. Thread-safe snapshot."""
        with self._wakeup:
            return [s.request_id for s in self._running if not s.dead]

    def _register_written_blocks(self, seq: _Seq) -> None:
        """Register sealed blocks whose KV is fully written. A block sealed
        by a just-sampled token must wait: that token's KV lands on the next
        decode step. Registering early would let another request prefix-hit
        a block with an unwritten tail slot."""
        if seq.block_seq is None:
            return
        bs = self.args.block_size
        while (
            seq.registered_blocks < len(seq.block_seq.blocks)
            and (seq.registered_blocks + 1) * bs <= seq.kv_written
        ):
            blk = seq.block_seq.blocks[seq.registered_blocks]
            bid = seq.block_ids[seq.registered_blocks]
            self.pool.register_block(bid, blk.sequence_hash, blk.parent_sequence_hash)
            if seq.side is not None:
                self.side.registered(seq, seq.registered_blocks, blk)
            # Write-through offload: queue the sealed block for the end-of-
            # step batched extract (bounded; duplicates in tiers skipped).
            if (
                self.tiers.enabled
                and len(self._offload_pending) < 256
                and not (self.tiers.host and self.tiers.host.contains(blk.sequence_hash))
            ):
                self._offload_pending.append((bid, blk.sequence_hash))
            seq.registered_blocks += 1

    # -- decode ------------------------------------------------------------

    def _ensure_block(self, seq: _Seq, lookahead: int = 1) -> bool:
        """Cover write positions [next_write_pos, next_write_pos+lookahead)
        with blocks; grow as needed."""
        last_pos = seq.next_write_pos + lookahead - 1
        while len(seq.block_ids) * self.args.block_size <= last_pos:
            try:
                seq.block_ids.append(self.pool.allocate_block())
            except NoFreeBlocksError:
                return False
        return self.side is None or self.side.cover_decode(seq, seq.next_write_pos + self._pend(seq), last_pos)

    def _maybe_pressure_offer(self) -> None:
        """Proactive defrag (ISSUE 19 tentpole (d)): when KV pool usage
        crosses ``kv_pressure_offer``, fire the migration-offer hook for
        the CHEAPEST victim — fewest resident blocks, so the relocation
        streams the least KV — before allocation failure forces a
        recompute-preemption. Purely advisory: the hook's relocation
        either frees the blocks (migration_finish) or nothing changes
        and the preemption boundary still owns correctness. Scheduler
        thread only; rate-limited to one offer per pressure window."""
        cb = self.migration_offer
        if cb is None or not self._running:
            return
        now = time.monotonic()
        if now < self._pressure_offer_next:
            return
        if self.pool.usage < self.kv_pressure_offer:
            return
        victim: _Seq | None = None
        for s in self._running:
            if s.dead or s.mig is not None or s.export:
                continue
            if victim is None or len(s.block_ids) < len(victim.block_ids):
                victim = s
        if victim is None:
            return
        self._pressure_offer_next = now + self.kv_pressure_offer_window_s
        self.pressure_offers += 1
        # Reuse the preemption-offer grace stamp: if pressure keeps
        # climbing and this victim IS chosen for preemption inside the
        # grace, the kill waits for the already-running relocation.
        victim.offer_deadline = now + self.preempt_offer_grace_s
        try:
            cb(victim.request_id)
        except Exception:  # noqa: BLE001 — the proactive offer is advisory; a broken hook must never stall the scheduler
            log.exception("kv-pressure migration offer hook failed")

    def _offer_migration_grace(self, victim: _Seq) -> bool:
        """QoS preemption offers migration before killing: when an offer
        hook is wired, fire it once for the chosen victim and grant a
        bounded grace window for the relocation to free its blocks.
        False (kill now) when unwired, the hook fails, or the victim's
        grace already expired. Scheduler thread only."""
        cb = self.migration_offer
        if cb is None:
            return False
        now = time.monotonic()
        if victim.offer_deadline == 0.0:
            victim.offer_deadline = now + self.preempt_offer_grace_s
            try:
                cb(victim.request_id)
            except Exception:  # noqa: BLE001 — a broken offer hook must never block the preemption fallback
                log.exception("migration offer hook failed")
                return False
            return True
        return now < victim.offer_deadline

    def _preempt_victim(self) -> _Seq:
        """Class-aware victim selection: evict the LOWEST class first,
        newest admission within it — the newest victim has the least
        sunk prefill work, and a preempted batch request's freed blocks
        admit the waiting interactive request on the next step. Uniform
        ranks (no-QoS) select exactly ``self._running[-1]``, the
        pre-QoS newest-first rule."""
        best = self._running[-1]
        for s in self._running:  # later index = newer admission event
            if s.qos_rank <= best.qos_rank:
                best = s
        return best

    def _preempt(self, seq: _Seq) -> None:
        """Recompute-preemption: free blocks, requeue with all tokens as the
        new prompt (reference behaviour matches vLLM recompute mode)."""
        self._drain_completed(force=True)  # pending tokens must be host-visible
        if seq.dead or seq not in self._running:
            return  # resolution finished it (stop condition on token 1)
        # An outbound migration of the victim tears down first: its KV is
        # about to be freed, so the stream can never complete. (Frozen
        # sequences are not in _running, so they are immune to victim
        # selection — the bounded cutover window is never preempted.)
        if seq.mig is not None:
            self._abort_migration(seq.mig, "preempted")
        log.warning(
            "preempting request %s (KV pressure, class=%s)",
            seq.request_id, seq.qos,
        )
        self.total_preemptions_by[seq.qos] += 1
        seq.offer_deadline = 0.0  # a later re-admission can be offered again
        self._running.remove(seq)
        if seq.slot is not None:
            self._free_slots.append(seq.slot)
            seq.slot = None
        # Unpin the adapter: re-admission re-acquires (a still-resident
        # adapter is a free hit; an evicted one pages back in). The
        # serial device stream orders any later slot upload after this
        # sequence's already-dispatched work.
        self._release_adapter(seq)
        # Purge queued offloads of the freed blocks: they become evictable
        # now and could be recycled before the next flush.
        freed = set(seq.block_ids)
        self._offload_pending = [(b, h) for b, h in self._offload_pending if b not in freed]
        # A preempted streaming export aborts (the decode worker falls
        # back to local prefill) and the re-admission runs non-streamed:
        # re-registering the same handle under a fresh object would race
        # a consumer already waiting on this one. export must drop too —
        # otherwise re-admission runs a legacy one-shot extract under a
        # handle no consumer ever learned, parking the payload on the
        # heap until the TTL reap.
        if seq.export_stream is not None:
            seq.export_stream.abort("preempted")
            seq.export_stream = None
            seq.export_handle = None
            seq.export_pub_blocks = 0
            seq.export = False
        self._release_side(seq)
        self.pool.free_sequence(seq.block_ids)
        seq.block_ids = []
        seq.registered_blocks = 0
        seq.kv_written = 0
        # prompt_len stays at the ORIGINAL prompt length: it delimits the
        # penalty token window (generated = tokens[prompt_len:]), which must
        # survive preemption; _prefill_seq re-runs over seq.tokens anyway.
        seq.block_seq = None
        seq.preempted = True
        self._waiting.appendleft(seq)

    # -- decode window pipeline -------------------------------------------
    #
    # A host sync is a device round trip (cost on the attached chip not
    # yet measured), so the engine keeps up to ``pipeline_depth`` decode
    # windows in flight: window w+1 is dispatched (chaining its input
    # tokens from w's on-device outputs via the per-slot fold buffer)
    # BEFORE w's results are fetched, and every fetch is started asynchronously at
    # dispatch, so the fetch roundtrips overlap later windows' device
    # execution. Consequences handled here:
    # - stops are discovered up to depth windows late; a stopped sequence
    #   rides the remaining in-flight windows as a zombie row whose
    #   output is discarded (waste bounded by depth × K tokens, same
    #   order as the fused window itself);
    # - zombie rows only write KV at positions beyond the drained
    #   boundary, and block registration is gated by complete kept-token
    #   blocks, so prefix reuse never sees junk;
    # - the device stream is serial and a sequence's blocks/slot are only
    #   freed after every window containing it has been DISPATCHED, so
    #   later prefills/samples reusing freed blocks or slots are ordered
    #   after all zombie writes and folds;
    # - the full sampler needs host-visible penalty windows, so sampler-
    #   heavy batches drain everything first and run unpipelined.

    def _pend(self, seq: _Seq) -> int:
        """Tokens already sampled on device for this sequence but not yet
        drained/emitted (its host-visible length lags by this many): K
        steps per in-flight window it rides plus an unfetched admission
        sample. _Spec items are invisible here BY INVARIANT: their
        pending count is data-dependent (1 + accepted), so
        _decode_iteration force-drains any queued _Spec before any
        planning that consults _pend."""
        p = 1 if seq.first_pend else 0
        for item in self._fetchq:
            if isinstance(item, _Window) and seq in item.row_of:
                p += item.K
        return p

    def _inflight_windows(self) -> int:
        return sum(1 for it in self._fetchq if isinstance(it, _Window))

    def _drain_completed(self, force: bool = False) -> None:
        """Harvest the completion queue from the front, strictly FIFO.
        Non-forced: pop only items whose async fetch already finished
        (free — the host never blocks). Forced: fetch-blocking drain of
        everything (needed when the pipeline is full, host-visible tokens
        are required, or all consumers died)."""
        while self._fetchq:
            if not force and not host_ready(self._fetchq[0].fetch_arrays()):
                break
            self._drain_one(self._fetchq.popleft())

    def _drain_one(self, item: "_First | _Window | _Spec") -> None:
        """Fetch + emit one queue item, attributing the fetch time by
        whether the host actually had to wait for it."""
        ready = host_ready(item.fetch_arrays())
        if isinstance(item, _First):
            self._drain_first(item, blocked=not ready)
        elif isinstance(item, _Spec):
            self._drain_spec(item, blocked=not ready)
        else:
            self._drain_window(item, blocked=not ready)

    def _drain_first(self, f: _First, blocked: bool = True) -> None:
        """Fetch + emit one admission wave's first-token samples."""
        back = self._cur
        t_fetch = self._enter("first_sample" if blocked else "drain_ready")
        toks = np.asarray(f.out_d)
        lps = np.asarray(f.lps_d)
        tvals_l = tids_l = None
        if f.top_ref is not None:
            tvals_l = np.asarray(f.top_ref.arrs[0]).tolist()
            tids_l = np.asarray(f.top_ref.arrs[1]).tolist()
        for hist in f.routed:
            self.moe_hist["prefill"] += np.asarray(hist)
        t0 = self._enter("emit")
        # How long the sample lay ready before it was read: from the probe
        # that first saw it so (this fetch's start, if none had) to here.
        unread = 0.0 if blocked else t0 - (f.t_ready or t_fetch)
        self.first_ready_unread_s += unread
        self.first_fetches["device" if blocked else "host"] += 1
        toks_l, lps_l = toks.tolist(), lps.tolist()
        for seq, row in f.entries:
            seq.first_pend = False
            seq.t_first, seq.first_waited, seq.first_unread_s = t0, blocked, unread
            if seq.dead:
                continue  # cancelled while the sample was in flight
            tops = None
            if tids_l is not None and seq.sampling.top_logprobs:
                n = seq.sampling.top_logprobs
                tops = [[list(p) for p in zip(tids_l[row][:n], tvals_l[row][:n])]]
            self._emit_tokens(seq, [toks_l[row]], [lps_l[row]], tops)
        self._enter(back)

    def _plan_window(self) -> tuple[int, int]:
        """→ (K, depth). K=1 is the end-of-life tail near max_model_len;
        pipelining (depth > 0) needs K>1 and no full-sampler rows.
        Grammar rows also force K=1: their FSM advances host-side per
        emitted token and the NEXT token's mask depends on it, so the
        fused multi-step window (which samples K tokens on device) could
        only mask its first substep. The speculative tree path is the
        constrained fast path — there every node's mask is known at
        dispatch because the draft tokens are."""
        K = max(1, self.args.decode_steps)
        if K > 1:
            for s in self._running:
                if len(s.tokens) + self._pend(s) + K > self.args.max_model_len:
                    K = 1
                    break
        if K > 1 and any(s.grammar is not None for s in self._running):
            K = 1
        depth = self.args.effective_pipeline_depth
        if K == 1 or any(self._needs_full_sampler(s) for s in self._running):
            depth = 0
        return K, depth

    def _decode_iteration(self) -> None:
        # A queued _Spec hides an unknown number of pending tokens per
        # row (1 + accepted), so no decode work may be PLANNED past it:
        # positions, block lookahead and chain pends would all be wrong.
        # Its fetch has been in flight since dispatch (overlapping the
        # admission/prefill work _step did meanwhile); settle it first.
        if any(isinstance(it, _Spec) for it in self._fetchq):
            self._drain_completed(force=True)
        if not self._running:
            self._drain_completed(force=True)
            return
        if self._try_speculative():
            return
        K, depth = self._plan_window()
        if depth == 0 and self._fetchq:
            # Unpipelined plan (full sampler / K=1 tail): host-visible
            # tokens (penalty windows, per-step inputs) are required, so
            # everything pending drains first — then re-plan on the
            # drained state.
            self._drain_completed(force=True)
            return self._decode_iteration()
        # Grow block tables K ahead; under KV pressure drain the in-flight
        # windows first (their tokens must land before a preempted
        # sequence re-queues), then preempt newest-first. A lone sequence
        # that cannot grow is finished (cache physically too small).
        while self._running:
            blocked = next(
                (s for s in self._running
                 if not self._ensure_block(s, lookahead=K + self._pend(s))),
                None,
            )
            if blocked is None:
                break
            if self._fetchq:
                self._drain_completed(force=True)
                return self._decode_iteration()
            if len(self._running) == 1:
                self._finish(blocked, FinishReason.LENGTH)
            else:
                victim = self._preempt_victim()
                if self._offer_migration_grace(victim):
                    # Bounded grace: skip planning this step — either
                    # the offered relocation frees the victim's blocks
                    # (migration_finish) or the deadline expires and the
                    # next pass preempts for real.
                    self._drain_completed(force=True)
                    time.sleep(0.002)
                    return
                self._preempt(victim)
        if not self._running:
            self._drain_completed(force=True)
            return

        if K > 1:
            w = self._dispatch_window(K)
            self._fetchq.append(w)
            # Opportunistic harvest first (free), then enforce the depth
            # bound: block-draining the OLDEST window while the newest
            # executes is where the fetch roundtrip hides.
            self._drain_completed()
            while self._inflight_windows() > depth and self._fetchq:
                self._drain_one(self._fetchq.popleft())
            if not self._running:
                # Every sequence finished during the drains — remaining
                # queued windows are all zombie rows and nothing would
                # ever wake the loop to fetch them (the idle predicate
                # ignores _fetchq), so release them now.
                self._drain_completed(force=True)
        else:
            self._decode_single_step()

    def _dispatch_window(self, K: int) -> "_Window":
        """Enqueue one fused K-step window over the current running set.
        Rows with device-pending tokens (in-flight window output or an
        unfetched admission sample) chain their input from the per-slot
        buffer (no host sync)."""
        batch = list(self._running)
        B = self.args.bucket_decode(len(batch))
        # Table width = smallest bucket covering the longest sequence in
        # the batch (block growth for pend+K already happened): attention
        # cost tracks actual lengths, not max_model_len.
        W = self.args.bucket_table(max(len(s.block_ids) for s in batch))
        tokens = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        tables = np.zeros((B, W), np.int32)
        active = np.zeros((B,), bool)
        fold_slots = np.full((B,), self.args.max_num_seqs, np.int32)
        pos0: list[int] = []
        chain: list[tuple[int, int]] = []  # (this row, chain SLOT)
        for i, seq in enumerate(batch):
            pend = self._pend(seq)
            p0 = seq.next_write_pos + pend
            pos0.append(p0)
            positions[i] = p0
            tables[i, : len(seq.block_ids)] = seq.block_ids
            active[i] = True
            fold_slots[i] = seq.slot
            if pend:
                # Input rides the per-slot chain buffer: fed by the
                # in-flight window's fold and/or the admission sample.
                chain.append((i, seq.slot))
            else:
                tokens[i] = seq.tokens[-1]

        temps = np.ones((B,), np.float32)
        seeds = np.zeros((B,), np.uint32)
        steps0 = np.zeros((B,), np.int32)
        tks = np.zeros((B,), np.int32)
        tps = np.ones((B,), np.float32)
        freqs = np.zeros((B,), np.float32)
        press = np.zeros((B,), np.float32)
        for i, s in enumerate(batch):
            temps[i] = s.sampling.temperature
            seeds[i] = s.sample_seed
            steps0[i] = s.step_base + s.emitted + self._pend(s)
            tks[i] = s.sampling.top_k or 0
            tps[i] = s.sampling.top_p if s.sampling.top_p is not None else 1.0
            freqs[i] = s.sampling.frequency_penalty
            press[i] = s.sampling.presence_penalty
        if any(self._needs_full_sampler(s) for s in batch):
            # Only reachable unpipelined (chain is empty then).
            mode = "full"
            pen = self._penalty_window(batch, B)
        else:
            mode = "greedy" if all(t < 1e-5 for t in temps[: len(batch)]) else "simple"
            pen = np.full((B, 1), -1, np.int32)  # placeholder, untraced-const shape

        wchain = None
        if chain:
            wchain = ([d for d, _ in chain], [s for _, s in chain])
        top_n = (
            self.args.top_logprobs_max
            if any(s.sampling.top_logprobs for s in batch) else 0
        )
        aslots = self._adapter_row_slots(batch, B)
        side = self.side and self.side.decode_rows(batch, pos0, B, K, W)
        self._enter("decode_dispatch")
        self._dispatching()
        ref = self._runner.multi_decode(
            K, mode, tokens, wchain, positions, tables, active,
            temps, seeds, steps0, tks, tps, freqs, press, pen, fold_slots,
            top_n, aslots, **self._side_kw(side),
        )
        self._dispatched(ref.arrs)
        w = _Window(batch, pos0, K, ref, top_n)
        start_host_fetch(w.fetch_arrays())
        self.total_decode_rows_dispatched += B * K
        self._enter("plan")
        return w

    def _drain_window(self, w: "_Window", blocked: bool = True) -> None:
        self.total_decode_steps += w.K
        back = self._cur
        self._enter("drain_sync" if blocked else "drain_ready")
        toks_np = np.asarray(w.ref.arrs[0])  # [K, B] — the one host fetch
        logps_np = np.asarray(w.ref.arrs[1])
        if w.ref.hist is not None:
            self.moe_hist["decode"] += np.asarray(w.ref.hist)
        tvals_l = tids_l = None
        if w.top_n:
            # transpose → [B, K, top_n]; bulk-converted once (per-element
            # int()/float() at K·B·n scale was measurable emit cost).
            tvals_l = np.asarray(w.ref.arrs[2]).transpose(1, 0, 2).tolist()
            tids_l = np.asarray(w.ref.arrs[3]).transpose(1, 0, 2).tolist()
        self._enter("emit")
        toks_l = toks_np.T.tolist()    # [B][K] python ints
        logps_l = logps_np.T.tolist()  # [B][K] python floats
        for i, seq in enumerate(w.rows):
            if seq.dead:
                continue  # finished/cancelled while this window was in flight
            # Dense accounting: K per-sequence weight passes, one token
            # each (the tokens-per-weight-pass denominator/numerator).
            self.total_row_passes += w.K
            self.total_row_tokens += w.K
            seq.kv_written = w.pos0[i] + w.K
            self._register_written_blocks(seq)
            tops = None
            if tids_l is not None and seq.sampling.top_logprobs:
                n = seq.sampling.top_logprobs
                tops = [
                    [list(p) for p in zip(tids_l[i][j][:n], tvals_l[i][j][:n])]
                    for j in range(w.K)
                ]
            self.total_decode_rows_emitted += self._emit_tokens(
                seq, toks_l[i], logps_l[i], tops)
        self._enter(back)

    # -- speculative decoding ---------------------------------------------
    #
    # Decode is weight-bandwidth-bound: a dense substep streams the full
    # weights for ONE token per sequence. A speculative pass streams them
    # once for up to spec_tokens+1 tokens per sequence: the host drafts
    # each row's likely continuation by n-gram prompt lookup (free), the
    # device scores draft_len+1 positions in one forward
    # (model.spec_verify — a decode-time prefill chunk over the same
    # paged-attention path), and on-device acceptance keeps the longest
    # prefix the target model agrees with plus one corrected/bonus token.
    # Greedy rows are byte-identical to the dense path (argmax match);
    # sampled rows use rejection sampling, leaving the output
    # distribution unchanged.
    #
    # Scheduling contract: drafting needs the full host-visible history
    # and the drain reveals how far each row advanced, so a speculative
    # pass is a pipeline BARRIER — everything pending drains before
    # dispatch, and the pass itself drains before the next decode plan
    # (admission + prefill dispatch still overlap it: the _Spec rides
    # _fetchq with its fetch in flight while _step admits new work).
    # Rows whose drafts keep being rejected (or that never match) decay
    # an acceptance EMA / enter a probe cooldown, so incompressible
    # workloads fall back to the dense window pipeline at full depth.

    def _row_draft(self, seq: _Seq, budget: int):
        """Propose a draft for one row — a token list (linear drafter)
        or a TreeDraft (tree drafter) — applying the adaptive controls.
        Empty ⇒ the row rides the pass with draft_len 0 (a plain
        next-token step) or, if no row drafts, the batch falls back to
        the dense path entirely. ``budget`` is this row's draft-node
        allowance: uniform spec_tokens, or 2S under adaptive batch
        budgets (drafting is optimistic there — the EMA shrink below
        still applies, scaled to the allowance, and trim_spec_budgets
        enforces the batch total afterwards)."""
        args = self.args
        # Never draft past the model length: the pass emits up to
        # potential+1 tokens and writes KV slots up to positions0 +
        # draft-node count (tree slots are slot-ordered, so the node
        # budget bounds the write extent for any shape).
        cap = min(budget, args.max_model_len - len(seq.tokens) - 1)
        if cap <= 0 or seq.spec_cool > 0:
            return []
        # EMA-proportional shrink: full drafts at ema >= 0.5, linearly
        # shorter below, floor 1 — a just-re-enabled low-EMA row
        # proposes a naturally short probe, and acceptance lifts the
        # EMA back up.
        eff = min(cap, max(1, round(budget * min(1.0, seq.spec_ema / 0.5))))
        if seq.draft_state is None:
            seq.draft_state = self._drafter.new_state()
        constraint = None
        if seq.grammar is not None:
            # Grammar-pruned drafting: candidates filtered to FSM-legal
            # continuations, forced states contributing their single
            # legal token (certainty) — constrained rows draft near-
            # perfect trees, which is where tree speculation pays
            # hardest on structured traffic.
            g, st = seq.grammar, seq.grammar_state
            constraint = DraftConstraint(st, g.advance, g.forced)
        if hasattr(self._drafter, "draft_tree"):
            return self._drafter.draft_tree(
                seq.tokens, seq.draft_state, eff, constraint=constraint
            )
        d = self._drafter.draft(seq.tokens, seq.draft_state, eff)
        if constraint is not None:
            d = constrain_chain(d, constraint, eff)
        return d

    @staticmethod
    def _draft_potential(d) -> int:
        """Best-case accepted run of one proposal: the whole draft for a
        chain, the deepest root path for a tree."""
        return d.max_depth if isinstance(d, TreeDraft) else len(d)

    def _spec_gate_passes(self, drafts: dict["_Seq", Any]) -> bool:
        """Batch-level dispatch decision: the EMA-weighted expected
        tokens per row-pass, mean(1 + ema_i * potential_i), must clear
        spec_gate — and at least one draft must exist at all."""
        if not drafts or not any(len(d) for d in drafts.values()):
            return False
        expected = sum(
            1.0 + s.spec_ema * self._draft_potential(d)
            for s, d in drafts.items()
        ) / len(drafts)
        return expected >= self.args.spec_gate

    def _try_speculative(self) -> bool:
        """Dispatch one speculative verify pass over the running set if
        it is eligible and at least one row has a draft. → True when a
        pass was dispatched (the caller's decode iteration is done).

        Two-phase drafting keeps the dense pipeline intact when there is
        nothing to verify: a cheap scan over the HOST-VISIBLE history
        (which may lag in-flight windows) decides whether draining the
        pipeline could pay off at all; only a scan hit forces the drain,
        after which rows re-draft on their complete histories for the
        actual dispatch. The drafter's incremental index makes the
        per-iteration scan O(newly visible tokens)."""
        S = self.spec_tokens
        if S <= 0:
            return False
        # Full-sampler rows need host-visible penalty windows stepwise;
        # same constraint that forces the dense path unpipelined.
        if any(self._needs_full_sampler(s) for s in self._running):
            return False
        # Tick rejection cooldowns once per scheduler STEP (this method
        # can run twice in a step when a drain forces a replan): a row
        # whose acceptance EMA collapsed proposes nothing until its
        # cooldown expires, then re-probes with an EMA-shortened draft.
        if self._spec_ticked != self._step_no:
            self._spec_ticked = self._step_no
            for s in self._running:
                if s.spec_cool > 0:
                    s.spec_cool -= 1
        self._enter("draft")
        drafts = self._draft_all(S)
        if not self._spec_gate_passes(drafts):
            self._enter("plan")
            return False
        # The gate passed on the visible history: drafting positions +
        # inputs need COMPLETE histories, so settle everything in flight,
        # then re-draft rows whose histories just advanced.
        if self._fetchq:
            self._drain_completed(force=True)
            if not self._running:
                self._enter("plan")
                return True
            drafts = self._draft_all(S)
        self._enter("plan")
        if not self._spec_gate_passes(drafts):
            return False
        batch = list(self._running)
        # Cover writes at positions0 + draft-node-count; rows that
        # cannot grow fall back to the dense path's pressure handling
        # (drain/preempt).
        for seq in batch:
            if not self._ensure_block(seq, lookahead=len(drafts[seq]) + 1):
                return False
        self._enter("spec_dispatch")
        B = self.args.bucket_decode(len(batch))
        # Verify-shape bucket: the uniform S+1 covers every draft at or
        # under the per-row allowance; an adaptive reallocation that let
        # a hot row draft past S (its only way past S) upgrades the pass
        # to the 2S+1 shape — two S1 buckets total, both AOT-warmable.
        max_nodes = max((len(d) for d in drafts.values()), default=0)
        widths = spec_verify_widths(S, self.spec_budget_adaptive)
        S1 = widths[0] if max_nodes <= S else widths[-1]
        if max_nodes > S:
            self.total_spec_budget_reallocs += 1
        W = self.args.bucket_table(max(len(s.block_ids) for s in batch))
        tokens = np.zeros((B, S1), np.int32)
        pos0_arr = np.zeros((B,), np.int32)
        dlen = np.zeros((B,), np.int32)
        tables = np.zeros((B, W), np.int32)
        active = np.zeros((B,), bool)
        fold_slots = np.full((B,), self.args.max_num_seqs, np.int32)
        temps = np.ones((B,), np.float32)
        seeds = np.zeros((B,), np.uint32)
        steps0 = np.zeros((B,), np.int32)
        pos0: list[int] = []
        draft_lens: list[int] = []
        potentials: list[int] = []
        node_tokens: list[list[int]] = []
        node_parents: list[list[int]] = []
        # A batch whose proposals are all CHAINS dispatches through the
        # PR 5 linear op (byte-for-byte that path, including stepwise
        # parity); any branched proposal upgrades the whole batch to the
        # topology-masked tree op (chains are trees too). Grammar rows
        # ALSO force the tree op: per-node masks ride only the tree
        # acceptance path (even a draft-less constrained row needs its
        # root mask for the bonus sample).
        any_gram = any(s.grammar is not None for s in batch)
        any_tree = any_gram or any(
            isinstance(d, TreeDraft) and not d.is_chain()
            for d in drafts.values()
        )
        for i, seq in enumerate(batch):
            d = drafts[seq]
            if isinstance(d, TreeDraft):
                toks, pars = d.tokens, d.parents
            else:
                toks, pars = list(d), list(range(len(d)))
            tokens[i, 0] = seq.tokens[-1]
            tokens[i, 1 : 1 + len(toks)] = toks
            p0 = seq.next_write_pos
            pos0.append(p0)
            pos0_arr[i] = p0
            dlen[i] = len(toks)
            draft_lens.append(len(toks))
            potentials.append(self._draft_potential(d))
            node_tokens.append([seq.tokens[-1]] + list(toks))
            node_parents.append([0] + list(pars))
            tables[i, : len(seq.block_ids)] = seq.block_ids
            active[i] = True
            fold_slots[i] = seq.slot
            temps[i] = seq.sampling.temperature
            seeds[i] = seq.sample_seed
            steps0[i] = seq.step_base + seq.emitted
        mode = "greedy" if all(t < 1e-5 for t in temps[: len(batch)]) else "simple"
        top_n = (
            self.args.top_logprobs_max
            if any(s.sampling.top_logprobs for s in batch) else 0
        )
        tree = None
        if any_tree:
            tree = self._build_tree_args(B, S1, node_parents)
        masks = None
        if any_gram:
            masks = self._build_tree_masks(batch, B, S1, node_tokens,
                                           node_parents)
        aslots = self._adapter_row_slots(batch, B)
        self._dispatching()
        ref = self._runner.spec_verify(
            S1, mode, tokens, pos0_arr, dlen, tables, active,
            temps, seeds, steps0, fold_slots, top_n, tree, masks, aslots,
        )
        self._dispatched(ref.arrs)
        item = _Spec(
            batch, pos0, draft_lens, ref, top_n,
            potentials=potentials, tree=any_tree,
            node_tokens=node_tokens, node_parents=node_parents,
        )
        start_host_fetch(item.fetch_arrays())
        self._fetchq.append(item)
        self._enter("plan")
        return True

    def _draft_all(self, S: int) -> dict:
        """Draft every running row under the batch node budget. Uniform
        mode: each row proposes up to S (EMA-shrunk — PR 10 behavior,
        byte-for-byte). Adaptive mode (spec_budget_adaptive): every row
        drafts optimistically up to 2S (its EMA shrink still applies,
        scaled to the doubled allowance), then trim_spec_budgets
        enforces the FIXED batch total rows x S by trimming EMA-cold
        rows back toward their uniform-path draft length — rows with
        nothing to say donate their allowance, and the hot rows (above
        all grammar-constrained rows, whose forced JSON runs exceed S)
        spend it."""
        if not self.spec_budget_adaptive:
            return {s: self._row_draft(s, S) for s in self._running}
        rows = list(self._running)
        cap = SPEC_BUDGET_MAX_MULT * S
        drafts = {s: self._row_draft(s, cap) for s in rows}
        keep = trim_spec_budgets(
            [(s.spec_ema, len(drafts[s])) for s in rows], S
        )
        for s, k in zip(rows, keep):
            d = drafts[s]
            if len(d) <= k:
                continue
            if isinstance(d, TreeDraft):
                d.truncate(k)
            else:
                drafts[s] = d[:k]
        return drafts

    def _build_tree_masks(
        self, batch: list[_Seq], B: int, S1: int,
        node_tokens: list[list[int]], node_parents: list[list[int]],
    ) -> np.ndarray:
        """Per-(row, node) packed grammar masks for one tree verify
        dispatch → [B, S1, W32] uint32. Node j masks by ITS OWN FSM
        state — the state reached by walking the draft tokens from the
        sequence's current state along the tree's parent chain — because
        node j's logits are the distribution its children are checked
        against and its correction/bonus token samples from.
        Unconstrained rows (and dead slots) ride all-ones masks: bitwise
        identity under where(). Pruned drafting guarantees every walk
        step succeeds; the defensive parent-state fallback only matters
        for an illegal draft node, which acceptance can never reach
        anyway (its own edge probability is masked to zero)."""
        t0 = time.perf_counter()
        masks = np.full(
            (B, S1, mask_words(self.cfg.vocab_size)), 0xFFFFFFFF, np.uint32
        )
        for i, seq in enumerate(batch):
            g = seq.grammar
            if g is None:
                continue
            states = [seq.grammar_state]
            masks[i, 0] = g.mask(states[0], seq.grammar_eos_bits)
            toks_i, pars_i = node_tokens[i], node_parents[i]
            for j in range(1, len(toks_i)):
                st = g.advance(states[pars_i[j]], toks_i[j])
                states.append(st if st is not None else states[pars_i[j]])
                masks[i, j] = g.mask(states[j], seq.grammar_eos_bits)
        self.total_grammar_mask_s += time.perf_counter() - t0
        return masks

    @staticmethod
    def _build_tree_args(
        B: int, S1: int, node_parents: list[list[int]]
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Host-side tree topology for one verify dispatch → (parents
        [B, S1], ancestor-or-self mask [B, S1, S1] int8, depth [B, S1]).
        Rows beyond the live batch stay all-zero (inactive)."""
        parents = np.zeros((B, S1), np.int32)
        anc = np.zeros((B, S1, S1), np.int8)
        depth = np.zeros((B, S1), np.int32)
        for i, pars in enumerate(node_parents):
            anc[i, 0, 0] = 1
            for j in range(1, len(pars)):
                p = pars[j]
                parents[i, j] = p
                anc[i, j] = anc[i, p]
                anc[i, j, j] = 1
                depth[i, j] = depth[i, p] + 1
        return parents, anc, depth

    def _drain_spec(self, sp: "_Spec", blocked: bool = True) -> None:
        self.total_spec_passes += 1
        if sp.tree:
            self.total_spec_tree_passes += 1
        back = self._cur
        self._enter("drain_sync" if blocked else "drain_ready")
        out_l = np.asarray(sp.ref.arrs[0]).tolist()     # [B][S1]
        n_emit_l = np.asarray(sp.ref.arrs[1]).tolist()  # [B]
        logps_l = np.asarray(sp.ref.arrs[2]).tolist()   # [B][S1]
        cand_l = np.asarray(sp.ref.arrs[3]).tolist()    # [B][S1]
        tvals_l = tids_l = None
        if sp.top_n:
            tvals_l = np.asarray(sp.ref.arrs[4]).tolist()  # [B][S1][n]
            tids_l = np.asarray(sp.ref.arrs[5]).tolist()
        self._enter("emit")
        alpha = self.args.spec_ema_alpha
        for i, seq in enumerate(sp.rows):
            if seq.dead:
                continue  # finished/cancelled while the pass was in flight
            n = int(n_emit_l[i])
            a = n - 1
            S_i = sp.draft_lens[i]
            self.total_spec_rows += 1
            self.total_spec_emitted += n
            self.total_row_passes += 1
            self.total_row_tokens += n
            if S_i > 0:
                self.total_spec_proposed += S_i
                self.total_spec_accepted += a
                # EMA over ACHIEVABLE acceptance: a tree that branches 4
                # wide can only accept down its deepest path, so the
                # potential (max depth; == S_i for a chain) is the
                # honest denominator for the shrink/disable controls.
                pot = max(1, sp.potentials[i])
                seq.spec_ema = (1 - alpha) * seq.spec_ema + alpha * (a / pot)
                if seq.spec_ema < self.args.spec_ema_disable:
                    seq.spec_cool = self.args.spec_probe_every
                if sp.tree:
                    self.total_spec_tree_rows += 1
                    self.total_spec_tree_depth += a
                    self._spec_depth_hist[a] += 1
            # Jacobi-pool refresh for EVERY live row — including rows
            # that proposed nothing (their root-node cand is exactly the
            # zero-history-hit seed the Lookahead pool exists for):
            # every node's (context → argmax prediction) pair is free
            # drafting signal, rejected branches included. seq.tokens
            # still ends at this pass's root (emission happens below).
            if seq.draft_state is not None and sp.node_tokens is not None:
                self._drafter.observe(
                    seq.draft_state, seq.tokens, sp.node_tokens[i],
                    sp.node_parents[i], S_i + 1, cand_l[i],
                )
            # Positions p0..p0+a hold CORRECT KV ([last, accepted
            # drafts]); the correction/bonus token's KV lands on the next
            # dispatch, exactly like a dense window's last sample. Junk
            # KV past the boundary is never registered and gets rewritten
            # by the next dispatch (next_write_pos rolls back with the
            # emitted count).
            seq.kv_written = sp.pos0[i] + n
            self._register_written_blocks(seq)
            tops = None
            if tids_l is not None and seq.sampling.top_logprobs:
                tn = seq.sampling.top_logprobs
                tops = [
                    [list(p) for p in zip(tids_l[i][j][:tn], tvals_l[i][j][:tn])]
                    for j in range(n)
                ]
            self._emit_tokens(seq, out_l[i][:n], logps_l[i][:n], tops)
        self._enter(back)

    def _decode_single_step(self) -> None:
        # Per-step path needs host-visible tokens (inputs come from
        # seq.tokens[-1]); drain everything pending first.
        self._drain_completed(force=True)
        if not self._running:
            return
        self._enter("single_step")
        batch = list(self._running)
        B = self.args.bucket_decode(len(batch))
        W = self.args.bucket_table(max(len(s.block_ids) for s in batch))
        tokens = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        tables = np.zeros((B, W), np.int32)
        active = np.zeros((B,), bool)
        for i, seq in enumerate(batch):
            tokens[i] = seq.tokens[-1]
            positions[i] = seq.next_write_pos
            tables[i, : len(seq.block_ids)] = seq.block_ids
            active[i] = True
        aslots = self._adapter_row_slots(batch, B)
        side = self.side and self.side.decode_rows(batch, [int(p) for p in positions[: len(batch)]], B, 1, W)
        self._dispatching()
        ref = self._runner.decode_step(tokens, positions, tables, active, aslots, **self._side_kw(side))
        self._dispatched(ref.arrs)
        self.total_decode_steps += 1
        self.total_decode_rows_dispatched += B
        self.total_row_passes += len(batch)
        self.total_row_tokens += len(batch)
        # The step just wrote each sequence's KV at `positions[i]`.
        for i, seq in enumerate(batch):
            seq.kv_written = int(positions[i]) + 1
            self._register_written_blocks(seq)
        srcs = [(ref, i) for i in range(len(batch))]
        srcs += [(ref, 0)] * (B - len(batch))
        sampled, logps, tref = self._sample_rows(
            srcs, batch,
            top_n=(self.args.top_logprobs_max
                   if any(s.sampling.top_logprobs for s in batch) else 0),
        )
        tvals = tids = None
        if tref is not None:
            tvals, tids = np.asarray(tref.arrs[0]), np.asarray(tref.arrs[1])
        for i, seq in enumerate(batch):
            tops = None
            if tvals is not None and seq.sampling.top_logprobs:
                n = seq.sampling.top_logprobs
                tops = [[[int(tids[i, r]), float(tvals[i, r])] for r in range(n)]]
            self.total_decode_rows_emitted += self._emit_tokens(
                seq, [int(sampled[i])], [float(logps[i])], tops)
        self._enter("plan")

    @staticmethod
    def _needs_full_sampler(seq: _Seq) -> bool:
        s = seq.sampling
        return row_needs_full(s.top_k, s.top_p, s.frequency_penalty, s.presence_penalty)

    @staticmethod
    def _penalty_window(seqs: list[_Seq], B: int) -> np.ndarray:
        """[B, L] generated-so-far ids (-1 pad), L bucketed pow2 so the
        shape set stays small."""
        # Generated = everything past the prompt boundary — a resumed
        # (migrated) sequence's carried tokens count even though its
        # this-leg emitted does not include them.
        max_gen = max((len(s.tokens) - s.prompt_len for s in seqs), default=0)
        L = 16
        while L < max_gen:
            L *= 2
        pen = np.full((B, L), -1, np.int32)
        for i, s in enumerate(seqs):
            gen = s.tokens[s.prompt_len : s.prompt_len + L]
            pen[i, : len(gen)] = gen
        return pen

    def _sample_rows(self, srcs, seqs: list[_Seq], top_n: int = 0):
        """Sample one token per row for the first len(seqs) rows, synced.
        ``srcs``: list of (StepRef, row|None) logits sources (padded to a
        bucket). → (tokens [B], chosen logprobs [B], top_ref|None)."""
        out, logps, top_ref = self._sample_rows_device(srcs, seqs, None, top_n)
        return np.asarray(out), np.asarray(logps), top_ref  # the one host sync

    def _sample_rows_device(self, srcs, seqs: list[_Seq], fold_slots, top_n: int = 0):
        """Device-side sampling; with ``fold_slots`` the tokens also land
        in the chain buffer for the next window (async admission).
        → (tokens [B], logprobs [B], top_ref|None) unfetched."""
        B = len(srcs)
        temps = np.ones((B,), np.float32)
        tks = np.zeros((B,), np.int32)
        tps = np.ones((B,), np.float32)
        freqs = np.zeros((B,), np.float32)
        press = np.zeros((B,), np.float32)
        seeds = np.zeros((B,), np.uint32)
        steps = np.zeros((B,), np.int32)
        for i, s in enumerate(seqs):
            temps[i] = s.sampling.temperature
            tks[i] = s.sampling.top_k or 0
            tps[i] = s.sampling.top_p if s.sampling.top_p is not None else 1.0
            freqs[i] = s.sampling.frequency_penalty
            press[i] = s.sampling.presence_penalty
            seeds[i] = s.sample_seed
            steps[i] = s.step_base + s.emitted
        full = needs_full(tks.tolist(), tps.tolist(), freqs.tolist(), press.tolist())
        pen = (
            self._penalty_window(seqs, B) if full
            else np.full((B, 1), -1, np.int32)
        )
        # Grammar rows sample from their FSM state's masked vocabulary
        # (admission = the start state; single-step = the state after
        # every emitted token, host-visible because grammar batches
        # always run force-drained K=1).
        masks = self._grammar_row_masks(seqs, B)
        self._dispatching()
        out = self._runner.sample_rows(
            srcs, temps, tks, tps, pen, freqs, press, seeds, steps, full,
            fold_slots, top_n, masks,
        )
        self._dispatched(out[:2])
        return out

    # -- token emission / finish ------------------------------------------

    def _emit_tokens(self, seq: _Seq, toks: list[int], logps: list[float] | None = None,
                     tops: list | None = None) -> int:
        """Append sampled tokens (a multi-step window or a single token),
        truncating at the first stop condition. Posts ONE output delta with
        the kept tokens — tokens past a mid-window stop are wasted device
        work, never surfaced. → the number of tokens kept."""
        kept: list[int] = []
        finish: FinishReason | None = None
        for token in toks:
            token = int(token)  # numpy scalar → msgpack-able python int
            seq.tokens.append(token)
            seq.emitted += 1
            self.total_generated += 1
            kept.append(token)
            # Advance the grammar FSM per emitted token (EOS stops the
            # walk, it is not part of the match). Masked sampling makes
            # every emitted token legal by construction; the defensive
            # None check keeps a state-desync from cascading (the row
            # would just stop constraining instead of crashing the
            # scheduler thread).
            if seq.grammar is not None and token not in seq.eos_ids:
                ns = seq.grammar.advance(seq.grammar_state, token)
                if ns is not None:
                    seq.grammar_state = ns
            # Block-hash bookkeeping only; registration waits until the
            # sealed block's KV is fully written (_register_written_blocks).
            if seq.block_seq is not None:
                seq.block_seq.append(token)
            if (
                token in seq.eos_ids
                and not seq.stop.ignore_eos
                and seq.emitted >= seq.stop.min_tokens  # eos counts toward min (vLLM)
            ):
                finish = FinishReason.STOP
            elif seq.stop.max_tokens is not None and seq.emitted >= seq.stop.max_tokens:
                finish = FinishReason.LENGTH
            elif len(seq.tokens) >= self.args.max_model_len:
                finish = FinishReason.LENGTH
            if finish is not None:
                break
        self._post(
            seq,
            LLMEngineOutput(
                token_ids=kept,
                finish_reason=finish,
                log_probs=logps[: len(kept)] if logps and seq.sampling.logprobs else None,
                top_log_probs=(
                    tops[: len(kept)]
                    if tops and seq.sampling.logprobs and seq.sampling.top_logprobs
                    else None
                ),
                kv_transfer_params=seq.export_meta if finish is not None else None,
            ).to_dict(),
        )
        if finish is not None:
            self._finish(seq, finish, already_posted=True)
        return len(kept)

    def _finish(
        self,
        seq: _Seq,
        reason: FinishReason,
        error: str | None = None,
        already_posted: bool = False,
    ) -> None:
        if seq.mig is not None:
            # Finished (stop/cancel/error) while migrating out: the
            # destination's pull sees the abort and the coordinator's
            # cutover gets a typed "done" — the stream completed in place.
            self._abort_migration(seq.mig, "finished")
        seq.dead = True
        if seq.export_stream is not None and not seq.export_stream.sealed:
            # Error/cancel before the prefill sealed the stream: the
            # puller must not wait out its deadline on a dead export.
            seq.export_stream.abort("prefill_failed")
        if seq in self._running:
            self._running.remove(seq)
        if seq.slot is not None:
            self._free_slots.append(seq.slot)
            seq.slot = None
        self._release_adapter(seq)
        # Purge queued offloads of blocks about to become evictable (same
        # as _preempt): once freed they can be recycled by any allocation
        # before the next flush, and a late extract would snapshot the NEW
        # occupant's KV under the OLD sequence hash — poisoning the tier.
        if self._offload_pending:
            freed = set(seq.block_ids)
            self._offload_pending = [
                (b, h) for b, h in self._offload_pending if b not in freed
            ]
        self._release_side(seq)
        self.pool.free_sequence(seq.block_ids)
        seq.block_ids = []
        if not already_posted:
            self._post(seq, LLMEngineOutput(finish_reason=reason, error=error).to_dict())
        self._post_done(seq)

    def _post(self, seq: _Seq, item: Any) -> None:
        if self._loop is not None:
            self._loop.call_soon_threadsafe(seq.queue.put_nowait, item)

    def _post_done(self, seq: _Seq) -> None:
        self._post(seq, _SENTINEL_DONE)
