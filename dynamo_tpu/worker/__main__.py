"""Worker CLI: `python -m dynamo_tpu.worker`.

Boots the engine, serves the ``generate`` endpoint plus the KV-event and
load-metrics endpoints, and registers the model card — the frontend
discovers the model via the store watch
(reference worker startup flow: components/backends/vllm/src/dynamo/vllm/
main.py:65-223).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import os
import signal

from dynamo_tpu.kv_router.publisher import KvEventBroadcaster, serve_kv_endpoints
from dynamo_tpu.llm.model_card import ModelDeploymentCard, register_model
from dynamo_tpu.llm.tokenizer import ByteTokenizer, load_tokenizer
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.logging import get_logger

log = get_logger("worker")


def parse_args(argv=None):
    p = argparse.ArgumentParser(prog="dynamo_tpu.worker")
    p.add_argument("--store-url", default=None)
    p.add_argument("--namespace", default="dynamo")
    p.add_argument("--component", default="backend")
    p.add_argument("--endpoint", default="generate")
    p.add_argument("--model-name", default=None, help="served model name (defaults to preset name)")
    p.add_argument("--engine", choices=["tpu", "mocker"], default="tpu")
    p.add_argument("--preset", default="llama-1b", help="model preset (engine=tpu, random weights)")
    p.add_argument(
        "--model-path", default=None,
        help="local HF checkpoint dir (config.json + *.safetensors + tokenizer.json); "
             "overrides --preset with real weights",
    )
    p.add_argument("--tokenizer", default="byte", help='"byte" or "hf:<path>" (defaults to hf:<model-path> when --model-path is set)')
    p.add_argument("--context-length", type=int, default=None)
    p.add_argument("--migration-limit", type=int, default=0)
    # disaggregated prefill/decode (reference: --is-prefill-worker,
    # components/backends/vllm/src/dynamo/vllm/main.py:65-88)
    p.add_argument("--is-prefill-worker", action="store_true",
                   help="serve prefill-only + kv_fetch; no model card (run with --component prefill)")
    p.add_argument("--disagg", choices=["auto", "on", "off"], default="auto",
                   help="disaggregated prefill/decode as the serving shape: "
                        "auto (default) wires the decode-side disagg handler on "
                        "every TPU worker — with no prefill fleet discovered it "
                        "costs one set lookup per long prompt and serves "
                        "aggregated; off restores the bare engine")
    p.add_argument("--remote-prefill", action="store_true",
                   help="alias for --disagg on (kept for compatibility)")
    p.add_argument("--no-disagg-stream", action="store_true",
                   help="legacy one-shot KV pull after prefill instead of the "
                        "streaming data plane (dynamo_tpu/transfer)")
    p.add_argument("--prefill-component", default="prefill")
    p.add_argument("--max-local-prefill-length", type=int, default=512,
                   help="prompts with more uncached tokens than this prefill remotely")
    p.add_argument("--prefill-dispatch", choices=["queue", "push"], default="queue",
                   help="queue = competing-consumer work queue (reference behaviour); "
                        "push = round-robin RPC to a prefill worker")
    # Closed-loop autoscaler (docs/autoscaler.md): "on" hands endpoint/
    # card wiring to the WorkerRoleManager so the operator can MOVE this
    # engine between the prefill and decode pools at runtime (admin RPC,
    # drain-ordered) and retire it with zero downtime. "off" (default)
    # is the exact pre-autoscaler wiring — serving is byte-identical.
    p.add_argument("--autoscaler", choices=["on", "off"], default="off",
                   help="register with the closed-loop SLA autoscaler: "
                        "live pool moves + zero-downtime retirement via "
                        "the workerctl admin endpoint")
    p.add_argument("--autoscaler-role", choices=["decode", "prefill"], default=None,
                   help="initial pool under --autoscaler on (default: decode, "
                        "or prefill when --is-prefill-worker is set)")
    p.add_argument("--sla-profile", default=None,
                   help="profiled SLA npz (tools/profile_sweep.py) shipped "
                        "inside this worker's model card so frontends and "
                        "the planner discover the latency curves instead of "
                        "needing a --qos-profile path")
    # engine shape knobs
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--num-kv-blocks", type=int, default=2048)
    p.add_argument("--max-num-seqs", type=int, default=16)
    p.add_argument("--max-model-len", type=int, default=2048)
    p.add_argument("--decode-steps", type=int, default=8)
    # Decode-window pipelining: max windows dispatched-but-unfetched (0 =
    # unpipelined; fetches still start async). Stops are discovered up to
    # this many windows late (≤ depth × decode-steps wasted tokens).
    p.add_argument("--pipeline-depth", type=int, default=2)
    # Prefill T-bucket ladder: "fine" (1.5x midpoints ≤512), "coarse"
    # (legacy 2x/4x, fewest compiles) or an explicit comma list.
    p.add_argument("--prefill-buckets", default="fine")
    p.add_argument("--no-prefill-tail-split", action="store_true",
                   help="disable splitting padded prefill tails into smaller buckets")
    # Streaming delta coalescing (both engines): cap on tokens merged into
    # one wire frame when a stream's consumer lags (0 = one frame per
    # decode window), and an optional bounded gather wait in ms (adds up
    # to that much ITL; keep <= one decode step).
    p.add_argument("--delta-max-tokens", type=int, default=64)
    p.add_argument("--delta-max-ms", type=float, default=0.0)
    # Speculative decoding: n-gram prompt-lookup drafts verified in one
    # batched forward per pass (engine/drafter.py + model.spec_verify).
    # 0 = off. Greedy output is byte-identical to the dense path; sampled
    # requests keep their exact distribution via rejection sampling. A
    # per-sequence acceptance EMA auto-disables speculation on
    # incompressible streams.
    p.add_argument("--spec-tokens", type=int, default=0,
                   help="max draft tokens verified per speculative pass (0 = off)")
    p.add_argument("--spec-ngram", type=int, default=3,
                   help="n-gram match length for the prompt-lookup drafter")
    p.add_argument("--spec-stepwise", action="store_true",
                   help="verify drafts with a stepwise scan (bitwise parity "
                        "with the dense path; forfeits the single-weight-"
                        "stream win) instead of the fused single-pass forward")
    p.add_argument("--spec-tree-width", type=int, default=1,
                   help="max draft-tree branching factor (1 = linear drafts; "
                        ">= 2 enables SpecInfer-style tree verification with "
                        "the topology-masked kernel + Lookahead Jacobi pool)")
    p.add_argument("--spec-tree-depth", type=int, default=0,
                   help="max draft-tree path depth (0 = spec-tokens)")
    p.add_argument("--spec-budget", choices=["adaptive", "uniform"],
                   default="adaptive",
                   help="per-pass draft-node allocation: adaptive moves nodes "
                        "from acceptance-EMA-cold rows to hot ones under the "
                        "fixed batch budget (rows x spec-tokens); uniform = "
                        "every row gets spec-tokens (the pre-r11 behavior)")
    # Multi-LoRA multiplexing (engine/lora.py): serve MANY fine-tunes of
    # the base model on this one engine. --lora-slots sizes the HBM
    # adapter bank (0 = off); --lora registers adapters (repeatable,
    # NAME[:RANK[:SEED]]), each published as its own served model whose
    # requests decode under the adapter — base and adapter rows share
    # every batch via the gathered LoRA matmul. More adapters than slots
    # page through the G2/G3 tier economy on demand.
    p.add_argument("--qos-sched", choices=["on", "off"], default="on",
                   help="class-aware engine scheduling: admission and "
                        "KV-pressure preemption ordered by (priority "
                        "class, age). No-priority traffic is byte-"
                        "identical either way; off pins one class "
                        "(docs/qos.md)")
    p.add_argument("--lora-slots", type=int, default=0,
                   help="device-resident LoRA adapter slots (0 = LoRA off)")
    p.add_argument("--lora-rank", type=int, default=8,
                   help="static adapter bank rank (max over registered adapters)")
    p.add_argument("--lora", action="append", default=[], metavar="NAME[:RANK[:SEED]]",
                   help="register one adapter served as model NAME (repeatable)")
    p.add_argument("--attn-impl", choices=["auto", "xla", "pallas", "pallas_interpret"],
                   default="auto", help="attention backend (ops/paged_attention.py)")
    p.add_argument("--quant", choices=["none", "int8"], default="none",
                   help="weight format (int8 = weight-only quantization, engine/quant.py)")
    p.add_argument("--kv-quant", choices=["none", "int8"], default="none",
                   help="paged KV cache storage (int8 = quantized pages + "
                        "per-position-per-head scales; ~2x num_kv_blocks in "
                        "the same HBM, half the tier/transfer bytes)")
    p.add_argument("--host-kv-blocks", type=int, default=0,
                   help="G2 host-RAM KV tier capacity in blocks (0 = off)")
    p.add_argument("--disk-kv-dir", default=None, help="G3 disk KV tier directory")
    p.add_argument("--disk-kv-blocks", type=int, default=4096)
    p.add_argument("--fleet-kv-dir", default=None,
                   help="G4 fleet-SHARED KV pool directory (mounted by "
                        "every engine; salted-hash-keyed files dedup "
                        "across the fleet, block_manager/tiers.py)")
    p.add_argument("--fleet-kv-blocks", type=int, default=16384)
    p.add_argument("--kv-pressure-offer", type=float, default=0.0,
                   help="pool-usage fraction above which the engine "
                        "proactively OFFERS its cheapest running sequence "
                        "for migration before preemption is forced "
                        "(0 = off; the offer reuses the same "
                        "migration_offer hook as the preemption-boundary "
                        "grace window, docs/autoscaler.md#fleet-balancer)")
    p.add_argument("--kv-directory", choices=["on", "off"], default="off",
                   help="publish this engine's KV block residency to the "
                        "global prefix directory (fleet/directory.py) so "
                        "frontends can price transfer-vs-recompute and "
                        "the autoscaler sees cache heat")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--seed", type=int, default=0)
    # DP-attention: one worker PROCESS per dp rank, all serving the same
    # model behind the router — rank separation is process separation, so
    # no collective spans ranks and a dead rank loses only its own KV
    # (reference: one dynamo worker per vLLM dp_rank,
    # components/backends/vllm/launch/dsr1_dep.sh:86-105; per-rank port
    # math args.py:170-203). `--dp-size N` alone spawns and supervises N
    # rank processes; `--dp-rank i` marks one rank (set by the spawner).
    p.add_argument("--dp-size", type=int, default=1)
    p.add_argument("--dp-rank", type=int, default=None)
    p.add_argument("--dp-base-port", type=int, default=29600,
                   help="first port of the per-rank port blocks (dp_rank_ports)")
    p.add_argument("--dp-restart", action="store_true",
                   help="restart a crashed dp rank with jittered exponential "
                        "backoff (fleet supervision hygiene, "
                        "dynamo_tpu/fleet/supervisor.py) instead of letting "
                        "the slot stay down until the spawner exits")
    # multi-host: ONE logical worker spanning several processes/hosts.
    # Launch one process per host; process 0 serves the endpoint, the
    # rest replay its dispatch stream (engine/runner.py). All processes
    # need identical model/shape flags; --tp counts GLOBAL devices.
    # (reference analogue: per-node engine ranks under NCCL/MPI --
    # components/backends/sglang/slurm_jobs/submit_job_script.py)
    p.add_argument("--dist-num-processes", type=int, default=1)
    p.add_argument("--dist-process-id", type=int, default=0)
    p.add_argument("--dist-coordinator", default="127.0.0.1:29500",
                   help="jax.distributed coordinator host:port (process 0's host)")
    p.add_argument("--dist-step-addr", default=None,
                   help="leader step-stream addr (default: coordinator host, port+1)")
    # mocker timing
    p.add_argument("--mocker-ttft-ms", type=float, default=20.0)
    p.add_argument("--mocker-itl-ms", type=float, default=5.0)
    p.add_argument("--mocker-speedup", type=float, default=1.0)
    p.add_argument("--mocker-delta-tokens", type=int, default=1,
                   help="tokens per simulated decode window (mirror engine decode_steps)")
    args = p.parse_args(argv)
    if args.remote_prefill:
        args.disagg = "on"
    if args.lora and args.lora_slots <= 0:
        p.error("--lora requires --lora-slots > 0")
    if args.lora and args.engine == "mocker":
        p.error("--lora requires --engine tpu (the mocker has no adapter bank)")
    try:
        # Parsed ONCE here (argparse-grade error UX); consumers read
        # args.lora_specs instead of re-parsing.
        args.lora_specs = parse_lora_specs(args.lora, args.lora_rank)
    except ValueError as e:
        p.error(str(e))
    if args.engine == "mocker" and (args.disagg == "on" or args.is_prefill_worker):
        # The disagg handlers drive the real engine's KV extract/inject
        # surface (prefix_hit_length, kv pages); the mocker has neither.
        # (--disagg auto silently stays aggregated on a mocker.)
        p.error("--engine mocker cannot combine with --disagg on/--is-prefill-worker")
    if (args.dp_rank is not None or args.dp_size > 1) and args.dist_num_processes > 1:
        # A dp rank is a self-contained JAX world; spanning hosts within a
        # rank would need per-rank coordinator port blocks — run multi-host
        # workers as independent fleet replicas instead. Checked for the
        # spawner too so the parent fails fast instead of every child.
        p.error("--dp-size/--dp-rank cannot combine with --dist-num-processes > 1")
    if args.dp_rank is not None and not 0 <= args.dp_rank < args.dp_size:
        p.error("--dp-rank must be in [0, --dp-size)")
    if args.dp_size > 1 and args.dp_rank is None and args.tp not in _CHIP_BOUNDS:
        # The spawner pins --tp chips to each rank; a rank launched from
        # outside (--dp-rank) is pinned by whoever launched it.
        p.error(
            f"--dp-size {args.dp_size} --tp {args.tp}: the spawner pins --tp "
            f"chips to each rank, and only --tp in {sorted(_CHIP_BOUNDS)} has "
            f"run that way on a chip"
        )
    return args


def parse_lora_specs(entries: list[str], default_rank: int) -> list[tuple[str, int, int]]:
    """--lora NAME[:RANK[:SEED]] entries → [(name, rank, seed)]."""
    out = []
    for e in entries:
        parts = e.split(":")
        name = parts[0]
        if not name:
            raise ValueError(f"--lora entry {e!r}: empty adapter name")
        try:
            rank = int(parts[1]) if len(parts) > 1 and parts[1] else default_rank
            seed = int(parts[2]) if len(parts) > 2 and parts[2] else 0
        except ValueError:
            raise ValueError(
                f"--lora entry {e!r}: RANK and SEED must be integers"
            ) from None
        out.append((name, rank, seed))
    return out


def adapter_cards(card, lora_specs) -> list:
    """One ModelDeploymentCard per --lora adapter, derived from the base
    card — shared by the plain serving path and the role manager so
    both publish identical adapter metadata."""
    import dataclasses as _dc

    return [
        _dc.replace(
            card, name=lname,
            lora={"adapter_id": lname, "base": card.name,
                  "rank": lrank, "resident_tier": "G2"},
        )
        for lname, lrank, _lseed in lora_specs
    ]


def dp_rank_ports(base_port: int, dp_rank: int, stride: int = 4) -> dict:
    """Deterministic per-rank port block (reference analogue: vLLM
    dp_rank port math, components/backends/vllm/src/dynamo/vllm/
    args.py:170-203): rank r owns [base + r*stride, base + (r+1)*stride).
    Only the ``system`` slot (status HTTP when DYNTPU_SYSTEM_ENABLED) is
    consumed today — per-rank multi-host is rejected in parse_args, so no
    coordinator/step ports are needed; the rest of the block is reserved
    for rank-local services so external launchers can rely on the
    stride."""
    b = base_port + dp_rank * stride
    return {"system": b, "reserved": (b + 1, b + stride)}


# TPU_CHIPS_PER_PROCESS_BOUNDS (x,y,z) for a rank of k chips: the sizes
# that have come up this way on a chip (v5e 2x2 host, libtpu 0.0.34).
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1"}


def dp_rank_chip_env(rank: int, chips: int) -> dict[str, str]:
    """Environment that makes chips [rank*chips, (rank+1)*chips) of this
    host a TPU world of the rank's own. A chip belongs to one process:
    unpinned, every rank opens every chip, and ``TPU_VISIBLE_CHIPS`` alone
    still fails on libtpu's multi-process lock (v5e 2x2, libtpu 0.0.34);
    the two bounds tell the runtime its world is just these chips."""
    return {
        "TPU_VISIBLE_CHIPS": ",".join(
            str(c) for c in range(rank * chips, (rank + 1) * chips)
        ),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _CHIP_BOUNDS[chips],
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


from dynamo_tpu.llm.tokenizer import parse_tokenizer_spec as tokenizer_spec


async def build_engine(args, config=None):
    """→ (engine, model_card). Engine exposes .generate/.metrics/.pool."""
    if args.model_path:
        # Hub names (`org/repo`) and .gguf files resolve to local paths
        # up front (engine/hub.py; reference: hub.rs:126) so every later
        # consumer (tokenizer, loader, card) sees a concrete path.
        from dynamo_tpu.engine.hub import is_gguf, resolve_model

        args.model_path = resolve_model(args.model_path)
        if args.tokenizer == "byte":
            prefix = "gguf:" if is_gguf(args.model_path) else "hf:"
            args.tokenizer = prefix + args.model_path
    tok_spec = tokenizer_spec(args.tokenizer)
    tokenizer = load_tokenizer(tok_spec)
    eos_ids = list(tokenizer.eos_token_ids)
    if args.engine == "mocker":
        from dynamo_tpu.mocker.engine import MockerArgs, MockerEngine
        from dynamo_tpu.runtime.chaos import ChaosInjector
        from dynamo_tpu.runtime.config import Config

        cfg = config or Config.from_env()
        engine = MockerEngine(
            MockerArgs(
                block_size=args.block_size,
                num_kv_blocks=args.num_kv_blocks,
                max_num_seqs=args.max_num_seqs,
                ttft_ms=args.mocker_ttft_ms,
                itl_ms=args.mocker_itl_ms,
                speedup=args.mocker_speedup,
                delta_tokens=args.mocker_delta_tokens,
                delta_max_tokens=args.delta_max_tokens,
                delta_max_ms=args.delta_max_ms,
                # Env-driven fault injection (DYNTPU_CHAOS_*): engine-level
                # kill draws; the messaging layer reads the same section.
                chaos=ChaosInjector.from_config(cfg.chaos),
            )
        )
        name = args.model_name or "mock-model"
        context_length = args.context_length or args.max_model_len
    else:
        from dynamo_tpu.engine.config import EngineArgs, ModelConfig
        from dynamo_tpu.engine.engine import TpuEngine

        params = None
        sharding = None
        if args.model_path:
            from dynamo_tpu.engine.loader import load_config, load_model

            if args.tp > 1:
                from dynamo_tpu.parallel.mesh import ModelSharding, build_mesh

                hf_cfg = load_config(args.model_path)
                sharding = ModelSharding(build_mesh(tp=args.tp, cfg=hf_cfg), hf_cfg)
            model, params = await asyncio.to_thread(
                load_model, args.model_path, args.dtype, sharding, args.quant
            )
        else:
            model = ModelConfig.preset(args.preset)
        eargs = _engine_args(args, model)
        runner = None
        if args.dist_num_processes > 1:
            from dynamo_tpu.engine.runner import LeaderRunner

            host, port = _step_addr(args).rsplit(":", 1)
            runner = LeaderRunner(
                eargs, params=params, seed=args.seed, sharding=sharding,
                listen_addr=f"0.0.0.0:{port}",
                num_followers=args.dist_num_processes - 1,
            )
        engine = await TpuEngine(
            eargs, params=params, seed=args.seed, sharding=sharding, runner=runner
        ).start()
        name = args.model_name or model.name
        context_length = args.context_length or min(args.max_model_len, model.max_position)
    card = ModelDeploymentCard(
        name=name,
        tokenizer=tok_spec,
        context_length=context_length,
        kv_cache_block_size=args.block_size,
        migration_limit=args.migration_limit,
        eos_token_ids=eos_ids or [ByteTokenizer.EOS],
        component=args.component,
        endpoint=args.endpoint,
        max_batch_size=args.max_num_seqs,
        total_kv_blocks=args.num_kv_blocks,
    )
    if getattr(args, "sla_profile", None):
        # Ship the profiled latency curves inside the model card so
        # frontends (admission-time TTFT prediction) and the planner
        # pick them up via discovery instead of a --qos-profile CLI
        # path copied to every box (ROADMAP 2c).
        from dynamo_tpu.planner.interpolate import load_profile, profile_as_card_dict

        prof_decode, prof_prefill = load_profile(args.sla_profile)
        card.sla_profile = profile_as_card_dict(
            decode=prof_decode, prefill=prof_prefill
        )
        log.info("sla profile %s embedded in model card", args.sla_profile)
    return engine, card


async def async_main(args) -> None:
    from dynamo_tpu.runtime import tracing

    # Trace-lane identity: role-named lane (DYNTPU_PROC_LANE wins) so the
    # stitched fleet timeline shows "prefill-…"/"worker-…" rows, not PIDs
    # of indistinct processes.
    lane = os.environ.get("DYNTPU_PROC_LANE")
    if not lane:
        lane = f"{'prefill' if args.is_prefill_worker else 'worker'}-{os.getpid()}"
        tracing.set_default_lane(lane)
    rt = await DistributedRuntime.create(store_url=args.store_url, proc_label=lane)
    trace_exporter = None
    if tracing.enabled() and os.environ.get("DYNTPU_TRACE_EXPORT", "") not in ("", "0"):
        from dynamo_tpu.runtime.trace_export import TraceExporter

        trace_exporter = await TraceExporter(
            rt.store, os.environ.get("DYNTPU_FLEET_ID", "default"), lane=lane
        ).start()
    engine, card = await build_engine(args, config=rt.config)
    # Multi-LoRA: register every --lora adapter on the engine (paged
    # into the tier economy now; device slots fill on first request).
    # Prefill workers register them too — a remote prefill carries the
    # request's adapter_id and must resolve it.
    lora_specs = args.lora_specs
    for lname, lrank, lseed in lora_specs:
        engine.register_adapter(lname, rank=lrank, seed=lseed)
    # Engine-level chaos draws (mocker kill_p) count on this process's
    # /metrics alongside the messaging-layer injector's.
    engine_chaos = getattr(getattr(engine, "args", None), "chaos", None)
    if engine_chaos is not None:
        engine_chaos.bind_metrics(rt.metrics)
    # TPU engine hot-loop gauges (in-flight windows, pending first-sample
    # fetches, prefill pad ratio); catalog-guarded by tools/check_metrics.py.
    if hasattr(engine, "bind_metrics"):
        engine.bind_metrics(rt.metrics)

    broadcaster = KvEventBroadcaster(engine.pool)
    publisher = None
    if args.kv_directory == "on":
        # Global prefix directory (fleet/directory.py): mirror this
        # engine's block residency — G1 from the pool event stream, the
        # host/disk/fleet tiers from the TierStack sink — so frontends
        # can price transfer-vs-recompute and the autoscaler sees heat.
        from dynamo_tpu.fleet.directory import DirectoryPublisher

        publisher = await DirectoryPublisher(
            rt.store, args.namespace, await rt.primary_lease()
        ).start()
        engine.pool.set_event_sink(
            lambda ev: (broadcaster.publish(ev), publisher.pool_sink(ev))
        )
        tiers = getattr(engine, "tiers", None)
        if tiers is not None and hasattr(tiers, "set_event_sink"):
            tiers.set_event_sink(publisher.tier_sink)
    else:
        engine.pool.set_event_sink(broadcaster.publish)

    manager = None
    if args.autoscaler == "on":
        from dynamo_tpu.planner.actions import POOL_DECODE, POOL_PREFILL
        from dynamo_tpu.runtime.chaos import ChaosInjector
        from dynamo_tpu.worker.roles import WorkerRoleManager

        cards = [card] + adapter_cards(card, lora_specs)
        role = (
            POOL_PREFILL
            if args.is_prefill_worker or args.autoscaler_role == "prefill"
            else POOL_DECODE
        )
        manager = await WorkerRoleManager(
            rt, engine, cards, args, broadcaster,
            chaos=ChaosInjector.from_config(rt.config.chaos),
        ).start(role)
        role = f"autoscaled {manager.role} worker"
        print(
            f"dynamo_tpu {role}: serving {card.name} in namespace "
            f"{args.namespace} (workerctl/admin live)",
            flush=True,
        )
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            with contextlib.suppress(NotImplementedError):
                loop.add_signal_handler(sig, stop.set)
        stop_task = loop.create_task(stop.wait())
        retired_task = loop.create_task(manager.retired.wait())
        await asyncio.wait(
            (stop_task, retired_task), return_when=asyncio.FIRST_COMPLETED
        )
        for t in (stop_task, retired_task):
            t.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await t
        log.info("worker shutting down")
        await manager.close()
        if publisher is not None:
            with contextlib.suppress(Exception):
                await publisher.close()
        if trace_exporter is not None:
            with contextlib.suppress(Exception):
                await trace_exporter.close()
        stop_fn = getattr(engine, "stop", None)
        if stop_fn is not None:
            await stop_fn()
        await rt.shutdown()
        return

    comp = rt.namespace(args.namespace).component(args.component)

    # G4 cross-worker reuse: every real engine answers peer prefix
    # fetches from its host tiers (llm/peer_kv.py; no-op without tiers).
    if args.engine == "tpu":
        from dynamo_tpu.llm.peer_kv import KV_PREFIX_ENDPOINT, make_kv_prefix_handler

        await comp.endpoint(KV_PREFIX_ENDPOINT).serve(make_kv_prefix_handler(engine))

    if args.is_prefill_worker:
        from dynamo_tpu.llm.disagg import DisaggConfig, PrefillHandler, PrefillPuller
        from dynamo_tpu.runtime.chaos import ChaosInjector
        from dynamo_tpu.runtime.queue import WorkQueue

        dcfg = DisaggConfig()
        # Env-driven kill-mid-transfer faults (DYNTPU_CHAOS_TRANSFER_CUT_P)
        # ride the same [chaos] section as the messaging-layer injector.
        handler = PrefillHandler(
            engine, frame_bytes=dcfg.frame_bytes,
            chaos=ChaosInjector.from_config(rt.config.chaos),
        )
        gen_handle = await comp.endpoint(args.endpoint).serve(handler.generate)
        await comp.endpoint("kv_fetch").serve(handler.kv_fetch)
        await serve_kv_endpoints(comp, broadcaster, engine.metrics)
        # Pull queued prefill jobs too (competing consumer across the
        # prefill fleet) — push and queue dispatch both work.
        PrefillPuller(
            engine,
            WorkQueue(rt.store, dcfg.queue_name),
            rt.store,
            gen_handle.instance.instance_id,
            lane=lane,
        ).start()
        # No model card: the frontend must route only to decode workers.
        role = "prefill worker"
    else:
        # Disaggregated prefill/decode is the DEFAULT serving shape for
        # TPU decode workers (--disagg auto): the handler costs one
        # discovery-set lookup per long prompt when no prefill fleet
        # exists and serves aggregated, so wiring it is free — a prefill
        # component joining the namespace starts taking long prefills
        # with no decode-worker restart.
        if args.engine == "tpu" and args.disagg != "off":
            from dynamo_tpu.llm.disagg import DisaggConfig, DisaggDecodeHandler
            from dynamo_tpu.runtime.push_router import RouterMode

            from dynamo_tpu.runtime.queue import WorkQueue

            pcomp = rt.namespace(args.namespace).component(args.prefill_component)
            cfg = DisaggConfig(
                max_local_prefill_length=args.max_local_prefill_length,
                prefill_component=args.prefill_component,
                stream=not args.no_disagg_stream,
            )
            handler = DisaggDecodeHandler(
                engine,
                await pcomp.endpoint(cfg.prefill_endpoint).router(RouterMode.ROUND_ROBIN),
                await pcomp.endpoint(cfg.fetch_endpoint).router(RouterMode.DIRECT),
                cfg,
                queue=(
                    None if args.prefill_dispatch == "push"
                    else WorkQueue(rt.store, cfg.queue_name)
                ),
                store=rt.store,
            )
            # disagg_remote_prefill_total / disagg_fallback_total{reason}
            # + transfer bytes/inflight/overlap on this process's /metrics.
            handler.bind_metrics(rt.metrics)
        else:
            handler = engine

        if args.engine == "tpu":
            # Resolve router peer_prefix hints (G4) ahead of disagg/admission.
            from dynamo_tpu.llm.peer_kv import KV_PREFIX_ENDPOINT, PeerPrefixFetcher
            from dynamo_tpu.runtime.push_router import RouterMode

            handler = PeerPrefixFetcher(
                engine,
                await comp.endpoint(KV_PREFIX_ENDPOINT).router(RouterMode.DIRECT),
                inner=handler,
            )

        async def gen_handler(payload, ctx):
            async for item in handler.generate(payload, ctx):
                yield item

        await comp.endpoint(args.endpoint).serve(gen_handler)
        await serve_kv_endpoints(comp, broadcaster, engine.metrics)
        if hasattr(engine, "embed"):
            async def embed_handler(payload, ctx):
                try:
                    vec = await engine.embed((payload or {}).get("token_ids") or [])
                    yield {"embedding": vec}
                except Exception as e:  # noqa: BLE001 — per-request failure
                    yield {"error": str(e)}

            await comp.endpoint("embed").serve(embed_handler)
        if hasattr(engine, "clear_kv_blocks"):
            async def clear_handler(payload, ctx):
                yield {"cleared": engine.clear_kv_blocks()}

            await comp.endpoint("clear_kv").serve(clear_handler)
        await register_model(rt, args.namespace, card)
        # One model card per adapter: the frontend lists each fine-tune
        # as its own served model (/v1/models carries the lora metadata),
        # the preprocessor stamps adapter_id from the card, and routing
        # lands on the same component/endpoint this engine serves —
        # adapters start cold in the tiers (resident_tier G2) and page
        # into G1 on first request.
        for acard in adapter_cards(card, lora_specs):
            await register_model(rt, args.namespace, acard)
        role = "worker"
    rank = "" if args.dp_rank is None else f" [dp rank {args.dp_rank}/{args.dp_size}]"
    print(
        f"dynamo_tpu {role}: serving {card.name} as "
        f"{args.namespace}/{args.component}/{args.endpoint}{rank}",
        flush=True,
    )

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        with contextlib.suppress(NotImplementedError):
            loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    log.info("worker shutting down")
    if publisher is not None:
        with contextlib.suppress(Exception):
            await publisher.close()
    if trace_exporter is not None:
        with contextlib.suppress(Exception):
            await trace_exporter.close()
    stop_fn = getattr(engine, "stop", None)
    if stop_fn is not None:
        await stop_fn()
    await rt.shutdown()


def _step_addr(args) -> str:
    if args.dist_step_addr:
        return args.dist_step_addr
    host, port = args.dist_coordinator.rsplit(":", 1)
    return f"{host}:{int(port) + 1}"


def _engine_args(args, model):
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.llm.tokenizer import parse_tokenizer_spec as tokenizer_spec

    return EngineArgs(
        model=model,
        block_size=args.block_size,
        num_kv_blocks=args.num_kv_blocks,
        max_num_seqs=args.max_num_seqs,
        max_model_len=args.max_model_len,
        dtype=args.dtype,
        tp=args.tp,
        decode_steps=args.decode_steps,
        pipeline_depth=args.pipeline_depth,
        pipeline_windows=args.pipeline_depth > 0,
        prefill_buckets_spec=args.prefill_buckets,
        prefill_tail_split=not args.no_prefill_tail_split,
        delta_max_tokens=args.delta_max_tokens,
        delta_max_ms=args.delta_max_ms,
        spec_tokens=args.spec_tokens,
        spec_ngram=args.spec_ngram,
        spec_fused=not args.spec_stepwise,
        spec_tree_width=args.spec_tree_width,
        spec_tree_depth=args.spec_tree_depth,
        spec_budget_adaptive=args.spec_budget == "adaptive",
        lora_slots=args.lora_slots,
        lora_rank=max([args.lora_rank] + [r for _, r, _ in args.lora_specs]),
        qos_scheduling=args.qos_sched == "on",
        # Grammar token-mask FSMs compile over the SERVING tokenizer's
        # vocabulary (engine/grammar.py) — response_format masks must
        # legalize exactly the ids the detokenizer can render.
        grammar_tokenizer=tokenizer_spec(args.tokenizer),
        attn_impl=args.attn_impl,
        quant=args.quant,
        kv_quant=args.kv_quant,
        kv_pressure_offer=args.kv_pressure_offer,
        host_kv_blocks=args.host_kv_blocks,
        disk_kv_dir=args.disk_kv_dir,
        disk_kv_blocks=args.disk_kv_blocks,
        fleet_kv_dir=args.fleet_kv_dir,
        fleet_kv_blocks=args.fleet_kv_blocks,
    )


def run_follower(args) -> None:
    '''Multi-host follower: no store, no endpoint; replays the leader
    dispatch stream against this host\'s shard of the mesh.'''
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.runner import follower_loop

    params = None
    sharding = None
    if args.model_path:
        from dynamo_tpu.engine.loader import load_config, load_model
        from dynamo_tpu.parallel.mesh import ModelSharding, build_mesh

        model = load_config(args.model_path)
        if args.tp > 1:
            sharding = ModelSharding(build_mesh(tp=args.tp, cfg=model), model)
        model, params = load_model(args.model_path, args.dtype, sharding, args.quant)
    else:
        model = ModelConfig.preset(args.preset)
    eargs = _engine_args(args, model)
    print(f"dynamo_tpu follower {args.dist_process_id}/{args.dist_num_processes}", flush=True)
    follower_loop(eargs, _step_addr(args), params=params, seed=args.seed, sharding=sharding)


def run_dp_spawner(args, argv) -> int:
    """Spawn and supervise one worker process per dp rank (reference:
    dsr1_dep.sh launches one dynamo worker per vLLM dp_rank). Ranks are
    independent replicas of the same model: a dead rank loses only its
    own KV and lease — the rest keep serving, so the spawner does not
    gang-kill on a single failure; it forwards SIGINT/SIGTERM and exits
    with the worst child code once all ranks are done. With
    ``--dp-restart`` a dead rank is respawned after jittered exponential
    backoff (the frontend fleet's supervision hygiene,
    fleet/supervisor.py:BackoffPolicy) — the replacement re-registers
    under a fresh lease and the router folds it back in."""
    import os
    import signal as sig
    import subprocess
    import sys
    import time

    base = [a for a in (argv if argv is not None else sys.argv[1:])]
    procs: list[subprocess.Popen] = []
    stopping = False

    def forward(signum, _frame):
        nonlocal stopping
        stopping = True  # mid-launch: abort spawning further ranks too
        for p in procs:
            if p.poll() is None:
                p.send_signal(signum)

    # Installed BEFORE spawning: a signal mid-launch must still reach the
    # ranks already running, or they orphan with chips and leases held.
    sig.signal(sig.SIGTERM, forward)
    sig.signal(sig.SIGINT, forward)
    def spawn_rank(r: int) -> subprocess.Popen:
        env = dict(os.environ)
        env.update(dp_rank_chip_env(r, args.tp))
        if env.get("DYNTPU_SYSTEM_ENABLED"):
            env["DYNTPU_SYSTEM_PORT"] = str(
                dp_rank_ports(args.dp_base_port, r)["system"]
            )
        return subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.worker", *base, "--dp-rank", str(r)],
            env=env,
        )

    try:
        for r in range(args.dp_size):
            if stopping:
                break
            procs.append(spawn_rank(r))
    except Exception:
        # A failed spawn must not leave earlier ranks orphaned (they hold
        # chips and store leases with nobody to signal them).
        for p in procs:
            if p.poll() is None:
                p.terminate()
        raise
    if stopping:
        # A rank spawned while the handler ran may have missed the signal.
        for p in procs:
            if p.poll() is None:
                p.terminate()
    print(f"dynamo_tpu dp spawner: {args.dp_size} ranks launched", flush=True)
    if args.dp_restart and not stopping:
        # Fleet supervision hygiene for dp ranks: respawn a dead rank
        # after jittered exponential backoff instead of serving degraded
        # until an operator notices. A rank is an independent replica, so
        # the restart is invisible to its siblings.
        from dynamo_tpu.fleet.backoff import BackoffPolicy
        from dynamo_tpu.runtime.config import Config

        # Same knobs as the frontend fleet's restarts: an operator tuning
        # DYNTPU_FLEET_RESTART_BACKOFF_* tunes BOTH supervision paths.
        fcfg = Config.from_env().fleet
        backoff = BackoffPolicy(
            fcfg.restart_backoff_base,
            fcfg.restart_backoff_max,
            fcfg.restart_reset_after,
        )
        failures = [0] * len(procs)
        started = [time.monotonic()] * len(procs)
        restart_at = [0.0] * len(procs)
        while not stopping:
            now = time.monotonic()
            for r, p in enumerate(procs):
                # rc=0 is a deliberate exit (operator SIGTERMed the rank
                # directly, or it finished): leave the slot down — only
                # CRASHED ranks restart, as the flag advertises.
                if p.poll() is None or p.returncode == 0:
                    continue
                if restart_at[r] == 0.0:
                    if now - started[r] > backoff.reset_after:
                        failures[r] = 0
                    failures[r] += 1
                    restart_at[r] = now + backoff.delay(failures[r])
                    print(
                        f"dynamo_tpu dp spawner: rank {r} exited rc={p.returncode}, "
                        f"restart in {restart_at[r] - now:.2f}s", flush=True,
                    )
                elif now >= restart_at[r] and not stopping:
                    try:
                        procs[r] = spawn_rank(r)
                    except Exception:
                        # Same rule as the startup loop: a failed spawn
                        # must not leave live ranks orphaned with chips
                        # and leases held and nobody to signal them.
                        for q in procs:
                            if q.poll() is None:
                                q.terminate()
                        raise
                    started[r] = now
                    restart_at[r] = 0.0
            if all(p.poll() is not None and p.returncode == 0 for p in procs):
                break  # every rank exited cleanly: nothing left to supervise
            time.sleep(0.25)
        for p in procs:
            if p.poll() is None:
                p.send_signal(sig.SIGTERM)
    rcs = [p.wait() for p in procs]
    return max((abs(rc) for rc in rcs), default=0)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.dp_size > 1 and args.dp_rank is None:
        # The spawner stays off JAX: a parent that opens the backend holds
        # the chips its ranks need.
        return run_dp_spawner(args, argv)
    if args.engine == "tpu":
        # Persistent compile cache: restart MTTR drops from minutes of XLA
        # compiles to seconds once the lattice has been warmed.
        from dynamo_tpu.engine.compile_cache import configure_compile_cache

        configure_compile_cache()
    if args.dist_num_processes > 1:
        import jax

        jax.distributed.initialize(
            coordinator_address=args.dist_coordinator,
            num_processes=args.dist_num_processes,
            process_id=args.dist_process_id,
        )
        if args.dist_process_id > 0:
            run_follower(args)
            return 0
    asyncio.run(async_main(args))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
