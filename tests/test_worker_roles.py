"""WorkerRoleManager: live prefill↔decode pool moves on a real runtime
(memory store, mocker engine) — registration truth, drain-ordered
transitions with an in-flight stream completing across the move,
retirement leaving zero keys, and the admin RPC surface the autoscaler
actuates through."""

import asyncio
import json
from types import SimpleNamespace

import pytest

from dynamo_tpu.kv_router.publisher import KvEventBroadcaster
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.tokenizer import ByteTokenizer
from dynamo_tpu.mocker.engine import MockerArgs, MockerEngine
from dynamo_tpu.planner.actions import POOL_DECODE, POOL_PREFILL, PoolMove
from dynamo_tpu.planner.actuate import RuntimeActuator, read_pools, worker_key
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.push_router import RouterMode
from dynamo_tpu.worker.roles import (
    ADMIN_COMPONENT,
    ADMIN_ENDPOINT,
    WorkerRoleManager,
)

pytestmark = pytest.mark.integration

NS = "roles-test"


def wargs() -> SimpleNamespace:
    return SimpleNamespace(
        namespace=NS, component="backend", prefill_component="prefill",
        endpoint="generate", engine="mocker", disagg="auto",
        max_local_prefill_length=512, no_disagg_stream=False,
        prefill_dispatch="queue",
    )


async def make_worker(url: str, role: str, itl_ms: float = 0.1):
    rt = await DistributedRuntime.create(store_url=url)
    engine = MockerEngine(
        MockerArgs(block_size=4, num_kv_blocks=128, max_num_seqs=32,
                   ttft_ms=0.5, itl_ms=itl_ms)
    )
    bc = KvEventBroadcaster(engine.pool)
    engine.pool.set_event_sink(bc.publish)
    card = ModelDeploymentCard(
        name="roles-model", kv_cache_block_size=4,
        eos_token_ids=[ByteTokenizer.EOS], context_length=512,
    )
    mgr = await WorkerRoleManager(rt, engine, [card], wargs(), bc).start(role)
    return rt, mgr


def req_dict(i: int, max_tokens: int = 8) -> dict:
    return {
        "model": "roles-model",
        "token_ids": list(range(16)),
        "stop": {"max_tokens": max_tokens, "ignore_eos": True},
        "sampling": {"seed": i},
        "eos_token_ids": [ByteTokenizer.EOS],
    }


def test_role_round_trip_registrations_and_cards():
    async def go():
        url = "memory://roles-roundtrip"
        wrt, mgr = await make_worker(url, POOL_DECODE)
        ort = await DistributedRuntime.create(store_url=url)
        router = await (
            ort.namespace(NS).component(ADMIN_COMPONENT)
            .endpoint(ADMIN_ENDPOINT).router(RouterMode.DIRECT)
        )
        act = RuntimeActuator(ort.store, NS, router, converge_timeout_s=10)

        pools = await act.pools()
        assert len(pools[POOL_DECODE]) == 1 and not pools[POOL_PREFILL]
        assert len(await ort.store.get_prefix("models/")) == 1

        # Registration value names the role + instance for the operator.
        lease = await wrt.primary_lease()
        entry = await ort.store.get(worker_key(NS, lease))
        reg = json.loads(entry.value)
        assert reg["role"] == POOL_DECODE and reg["instance_id"] == lease

        await act.move(PoolMove(worker="", instance_id=0,
                                src=POOL_DECODE, dst=POOL_PREFILL))
        pools = await act.pools()
        assert len(pools[POOL_PREFILL]) == 1 and not pools[POOL_DECODE]
        # No model card under the prefill role: frontends must route
        # only to decode workers.
        assert await ort.store.get_prefix("models/") == []
        # Prefill endpoints live (generate + kv_fetch).
        assert any(
            "/prefill/generate:" in e.key
            for e in await ort.store.get_prefix(f"instances/{NS}/")
        )

        await act.move(PoolMove(worker="", instance_id=0,
                                src=POOL_PREFILL, dst=POOL_DECODE))
        pools = await act.pools()
        assert len(pools[POOL_DECODE]) == 1
        assert len(await ort.store.get_prefix("models/")) == 1

        await mgr.close()
        await wrt.shutdown()
        await ort.shutdown()

    asyncio.run(go())


def test_in_flight_stream_completes_across_pool_move():
    """The zero-failure drain contract: a stream running on the worker
    when the move is commanded finishes with its full token count; the
    move completes after."""

    async def go():
        url = "memory://roles-drain"
        wrt, mgr = await make_worker(url, POOL_DECODE, itl_ms=10.0)
        ort = await DistributedRuntime.create(store_url=url)
        admin = await (
            ort.namespace(NS).component(ADMIN_COMPONENT)
            .endpoint(ADMIN_ENDPOINT).router(RouterMode.DIRECT)
        )
        act = RuntimeActuator(ort.store, NS, admin, converge_timeout_s=20)
        gen = await (
            ort.namespace(NS).component("backend").endpoint("generate")
            .router(RouterMode.ROUND_ROBIN)
        )

        async def slow_stream():
            tokens = 0
            async for frame in gen.generate(req_dict(1, max_tokens=40), Context()):
                if isinstance(frame, dict):
                    tokens += len(frame.get("token_ids") or ())
            return tokens

        stream = asyncio.get_running_loop().create_task(slow_stream())
        await asyncio.sleep(0.05)  # stream is mid-flight (~400ms total)
        assert not stream.done()
        await act.move(PoolMove(worker="", instance_id=0,
                                src=POOL_DECODE, dst=POOL_PREFILL))
        tokens = await stream
        assert tokens == 40, f"stream lost tokens across the move: {tokens}"
        pools = await act.pools()
        assert len(pools[POOL_PREFILL]) == 1

        await mgr.close()
        await wrt.shutdown()
        await ort.shutdown()

    asyncio.run(go())


def test_retire_drains_and_leaves_zero_keys():
    async def go():
        url = "memory://roles-retire"
        wrt, mgr = await make_worker(url, POOL_DECODE, itl_ms=5.0)
        ort = await DistributedRuntime.create(store_url=url)
        admin = await (
            ort.namespace(NS).component(ADMIN_COMPONENT)
            .endpoint(ADMIN_ENDPOINT).router(RouterMode.DIRECT)
        )
        gen = await (
            ort.namespace(NS).component("backend").endpoint("generate")
            .router(RouterMode.ROUND_ROBIN)
        )

        async def stream():
            tokens = 0
            async for frame in gen.generate(req_dict(2, max_tokens=20), Context()):
                if isinstance(frame, dict):
                    tokens += len(frame.get("token_ids") or ())
            return tokens

        s = asyncio.get_running_loop().create_task(stream())
        await asyncio.sleep(0.03)
        lease = await wrt.primary_lease()
        frames = []
        async for f in admin.generate({"cmd": "retire"}, Context(),
                                      instance_id=lease):
            frames.append(f)
        assert frames and frames[0].get("ok")
        assert await s == 20  # in-flight stream drained to completion
        await mgr.retired.wait()
        # Everything deregistered: generate/kv endpoints, model card,
        # autoscaler registration.
        for prefix in ("autoscaler/", "models/"):
            assert await ort.store.get_prefix(prefix) == [], prefix
        gen_keys = [
            e.key for e in await ort.store.get_prefix(f"instances/{NS}/backend/generate")
        ]
        assert gen_keys == []

        await mgr.close()
        await wrt.shutdown()
        await ort.shutdown()

    asyncio.run(go())


def test_admin_rpc_rejects_unknown_commands_and_roles():
    async def go():
        url = "memory://roles-admin"
        wrt, mgr = await make_worker(url, POOL_DECODE)
        ort = await DistributedRuntime.create(store_url=url)
        admin = await (
            ort.namespace(NS).component(ADMIN_COMPONENT)
            .endpoint(ADMIN_ENDPOINT).router(RouterMode.DIRECT)
        )
        lease = await wrt.primary_lease()

        async def rpc(payload):
            frames = []
            async for f in admin.generate(payload, Context(), instance_id=lease):
                frames.append(f)
            return frames[-1]

        assert "error" in await rpc({"cmd": "bogus"})
        assert "error" in await rpc({"cmd": "set_role", "role": "sideways"})
        status = await rpc({"cmd": "status"})
        assert status["role"] == POOL_DECODE and status["ok"]
        # set_role to the current role is an idempotent no-op.
        same = await rpc({"cmd": "set_role", "role": POOL_DECODE})
        assert same["role"] == POOL_DECODE

        await mgr.close()
        await wrt.shutdown()
        await ort.shutdown()

    asyncio.run(go())


def test_read_pools_tolerates_junk_entries():
    async def go():
        from dynamo_tpu.runtime.store import connect_store

        store = await connect_store("memory://roles-junk")
        await store.put(f"autoscaler/{NS}/workers/zz", b"not json")
        await store.put(
            f"autoscaler/{NS}/workers/1f",
            json.dumps({"role": POOL_DECODE, "instance_id": 31}).encode(),
        )
        pools = await read_pools(store, NS)
        assert [w.instance_id for w in pools[POOL_DECODE]] == [31]
        return pools

    asyncio.run(go())


def test_replica_scale_down_retires_distinct_victims():
    """Regression: the retire RPC acks before the registration key
    vanishes (background drain) — a multi-step shrink must not re-pick
    the same still-registered victim and then stall out."""

    async def go():
        from dynamo_tpu.planner.actions import ReplicaScale

        url = "memory://roles-shrink"
        workers = [await make_worker(url, POOL_DECODE) for _ in range(3)]
        ort = await DistributedRuntime.create(store_url=url)
        admin = await (
            ort.namespace(NS).component(ADMIN_COMPONENT)
            .endpoint(ADMIN_ENDPOINT).router(RouterMode.DIRECT)
        )
        act = RuntimeActuator(ort.store, NS, admin, converge_timeout_s=15)
        assert len((await act.pools())[POOL_DECODE]) == 3

        await act.scale(ReplicaScale(pool=POOL_DECODE, target=1, current=3))
        pools = await act.pools()
        assert len(pools[POOL_DECODE]) == 1, pools
        retired = [m for _, m in workers if m.retired.is_set()]
        assert len(retired) == 2, "exactly two distinct workers must retire"

        for rt, mgr in workers:
            await mgr.close()
            await rt.shutdown()
        await ort.shutdown()

    asyncio.run(go())


def test_closed_loop_scales_up_then_moves_a_pool_under_streaming_traffic():
    """The live observe → decide → actuate stack, clean (its chaos twin is
    tests/test_autoscaler_chaos.py): the production SlaAutoscaler over a
    real RuntimeActuator and in-process workers takes ONE replica
    scale-up (an ITL breach) and then ONE pool move (a TTFT breach at a
    full fleet) while clients stream throughout. Both actions end ``ok``
    in the journal and in ``planner_scale_actions_total``, no stream
    fails or comes up short, and teardown leaves no key behind."""
    from dynamo_tpu.planner.actions import ActionJournal
    from dynamo_tpu.planner.core import PlannerObservation
    from dynamo_tpu.planner.operator import (
        ControlLaw,
        OperatorConfig,
        SlaAutoscaler,
        register_planner_metrics,
    )
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    async def go():
        url = "memory://roles-closed-loop"
        workers = [await make_worker(url, POOL_PREFILL, itl_ms=2.0),
                   await make_worker(url, POOL_DECODE, itl_ms=2.0)]
        ort = await DistributedRuntime.create(store_url=url)
        admin = await (
            ort.namespace(NS).component(ADMIN_COMPONENT)
            .endpoint(ADMIN_ENDPOINT).router(RouterMode.DIRECT)
        )
        gen = await (
            ort.namespace(NS).component("backend").endpoint("generate")
            .router(RouterMode.ROUND_ROBIN)
        )

        class Launcher:
            async def launch(self, pool: str) -> None:
                workers.append(await make_worker(url, pool, itl_ms=2.0))

        act = RuntimeActuator(ort.store, NS, admin, launcher=Launcher(),
                              converge_timeout_s=15)
        cfg = OperatorConfig(
            itl_sla_ms=20.0, ttft_sla_ms=300.0, mean_input_tokens=64.0,
            mean_output_tokens=16.0, predictor="constant", max_engines=3,
            hysteresis_cycles=1, cooldown_s=0.0, replica_scaling=True,
            decode_tok_s=100.0, prefill_tok_s=1000.0, interval_s=0.1,
        )
        observations = [
            # decode breached, room to grow: scale decode 1 → 2
            PlannerObservation(request_rate=2.0, output_token_rate=150.0,
                               itl_ms=90.0, ttft_ms=20.0),
            # fleet full, prefill breached with decode headroom: move one
            PlannerObservation(request_rate=2.0, output_token_rate=20.0,
                               input_token_rate=1500.0, itl_ms=5.0, ttft_ms=900.0),
        ]

        async def observe():
            return observations.pop(0)

        reg = MetricsRegistry()
        metrics = register_planner_metrics(reg)
        auto = SlaAutoscaler(
            ControlLaw(cfg), observe, pool_actuator=act,
            journal=ActionJournal(ort.store, "loop", await ort.primary_lease()),
            metrics=metrics,
        )

        stop = asyncio.Event()
        done, short = [], []

        async def client(i):
            n = 0
            while not stop.is_set():
                tokens = 0
                async for frame in gen.generate(req_dict(1000 * i + n, max_tokens=12),
                                                Context()):
                    if isinstance(frame, dict):
                        tokens += len(frame.get("token_ids") or ())
                (done if tokens == 12 else short).append(tokens)
                n += 1

        clients = [asyncio.get_running_loop().create_task(client(i)) for i in range(4)]
        await asyncio.sleep(0.1)
        await auto.step()
        pools = await act.pools()
        assert (len(pools[POOL_PREFILL]), len(pools[POOL_DECODE])) == (1, 2)
        await auto.step()
        pools = await act.pools()
        assert (len(pools[POOL_PREFILL]), len(pools[POOL_DECODE])) == (2, 1)
        await asyncio.sleep(0.1)
        stop.set()
        await asyncio.gather(*clients)   # a failed stream raises here

        assert short == [] and len(done) >= 4
        assert metrics["actions"].value(kind="replica_scale", outcome="ok") == 1
        assert metrics["actions"].value(kind="pool_move", outcome="ok") == 1
        entries = await auto.journal.entries()
        assert [(e["kind"], e["phase"]) for e in entries
                if e["phase"] != "started"] == [("replica_scale", "ok"),
                                                ("pool_move", "ok")]
        page = reg.render()
        assert "planner_pool_size" in page and "planner_decision_lag_seconds" in page

        for rt, mgr in workers:
            await mgr.close()
            await rt.shutdown()
        for prefix in ("autoscaler/", "models/", f"instances/{NS}/"):
            left = [e.key for e in await ort.store.get_prefix(prefix)]
            assert left == [], (prefix, left)
        await ort.shutdown()

    asyncio.run(go())
