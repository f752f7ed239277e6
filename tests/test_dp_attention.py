"""DP-attention: per-rank worker processes behind the KV router.

Reference behaviour being matched: one dynamo worker per engine dp rank
with coordinated ports (reference: components/backends/vllm/launch/
dsr1_dep.sh:86-105, args.py:170-203). Here `worker --dp-size N` spawns N
independent rank processes of the same model; the KV router does the
cross-rank load balancing the reference's DP load balancer does.
"""

import asyncio
import socket

import pytest

from dynamo_tpu.kv_router.router import KvPushRouter, KvRouterConfig
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.push_router import RouterMode
from dynamo_tpu.worker.__main__ import dp_rank_chip_env, dp_rank_ports, parse_args

from procutil import ManagedProcess


def test_dp_rank_ports_disjoint_and_deterministic():
    blocks = [dp_rank_ports(29600, r) for r in range(8)]
    # Rank blocks must not overlap: each rank's [system, reserved-end).
    spans = [(b["system"], b["reserved"][1]) for b in blocks]
    for (lo1, hi1), (lo2, hi2) in zip(spans, spans[1:]):
        assert hi1 <= lo2
    assert blocks[0]["system"] == 29600
    assert blocks[1]["system"] == 29604
    assert dp_rank_ports(29600, 3) == dp_rank_ports(29600, 3)


def test_dp_rank_chip_env_gives_each_rank_its_own_chips():
    """Each rank is a TPU world of its own chips: disjoint
    TPU_VISIBLE_CHIPS, and the bounds that TPU_VISIBLE_CHIPS alone lacks
    (without them ranks 1..N-1 die on libtpu's multi-process lock)."""
    envs = [dp_rank_chip_env(r, 1) for r in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    two = dp_rank_chip_env(1, 2)
    assert two["TPU_VISIBLE_CHIPS"] == "2,3"
    assert two["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    assert two["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # A rank gets --tp chips, the size of its mesh. Sizes that have not
    # come up this way on a chip are refused before anything is spawned;
    # a rank launched from outside is not the spawner's to pin.
    assert parse_args(["--dp-size", "2", "--tp", "2"]).tp == 2
    for tp in ("3", "4"):
        with pytest.raises(SystemExit):
            parse_args(["--dp-size", "2", "--tp", tp])
    assert parse_args(["--dp-size", "2", "--dp-rank", "1", "--tp", "4"]).tp == 4


@pytest.mark.e2e
def test_dp_spawner_ranks_serve_and_route_across():
    """`--dp-size 2` spawns two rank processes; the KV router spreads
    distinct concurrent prompts over BOTH ranks; SIGTERM tears the whole
    group down cleanly."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        store_port = s.getsockname()[1]
    store_url = f"tcp://127.0.0.1:{store_port}"

    with ManagedProcess(
        ["-m", "dynamo_tpu.runtime.store_server", "--host", "127.0.0.1",
         "--port", str(store_port)], name="store",
    ) as store:
        store.wait_for(r"store server: tcp://")
        with ManagedProcess(
            ["-m", "dynamo_tpu.worker", "--store-url", store_url,
             "--engine", "mocker", "--model-name", "dp-model",
             "--mocker-speedup", "1000", "--dp-size", "2"],
            name="dp-group",
        ) as group:
            # Both ranks announce through the spawner's inherited stdout.
            group.wait_for(r"dp rank \d/2", timeout=60)
            group.wait_for(r"dp rank \d/2", timeout=60)
            ranks = {
                m for ln in group.lines
                for m in __import__("re").findall(r"dp rank (\d)/2", ln)
            }
            assert ranks == {"0", "1"}

            async def drive():
                rt = await DistributedRuntime.create(store_url=store_url)
                try:
                    ep = rt.namespace("dynamo").component("backend").endpoint("generate")
                    push = await ep.router(RouterMode.DIRECT)
                    await push.discovery.wait_for_instances(2)
                    router = await KvPushRouter(push, KvRouterConfig(block_size=4)).start()
                    try:
                        async def one(i):
                            r = PreprocessedRequest(
                                model="dp-model",
                                token_ids=[100 * i + j for j in range(1, 13)],
                            )
                            r.stop.max_tokens = 8
                            ctx = Context()
                            out = [x async for x in router.generate(r.to_dict(), ctx)]
                            assert out[-1].get("finish_reason")
                            return ctx.metadata["worker_instance_id"]

                        placed = await asyncio.gather(*(one(i) for i in range(1, 9)))
                        assert len(set(placed)) == 2  # both ranks served traffic
                    finally:
                        await router.close()
                finally:
                    await rt.shutdown()

            asyncio.run(drive())
            # Clean group teardown: SIGTERM to the spawner stops all ranks.
            group.terminate()
            assert group.proc.returncode in (0, -15)
