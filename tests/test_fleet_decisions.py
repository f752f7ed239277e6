"""Store-backed router decision cache: cross-process stickiness state.

Covers the mirror protocol (write → watch → sibling lookup), prefix-depth
semantics over chained block hashes, TTL expiry via rotating leases,
drain-flush, and the KvPushRouter integration (cache as overlap floor —
never overriding a live-index win or resurrecting a dead worker)."""

import asyncio

from dynamo_tpu.fleet.decisions import RouterDecisionCache
from dynamo_tpu.kv_router.indexer import OverlapScores
from dynamo_tpu.runtime.store import MemoryStore


def test_mirror_propagates_and_depth_is_shared_prefix():
    async def go():
        store = MemoryStore()
        c1 = await RouterDecisionCache(store, "f").start()
        c2 = await RouterDecisionCache(store, "f").start()
        turn1 = [11, 22, 33]
        c1.record("m", turn1, worker=0xA)
        await asyncio.sleep(0.05)
        # Follow-up turn extends the chain: depth = shared prefix blocks.
        turn2 = turn1 + [44, 55]
        assert c2.lookup("m", turn2) == (0xA, 3)
        assert c1.lookup("m", turn2) == (0xA, 3)  # writer's own mirror too
        # Different model scope: no bleed.
        assert c2.lookup("other", turn2) is None
        # Unrelated chain: no hit.
        assert c2.lookup("m", [9, 8, 7]) is None
        # Deeper decision (turn 2 routed) shadows the shallower one.
        c2.record("m", turn2, worker=0xB)
        await asyncio.sleep(0.05)
        turn3 = turn2 + [66]
        assert c1.lookup("m", turn3) == (0xB, 5)
        await c1.close()
        await c2.close()

    asyncio.run(go())


def test_entries_expire_via_rotating_leases():
    async def go():
        store = MemoryStore()
        c1 = await RouterDecisionCache(store, "f", ttl=0.8).start()
        c2 = await RouterDecisionCache(store, "f", ttl=0.8).start()
        c1.record("m", [1, 2], worker=5)
        await asyncio.sleep(0.05)
        assert c2.lookup("m", [1, 2]) == (5, 2)
        await asyncio.sleep(1.5)  # > ttl + reaper tick
        assert c2.lookup("m", [1, 2]) is None, "entry outlived its TTL"
        assert c1.lookup("m", [1, 2]) is None, "writer mirror not pruned"
        await c1.close()
        await c2.close()

    asyncio.run(go())


def test_drain_flush_revokes_entries_immediately():
    """Satellite: a SIGTERM-drained process must flush its decision-cache
    entries before exit instead of leaving them to age out."""

    async def go():
        store = MemoryStore()
        c1 = await RouterDecisionCache(store, "f", ttl=300.0).start()
        c2 = await RouterDecisionCache(store, "f", ttl=300.0).start()
        c1.record("m", [1, 2, 3], worker=5)
        await asyncio.sleep(0.05)
        assert c2.lookup("m", [1, 2, 3]) == (5, 3)
        await c1.close(flush=True)
        await asyncio.sleep(0.05)
        assert c2.lookup("m", [1, 2, 3]) is None, "entries lingered past drain"
        await c2.close()

    asyncio.run(go())


def test_repeat_record_same_worker_writes_once():
    async def go():
        store = MemoryStore()
        c1 = await RouterDecisionCache(store, "f").start()
        c1.record("m", [1, 2], worker=5)
        await asyncio.sleep(0.05)
        rev1 = (await store.get_prefix("fleet/f/route/")).pop().mod_revision
        c1.record("m", [1, 2], worker=5)  # no-op: already published
        await asyncio.sleep(0.05)
        rev2 = (await store.get_prefix("fleet/f/route/")).pop().mod_revision
        assert rev1 == rev2
        c1.record("m", [1, 2], worker=9)  # placement moved: re-published
        await asyncio.sleep(0.05)
        assert c1.lookup("m", [1, 2]) == (9, 2)
        await c1.close()

    asyncio.run(go())


class _StubIndex:
    def __init__(self, scores):
        self._scores = scores

    def find_matches(self, hashes, top_k=0):
        return OverlapScores(dict(self._scores))


class _StubDiscovery:
    def __init__(self, ids):
        self._ids = ids
        self.version = 1

    def instance_ids(self):
        return list(self._ids)


def _router_with(decisions, index_scores, workers):
    """KvPushRouter with stubbed discovery/index: only _place matters."""
    from dynamo_tpu.kv_router.router import KvPushRouter

    r = KvPushRouter.__new__(KvPushRouter)
    from dynamo_tpu.kv_router.router import KvRouterConfig
    from dynamo_tpu.kv_router.scheduler import KvScheduler
    from dynamo_tpu.kv_router.sequence import ActiveSequences

    r.config = KvRouterConfig(block_size=4)
    r.decisions = decisions
    r.index = _StubIndex(index_scores)
    r.discovery = _StubDiscovery(workers)
    r.scheduler = KvScheduler()
    r.active = ActiveSequences()
    r.directory = None
    r._m = {}
    r._roster = []
    r._roster_set = set()
    r._roster_version = -1
    r._roster_stamp = 0.0
    return r


class _FixedDecisions:
    def __init__(self, hit):
        self.hit = hit
        self.recorded = []

    def lookup(self, hashes):
        return self.hit

    def record(self, hashes, worker):
        self.recorded.append((tuple(hashes), worker))


def test_router_uses_cache_as_overlap_floor():
    tokens = list(range(32))  # 8 blocks at block_size 4
    # Cache says worker 2 holds 6 blocks; live index knows nothing.
    r = _router_with(_FixedDecisions((2, 6)), {}, [1, 2, 3])
    placement, _hashes, scores, _workers, _runs = r._place(tokens)
    assert placement.worker == 2
    assert placement.overlap_blocks == 6
    assert scores[2] == 6


def test_router_live_index_beats_shallower_cache():
    tokens = list(range(32))
    # Index: worker 1 holds 7 blocks; cache: worker 2 holds 3.
    r = _router_with(_FixedDecisions((2, 3)), {1: 7}, [1, 2, 3])
    placement, _, _, _, _ = r._place(tokens)
    assert placement.worker == 1


def test_router_ignores_cached_dead_worker():
    tokens = list(range(32))
    # Cached worker 9 is not in the live set: boost must not apply.
    r = _router_with(_FixedDecisions((9, 6)), {1: 1}, [1, 2])
    placement, _, scores, _, _ = r._place(tokens)
    assert placement.worker == 1
    assert 9 not in scores


def test_mirror_is_a_bounded_lru_that_keeps_recent_hits_and_sweeps_by_worker():
    """Ten times more conversations than ``max_entries``: the mirror never
    grows past its cap, a conversation that keeps being looked up survives
    the churn while cold ones are evicted, the per-worker index shrinks
    with every eviction, and dropping a worker removes exactly its entries."""
    async def go():
        store = MemoryStore()
        cap = 64
        cache = await RouterDecisionCache(store, "f", max_entries=cap).start()
        hot = [7, 8, 9]
        cache.record("m", hot, worker=0xA)
        for conv in range(10 * cap):
            cache.record("m", [10_000 + conv, 20_000 + conv], worker=conv % 5)
            assert len(cache._mirror) <= cap
            if conv % 16 == 0:
                assert cache.lookup("m", hot + [1234]) == (0xA, 3)
        assert len(cache._mirror) == cap
        assert cache.lookup("m", hot) == (0xA, 3)              # the recent hit stayed
        assert cache.lookup("m", [10_000, 20_000]) is None       # the coldest went
        newest = 10 * cap - 1
        assert cache.lookup("m", [10_000 + newest, 20_000 + newest]) == (newest % 5, 2)
        assert sum(len(v) for v in cache._by_worker.values()) == cap
        mine = len(cache._by_worker[3])
        cache.drop_worker(3)
        assert len(cache._mirror) == cap - mine
        assert all(w != 3 for w, _ in cache._mirror.values())
        await cache.close()

    asyncio.run(go())
