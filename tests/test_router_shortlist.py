"""Shortlist-pruned placement: equivalence with the full O(fleet) scan.

Tentpole coverage for the cluster-scale placement hot path:

- randomized fleets: pruned scheduling (index top-k shortlist +
  incremental load state) picks the same argmin-cost worker as the
  full scan at temperature → 0 whenever the shortlist covers every
  holder (the recall guarantee of KvScheduler.schedule's docstring);
- ``shortlist_k=0`` is byte-identical through ``_place`` — hashes,
  scores, and the chosen placement match a straight-line reference
  implementation of the legacy loop, rng stream included;
- the index's top-k shortlist is exactly the k deepest holders of the
  full score dict (RadixIndex, ShardedRadixIndex, ApproxKvIndexer);
- ActiveSequences fleet aggregates (roster mean + lazy idle heap) stay
  consistent across add/free/remove/resync.
"""

import random

import pytest

from dynamo_tpu.kv_router.approx import ApproxKvIndexer
from dynamo_tpu.kv_router.indexer import OverlapScores, RadixIndex, ShardedRadixIndex
from dynamo_tpu.kv_router.protocols import KvCacheEvent, StoredBlock
from dynamo_tpu.kv_router.router import KvPushRouter, KvRouterConfig
from dynamo_tpu.kv_router.scheduler import KvScheduler, KvSchedulerConfig
from dynamo_tpu.kv_router.sequence import ActiveSequences
from dynamo_tpu.tokens import compute_block_hashes


def _store_chain(idx, worker, hashes, eid_start=1):
    parent = None
    for eid, h in enumerate(hashes, start=eid_start):
        idx.apply(worker, KvCacheEvent.stored([StoredBlock(h, parent)], event_id=eid))
        parent = h


def _ref_costs(workers, request_blocks, scores, active, cfg):
    """Straight-line reimplementation of the legacy full-scan cost loop
    (fetchable=None), used as the oracle."""
    loads = [active.active_blocks(w) for w in workers]
    if cfg.migrate_cost_blocks is not None and len(loads) >= 2:
        mean = sum(loads) / len(loads)
        priced = [min(float(l), mean + cfg.migrate_cost_blocks) for l in loads]
    else:
        priced = [float(l) for l in loads]
    costs = []
    for w, load in zip(workers, priced):
        overlap = min(scores.get(w, 0), request_blocks)
        costs.append(
            cfg.overlap_score_weight * (request_blocks - overlap)
            + load + request_blocks
        )
    return costs


# -- randomized pruned-vs-full equivalence -----------------------------------


def test_pruned_placement_matches_full_scan_argmin_randomized():
    rng = random.Random(0x5EED)
    K, M = 8, 3
    for trial in range(25):
        n_workers = rng.randint(40, 200)
        workers = list(range(1, n_workers + 1))
        idx = RadixIndex()
        # A handful of tenant prefix chains, each held by <= K workers so
        # the top-k shortlist provably covers every holder.
        chains = []
        base = trial * 100_000
        for t in range(6):
            chain = [base + t * 1000 + i for i in range(1, rng.randint(3, 12))]
            holders = rng.sample(workers, rng.randint(1, K))
            for w in holders:
                _store_chain(idx, w, chain)
            chains.append(chain)
        # Distinct integer loads make the argmin unique.
        loads = rng.sample(range(0, 5 * n_workers), n_workers)
        active = ActiveSequences()
        active.sync_roster(workers)
        for w, load in zip(workers, loads):
            active.add_request(f"r{w}", w, load, 0, 0)
        # Request extends one tenant chain past its stored depth.
        chain = rng.choice(chains)
        req_hashes = chain + [base + 99_999]
        request_blocks = len(req_hashes)

        full = idx.find_matches(req_hashes)
        pruned_overlaps = idx.find_matches(req_hashes, top_k=K)

        oracle = KvScheduler(KvSchedulerConfig(shortlist_k=0),
                             rng=random.Random(1))
        sched = KvScheduler(
            KvSchedulerConfig(shortlist_k=K, least_loaded_m=M),
            rng=random.Random(1),
        )
        want = oracle.schedule(workers, request_blocks,
                               OverlapScores(dict(full.scores)), active)
        got = sched.schedule(workers, request_blocks, pruned_overlaps, active)
        assert got.full_scan is False
        assert got.candidates_considered <= K + M
        costs = _ref_costs(workers, request_blocks, full.scores, active,
                           oracle.config)
        best = min(costs)
        assert costs[workers.index(got.worker)] == best, (
            f"trial {trial}: pruned choice {got.worker} not argmin"
        )
        assert got.worker == want.worker
        assert got.overlap_blocks == want.overlap_blocks


def test_pruned_placement_zero_overlap_falls_to_least_loaded():
    # No holders at all: the pruned candidate set is just least-loaded-m,
    # and the argmin among zero-overlap workers is the least loaded.
    workers = list(range(1, 101))
    active = ActiveSequences()
    active.sync_roster(workers)
    rng = random.Random(7)
    loads = rng.sample(range(10, 1000), 100)
    for w, load in zip(workers, loads):
        active.add_request(f"r{w}", w, load, 0, 0)
    sched = KvScheduler(KvSchedulerConfig(shortlist_k=8, least_loaded_m=4),
                        rng=random.Random(2))
    got = sched.schedule(workers, 5, OverlapScores({}), active)
    assert got.worker == workers[loads.index(min(loads))]
    assert got.full_scan is False


def test_small_fleet_always_full_scans():
    workers = list(range(1, 6))
    active = ActiveSequences()
    active.sync_roster(workers)
    sched = KvScheduler(KvSchedulerConfig(shortlist_k=16, least_loaded_m=4),
                        rng=random.Random(3))
    got = sched.schedule(workers, 4, OverlapScores({1: 2}), active)
    assert got.full_scan is True
    assert got.candidates_considered == len(workers)


# -- shortlist_k=0 byte-identity through _place ------------------------------


class _StubIndexWrap:
    def __init__(self, idx):
        self._idx = idx

    def find_matches(self, hashes, top_k=0):
        return self._idx.find_matches(hashes, top_k=top_k)


class _StubDiscovery:
    def __init__(self, ids):
        self._ids = ids
        self.version = 1

    def instance_ids(self):
        return list(self._ids)


def _stub_router(idx, workers, shortlist_k, seed):
    r = KvPushRouter.__new__(KvPushRouter)
    r.config = KvRouterConfig(block_size=4, shortlist_k=shortlist_k)
    r.decisions = None
    r.directory = None
    r.index = _StubIndexWrap(idx)
    r.discovery = _StubDiscovery(workers)
    r.scheduler = KvScheduler(
        KvSchedulerConfig(shortlist_k=shortlist_k), rng=random.Random(seed)
    )
    r.active = ActiveSequences()
    r._m = {}
    r._roster = []
    r._roster_set = set()
    r._roster_version = -1
    r._roster_stamp = 0.0
    return r


def test_shortlist_zero_is_byte_identical_through_place():
    rng = random.Random(0xBEEF)
    for seed in range(8):
        n = rng.randint(30, 120)
        workers = list(range(1, n + 1))
        idx = RadixIndex()
        tokens = list(range(64))  # 16 blocks at block_size 4
        hashes = compute_block_hashes(tokens, 4)
        for w in rng.sample(workers, 10):
            _store_chain(idx, w, hashes[: rng.randint(1, len(hashes))])
        r = _stub_router(idx, workers, shortlist_k=0, seed=seed)
        placement, got_hashes, scores, eligible, _runs = r._place(tokens)
        # Reference: the legacy pipeline, straight-line.
        ref_scores = idx.find_matches(hashes).scores
        ref_costs = _ref_costs(workers, 16, ref_scores, r.active,
                               r.scheduler.config)
        ref_rng = random.Random(seed)
        lo = min(ref_costs)
        best = [i for i, c in enumerate(ref_costs) if c == lo]
        ref_worker = workers[ref_rng.choice(best)]
        assert got_hashes == hashes
        assert scores == ref_scores
        assert eligible == workers
        assert placement.worker == ref_worker
        assert placement.overlap_blocks == min(ref_scores.get(ref_worker, 0), 16)
        assert placement.full_scan is True


def test_place_pruned_agrees_with_escape_hatch_on_shared_state():
    # Same fleet, same index, same rng seed: the pruned router's argmin
    # equals the escape hatch's whenever holders fit the shortlist.
    rng = random.Random(0xF00D)
    n = 150
    workers = list(range(1, n + 1))
    idx = RadixIndex()
    tokens = list(range(40))  # 10 blocks
    hashes = compute_block_hashes(tokens, 4)
    for w in rng.sample(workers, 6):
        _store_chain(idx, w, hashes[: rng.randint(2, len(hashes))])
    loads = rng.sample(range(0, 600), n)

    def build(k, seed):
        r = _stub_router(idx, workers, shortlist_k=k, seed=seed)
        for w, load in zip(workers, loads):
            r.active.add_request(f"r{w}", w, load, 0, 0)
        return r

    full, _, _, _, _ = build(0, 11)._place(tokens)
    pruned, _, _, _, _ = build(16, 11)._place(tokens)
    assert pruned.worker == full.worker
    assert pruned.overlap_blocks == full.overlap_blocks
    assert pruned.full_scan is False and full.full_scan is True


# -- index top-k shortlist ---------------------------------------------------


def test_radix_top_k_is_k_deepest_holders():
    idx = RadixIndex()
    chain = list(range(100, 112))
    rng = random.Random(42)
    # 30 workers holding random depths of the chain.
    depth_of = {}
    for w in range(1, 31):
        d = rng.randint(1, len(chain))
        _store_chain(idx, w, chain[:d])
        depth_of[w] = d
    full = idx.find_matches(chain).scores
    assert full == depth_of
    k = 5
    short = idx.find_matches(chain, top_k=k).scores
    assert len(short) == k
    assert all(short[w] == full[w] for w in short)
    worst_kept = min(short.values())
    dropped = [d for w, d in full.items() if w not in short]
    assert all(d <= worst_kept for d in dropped)
    # Fewer holders than k: identical key/value set as the full scan.
    assert idx.find_matches(chain, top_k=100).scores == full


def test_sharded_top_k_merges_across_shards():
    idx = ShardedRadixIndex(num_shards=3)
    try:
        chain = list(range(200, 210))
        rng = random.Random(43)
        depth_of = {}
        for w in range(1, 25):
            d = rng.randint(1, len(chain))
            _store_chain(idx, w, chain[:d])
            depth_of[w] = d
        idx.flush()
        full = idx.find_matches(chain).scores
        assert full == depth_of
        short = idx.find_matches(chain, top_k=4).scores
        assert len(short) == 4
        worst_kept = min(short.values())
        assert all(d <= worst_kept for w, d in full.items() if w not in short)
    finally:
        idx.close()


def test_approx_top_k_and_indexed_remove():
    ax = ApproxKvIndexer(ttl_s=60.0)
    chain = [1, 2, 3, 4]
    ax.record_routing(7, chain)
    ax.record_routing(8, chain[:2])
    ax.record_routing(9, chain[:1])
    assert ax.find_matches(chain).scores == {7: 4, 8: 2, 9: 1}
    short = ax.find_matches(chain, top_k=2).scores
    assert short == {7: 4, 8: 2}
    # remove_worker goes through the per-worker hash index.
    ax.remove_worker(7)
    assert ax.find_matches(chain).scores == {8: 2, 9: 1}
    ax.remove_worker(9)
    assert ax.find_matches(chain).scores == {8: 2}


def test_radix_remove_worker_batch_prunes_chain():
    idx = RadixIndex()
    chain = list(range(300, 340))
    _store_chain(idx, 1, chain)
    _store_chain(idx, 2, chain[:5])
    idx.remove_worker(1)
    assert idx.find_matches(chain).scores == {2: 5}
    assert idx.num_blocks(1) == 0
    idx.remove_worker(2)
    assert idx.find_matches(chain).scores == {}
    assert not idx._nodes  # fully pruned, no leaked nodes


# -- ActiveSequences fleet aggregates ----------------------------------------


def test_active_sequences_roster_aggregates():
    a = ActiveSequences()
    a.sync_roster([1, 2, 3, 4])
    assert a.roster_mean_load() == 0.0
    a.add_request("r1", 1, 10, 0, 0)
    a.add_request("r2", 2, 6, 2, 0)  # 4 new blocks
    a.add_request("r3", 3, 8, 0, 0)
    assert a.roster_mean_load() == (10 + 4 + 8 + 0) / 4
    assert a.least_loaded(2) == [4, 2]
    a.free("r1")
    assert a.least_loaded(2) == [1, 4]
    assert a.roster_mean_load() == (0 + 4 + 8 + 0) / 4
    # exclude skips but does not starve the result.
    assert a.least_loaded(2, exclude={4}) == [1, 2]
    a.remove_worker(4)
    assert a.roster_size() == 3
    assert a.least_loaded(3) == [1, 2, 3]
    # Resync with a new worker: heap rebuilt, totals exact.
    a.sync_roster([1, 2, 3, 9])
    assert a.least_loaded(2) == [1, 9]
    assert a.roster_mean_load() == (0 + 4 + 8 + 0) / 4


def test_active_sequences_heap_survives_churn():
    a = ActiveSequences()
    roster = list(range(50))
    a.sync_roster(roster)
    rng = random.Random(99)
    live = []
    for i in range(500):
        if live and rng.random() < 0.4:
            a.free(live.pop(rng.randrange(len(live))))
        else:
            w = rng.choice(roster)
            a.add_request(f"q{i}", w, rng.randint(1, 20), 0, 0)
            live.append(f"q{i}")
    loads = {w: a.active_blocks(w) for w in roster}
    want = sorted(roster, key=lambda w: (loads[w], w))[:1]
    got = a.least_loaded(1)
    assert loads[got[0]] == loads[want[0]]
    assert abs(a.roster_mean_load() - sum(loads.values()) / 50) < 1e-9


# -- the placement series at fleet scale -------------------------------------


@pytest.mark.parametrize("fleet", [64, 1000])
def test_placement_series_count_the_pruning_at_fleet_scale(fleet):
    """A seeded prefix-heavy trace through ``_place`` at 64 and 1,000
    engines, pruned against the full scan on the same index and loads:
    the same worker every time, ``router_candidates_considered`` grows by
    the whole fleet a placement without pruning and by at most k + m with
    it, ``router_place_seconds`` counts every placement, and no pruned
    placement falls back to the full scan."""
    from dynamo_tpu.kv_router.router import register_router_metrics
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    rng = random.Random(fleet)
    workers = list(range(1, fleet + 1))
    idx = RadixIndex()
    prefixes = []
    for t in range(12):
        tokens = [t * 10_000 + i for i in range(48)]  # 12 blocks at block_size 4
        hashes = compute_block_hashes(tokens, 4)
        for w in rng.sample(workers, rng.randint(1, 6)):
            _store_chain(idx, w, hashes[: rng.randint(3, len(hashes))])
        prefixes.append(tokens)
    loads = rng.sample(range(0, 8 * fleet), fleet)
    requests = [rng.choice(prefixes) + [rng.randrange(10**6) for _ in range(8)]
                for _ in range(200)]

    def run(k):
        r = _stub_router(idx, workers, shortlist_k=k, seed=5)
        reg = MetricsRegistry()
        r._m = register_router_metrics(reg)
        for w, load in zip(workers, loads):
            r.active.add_request(f"r{w}", w, load, 0, 0)
        placed = [r._place(tokens)[0] for tokens in requests]
        assert (f"dynamo_tpu_router_place_seconds_count {len(requests)}"
                in reg.render().splitlines())
        return placed, r._m

    full, full_m = run(0)
    pruned, pruned_m = run(16)
    assert [p.worker for p in pruned] == [p.worker for p in full]
    assert any(p.overlap_blocks >= 3 for p in pruned)  # holders win some
    assert all(p.full_scan for p in full) and not any(p.full_scan for p in pruned)
    k_m = 16 + KvSchedulerConfig().least_loaded_m
    assert full_m["candidates_considered"].value() == fleet * len(requests)
    assert 0 < pruned_m["candidates_considered"].value() <= k_m * len(requests)
    assert pruned_m["shortlist_fallback"].value() == 0
