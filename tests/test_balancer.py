"""Fleet balancer decision core + shell, and migration-aware pricing.

Three sections:

- :class:`BalancerLaw` units — the pure decision core (the SAME code the
  production FleetBalancer runs), driven
  with an injected clock so every stability gate (hysteresis, per-pair
  cooldown, destination settling / ping-pong suppression) is exercised
  deterministically.
- :class:`FleetBalancer` shell over fake seams (pools / load_source /
  mover) — actuation outcomes, refused/error handling, unreachable
  -engine skipping.
- ``KvScheduler._priced_loads`` — the router-side composition: with a
  balancer running, decode load above the fleet mean is transient, so
  cache affinity wins placements it would otherwise lose.
"""

import asyncio
import math
import random
from types import SimpleNamespace

import pytest

from dynamo_tpu.kv_router.indexer import OverlapScores
from dynamo_tpu.kv_router.scheduler import KvScheduler, KvSchedulerConfig
from dynamo_tpu.kv_router.sequence import ActiveSequences
from dynamo_tpu.planner.actions import POOL_DECODE
from dynamo_tpu.planner.balancer import (
    REASON_HOT_SPOT,
    REASON_KV_PRESSURE,
    BalancerConfig,
    BalancerLaw,
    EngineLoad,
    FleetBalancer,
)


def load(iid, active=0, slots=4, waiting=0, kv=0.0):
    return EngineLoad(
        instance_id=iid, active=active, slots=slots, waiting=waiting, kv_usage=kv
    )


HOT = dict(active=4, waiting=4, kv=0.9)    # score 0.5 + 0.27 + 0.2 = 0.97
COLD = dict()                              # score 0.0


# -- BalancerLaw: scoring ----------------------------------------------------


def test_score_blends_batch_kv_queue():
    law = BalancerLaw()
    # batch 2/4, kv 0.5, queue 1/4 → 0.5*0.5 + 0.3*0.5 + 0.2*0.25
    assert abs(law.score(load(1, active=2, waiting=1, kv=0.5)) - 0.45) < 1e-9
    # Each term clamps to [0, 1] — a deep queue can't push the score
    # past the blend's ceiling, zero slots can't divide by zero.
    assert law.score(load(1, active=99, slots=0, waiting=99, kv=2.0)) <= 1.0


def test_single_engine_never_moves():
    law = BalancerLaw(BalancerConfig(hysteresis_cycles=1))
    assert law.decide([load(1, **HOT)], now=0.0) == []


# -- BalancerLaw: saturate → shed → steady -----------------------------------


def test_saturate_shed_steady():
    law = BalancerLaw()  # hysteresis_cycles=2
    hot_cold = [load(1, **HOT), load(2, **COLD)]
    # Cycle 1: the pair wins but must hold for hysteresis_cycles.
    assert law.decide(hot_cold, now=0.0) == []
    assert law.state.holds.get("hysteresis") == 1
    # Cycle 2: shed.
    moves = law.decide(hot_cold, now=1.0)
    assert len(moves) == 1
    m = moves[0]
    assert (m.src, m.dst) == (1, 2)
    assert m.reason == REASON_KV_PRESSURE  # kv 0.9 ≥ kv_pressure
    assert m.src_score > m.dst_score
    law.notify_actuated(m, now=1.0)
    # Same snapshot immediately after: the pair is frozen (cooldown) —
    # no second shed even though the scores still claim hot/cold.
    assert law.decide(hot_cold, now=1.1) == []
    assert law.state.holds.get("cooldown", 0) >= 1
    # Loads even out: steady state, nothing proposed, ever.
    even = [load(1, active=2, kv=0.4), load(2, active=2, kv=0.4)]
    for t in range(40, 80):
        assert law.decide(even, now=float(t)) == []
    assert law.state.moves_actuated == 1


def test_symmetric_load_never_oscillates():
    law = BalancerLaw(BalancerConfig(hysteresis_cycles=1))
    # Two equally HOT engines: a source exists but no destination is
    # below idle — the law holds rather than shuffling load in circles.
    both_hot = [load(1, **HOT), load(2, **HOT)]
    for t in range(20):
        assert law.decide(both_hot, now=float(t)) == []
    assert law.state.holds.get("no_destination", 0) >= 20
    assert law.state.moves_proposed == 0


def test_min_gap_gates_marginal_pairs():
    # src 0.85 (kv below kv_pressure), dst 0.34: gap 0.51 < min_gap 0.6.
    cfg = BalancerConfig(min_gap=0.6, hysteresis_cycles=1)
    law = BalancerLaw(cfg)
    loads = [load(1, active=4, waiting=4, kv=0.5), load(2, active=2, kv=0.3)]
    assert law.decide(loads, now=0.0) == []
    assert law.state.holds.get("no_destination") == 1
    # KV pressure bypasses min_gap: same batch picture, KV at 0.95 —
    # proactive defrag moves BEFORE the preemption boundary forces it.
    law2 = BalancerLaw(cfg)
    loads[0] = load(1, active=4, waiting=4, kv=0.95)
    moves = law2.decide(loads, now=0.0)
    assert len(moves) == 1 and moves[0].reason == REASON_KV_PRESSURE


def test_kv_pressure_qualifies_a_batch_cold_source():
    # Batch-cold (score 0.41 < saturation) but KV-hot: still a source.
    law = BalancerLaw(BalancerConfig(hysteresis_cycles=1))
    loads = [load(1, active=1, kv=0.95), load(2, **COLD)]
    moves = law.decide(loads, now=0.0)
    assert len(moves) == 1 and moves[0].reason == REASON_KV_PRESSURE


def test_plain_hot_spot_reason():
    law = BalancerLaw(BalancerConfig(hysteresis_cycles=1))
    loads = [load(1, active=4, waiting=4, kv=0.5), load(2, **COLD)]
    moves = law.decide(loads, now=0.0)
    assert len(moves) == 1 and moves[0].reason == REASON_HOT_SPOT


# -- BalancerLaw: stability gates --------------------------------------------


def test_hysteresis_needs_consecutive_cycles():
    law = BalancerLaw(BalancerConfig(hysteresis_cycles=2))
    hot_cold = [load(1, **HOT), load(2, **COLD)]
    even = [load(1, active=2, kv=0.4), load(2, active=2, kv=0.4)]
    assert law.decide(hot_cold, now=0.0) == []   # count 1
    assert law.decide(even, now=1.0) == []       # pair gone → momentum reset
    assert law.decide(hot_cold, now=2.0) == []   # count restarts at 1
    assert len(law.decide(hot_cold, now=3.0)) == 1


def test_pair_cooldown_blocks_both_directions():
    cfg = BalancerConfig(
        hysteresis_cycles=1, pair_cooldown_s=30.0, settle_s=0.0
    )
    law = BalancerLaw(cfg)
    [m] = law.decide([load(1, **HOT), load(2, **COLD)], now=0.0)
    law.notify_actuated(m, now=0.0)
    # The REVERSE pair (2 → 1) is frozen too: even if the destination
    # flips hot (settling disabled here to isolate the cooldown gate),
    # the sequence cannot bounce straight back.
    flipped = [load(2, **HOT), load(1, **COLD)]
    assert law.decide(flipped, now=1.0) == []
    assert law.state.holds.get("cooldown", 0) >= 1
    # Past the window the pair thaws.
    assert len(law.decide(flipped, now=31.0)) == 1


def test_settling_destination_suppresses_pingpong():
    cfg = BalancerConfig(
        hysteresis_cycles=1, pair_cooldown_s=0.0, settle_s=30.0
    )
    law = BalancerLaw(cfg)
    [m] = law.decide([load(1, **HOT), load(2, **COLD)], now=0.0)
    law.notify_actuated(m, now=0.0)
    # Engine 2 just RECEIVED a sequence; cooldown is disabled here, so
    # only the settle gate stands between the moved sequence and an
    # immediate bounce to a third engine — it must hold.
    flipped = [load(2, **HOT), load(1, **COLD), load(3, **COLD)]
    assert law.decide(flipped, now=1.0) == []
    assert law.state.pingpong_suppressed == 1
    assert law.state.holds.get("settling") == 1
    # After the settle window the move is legitimate load-shedding.
    assert len(law.decide(flipped, now=31.0)) == 1


def test_failed_move_restarts_hysteresis_without_cooldown():
    law = BalancerLaw(BalancerConfig(hysteresis_cycles=2))
    hot_cold = [load(1, **HOT), load(2, **COLD)]
    law.decide(hot_cold, now=0.0)
    [m] = law.decide(hot_cold, now=1.0)
    law.notify_failed(m)
    # No cooldown opened — the balancer may retry — but the pair must
    # re-win hysteresis from scratch (no hammering within one cycle).
    assert law.decide(hot_cold, now=2.0) == []
    assert law.state.holds.get("cooldown", 0) == 0
    [m2] = law.decide(hot_cold, now=3.0)
    assert (m2.src, m2.dst) == (1, 2)


def test_forget_drops_departed_engine_state():
    law = BalancerLaw(BalancerConfig(hysteresis_cycles=1))
    [m] = law.decide([load(1, **HOT), load(2, **COLD)], now=0.0)
    law.notify_actuated(m, now=0.0)
    law.decide([load(1, **HOT), load(2, **COLD)], now=1.0)  # repopulate pending
    law.forget(2)
    assert all(2 not in p for p in law._pair_cooldown_until)
    assert all(2 not in p for p in law._pending)
    assert 2 not in law._settle_until


def test_max_moves_per_cycle_pairs_disjoint_engines():
    law = BalancerLaw(BalancerConfig(hysteresis_cycles=1, max_moves_per_cycle=2))
    loads = [load(1, **HOT), load(2, **HOT), load(3, **COLD), load(4, **COLD)]
    moves = law.decide(loads, now=0.0)
    assert len(moves) == 2
    touched = [m.src for m in moves] + [m.dst for m in moves]
    assert len(set(touched)) == 4  # no engine on both sides of a cycle
    # Default cap of 1: same picture sheds one pair per cycle.
    law1 = BalancerLaw(BalancerConfig(hysteresis_cycles=1))
    assert len(law1.decide(loads, now=0.0)) == 1


# -- FleetBalancer shell over fake seams -------------------------------------


def snapshot(active=0, slots=4, waiting=0, kv=0.0):
    """ForwardPassMetrics-shaped fake (load_from_metrics reads these)."""
    return SimpleNamespace(
        worker=SimpleNamespace(
            request_active_slots=active, request_total_slots=slots,
            num_requests_waiting=waiting,
        ),
        kv=SimpleNamespace(gpu_cache_usage_perc=kv),
    )


def make_shell(snaps, mover, clock=lambda: 0.0, cfg=None):
    async def pools():
        return {POOL_DECODE: [SimpleNamespace(instance_id=i) for i in snaps]}

    async def load_source(instance_id):
        snap = snaps[instance_id]
        if isinstance(snap, Exception):
            raise snap
        return snap

    law = BalancerLaw(cfg or BalancerConfig(hysteresis_cycles=1))
    return FleetBalancer(law, pools, load_source, mover, clock=clock)


def test_shell_actuates_and_freezes_pair():
    async def go():
        calls = []

        async def mover(src, dst):
            calls.append((src, dst))
            return {"ok": True, "handle": "mig-x"}

        snaps = {1: snapshot(active=4, waiting=4, kv=0.9), 2: snapshot()}
        now = [0.0]
        fb = make_shell(snaps, mover, clock=lambda: now[0])
        moves = await fb.step()
        assert len(moves) == 1 and calls == [(1, 2)]
        assert fb.moves_done == [(moves[0], "ok")]
        # The success opened the cooldown: the identical picture one
        # tick later proposes nothing.
        now[0] = 0.1
        assert await fb.step() == []
        st = fb.status()
        assert st["moves_proposed"] == 1 and st["moves_actuated"] == 1
        assert st["pingpong_suppressed"] == 0

    asyncio.run(go())


def test_shell_refusal_and_error_never_open_cooldown():
    async def go():
        replies = [
            {"ok": False, "reason": "paced"},   # typed refusal (bandwidth cap)
            RuntimeError("dest vanished"),      # chaos-shaped hard failure
            {"ok": True},
        ]

        async def mover(src, dst):
            r = replies.pop(0)
            if isinstance(r, Exception):
                raise r
            return r

        snaps = {1: snapshot(active=4, waiting=4, kv=0.9), 2: snapshot()}
        fb = make_shell(snaps, mover)
        assert await fb.step() != []   # refused
        assert await fb.step() != []   # errored — hysteresis restarted, no freeze
        assert await fb.step() != []   # third try lands
        outcomes = [o for _, o in fb.moves_done]
        assert outcomes == ["refused", "error", "ok"]
        st = fb.status()
        assert st["moves_proposed"] == 3 and st["moves_actuated"] == 1
        assert fb.law.state.holds.get("cooldown", 0) == 0

    asyncio.run(go())


def test_shell_publishes_status_every_cycle():
    async def go():
        async def mover(src, dst):
            return {"ok": True}

        published = []

        async def publisher(status):
            published.append(status)

        snaps = {1: snapshot(active=4, waiting=4, kv=0.9), 2: snapshot()}
        fb = make_shell(snaps, mover)
        fb.publisher = publisher
        await fb.step()
        assert published and published[-1]["moves_actuated"] == 1
        # A broken sink never stalls rebalancing (GET /fleet is advisory).
        async def bad(status):
            raise OSError("store down")

        fb.publisher = bad
        await fb.step()  # must not raise
        assert fb.status()["moves_proposed"] == 1  # cooldown held cycle 2

    asyncio.run(go())


def test_shell_skips_unreachable_engines():
    async def go():
        async def mover(src, dst):  # pragma: no cover — must not be called
            raise AssertionError("moved with an unreachable peer")

        # Engine 2's load pull fails: it is neither source nor
        # destination this cycle, and one reachable engine can't shed.
        snaps = {1: snapshot(active=4, waiting=4, kv=0.9),
                 2: TimeoutError("load_metrics timed out")}
        fb = make_shell(snaps, mover)
        assert await fb.step() == []
        loads = await fb.observe()
        assert [l.instance_id for l in loads] == [1]

    asyncio.run(go())


# -- KvScheduler._priced_loads: migration-aware placement --------------------


def test_priced_loads_off_by_default_and_for_single_worker():
    sched = KvScheduler(KvSchedulerConfig())
    assert sched._priced_loads([12, 0]) == [12.0, 0.0]
    sched2 = KvScheduler(KvSchedulerConfig(migrate_cost_blocks=1.0))
    assert sched2._priced_loads([12]) == [12.0]


def test_priced_loads_caps_excess_at_mean_plus_migration():
    sched = KvScheduler(KvSchedulerConfig(migrate_cost_blocks=1.0))
    # mean 6 → cap 7: the loaded worker's excess is priced as "admit
    # here, shed later", the idle worker is untouched.
    assert sched._priced_loads([12, 0]) == [7.0, 0.0]


def test_migration_pricing_lets_cache_affinity_win():
    # Worker 1 holds the FULL prefix but is loaded; worker 2 is cold and
    # idle. At face value the load dominates and the prefix is wasted;
    # with a balancer running the load is transient, so affinity wins.
    overlaps = OverlapScores(scores={1: 8})
    active = ActiveSequences()
    active.add_request("r1", 1, total_blocks=12, overlap_blocks=0,
                       prompt_tokens=48)
    face = KvScheduler(KvSchedulerConfig(router_temperature=0.0))
    assert face.schedule([1, 2], 8, overlaps, active).worker == 2
    priced = KvScheduler(KvSchedulerConfig(
        router_temperature=0.0, migrate_cost_blocks=1.0
    ))
    placement = priced.schedule([1, 2], 8, overlaps, active)
    assert placement.worker == 1 and placement.overlap_blocks == 8


# -- BalancerLaw over a seeded day of skewed load ----------------------------


def _replay_day(seed: int, balance: bool):
    """A 24-engine decode fleet under ten minutes of seeded load whose hot
    spot walks round the fleet (sticky placement piles arrivals on one
    engine at a time). Each one-second tick the production law sees the
    fleet's load snapshots on an injected clock; an actuated move takes
    the source's newest sequence to the destination. → (moves as (t, seq,
    src, dst), ticks x engines a sequence spent queued behind a full batch)."""
    rng = random.Random(seed)
    n, slots, ticks = 24, 8, 600
    law = BalancerLaw(BalancerConfig(
        hysteresis_cycles=2, pair_cooldown_s=30.0, settle_s=30.0,
        max_moves_per_cycle=2,
    ))
    resident: dict[int, list[list[int]]] = {e: [] for e in range(n)}  # [seq, left]
    moves, queued, next_seq = [], 0, 0
    for t in range(ticks):
        hot = (t // 60 * 7) % n
        rate = 1.2 + 0.8 * math.sin(2 * math.pi * t / ticks)
        for _ in range(int(rate) + (rng.random() < rate % 1)):
            e = hot if rng.random() < 0.7 else rng.randrange(n)
            resident[e].append([next_seq, rng.randint(20, 60)])
            next_seq += 1
        loads = []
        for e, seqs in resident.items():
            running = seqs[:slots]
            queued += len(seqs) - len(running)
            loads.append(EngineLoad(
                instance_id=e, active=len(running), slots=slots,
                waiting=len(seqs) - len(running),
                kv_usage=min(1.0, len(seqs) / (2 * slots)),
            ))
            for s in running:
                s[1] -= 1
            resident[e] = [s for s in seqs if s[1] > 0]
        if not balance:
            continue
        for m in law.decide(loads, now=float(t)):
            if not resident[m.src]:
                law.notify_failed(m)
                continue
            seq = resident[m.src].pop()
            resident[m.dst].append(seq)
            law.notify_actuated(m, now=float(t))
            moves.append((t, seq[0], m.src, m.dst))
    return moves, queued, law


@pytest.mark.parametrize("seed", [11, 2026, 90210])
def test_law_over_a_seeded_day_sheds_hot_spots_and_never_pingpongs(seed):
    """What the deleted diurnal simulator's balancer arm asserted, on the
    law itself: it actuates on a skewed day, no sequence is moved twice
    inside the settle/cooldown window, no engine that just received a
    sequence sheds one inside it, every move runs downhill by at least
    the gap, and the fleet queues no more than without a balancer."""
    moves, queued, law = _replay_day(seed, balance=True)
    _, queued_static, _ = _replay_day(seed, balance=False)
    assert len(moves) >= 1
    window = min(law.cfg.settle_s, law.cfg.pair_cooldown_s)
    last_moved: dict[int, int] = {}
    last_received: dict[int, int] = {}
    for t, seq, src, dst in moves:
        assert src != dst
        assert t - last_moved.get(seq, -10**9) >= window, (seq, t)
        assert t - last_received.get(src, -10**9) >= window, (src, t)
        last_moved[seq] = t
        last_received[dst] = t
    assert law.state.moves_proposed >= len(moves)
    assert queued <= queued_static
