"""Closed-loop autoscaler units: the ControlLaw's decisions (hysteresis,
cooldown, clamps, pool-move direction), the SlaAutoscaler shell's
journal/metrics accounting, and the satellite clamp audit — empty
windows, cold starts, non-finite inputs and beyond-profile operating
points must produce explicit Holds, never NaN/negative pool sizes
(docs/autoscaler.md)."""

import asyncio
import math
import random

import numpy as np
import pytest

from dynamo_tpu.planner import (
    DecodeInterpolator,
    PrefillInterpolator,
    interpolators_from_card_dict,
    profile_as_card_dict,
)
from dynamo_tpu.planner.actions import (
    KIND_POOL_MOVE,
    POOL_DECODE,
    POOL_PREFILL,
    ActionJournal,
    FleetResize,
    Hold,
    PoolMove,
    ReplicaScale,
    ScaleActionError,
)
from dynamo_tpu.planner.actuate import RecordingActuator
from dynamo_tpu.planner.core import PlannerObservation
from dynamo_tpu.planner.operator import (
    ControlLaw,
    OperatorConfig,
    SlaAutoscaler,
    register_planner_metrics,
)

pytestmark = pytest.mark.unit


def interps():
    dec = DecodeInterpolator(
        np.array([1, 16, 32]), np.array([5.0, 15.0, 30.0]),
        np.array([200.0, 1070.0, 1070.0]),
    )
    pre = PrefillInterpolator(
        np.array([64, 512]), np.array([50.0, 200.0]),
        np.array([1280.0, 2560.0]),
    )
    return dec, pre


def law(**kw) -> ControlLaw:
    defaults = dict(
        itl_sla_ms=20.0, ttft_sla_ms=300.0, mean_input_tokens=256.0,
        mean_output_tokens=64.0, predictor="constant", max_engines=6,
        hysteresis_cycles=2, cooldown_s=10.0, replica_scaling=False,
    )
    defaults.update(kw)
    dec, pre = interps()
    return ControlLaw(OperatorConfig(**defaults), dec, pre)


def actions_of(decisions, cls):
    return [d for d in decisions if isinstance(d, cls)]


def test_empty_window_is_explicit_hold_and_clears_momentum():
    lw = law()
    # Build one cycle of pool-move momentum...
    breach = PlannerObservation(request_rate=5.0, ttft_ms=900.0, itl_ms=5.0)
    d1 = lw.decide(breach, prefill_n=1, decode_n=3, now=0.0)
    assert actions_of(d1, Hold) and lw.state.proposals.get(KIND_POOL_MOVE) == 1
    # ...an empty window must hold AND drop it.
    d2 = lw.decide(PlannerObservation(empty_window=True), 1, 3, now=5.0)
    assert [h.reason for h in actions_of(d2, Hold)] == ["empty_window"]
    assert KIND_POOL_MOVE not in lw.state.proposals
    # The breach must re-earn its full hysteresis run.
    d3 = lw.decide(breach, 1, 3, now=10.0)
    assert not actions_of(d3, PoolMove)


def test_nonfinite_observation_clamps_to_hold():
    lw = law()
    for bad in (
        PlannerObservation(request_rate=float("nan")),
        PlannerObservation(request_rate=float("inf")),
        PlannerObservation(request_rate=-3.0),
    ):
        d = lw.decide(bad, 1, 3, now=0.0)
        assert [h.reason for h in actions_of(d, Hold)] == ["empty_window"]
    # Junk latency with a sane rate: latency is ignored, never NaN math.
    d = lw.decide(
        PlannerObservation(request_rate=2.0, ttft_ms=float("nan"), itl_ms=-1.0),
        1, 3, now=0.0,
    )
    for a in d:
        assert isinstance(a, (Hold, PoolMove, ReplicaScale, FleetResize))


def test_targets_never_negative_or_nan_even_beyond_profile():
    lw = law()
    lw.state.last_prediction = 1e12  # absurd predicted rate
    p, d = lw.targets(PlannerObservation(request_rate=1e12), 1, 3)
    assert 1 <= p <= lw.cfg.max_engines and 1 <= d <= lw.cfg.max_engines
    lw.state.last_prediction = 0.0
    p, d = lw.targets(PlannerObservation(), 1, 3)
    assert p >= 1 and d >= 1
    # Beyond-profile prompt lengths clamp to endpoint capacity (np.interp
    # semantics) — finite, positive, in bounds.
    obs = PlannerObservation(request_rate=5.0, input_token_rate=5.0 * 10_000)
    lw.state.last_prediction = 5.0
    p, d = lw.targets(obs, 1, 3)
    assert 1 <= p <= lw.cfg.max_engines


def test_interpolators_reject_nonfinite_profiles():
    with pytest.raises(ValueError):
        DecodeInterpolator(
            np.array([1.0, 2.0]), np.array([5.0, float("nan")]),
            np.array([10.0, 20.0]),
        )
    with pytest.raises(ValueError):
        PrefillInterpolator(
            np.array([64.0, float("inf")]), np.array([50.0, 60.0]),
            np.array([10.0, 20.0]),
        )


def test_idle_scale_down_needs_consecutive_idle_cycles():
    lw = law(idle_cycles_for_scale_down=3)
    idle = PlannerObservation(request_rate=0.0)
    assert [h.reason for h in actions_of(lw.decide(idle, 2, 4, now=0.0), Hold)] == ["idle_settling"]
    assert [h.reason for h in actions_of(lw.decide(idle, 2, 4, now=5.0), Hold)] == ["idle_settling"]
    # Third consecutive idle window may begin acting (still gated by
    # hysteresis); a busy window in between resets the count.
    lw2 = law(idle_cycles_for_scale_down=3)
    lw2.decide(idle, 2, 4, now=0.0)
    lw2.decide(PlannerObservation(request_rate=5.0, itl_ms=5.0), 2, 4, now=5.0)
    assert lw2.state.idle_cycles == 0


def test_pool_move_direction_and_donor_guard():
    lw = law(hysteresis_cycles=1)
    # TTFT breach + decode headroom → decode donates to prefill.
    obs = PlannerObservation(request_rate=5.0, ttft_ms=900.0, itl_ms=5.0)
    d = lw.decide(obs, 1, 3, now=0.0)
    moves = actions_of(d, PoolMove)
    assert moves and moves[0].src == POOL_DECODE and moves[0].dst == POOL_PREFILL
    # ITL breach + prefill headroom → prefill donates to decode.
    lw2 = law(hysteresis_cycles=1)
    obs2 = PlannerObservation(request_rate=5.0, ttft_ms=50.0, itl_ms=80.0)
    d2 = lw2.decide(obs2, 3, 1, now=0.0)
    moves2 = actions_of(d2, PoolMove)
    assert moves2 and moves2[0].src == POOL_PREFILL and moves2[0].dst == POOL_DECODE
    # Donor at its own demand: both breached → contended hold, no move.
    lw3 = law(hysteresis_cycles=1)
    obs3 = PlannerObservation(
        request_rate=40.0, ttft_ms=900.0, itl_ms=80.0,
        input_token_rate=40.0 * 512, output_token_rate=40.0 * 64,
    )
    d3 = lw3.decide(obs3, 1, 1, now=0.0)
    assert not actions_of(d3, PoolMove)


def test_hysteresis_requires_consecutive_agreeing_cycles():
    lw = law(hysteresis_cycles=3)
    obs = PlannerObservation(request_rate=5.0, ttft_ms=900.0, itl_ms=5.0)
    assert not actions_of(lw.decide(obs, 1, 3, now=0.0), PoolMove)
    assert not actions_of(lw.decide(obs, 1, 3, now=5.0), PoolMove)
    assert actions_of(lw.decide(obs, 1, 3, now=10.0), PoolMove)


def test_cooldown_blocks_back_to_back_actions():
    lw = law(hysteresis_cycles=1, cooldown_s=30.0)
    obs = PlannerObservation(request_rate=5.0, ttft_ms=900.0, itl_ms=5.0)
    assert actions_of(lw.decide(obs, 1, 4, now=0.0), PoolMove)
    lw.notify_actuated(KIND_POOL_MOVE, now=1.0)
    d = lw.decide(obs, 2, 3, now=5.0)  # still breached, inside cooldown
    assert not actions_of(d, PoolMove)
    assert lw.state.holds.get("cooldown", 0) >= 1
    # Past the cooldown the proposal can fire again.
    assert actions_of(lw.decide(obs, 2, 3, now=40.0), PoolMove)


def test_replica_scaling_up_and_down_with_bounds():
    lw = law(replica_scaling=True, hysteresis_cycles=1, max_engines=6,
             scale_down_headroom=1.0)
    # Demand far above 1+1 workers → scale up (never beyond max_engines).
    obs = PlannerObservation(
        request_rate=50.0, itl_ms=5.0, ttft_ms=50.0,
        input_token_rate=50.0 * 256, output_token_rate=50.0 * 64,
    )
    d = lw.decide(obs, 1, 1, now=0.0)
    scales = actions_of(d, ReplicaScale)
    assert scales and scales[0].target > scales[0].current
    assert scales[0].target <= 6
    # Idle long enough → scale down toward minimums, never below 1.
    lw2 = law(replica_scaling=True, hysteresis_cycles=1,
              idle_cycles_for_scale_down=1, scale_down_headroom=1.0)
    idle = PlannerObservation(request_rate=0.001)
    d2 = lw2.decide(idle, 3, 3, now=0.0)
    scales2 = actions_of(d2, ReplicaScale)
    assert scales2 and scales2[0].target < scales2[0].current
    assert scales2[0].target >= 1


def test_fleet_resize_decision():
    lw = law(hysteresis_cycles=1, fleet_child_rps=10.0, max_fleet=4)
    obs = PlannerObservation(request_rate=35.0, itl_ms=5.0, ttft_ms=50.0)
    d = lw.decide(obs, 1, 3, fleet_n=2, now=0.0)
    resizes = actions_of(d, FleetResize)
    assert resizes and resizes[0].target == 4  # ceil(35/10) = 4
    # Scale-down honors headroom.
    lw2 = law(hysteresis_cycles=1, fleet_child_rps=10.0, scale_down_headroom=1.5)
    obs2 = PlannerObservation(request_rate=14.0, itl_ms=5.0, ttft_ms=50.0)
    d2 = lw2.decide(obs2, 1, 3, fleet_n=2, now=0.0)
    assert not actions_of(d2, FleetResize)  # 14*1.5 > 1*10 → hold at 2


def test_autoscaler_shell_actuates_journals_and_counts():
    async def go():
        from dynamo_tpu.runtime.metrics import MetricsRegistry
        from dynamo_tpu.runtime.store import connect_store

        store = await connect_store("memory://autoscaler-shell")
        lease = await store.grant_lease(30)
        act = RecordingActuator(prefill=1, decode=3)
        obs_q = [
            PlannerObservation(request_rate=5.0, ttft_ms=900.0, itl_ms=5.0)
            for _ in range(3)
        ]

        async def observe():
            return obs_q.pop(0)

        reg = MetricsRegistry()
        metrics = register_planner_metrics(reg)
        auto = SlaAutoscaler(
            law(cooldown_s=0.0), observe, pool_actuator=act,
            journal=ActionJournal(store, "t", lease), metrics=metrics,
        )
        for _ in range(3):
            await auto.step()
        entries = await auto.journal.entries()
        return act, metrics, entries, reg.render()

    act, metrics, entries, exposition = asyncio.run(go())
    assert ("move", POOL_DECODE, POOL_PREFILL) in act.calls
    assert metrics["actions"].value(kind="pool_move", outcome="ok") == 1
    assert any(e["phase"] == "ok" and e["kind"] == "pool_move" for e in entries)
    assert "planner_pool_size" in exposition
    assert "planner_decision_lag_seconds" in exposition


def test_autoscaler_shell_survives_actuation_failure():
    async def go():
        act = RecordingActuator(prefill=1, decode=3)
        act.fail_next = ScaleActionError("injected")
        obs = PlannerObservation(request_rate=5.0, ttft_ms=900.0, itl_ms=5.0)

        async def observe():
            return obs

        from dynamo_tpu.runtime.metrics import MetricsRegistry

        reg = MetricsRegistry()
        metrics = register_planner_metrics(reg)
        auto = SlaAutoscaler(
            law(hysteresis_cycles=1, cooldown_s=0.0), observe,
            pool_actuator=act, metrics=metrics,
        )
        await auto.step()  # fails
        await auto.step()  # retries and succeeds
        return act, metrics, auto

    act, metrics, auto = asyncio.run(go())
    assert metrics["actions"].value(kind="pool_move", outcome="error") == 1
    assert metrics["actions"].value(kind="pool_move", outcome="ok") == 1
    assert [o for _, o in auto.actions_done] == ["error", "ok"]


def test_journal_is_lease_attached_and_bounded():
    async def go():
        from dynamo_tpu.runtime.store import connect_store

        store = await connect_store("memory://journal-bound")
        lease = await store.grant_lease(30)
        j = ActionJournal(store, "op", lease, keep=4)
        for i in range(10):
            seq = await j.record_intent(
                PoolMove(worker=f"w{i}", instance_id=i,
                         src=POOL_DECODE, dst=POOL_PREFILL)
            )
            await j.record_outcome(
                seq, PoolMove(worker=f"w{i}", instance_id=i,
                              src=POOL_DECODE, dst=POOL_PREFILL), "ok"
            )
        entries = await j.entries()
        assert len(entries) <= 5  # keep window (+ the in-flight slot)
        # Lease revocation reaps the whole journal — a dead operator
        # leaks no planner/ keys.
        await store.revoke_lease(lease)
        return await store.get_prefix("planner/")

    assert asyncio.run(go()) == []


def test_planner_observation_sanitize_and_empty_window():
    obs = PlannerObservation(
        request_rate=float("nan"), output_token_rate=-5.0,
        ttft_ms=float("inf"), itl_ms=20.0,
    ).sanitize()
    assert obs.request_rate == 0.0 and obs.output_token_rate == 0.0
    assert obs.ttft_ms is None and obs.itl_ms == 20.0
    assert obs.empty_window
    ok = PlannerObservation(request_rate=2.0, itl_ms=10.0).sanitize()
    assert not ok.empty_window and math.isfinite(ok.request_rate)


def test_planner_cold_start_holds_replicas():
    """A restarted Planner's first (empty) scrape window must not read
    rate 0.0 and scale a loaded fleet to min_replicas."""
    from dynamo_tpu.planner import Planner, PlannerConfig, RecordingConnector

    async def go():
        conn = RecordingConnector({"backend": 5})
        obs_q = [
            PlannerObservation(empty_window=True),       # cold-start scrape
            PlannerObservation(request_rate=40.0),        # real window
        ]

        async def source():
            return obs_q.pop(0)

        cfg = PlannerConfig(
            component="backend", predictor="constant", min_replicas=1,
            max_replicas=8, replica_tok_s=1000.0, mean_output_tokens=100.0,
            scale_down_headroom=1.0,
        )
        planner = Planner(cfg, conn, source)
        first = await planner.step()
        calls_after_cold = list(conn.calls)
        second = await planner.step()
        return first, second, calls_after_cold

    first, second, calls_after_cold = asyncio.run(go())
    assert first == 5, "cold start must hold the current replica count"
    assert calls_after_cold == [], "cold start must issue no connector calls"
    assert second == 4  # 4000 tok/s / 1000 per replica


def test_http_metrics_source_marks_first_scrape_empty():
    from dynamo_tpu.planner.core import HttpMetricsSource

    src = HttpMetricsSource("http://unused")
    assert src._last is None
    # The parse path marks the first differencing window empty; the
    # instance state transition is what step() keys off.
    obs = PlannerObservation(empty_window=src._last is None)
    assert obs.empty_window


def test_sla_profile_card_roundtrip():
    dec, pre = interps()
    d = profile_as_card_dict(decode=dec, prefill=pre)
    # Survives msgpack-style plain-JSON structure (lists, floats).
    import json

    d = json.loads(json.dumps(d))
    dec2, pre2 = interpolators_from_card_dict(d)
    assert dec2.itl_at(16) == dec.itl_at(16)
    assert pre2.ttft_at(128) == pre.ttft_at(128)
    # Malformed payloads degrade to (None, None), never raise.
    assert interpolators_from_card_dict(None) == (None, None)
    assert interpolators_from_card_dict({"d_batch": [1], "d_itl": "junk"}) == (None, None)
    assert interpolators_from_card_dict(
        {"d_batch": [1.0, 2.0], "d_itl": [1.0, float("nan")], "d_tok": [1.0, 2.0]}
    ) == (None, None)


def test_model_card_ships_sla_profile():
    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    dec, pre = interps()
    card = ModelDeploymentCard(
        name="m", sla_profile=profile_as_card_dict(decode=dec, prefill=pre)
    )
    card2 = ModelDeploymentCard.from_bytes(card.to_bytes())
    dec2, pre2 = interpolators_from_card_dict(card2.sla_profile)
    assert dec2 is not None and pre2 is not None
    assert dec2.throughput_at(16) == dec.throughput_at(16)
    # Cards without a profile stay byte-identical to the old wire shape
    # minus the new null field.
    bare = ModelDeploymentCard(name="m")
    assert ModelDeploymentCard.from_bytes(bare.to_bytes()).sla_profile is None


def test_worker_card_profile_discovery_end_to_end(tmp_path):
    """Satellite (ROADMAP 2c): the worker embeds its profiled npz in the
    model card (--sla-profile), discovery surfaces it to the frontend's
    on_card hook, and the planner's discover_card_profile finds it."""
    from dynamo_tpu.planner import save_profile
    from dynamo_tpu.planner.__main__ import discover_card_profile
    from dynamo_tpu.worker.__main__ import build_engine, parse_args

    dec, pre = interps()
    path = str(tmp_path / "prof.npz")
    save_profile(path, decode=dec, prefill=pre)

    async def go():
        from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
        from dynamo_tpu.llm.model_card import register_model
        from dynamo_tpu.runtime.distributed import DistributedRuntime

        args = parse_args([
            "--engine", "mocker", "--sla-profile", path,
            "--model-name", "profiled-model",
        ])
        engine, card = await build_engine(args)
        assert card.sla_profile and "d_batch" in card.sla_profile

        url = "memory://card-profile"
        wrt = await DistributedRuntime.create(store_url=url)
        await register_model(wrt, "dynamo", card)

        # Frontend side: the on_card hook sees the profile via discovery.
        frt = await DistributedRuntime.create(store_url=url)
        seen = {}

        def on_card(c):
            d2, p2 = interpolators_from_card_dict(c.sla_profile)
            seen["decode"], seen["prefill"] = d2, p2

        manager = ModelManager(frt, on_card=on_card)
        watcher = await ModelWatcher(frt, manager, namespace="dynamo").start()
        for _ in range(100):
            if seen:
                break
            await asyncio.sleep(0.02)
        assert seen["decode"] is not None and seen["prefill"] is not None
        assert seen["decode"].itl_at(16) == dec.itl_at(16)

        # Planner side: profile-from-discovery scan.
        d3, p3 = await discover_card_profile(frt.store, "dynamo")
        assert d3 is not None and p3 is not None
        assert p3.ttft_at(128) == pre.ttft_at(128)

        await watcher.close()
        await manager.close()
        await frt.shutdown()
        await wrt.shutdown()

    asyncio.run(go())


@pytest.mark.parametrize("seed", [3, 17, 404])
def test_flash_crowd_moves_pools_and_never_flaps(seed):
    """A prompt-heavy flash crowd over a fixed fleet of twelve engines, the
    loop closed through a queueing toy (a pool's latency grows with its
    demand over its size, observed with seeded noise): the law moves
    engines toward prefill while the crowd lasts and back when a
    decode-heavy evening follows, and no move is ever reversed within two control intervals."""
    rng = random.Random(seed)
    interval = 5.0
    lw = law(max_engines=12, hysteresis_cycles=2, cooldown_s=30.0,
             interval_s=interval, predictor="constant")
    n_p, n_d = 3, 9
    moves = []  # (tick, src, dst)
    for tick in range(240):
        crowd = 40 <= tick < 120
        if crowd:
            rate, isl, osl = 30.0, 900.0, 48.0
        elif tick >= 120:  # a decode-heavy evening after it
            rate, isl, osl = 14.0, 100.0, 400.0
        else:
            rate, isl, osl = 6.0, 200.0, 96.0
        rate *= rng.uniform(0.9, 1.1)
        pre_util = rate * isl / (n_p * 2560.0)
        dec_util = rate * osl / (n_d * 1070.0)
        obs = PlannerObservation(
            request_rate=rate, input_token_rate=rate * isl,
            output_token_rate=rate * osl,
            ttft_ms=120.0 * max(1.0, pre_util) ** 2 * rng.uniform(0.9, 1.1),
            itl_ms=8.0 * max(1.0, dec_util) ** 2 * rng.uniform(0.9, 1.1),
        )
        now = tick * interval
        for mv in actions_of(lw.decide(obs, n_p, n_d, now=now), PoolMove):
            n_p += 1 if mv.dst == POOL_PREFILL else -1
            n_d += 1 if mv.dst == POOL_DECODE else -1
            lw.notify_actuated(KIND_POOL_MOVE, now=now)
            moves.append((tick, mv.src, mv.dst))
        assert n_p >= 1 and n_d >= 1 and n_p + n_d == 12
    to_prefill = [t for t, _s, d in moves if d == POOL_PREFILL]
    assert to_prefill and 40 <= min(to_prefill) < 120, moves
    assert any(d == POOL_DECODE and t >= 120 for t, _s, d in moves), moves
    for (t0, s0, d0), (t1, s1, d1) in zip(moves, moves[1:]):
        if (s1, d1) == (d0, s0):
            assert t1 - t0 > 2, f"move reversed after {t1 - t0} intervals: {moves}"
