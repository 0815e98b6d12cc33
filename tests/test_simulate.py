"""Continuous-rebalance simulator tier (docs/SIMULATOR.md).

Covers the closed control loop end to end: scenario determinism (same
seed => byte-identical event log, SLO summary and rendered exposition
text), exact replay of a committed trace, the CI sim-smoke matrix
(3 fixed seeds x spot-preemption / zone-flap / weight-drift scenarios),
the SLO brute-force property tests (incremental tracker == ground-truth
recompute from the raw event log), the controller's supersede /
debounce / degradation behaviors, the recovery-exhaustion and
empty-candidate satellites on rebalance_async, and the slow-marked
7-virtual-day soak.
"""

import asyncio

import pytest

from blance_tpu.core.types import Partition, model
from blance_tpu.obs import Recorder, use_recorder
from blance_tpu.obs.slo import SloTracker
from blance_tpu.orchestrate import FaultPlan, NodeFaults
from blance_tpu.orchestrate.orchestrator import (
    OrchestratorOptions,
    orchestrate_moves,
)
from blance_tpu.rebalance import (
    ClusterDelta,
    DegradedPlacement,
    RebalanceController,
    count_moves,
    rebalance_async,
)
from blance_tpu.testing.scenarios import (
    SCENARIOS,
    hetero_drain,
    mixed_week,
    spot_preemption,
)
from blance_tpu.testing.simulate import (
    recompute_slo_from_log,
    run_scenario,
)

SIM_SMOKE_SEEDS = (11, 23, 37)
SMOKE_FAMILIES = ("spot_preemption", "zone_flap", "weight_drift")

TRACE_PATH = "tests/traces/sim_spot_preemption_s11.json"


def _pm(d):
    return {name: Partition(name, {s: list(ns) for s, ns in nbs.items()})
            for name, nbs in d.items()}


async def _noop_assign(stop_ch, node, partitions, states, ops):
    await asyncio.sleep(0)


# -- determinism & replay -----------------------------------------------------


@pytest.mark.parametrize("family", SMOKE_FAMILIES)
def test_scenario_bit_identical_across_runs(family):
    """Same scenario seed => byte-identical event log, equal SLO
    summary, and byte-identical rendered exposition text — the
    determinism contract the whole tier stands on."""
    a = run_scenario(SCENARIOS[family](11))
    b = run_scenario(SCENARIOS[family](11))
    assert a.log_text() == b.log_text()
    assert a.summary == b.summary
    assert a.exposition == b.exposition
    # And a different seed is a genuinely different trace.
    c = run_scenario(SCENARIOS[family](12))
    assert c.log_text() != a.log_text()


def test_committed_trace_replays_exactly():
    """The committed event log regenerates byte-for-byte — any drift in
    planner, orchestrator, controller or SLO arithmetic shows up as a
    diff here and must be understood (then the trace regenerated)."""
    with open(TRACE_PATH) as f:
        committed = f.read()
    live = run_scenario(spot_preemption(11)).log_text()
    assert live == committed, (
        "simulator behavior drifted from the committed trace "
        f"({TRACE_PATH}); if the change is intended, regenerate it: "
        "python -c \"from blance_tpu.testing.scenarios import "
        "spot_preemption; from blance_tpu.testing.simulate import "
        "run_scenario; open('" + TRACE_PATH + "', 'w').write("
        "run_scenario(spot_preemption(11)).log_text())\"")


SCHED_TRACE_PATH = "tests/traces/sim_hetero_drain_s41.json"


def test_hetero_drain_scheduled_trace_replays_exactly():
    """The committed hetero_drain trace is the CRITICAL-PATH-scheduled
    account of the family (docs/SCHEDULER.md): any drift in scheduler
    arithmetic — ranks, lane assignment, reschedule timing — shows up
    as a byte diff here and must be understood (then regenerated)."""
    import dataclasses

    with open(SCHED_TRACE_PATH) as f:
        committed = f.read()
    scn = dataclasses.replace(hetero_drain(41), scheduler="critical_path")
    assert run_scenario(scn).log_text() == committed, (
        "scheduled-simulation behavior drifted from the committed "
        f"trace ({SCHED_TRACE_PATH}); if intended, regenerate it: "
        "python -c \"import dataclasses; from blance_tpu.testing."
        "scenarios import hetero_drain; from blance_tpu.testing."
        "simulate import run_scenario; open('" + SCHED_TRACE_PATH
        + "', 'w').write(run_scenario(dataclasses.replace("
        "hetero_drain(41), scheduler='critical_path')).log_text())\"")


def test_hetero_drain_scheduled_beats_legacy_at_equal_churn():
    """The makespan claim (ISSUE 12): on the heterogeneous-latency
    drain family the critical-path order converges measurably faster
    than the app-weight order — strictly lower post-warmup makespan
    p95 — while executing the IDENTICAL move set (equal churn, equal
    final map; only the clock differs).  Virtual time, so the
    comparison is exact."""
    import dataclasses

    scn = hetero_drain(41)
    leg = run_scenario(scn)
    crit = run_scenario(
        dataclasses.replace(scn, scheduler="critical_path"))
    assert {k: v.nodes_by_state for k, v in leg.final_map.items()} == \
        {k: v.nodes_by_state for k, v in crit.final_map.items()}
    assert leg.summary.moves_executed == crit.summary.moves_executed
    # Incident 0 is the cost model's calibration join (identical either
    # way); the measured incidents are the two joins after it.
    leg_lags = leg.summary.first_converged_lags[1:]
    crit_lags = crit.summary.first_converged_lags[1:]
    assert len(leg_lags) == len(crit_lags) == 2
    assert max(crit_lags) < max(leg_lags)
    assert sum(crit_lags) < sum(leg_lags)


def test_mixed_week_schedule_buys_no_churn_or_tail():
    """On the fault-heavy week the two orders may retry different moves
    (move order changes which moves land in flaky windows), so the
    identity claim stays on hetero_drain; here the critical-path order
    may neither buy makespan with extra churn (at most 2% more executed
    moves) nor lengthen the first-converged makespan p95.  Virtual
    time, so both comparisons are exact."""
    import dataclasses

    def p95(lags):
        lags = sorted(lags)
        return lags[min(int(0.95 * len(lags)), len(lags) - 1)]

    scn = mixed_week(7)
    leg = run_scenario(scn)
    crit = run_scenario(
        dataclasses.replace(scn, scheduler="critical_path"))
    assert crit.summary.moves_executed <= \
        1.02 * leg.summary.moves_executed
    assert p95(crit.summary.first_converged_lags) <= \
        p95(leg.summary.first_converged_lags)


# -- the sim-smoke matrix -----------------------------------------------------


@pytest.mark.parametrize("seed", SIM_SMOKE_SEEDS)
@pytest.mark.parametrize("family", SMOKE_FAMILIES)
def test_sim_smoke(family, seed):
    """Final-map completeness, availability >= the scenario floor, no
    availability drop outside a scripted outage window, every incident
    converged."""
    r = run_scenario(SCENARIOS[family](seed))
    assert r.complete, f"{family}/{seed}: final map incomplete"
    assert r.summary.availability == 1.0
    assert r.summary.time_weighted_availability >= \
        SCENARIOS[family](seed).availability_floor
    assert r.unscripted_drops == []
    assert r.unconverged == 0
    assert len(r.convergence_lags) == r.deltas
    assert all(lag >= 0 for lag in r.convergence_lags)
    # The trace actually exercised the loop.
    assert r.rebalances >= 1 and r.summary.moves_executed > 0


@pytest.mark.parametrize("seed", SIM_SMOKE_SEEDS)
def test_slo_summary_matches_brute_force_recompute(seed):
    """Property test: the tracker's INCREMENTAL availability / churn /
    lag / violation account must equal a ground-truth recompute from
    the raw event log (catches incremental-view drift)."""
    for family in SMOKE_FAMILIES:
        r = run_scenario(SCENARIOS[family](seed))
        ref = recompute_slo_from_log(r.events)
        s = r.summary
        assert s.availability == ref["availability"], family
        assert s.moves_executed == ref["moves_executed"], family
        assert s.moves_failed == ref["moves_failed"], family
        assert abs(s.time_weighted_availability -
                   ref["time_weighted_availability"]) < 1e-12, family
        assert abs(s.violation_s - ref["violation_s"]) < 1e-12, family
        assert s.violation_intervals == ref["violation_intervals"], family
        assert abs(s.convergence_lag_s -
                   ref["convergence_lag_s"]) < 1e-12, family


# -- the long-horizon soak (slow tier) ---------------------------------------


@pytest.mark.slow
def test_seven_day_mixed_fault_soak():
    """7 virtual days of mixed faults (>= 20 deltas, overlapping ones
    included) must complete in well under 60 s wall-clock with a
    complete final map and zero availability drops outside scripted
    outage windows."""
    scn = mixed_week(7)
    assert scn.horizon_s == 7 * 86_400.0
    assert len(scn.events) >= 20
    r = run_scenario(scn)
    assert r.wall_s < 60.0, f"soak took {r.wall_s:.1f}s wall-clock"
    assert r.complete
    assert r.summary.availability == 1.0
    assert r.unscripted_drops == [], r.unscripted_drops
    assert r.superseded >= 1  # the overlapping deltas really overlap
    assert r.unconverged == 0
    assert r.summary.time_weighted_availability >= scn.availability_floor
    # Determinism holds at the week horizon too.
    assert run_scenario(mixed_week(7)).log_text() == r.log_text()


# -- SLO horizon accounting (unit) -------------------------------------------


def test_slo_timeline_and_time_weighted_availability():
    t = {"now": 0.0}
    beg = _pm({"p0": {"primary": ["a"]}, "p1": {"primary": ["a"]}})
    slo = SloTracker(beg, clock=lambda: t["now"], track_timeline=True,
                     availability_floor=0.9)
    assert slo.time_weighted_availability(0.0) == 1.0
    t["now"] = 10.0
    slo.strip_nodes({"a"}, now=10.0)  # availability 1 -> 0 at t=10
    assert slo.availability() == 0.0
    # [0,10) at 1.0, [10,20) at 0.0 -> 0.5 time-weighted.
    assert slo.time_weighted_availability(20.0) == 0.5
    assert slo.violation_intervals(20.0) == [(10.0, 20.0)]
    assert slo.violation_s(20.0) == 10.0

    class Mv:
        partition, node, state, op = "p0", "b", "primary", "add"

    t["now"] = 20.0
    slo.on_batch("b", [Mv()], ok=True, now=20.0)  # 0 -> 0.5 at t=20
    assert slo.availability() == 0.5
    tl = slo.timeline()
    assert tl == [(0.0, 1.0), (10.0, 0.0), (20.0, 0.5)]
    # [0,10)=1, [10,20)=0, [20,30)=0.5 over 30s -> 0.5
    assert slo.time_weighted_availability(30.0) == 0.5
    # Still below the 0.9 floor: the violation interval stays open.
    assert slo.violation_intervals(30.0) == [(10.0, 30.0)]
    s = slo.summary(30.0)
    assert s.time_weighted_availability == 0.5
    assert s.availability_floor == 0.9
    assert s.violation_s == 20.0


def test_slo_timeline_off_by_default():
    beg = _pm({"p0": {"primary": ["a"]}})
    slo = SloTracker(beg)
    assert slo.timeline() == []
    s = slo.summary()
    assert s.time_weighted_availability is None
    assert s.violation_intervals == []


def test_slo_horizon_gauges_published():
    rec = Recorder()
    beg = _pm({"p0": {"primary": ["a"]}})
    slo = SloTracker(beg, recorder=rec, track_timeline=True,
                     availability_floor=0.5)
    slo.publish()
    assert "slo.time_weighted_availability" in rec.gauges
    assert "slo.violation_seconds" in rec.gauges


# -- recovery exhaustion & empty-candidate satellites ------------------------


def _dead_cluster():
    m = model(primary=(0, 1))
    beg = _pm({f"p{i}": {"primary": [["a", "b"][i % 2]]}
               for i in range(4)})
    plan = FaultPlan(seed=3, nodes={"a": NodeFaults(dead=True),
                                    "b": NodeFaults(dead=True)})
    opts = OrchestratorOptions(move_timeout_s=0.25, max_retries=0,
                               quarantine_after=1, probe_after_s=600.0)
    return m, beg, plan, opts


def test_unconverged_rebalance_is_structured_not_silent():
    """Recovery exhaustion surfaces as converged=False + a residual
    summary + the rebalance.unconverged counter — never a partial map
    indistinguishable from success."""
    m, beg, plan, opts = _dead_cluster()
    rec = Recorder()
    with use_recorder(rec):
        r = asyncio.run(rebalance_async(
            m, beg, ["a", "b"], ["a"], [], plan.wrap(_noop_assign),
            orchestrator_options=opts, max_recovery_rounds=3,
            backend="greedy"))
    assert r.converged is False
    assert r.residual_failures and \
        sum(r.residual_failures.values()) > 0
    assert rec.counters.get("rebalance.unconverged", 0) == 1


def test_all_nodes_quarantined_degrades_structurally():
    """The all-nodes-quarantined edge: the recovery replan's candidate
    set is EMPTY — the result must be a structured empty-placement
    degradation, not a planner exception (the simulator's zone-outage
    scenarios hit this in normal operation)."""
    m, beg, plan, opts = _dead_cluster()
    rec = Recorder()
    with use_recorder(rec):
        r = asyncio.run(rebalance_async(
            m, beg, ["a", "b"], ["a"], [], plan.wrap(_noop_assign),
            orchestrator_options=opts, max_recovery_rounds=3,
            backend="greedy"))
    assert isinstance(r.degraded, DegradedPlacement)
    assert r.degraded.reason == "no-candidate-nodes"
    assert r.degraded.nodes_available == 0
    assert all(p.nodes_by_state.get("primary") == []
               for p in r.next_map.values())
    assert r.converged is False
    assert rec.counters.get("rebalance.degraded", 0) == 1
    # And it stopped burning recovery rounds once nothing could help:
    # one primary pass, not 1 + max_recovery_rounds.
    assert len(r.rounds) == 1


def test_converged_rebalance_reports_true():
    m = model(primary=(0, 1))
    beg = _pm({f"p{i}": {"primary": ["a"]} for i in range(4)})
    r = asyncio.run(rebalance_async(
        m, beg, ["a", "b"], ["a"], [], _noop_assign,
        orchestrator_options=OrchestratorOptions(move_timeout_s=0.25,
                                                 max_retries=1),
        max_recovery_rounds=2, backend="greedy"))
    assert r.converged is True
    assert r.residual_failures == {}
    assert r.degraded is None


# -- controller behaviors -----------------------------------------------------


def test_controller_debounce_coalesces_burst():
    """Two deltas inside the debounce window become ONE planning
    cycle."""
    async def drive():
        m = model(primary=(0, 1))
        cur = _pm({f"p{i}": {"primary": ["a"]} for i in range(6)})
        ctl = RebalanceController(m, ["a", "b", "c"], cur, _noop_assign,
                                  debounce_s=0.05)
        ctl.start()
        ctl.submit(ClusterDelta(remove=("a",)))
        ctl.submit(ClusterDelta(add=("d",)))
        await asyncio.wait_for(ctl.quiesce(), 10)
        await ctl.stop()
        return ctl
    ctl = asyncio.run(drive())
    assert ctl.cycles == 1
    assert "d" in ctl._nodes


def test_controller_supersede_resumes_from_achieved_map():
    """A delta fired mid-rebalance cancels the in-flight transition and
    the loop still converges on the survivors — same final map as a
    quiesced sequential run of the two deltas."""
    async def drive(interleaved):
        m = model(primary=(0, 1))
        nodes = ["a", "b", "c", "d"]
        cur = _pm({f"p{i}": {"primary": [nodes[i % 4]]}
                   for i in range(8)})
        fired = {"done": False}
        ctl = None

        async def assign(stop_ch, node, partitions, states, ops):
            if interleaved and not fired["done"]:
                fired["done"] = True
                ctl.submit(ClusterDelta(fail=("b",)))
            await asyncio.sleep(0.001)

        ctl = RebalanceController(m, nodes, cur, assign,
                                  debounce_s=0.001)
        ctl.start()
        ctl.submit(ClusterDelta(remove=("a",)))
        if not interleaved:
            await asyncio.wait_for(ctl.quiesce(), 10)
            ctl.submit(ClusterDelta(fail=("b",)))
        final = await asyncio.wait_for(ctl.quiesce(), 10)
        await ctl.stop()
        for _ in range(3):
            await asyncio.sleep(0)
        assert not ctl.pending_tasks()
        return ctl, final

    ctl_i, final_i = asyncio.run(drive(interleaved=True))
    ctl_s, final_s = asyncio.run(drive(interleaved=False))
    assert ctl_i.superseded >= 1
    assert ctl_s.superseded == 0
    m = model(primary=(0, 1))
    from blance_tpu.plan.api import plan_next_map

    # Both runs land on a complete planner FIXPOINT over the survivors
    # with the identical balance profile.  (Which partition sits on c
    # vs d legitimately differs with the cancellation point —
    # stickiness keeps whatever the achieved prefix placed; the
    # byte-equal final-map claim is pinned where it is forced, in the
    # supersede_mid_rebalance explorer scenario's sole-survivor
    # topology.)
    profiles = []
    for final in (final_i, final_s):
        counts: dict = {}
        for p in final.values():
            (n,) = p.nodes_by_state["primary"]
            assert n in ("c", "d")
            counts[n] = counts.get(n, 0) + 1
        profiles.append(sorted(counts.values()))
        nm, _ = plan_next_map(final, final, ["a", "b", "c", "d"],
                              ["a", "b"], [], m, backend="greedy")
        assert count_moves(m, final, nm) == 0
    assert profiles[0] == profiles[1] == [4, 4]


def test_controller_empty_candidates_keeps_current_placements():
    """With every node failed/removed there is nothing to plan onto:
    the controller reports no-candidate degradation and keeps serving
    whatever survived, instead of draining data to nowhere."""
    async def drive():
        m = model(primary=(0, 1))
        cur = _pm({f"p{i}": {"primary": ["a"]} for i in range(4)})
        ctl = RebalanceController(m, ["a", "b"], cur, _noop_assign,
                                  debounce_s=0.001)
        ctl.start()
        ctl.submit(ClusterDelta(fail=("b",), remove=("a",)))
        final = await asyncio.wait_for(ctl.quiesce(), 10)
        await ctl.stop()
        return ctl, final
    ctl, final = asyncio.run(drive())
    assert any(r.reason == "no-candidate-nodes"
               for r in ctl.degraded_reports)
    # "a" was a GRACEFUL removal with nowhere to drain to: its data
    # stays put (never deleted to nowhere).
    assert all(p.nodes_by_state.get("primary") == ["a"]
               for p in final.values())


def test_controller_shed_replicas_before_primaries():
    async def drive():
        m = model(primary=(0, 1), replica=(1, 1))
        cur = _pm({f"p{i}": {"primary": ["a"], "replica": ["b"]}
                   for i in range(4)})
        ctl = RebalanceController(m, ["a", "b"], cur, _noop_assign,
                                  debounce_s=0.001)
        ctl.start()
        ctl.submit(ClusterDelta(fail=("b",)))
        final = await asyncio.wait_for(ctl.quiesce(), 10)
        await ctl.stop()
        return ctl, final
    ctl, final = asyncio.run(drive())
    assert any(r.reason == "capacity-shed" and r.shed == {"replica": 1}
               for r in ctl.degraded_reports)
    for p in final.values():
        assert p.nodes_by_state.get("primary") == ["a"]
        assert p.nodes_by_state.get("replica", []) == []


def test_controller_readd_clears_breaker_and_failed_state():
    """A failed node re-added by a later delta comes back with a clean
    breaker slate (health.forget) and becomes a candidate again."""
    async def drive():
        m = model(primary=(0, 1))
        cur = _pm({f"p{i}": {"primary": ["a"]} for i in range(4)})
        ctl = RebalanceController(
            m, ["a", "b"], cur, _noop_assign, debounce_s=0.001,
            orchestrator_options=OrchestratorOptions(
                move_timeout_s=0.25, max_retries=1, quarantine_after=2))
        ctl.start()
        ctl.submit(ClusterDelta(fail=("a",)))
        await asyncio.wait_for(ctl.quiesce(), 10)
        assert "a" in ctl._failed
        ctl.submit(ClusterDelta(add=("a",)))
        final = await asyncio.wait_for(ctl.quiesce(), 10)
        await ctl.stop()
        return ctl, final
    ctl, final = asyncio.run(drive())
    assert "a" not in ctl._failed
    assert ctl.health.state("a") == "healthy"
    assert set(ctl.live_nodes()) == {"a", "b"}
    for p in final.values():
        assert len(p.nodes_by_state["primary"]) == 1


def test_orchestrator_cancel_counts_and_waits_drained():
    """cancel() is a counted stop; wait_drained() returns only after
    the full wind-down (progress stream closed, movers exited)."""
    async def drive():
        m = model(primary=(0, 1))
        beg = _pm({f"p{i}": {"primary": ["a"]} for i in range(4)})
        end = _pm({f"p{i}": {"primary": ["b"]} for i in range(4)})
        started = asyncio.Event()

        async def assign(stop_ch, node, partitions, states, ops):
            started.set()
            await asyncio.sleep(0.01)

        o = orchestrate_moves(m, OrchestratorOptions(), ["a", "b"],
                              beg, end, assign)

        async def drain():
            async for _p in o.progress_ch():
                pass
            o.stop()

        d = asyncio.ensure_future(drain())
        await started.wait()
        o.cancel()
        o.cancel()  # idempotent: counted once
        await asyncio.wait_for(o.wait_drained(), 5)
        await d
        for _ in range(3):
            await asyncio.sleep(0)
        assert o.pending_tasks() == []
        return o
    o = asyncio.run(drive())
    assert o._progress.tot_cancel == 1
    assert o._progress.tot_progress_close == 1


def test_controller_copies_plan_options():
    """Weight deltas fold into the controller's PRIVATE options view —
    a caller-shared PlanOptions must come out untouched."""
    from blance_tpu.core.types import PlanOptions

    shared = PlanOptions(partition_weights={"p0": 2})

    async def drive():
        m = model(primary=(0, 1))
        cur = _pm({f"p{i}": {"primary": ["a"]} for i in range(4)})
        ctl = RebalanceController(m, ["a", "b"], cur, _noop_assign,
                                  plan_options=shared, debounce_s=0.001)
        ctl.start()
        ctl.submit(ClusterDelta(partition_weights={"p1": 8},
                                remove=("a",)))
        await asyncio.wait_for(ctl.quiesce(), 10)
        await ctl.stop()
        return ctl
    ctl = asyncio.run(drive())
    assert shared.partition_weights == {"p0": 2}
    assert ctl.opts.partition_weights == {"p0": 2, "p1": 8}


def test_session_controller_mirrors_quarantine_into_session():
    """A node the breaker quarantines mid-run must be mirrored into
    the session as removed BEFORE the next session plan — otherwise
    the plan targets a node whose mover is excluded and the pass
    wedges on a moverless target (pre-fix: quiesce() hung forever)."""
    pytest.importorskip("jax")
    from blance_tpu.plan.session import PlannerSession

    async def drive():
        m = model(primary=(0, 1))
        nodes = ["a", "b", "c"]
        parts = [f"p{i}" for i in range(6)]
        cur = _pm({p: {"primary": ["a"]} for p in parts})
        session = PlannerSession(m, nodes, parts)
        session.load_map(cur)
        plan = FaultPlan(seed=5, nodes={"b": NodeFaults(dead=True)})
        ctl = RebalanceController(
            m, nodes, cur, plan.wrap(_noop_assign), session=session,
            debounce_s=0.001,
            orchestrator_options=OrchestratorOptions(
                move_timeout_s=0.25, max_retries=0, quarantine_after=1,
                probe_after_s=600.0))
        ctl.start()
        ctl.submit(ClusterDelta(remove=("a",)))
        final = await asyncio.wait_for(ctl.quiesce(), 30)
        await ctl.stop()
        return ctl, final, session
    ctl, final, session = asyncio.run(drive())
    assert "b" in ctl.quarantined_nodes()
    assert "b" in session.removed_nodes
    for p in final.values():
        assert p.nodes_by_state.get("primary") == ["c"]


def test_session_controller_readds_returned_node():
    """fail then re-add in session mode: the session's removal flag
    must clear so the returned capacity is planned onto again
    (pre-fix: the node stayed dark forever)."""
    pytest.importorskip("jax")
    from blance_tpu.plan.session import PlannerSession

    async def drive():
        m = model(primary=(0, 1))
        nodes = ["a", "b", "c"]
        parts = [f"p{i}" for i in range(6)]
        cur = _pm({p: {"primary": [nodes[i % 3]]}
                   for i, p in enumerate(parts)})
        session = PlannerSession(m, nodes, parts)
        session.load_map(cur)
        ctl = RebalanceController(m, nodes, cur, _noop_assign,
                                  session=session, debounce_s=0.001)
        ctl.start()
        ctl.submit(ClusterDelta(fail=("b",)))
        await asyncio.wait_for(ctl.quiesce(), 30)
        ctl.submit(ClusterDelta(add=("b",)))
        final = await asyncio.wait_for(ctl.quiesce(), 30)
        await ctl.stop()
        return ctl, final, session
    ctl, final, session = asyncio.run(drive())
    assert "b" not in session.removed_nodes
    assert set(ctl.live_nodes()) == {"a", "b", "c"}
    used = {n for p in final.values()
            for n in p.nodes_by_state.get("primary", [])}
    assert "b" in used, used


def test_session_backed_controller_rides_warm_carry():
    """A session-backed controller completes delta cycles and its
    fixpoint plan adopts the proposal (warm carry across cycles)."""
    jax = pytest.importorskip("jax")
    del jax
    from blance_tpu.plan.session import PlannerSession

    async def drive():
        m = model(primary=(0, 1))
        nodes = ["a", "b", "c"]
        parts = [f"p{i}" for i in range(8)]
        cur = _pm({p: {"primary": [nodes[i % 3]]}
                   for i, p in enumerate(parts)})
        session = PlannerSession(m, nodes, parts)
        session.load_map(cur)
        ctl = RebalanceController(m, nodes, cur, _noop_assign,
                                  session=session, debounce_s=0.001)
        ctl.start()
        ctl.submit(ClusterDelta(remove=("a",)))
        final = await asyncio.wait_for(ctl.quiesce(), 30)
        # Weight drift rides the same session.
        ctl.submit(ClusterDelta(partition_weights={parts[0]: 4}))
        final = await asyncio.wait_for(ctl.quiesce(), 30)
        await ctl.stop()
        return ctl, final, session
    ctl, final, session = asyncio.run(drive())
    for p in final.values():
        (n,) = p.nodes_by_state["primary"]
        assert n in ("b", "c")
    # The session adopted the last proposal (current == controller's).
    cur_map, _ = session.to_map("current")
    assert count_moves(model(primary=(0, 1)), cur_map, final) == 0
