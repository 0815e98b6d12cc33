"""Fleet-of-loops tier (ISSUE 13, docs/FLEET.md "Fleet of control
loops"): N per-tenant RebalanceController cycle engines multiplexed
over one shared PlanService + CarryCache, driven deterministically by
testing/fleetsim.py.

Covers: bit-identical replay (incl. the committed trace), the
coalesced-vs-sequential contract (identical final maps, equal churn,
measurably fewer device dispatches), the tenant-scale matrix, staggered
onboarding, noisy-neighbor fairness (service-level starvation +
quota-bounded batches), the ServicePlanner warm protocol (weight
change / returned capacity / mid-cycle invalidation each only ever
costs a cold solve — never a stale map), CarryCache eviction
observability, and the fleet SLO rollup gauges.
"""

import asyncio
import dataclasses

import numpy as np
import pytest

from blance_tpu.core.types import Partition, model
from blance_tpu.fleetloop import FleetController, ServicePlanner
from blance_tpu.obs import Recorder, use_recorder
from blance_tpu.obs.expo import default_registry
from blance_tpu.obs.slo import FleetSloRollup, SloTracker
from blance_tpu.plan.carry import CarryCache
from blance_tpu.plan.fleet import TenantProblem, solve_fleet
from blance_tpu.plan.service import PlanService
from blance_tpu.rebalance import ClusterDelta, RebalanceController
from blance_tpu.testing.fleetsim import run_fleet_scenario
from blance_tpu.testing.scenarios import (
    fleet_noisy_neighbor,
    fleet_onboarding,
    fleet_week,
    fleet_zone_outage,
)
from blance_tpu.testing.sched import DeterministicLoop, FifoPolicy

M = model(primary=(0, 1), replica=(1, 1))

FLEET_TRACE_PATH = "tests/traces/fleet_zone_outage_s5_t8.json"


def _nbs(pmap):
    return {name: {s: list(ns) for s, ns in p.nodes_by_state.items()}
            for name, p in pmap.items()}


def _maps_equal(a, b):
    return {k: _nbs(m) for k, m in a.items()} == \
        {k: _nbs(m) for k, m in b.items()}


# -- determinism & replay -----------------------------------------------------


def test_fleet_scenario_bit_identical_across_runs():
    """Same fleet scenario => byte-identical event log, equal per-tenant
    SLO summaries, byte-identical exposition — the determinism contract
    the whole multi-tenant tier stands on."""
    scn = fleet_zone_outage(seed=5, tenants=8)
    a = run_fleet_scenario(scn)
    b = run_fleet_scenario(scn)
    assert a.log_text() == b.log_text()
    assert a.exposition == b.exposition
    assert a.summaries == b.summaries
    assert a.fleet == b.fleet
    # A different seed is a genuinely different trace.
    c = run_fleet_scenario(fleet_zone_outage(seed=6, tenants=8))
    assert c.log_text() != a.log_text()


def test_committed_fleet_trace_replays_exactly():
    """The committed fleet event log regenerates byte-for-byte — any
    drift in planner, service coalescing, controller or SLO arithmetic
    shows up as a diff here and must be understood (then the trace
    regenerated)."""
    with open(FLEET_TRACE_PATH) as f:
        committed = f.read()
    live = run_fleet_scenario(fleet_zone_outage(seed=5, tenants=8))
    assert live.log_text() == committed, (
        "fleet-simulator behavior drifted from the committed trace "
        f"({FLEET_TRACE_PATH}); if the change is intended, regenerate: "
        "python -c \"from blance_tpu.testing.scenarios import "
        "fleet_zone_outage; from blance_tpu.testing.fleetsim import "
        "run_fleet_scenario; open('" + FLEET_TRACE_PATH + "', 'w')"
        ".write(run_fleet_scenario(fleet_zone_outage(seed=5, tenants=8))"
        ".log_text())\"")


@pytest.mark.parametrize("seed,tenants", [(5, 4), (5, 12), (7, 8)])
def test_tenant_scale_matrix(seed, tenants):
    """Fixed seeds x tenant-scale points: complete final maps on live
    nodes, full availability restored, and coalescing actually engaged
    (dispatches < plan requests)."""
    r = run_fleet_scenario(fleet_zone_outage(seed=seed, tenants=tenants))
    assert r.complete
    assert r.fleet.tenants == tenants
    assert r.fleet.availability_min == 1.0
    assert r.unconverged == 0
    assert r.plan_requests > 0
    if tenants > 1:
        assert r.dispatches < r.plan_requests


# -- the coalescing contract --------------------------------------------------


def test_coalesced_equals_sequential_at_fewer_dispatches():
    """The acceptance gate's core: the coalesced fleet loop and the
    sequential loop-per-tenant baseline (same code, zero window,
    max_batch=1) converge to IDENTICAL final maps with EQUAL executed
    moves and equal availability — and the coalesced run pays
    measurably fewer device dispatches."""
    scn = fleet_zone_outage(seed=5, tenants=8)
    co = run_fleet_scenario(scn, coalesce=True)
    seq = run_fleet_scenario(scn, coalesce=False)
    assert _maps_equal(co.final_maps, seq.final_maps)
    assert co.fleet.moves_executed == seq.fleet.moves_executed
    assert co.fleet.availability_min == seq.fleet.availability_min
    assert {k: s.availability for k, s in co.summaries.items()} == \
        {k: s.availability for k, s in seq.summaries.items()}
    # Sequential mode = one dispatch per plan request; coalescing must
    # beat it by a real margin, not by one.
    assert seq.dispatches == seq.plan_requests
    assert co.dispatches < seq.dispatches
    # Warm carries engaged on the shared cache in both modes.
    assert co.carry_hits > 0
    assert seq.carry_hits > 0


# -- scenario families --------------------------------------------------------


def test_onboarding_family_converges_from_empty():
    scn = fleet_onboarding(seed=13, tenants=12)
    r = run_fleet_scenario(scn)
    assert r.complete
    assert r.fleet.availability_min == 1.0
    onboarded = [t.key for t in scn.tenants if t.onboard_t > 0]
    assert onboarded, "family drifted: no staggered tenants"
    kinds = [e for e in r.events if e["kind"] == "onboard"]
    assert sorted(e["tenant"] for e in kinds) == sorted(onboarded)
    # An onboarding tenant starts empty, so placing everything is real
    # executed work.
    for key in onboarded:
        assert r.summaries[key].moves_executed >= \
            dict((t.key, t.partitions) for t in scn.tenants)[key]


def test_noisy_neighbor_family_keeps_neighbors_serving():
    scn = fleet_noisy_neighbor(seed=29, tenants=10)
    assert scn.fair_share is not None  # the fairness config is the point
    r = run_fleet_scenario(scn)
    assert r.complete
    noisy = scn.tenants[0].key
    # The chatty tenant consumes many converge cycles...
    waves = sum(1 for e in r.events
                if e["kind"] == "delta" and e["tenants"] == [noisy])
    assert waves >= 15
    # ...while every neighbor still ends fully available and under its
    # violation budget (the scripted node outage is the only dip).
    for key, s in r.summaries.items():
        assert s.availability == 1.0, key


# -- admission fairness (plan/service.py fair_share) --------------------------


def _tiny_tenant(key, seed, n=3):
    p, s, r = 2, 1, 1
    prev = np.full((p, s, r), -1, np.int32)
    prev[0, 0, 0] = seed % n
    prev[1, 0, 0] = (seed + 1) % n
    return TenantProblem(
        key=key, prev=prev,
        partition_weights=np.ones(p, np.float32),
        node_weights=np.ones(n, np.float32),
        valid_node=np.ones(n, bool),
        stickiness=np.full((p, s), 1.5, np.float32),
        gids=np.arange(n, dtype=np.int32).reshape(1, n),
        gid_valid=np.ones((1, n), bool),
        constraints=(1,), rules=((),))


def test_service_fair_share_defers_chatty_tenant():
    """A chatty tenant's concurrent requests beyond fair_share roll to
    later batches (counted as fleet.starved_admissions) and still
    resolve bit-exactly; no batch ever holds more than fair_share
    requests of one key; neighbors are unaffected."""
    batches = []

    class Capturing(PlanService):
        def _solve_batch(self, problems, trace_ids):
            batches.append([t.key for t in problems])
            return super()._solve_batch(problems, trace_ids)

    loop = DeterministicLoop(FifoPolicy(), max_steps=500_000)
    rec = Recorder(clock=loop.time)
    expected = {key: solve_fleet([_tiny_tenant(key, s)],
                                 record=False, batch_floor=16)[0].assign
                for key, s in (("chatty", 0), ("b", 1), ("c", 2))}

    async def drive():
        svc = Capturing(admission_window_s=0.05, fair_share=1,
                        inline_solve=True, max_pending=16,
                        recorder=rec, batch_floor=16)
        await svc.start()
        tags = [("chatty", 0)] * 4 + [("b", 1), ("c", 2)]
        results = await asyncio.gather(
            *[svc.submit(_tiny_tenant(key, s)) for key, s in tags])
        await svc.stop()
        return tags, results

    with use_recorder(rec):
        tags, results = loop.run_until_complete(drive())
    for (key, _s), res in zip(tags, results):
        assert res.key == key
        assert np.array_equal(res.assign, expected[key])
    starved = rec.counters.get("fleet.starved_admissions", 0)
    assert starved >= 3  # 4 chatty requests, quota 1 -> >= 3 deferrals
    for keys in batches:
        for key in set(keys):
            assert keys.count(key) <= 1, (key, keys)


def test_service_fair_share_validation():
    with pytest.raises(ValueError):
        PlanService(fair_share=0)


# -- the ServicePlanner warm protocol -----------------------------------------


def _cluster(nodes=12, parts=12):
    # 12 nodes / 12 partitions: the same bucket class as the smoke
    # scenario families, so the whole module shares compiled programs.
    names = [f"n{i}" for i in range(nodes)]
    pmap = {}
    for i in range(parts):
        p = f"p{i:03d}"
        pmap[p] = Partition(p, {"primary": [names[i % nodes]],
                                "replica": [names[(i + 1) % nodes]]})
    return names, pmap


def test_service_planner_warm_protocol_and_invalidation():
    """The planner's dirty protocol, driven cycle by cycle: a repeat
    plan on unchanged state rides the warm path bit-identically to its
    cold twin; a weight change, returned capacity, or a MID-CYCLE cache
    invalidation/eviction each demote to a cold solve whose map is
    bit-identical to the never-cached reference — an eviction can cost
    a cold solve, never a stale or wrong map."""
    from blance_tpu.core.types import PlanOptions

    nodes, pmap = _cluster()
    loop = DeterministicLoop(FifoPolicy(), max_steps=500_000)
    rec = Recorder(clock=loop.time)

    async def drive():
        # batch_floor=16 everywhere in this module: reuse the fleet
        # controller's compiled B-bucket instead of building B=1 twins.
        svc = PlanService(admission_window_s=0.0, inline_solve=True,
                          recorder=rec, batch_floor=16)
        await svc.start()
        planner = ServicePlanner("t0", svc)

        async def reference(current, removes, opts):
            # A fresh planner + fresh service: the never-cached cold
            # twin of the same cycle.
            svc2 = PlanService(admission_window_s=0.0,
                               inline_solve=True, recorder=rec,
                               batch_floor=16)
            await svc2.start()
            ref, _w = await ServicePlanner("t0", svc2).plan_cycle(
                current, nodes, removes, M, opts)
            await svc2.stop()
            return ref

        opts = PlanOptions()
        hits = lambda: rec.counters.get("plan.solve.carry_hit", 0)
        misses = lambda: rec.counters.get("plan.solve.carry_miss", 0)

        # Cycle 1: always cold.
        m1, _w = await planner.plan_cycle(pmap, nodes, [], M, opts)
        assert misses() >= 1 and hits() == 0

        # Cycle 2: a node fails -> warm-eligible (dark grew), and the
        # result is bit-identical to the cold reference.
        h0 = hits()
        m2, _w = await planner.plan_cycle(m1, nodes, ["n0"], M, opts)
        assert _nbs(m2) == _nbs(await reference(m1, ["n0"], opts))
        assert all("n0" not in ns for p in m2.values()
                   for ns in p.nodes_by_state.values())

        # Cycle 3: MID-CYCLE invalidation (the eviction stand-in) —
        # cold solve, same map as the never-cached reference.
        svc.carry_cache.invalidate("t0")
        mi0 = misses()
        m3, _w = await planner.plan_cycle(m2, nodes, ["n0"], M, opts)
        assert misses() > mi0
        assert _nbs(m3) == _nbs(await reference(m2, ["n0"], opts))

        # Cycle 4: weights changed -> the planner itself demotes to
        # cold (dirty=None), again bit-identical to the reference.
        hot = dataclasses.replace(opts, partition_weights={"p000": 8})
        h1, mi1 = hits(), misses()
        m4, _w = await planner.plan_cycle(m3, nodes, ["n0"], M, hot)
        assert misses() > mi1 and hits() == h1
        assert _nbs(m4) == _nbs(await reference(m3, ["n0"], hot))

        # Cycle 5: capacity returned (dark shrank) -> cold again.
        mi2 = misses()
        m5, _w = await planner.plan_cycle(m4, nodes, [], M, hot)
        assert misses() > mi2
        assert _nbs(m5) == _nbs(await reference(m4, [], hot))
        assert h0 <= hits()  # warm path engaged at least once overall
        await svc.stop()

    with use_recorder(rec):
        loop.run_until_complete(drive())


def test_shared_cache_eviction_under_fleet_only_costs_cold():
    """Satellite: a shared CarryCache under many concurrent controller
    loops with a ZERO byte budget (every store evicted immediately) —
    every solve goes cold, evictions are counted and labeled, and the
    fleet converges to exactly the maps of the identical run, because
    cold is always the single-problem solve on current inputs."""
    scn = dataclasses.replace(fleet_zone_outage(seed=5, tenants=6),
                              carry_bytes=0)
    a = run_fleet_scenario(scn)
    b = run_fleet_scenario(scn, coalesce=False)
    assert a.complete and b.complete
    assert a.carry_hits == 0 and b.carry_hits == 0
    assert a.carry_evictions.get("bytes", 0) > 0
    # All-cold decisions are mode-independent: byte-identical maps and
    # equal churn even under continuous eviction.
    assert _maps_equal(a.final_maps, b.final_maps)
    assert a.fleet.moves_executed == b.fleet.moves_executed


def test_warm_decodes_count_the_class_share(monkeypatch):
    """Over warm FleetController cycles every incremental decode is
    served by its class batch's decode share: ``fleet.decode_batched``
    equals the tenant decodes that took a share computed against the
    held prev, each is still a ``fleet.decode_patch``, and
    ``decode_patch == encode_warm`` holds as before."""
    from blance_tpu.plan.resident import EncodedState

    took: list[str] = []
    own = EncodedState.decode

    def spy(self, assign, current, removes, share=None):
        if share is not None and self.map is not None \
                and share.prev is self.problem.prev:
            took.append("share")
        return own(self, assign, current, removes, share)

    monkeypatch.setattr(EncodedState, "decode", spy)
    res = run_fleet_scenario(fleet_zone_outage(seed=5, tenants=6))
    assert res.complete
    assert res.encode_warm > 0
    assert res.decode_batched == len(took) > 0
    assert res.decode_batched == res.decode_patch == res.encode_warm


def test_planner_rejects_scoring_hooks():
    from blance_tpu.core.types import PlanOptions

    nodes, pmap = _cluster()
    loop = DeterministicLoop(FifoPolicy(), max_steps=100_000)
    rec = Recorder(clock=loop.time)

    async def drive():
        svc = PlanService(inline_solve=True, recorder=rec)
        await svc.start()
        planner = ServicePlanner("t0", svc)
        with pytest.raises(ValueError, match="node_score_booster"):
            await planner.plan_cycle(
                pmap, nodes, [], M,
                PlanOptions(node_score_booster=lambda *a: 0.0))
        await svc.stop()

    with use_recorder(rec):
        loop.run_until_complete(drive())


def test_add_tenant_rejects_scoring_hooks_at_registration():
    """A misconfigured tenant must fail at add_tenant (where the caller
    can handle it), not silently kill its engine task mid-run."""
    from blance_tpu.core.types import PlanOptions

    nodes, pmap = _cluster()
    loop = DeterministicLoop(FifoPolicy(), max_steps=100_000)
    rec = Recorder(clock=loop.time)

    async def drive():
        fc = FleetController(nodes, inline_solve=True, recorder=rec)
        await fc.start()
        with pytest.raises(ValueError, match="node_score_"):
            fc.add_tenant(
                "bad", M, pmap, lambda *a: None,
                plan_options=PlanOptions(node_scorer=lambda *a: 0.0))
        assert fc.keys() == []
        await fc.stop()

    with use_recorder(rec):
        loop.run_until_complete(drive())


def test_stop_survives_a_dead_tenant_loop():
    """A tenant engine that died with an exception must not abort the
    fleet wind-down partway: every other loop still stops, the shared
    service closes (no leaked dispatcher), and the crash re-raises to
    the caller afterwards."""
    nodes, pmap = _cluster()
    loop = DeterministicLoop(FifoPolicy(), max_steps=500_000)
    rec = Recorder(clock=loop.time)

    class _Boom(Exception):
        pass

    async def drive():
        async def assign(stop_ch, node, partitions, states, ops):
            await asyncio.sleep(0.1)

        fc = FleetController(nodes, inline_solve=True, debounce_s=0.1,
                             recorder=rec)
        await fc.start()
        good = fc.add_tenant("good", M, pmap, assign)
        bad = fc.add_tenant("bad", M, _cluster()[1], assign)

        async def exploding_plan(*a):
            raise _Boom("planner died")

        bad._planner = type(
            "P", (), {"plan_cycle": staticmethod(exploding_plan)})()
        fc.submit_all(ClusterDelta(fail=("n0",)))
        await good.quiesce()
        with pytest.raises(RuntimeError, match="tenant 'bad'"):
            await fc.stop()
        # The wind-down still completed: no orphan controller tasks,
        # and the shared service is closed.
        assert good.pending_tasks() == []
        from blance_tpu.plan.service import PlanServiceClosed

        with pytest.raises(PlanServiceClosed):
            await fc.service.submit(_tiny_tenant("x", 0))

    with use_recorder(rec):
        loop.run_until_complete(drive())


def test_session_and_planner_are_mutually_exclusive():
    class _FakePlanner:
        async def plan_cycle(self, *a):
            raise AssertionError("never called")

    nodes, pmap = _cluster()
    with pytest.raises(ValueError, match="mutually exclusive"):
        RebalanceController(M, nodes, pmap, lambda *a: None,
                            session=object(), planner=_FakePlanner())


# -- CarryCache eviction observability ----------------------------------------


def _carry_for(cache, key, n=64):
    from blance_tpu.plan.tensor import SolveCarry

    used = np.zeros((2, n), np.float32)
    carry = SolveCarry(prices=used.sum(axis=0),
                       assign=np.zeros((4, 2, 1), np.int32), used=used)
    cache.store(key, carry, np.zeros((4, 2, 1), np.int32))
    return carry


def test_carry_cache_eviction_stats_and_labeled_counter():
    rec = Recorder()
    cache = CarryCache(max_bytes=1, recorder=rec)
    _carry_for(cache, "a")  # over the byte budget immediately
    assert cache.evictions.get("bytes") == 1
    cache = CarryCache(max_entries=2, recorder=rec)
    _carry_for(cache, "a")
    _carry_for(cache, "b")
    _carry_for(cache, "c")  # third key: entry-count LRU drops "a"
    assert cache.evictions.get("entries") == 1
    assert sorted(cache.keys()) == ["b", "c"]
    # Shape reset with a live carry counts too.
    big = CarryCache(recorder=rec)
    _carry_for(big, "k")
    big.entry("k", partitions=9)  # re-shaped problem
    assert big.evictions.get("shape") == 1
    # The labeled counter landed, one series per reason.
    assert rec.counters.get('fleet.carry_evictions{reason="bytes"}') == 1
    assert rec.counters.get(
        'fleet.carry_evictions{reason="entries"}') == 1
    assert rec.counters.get('fleet.carry_evictions{reason="shape"}') == 1
    stats = cache.stats()
    assert stats["evictions"] == cache.evictions
    assert stats["entries"] == len(cache.keys())


# -- fleet SLO rollup ---------------------------------------------------------


def test_fleet_rollup_math_and_gauges():
    rec = Recorder()
    _nodes, pa = _cluster(parts=4)
    _nodes, pb = _cluster(parts=4)
    ta = SloTracker(pa, recorder=rec, publish_gauges=False)
    tb = SloTracker(pb, recorder=rec, publish_gauges=False)
    roll = FleetSloRollup(availability_floor=0.9, recorder=rec)
    roll.register("a", ta)
    roll.register("b", tb)
    tb.strip_nodes({"n0", "n1", "n2", "n3", "n4", "n5"})
    s = roll.summary()
    assert s.tenants == 2
    assert s.availability_min == 0.0 and s.worst_tenant == "b"
    assert s.availability_mean == 0.5
    assert s.tenants_below_floor == 1
    roll.publish()
    assert rec.gauges["slo.fleet_availability_min"] == 0.0
    assert rec.gauges["slo.fleet_availability_mean"] == 0.5
    assert rec.gauges["slo.fleet_tenants_below_floor"] == 1.0
    assert rec.gauges["fleet.tenants"] == 2.0
    # publish_gauges=False really silenced the per-tenant writes.
    assert "slo.partition_availability" not in rec.gauges


def test_fleet_loop_emits_only_declared_metrics():
    """Everything the fleet plane emits is in the registry (the
    test_telemetry drift guard covers docs <-> registry; this covers
    emission <-> registry)."""
    nodes, _ = _cluster()
    loop = DeterministicLoop(FifoPolicy(), max_steps=1_000_000)
    rec = Recorder(clock=loop.time)

    async def drive():
        async def assign(stop_ch, node, partitions, states, ops):
            await asyncio.sleep(1.0)

        fc = FleetController(nodes, inline_solve=True,
                             admission_window_s=0.25, debounce_s=0.5,
                             fair_share=2, carry_bytes=0,
                             availability_floor=0.8, recorder=rec)
        await fc.start()
        for j in range(3):
            _n, pmap = _cluster()
            fc.add_tenant(f"t{j}", M, pmap, assign)
        fc.submit_all(ClusterDelta(fail=("n0",)))
        await fc.quiesce_all()
        await fc.stop()

    with use_recorder(rec):
        loop.run_until_complete(drive())
    assert rec.counters.get("fleet.batches", 0) > 0
    assert default_registry().undeclared(rec) == []


# -- the multi-hundred-tenant week (the acceptance soak) ----------------------


@pytest.mark.slow
def test_fleet_week_multi_hundred_tenants_replays_bit_identically():
    """ISSUE 13 acceptance: a multi-hundred-tenant simulated week
    (staggered onboarding + correlated zone outage + spot burst +
    noisy-neighbor waves) replays bit-identically — event log, SLO
    summaries, rendered exposition — with coalescing collapsing the
    fleet's plan requests into a small number of bucketed dispatches."""
    scn = fleet_week()  # 240 tenants, 7 virtual days
    a = run_fleet_scenario(scn)
    b = run_fleet_scenario(scn)
    assert a.log_text() == b.log_text()
    assert a.exposition == b.exposition
    assert a.summaries == b.summaries
    assert a.complete
    assert a.tenants >= 200
    assert a.fleet.availability_min == 1.0
    assert a.unconverged == 0
    # The coalescing economics at fleet scale: way fewer dispatches
    # than plan requests (4x margin is conservative vs the ~4.6x
    # measured on the committed configuration).
    assert a.dispatches * 4 <= a.plan_requests


# -- cbgt's booster on the fleet path -----------------------------------------


def _boosted_opts(nodes, **kw):
    """Server groups of four, the replica outside the primary's group,
    cbgt's booster and one negative-weight node (boost 2 over the
    default stickiness)."""
    from blance_tpu.core.types import HierarchyRule, PlanOptions
    from blance_tpu.plan.native import cbgt_node_score_booster

    parents = {n: f"g{i // 4}" for i, n in enumerate(nodes)}
    parents.update({f"g{i}": "c0" for i in range(len(nodes) // 4)})
    return PlanOptions(node_hierarchy=parents,
                       hierarchy_rules={"replica": [HierarchyRule(2, 1)]},
                       node_score_booster=cbgt_node_score_booster,
                       node_weights={nodes[5]: -2}, **kw)


def _random_map(nodes, parts, seed):
    rng = np.random.default_rng(seed)
    pmap = {}
    for i in range(parts):
        a, b = rng.choice(len(nodes), 2, replace=False)
        p = f"p{i:03d}"
        pmap[p] = Partition(p, {"primary": [nodes[a]],
                                "replica": [nodes[b]]})
    return pmap


@pytest.mark.parametrize("seed", [3, 4])
def test_boosted_planner_matches_bucketed_pipeline(seed):
    """cbgt's booster through ServicePlanner -> PlanService ->
    solve_fleet gives the maps and warnings of the bucketed
    single-problem path (plan_pipeline with shape_bucketing) on the same
    inputs, over a rolling swap: the first removal may ride the warm
    repair, and every later cycle returns the node the one before
    removed, which solves cold."""
    from blance_tpu.plan.tensor import plan_pipeline

    nodes = [f"n{i}" for i in range(12)]
    opts = _boosted_opts(nodes)
    bucketed = dataclasses.replace(opts, shape_bucketing=True)
    loop = DeterministicLoop(FifoPolicy(), max_steps=500_000)
    rec = Recorder(clock=loop.time)

    async def drive():
        svc = PlanService(admission_window_s=0.0, inline_solve=True,
                          recorder=rec, batch_floor=16)
        await svc.start()
        planner = ServicePlanner("t0", svc, recorder=rec)
        current = _random_map(nodes, 40, seed)
        for removes in ([], ["n1"], ["n7"], ["n2"]):
            got, warns = await planner.plan_cycle(current, nodes, removes,
                                                  M, opts)
            want, want_warns, _m = plan_pipeline(
                current, current, nodes, removes, [], M, bucketed)
            assert _nbs(got) == _nbs(want), removes
            assert warns == want_warns, removes
            held = {n for p in got.values()
                    for ns in p.nodes_by_state.values() for n in ns}
            assert "n5" not in held and not set(removes) & held
            current = got
        await svc.stop()

    with use_recorder(rec):
        loop.run_until_complete(drive())
    assert rec.counters["fleet.batches"] == 4


def _hooks():
    from blance_tpu.core.types import PlanOptions

    return {
        "booster": PlanOptions(node_score_booster=lambda w, s: 0.0),
        "scorer": PlanOptions(node_scorer=lambda *a: 0.0),
        "sorter": PlanOptions(node_sorter=lambda ctx, ns: list(ns)),
    }


@pytest.mark.parametrize("hook", ["booster", "scorer", "sorter"])
@pytest.mark.parametrize("door", ["plan_cycle", "add_tenant"])
def test_fleet_doors_refuse_hooks_the_device_cannot_run(hook, door):
    """Any booster but cbgt's, a node_scorer and a node_sorter are still
    refused by both front doors, with the same message."""
    nodes, pmap = _cluster()
    opts = _hooks()[hook]
    loop = DeterministicLoop(FifoPolicy(), max_steps=100_000)
    rec = Recorder(clock=loop.time)

    async def drive():
        fc = FleetController(nodes, inline_solve=True, recorder=rec)
        await fc.start()
        with pytest.raises(ValueError, match="does not support node_score_"
                                             "booster/node_scorer/node_"
                                             "sorter hooks"):
            if door == "add_tenant":
                fc.add_tenant("bad", M, pmap, lambda *a: None,
                              plan_options=opts)
            else:
                await ServicePlanner("bad", fc.service).plan_cycle(
                    pmap, nodes, [], M, opts)
        assert fc.keys() == []
        await fc.stop()

    with use_recorder(rec):
        loop.run_until_complete(drive())


def test_add_tenant_runs_cbgt_booster_through_the_fleet():
    """A cbgt-boosted tenant onboards through FleetController.add_tenant
    and converges through ServicePlanner -> PlanService -> solve_fleet:
    the negative-weight node is emptied, the rules hold."""
    nodes = [f"n{i}" for i in range(12)]
    pmap = _random_map(nodes, 40, 5)
    loop = DeterministicLoop(FifoPolicy(), max_steps=2_000_000)
    rec = Recorder(clock=loop.time)

    async def drive():
        async def assign(stop_ch, node, partitions, states, ops):
            await asyncio.sleep(0.001)

        fc = FleetController(nodes, inline_solve=True, recorder=rec)
        await fc.start()
        ctl = fc.add_tenant("fts", M, pmap, assign,
                            plan_options=_boosted_opts(nodes), kick=True)
        final = await ctl.quiesce()
        await fc.stop()
        return final

    with use_recorder(rec):
        final = loop.run_until_complete(drive())
    assert rec.counters["fleet.requests"] >= 1
    assert rec.counters["fleet.batches"] >= 1
    for p in final.values():
        primary, replica = p.nodes_by_state["primary"], \
            p.nodes_by_state["replica"]
        assert len(primary) == len(replica) == 1
        assert "n5" not in primary + replica
        assert int(primary[0][1:]) // 4 != int(replica[0][1:]) // 4
