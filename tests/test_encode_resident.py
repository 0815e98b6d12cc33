"""Encode residency (ISSUE 14, docs/DESIGN.md "Encode residency"):
the delta-resident tenant encode layer (plan/resident.py + the
ServicePlanner warm protocol in fleetloop.py).

The contract under test: a warm converge cycle's delta-patched resident
state is BIT-EXACTLY the full ``encode_problem`` re-encode of the same
inputs — across every delta family (abrupt fail + strip, graceful
remove, re-add after fail, weight drift, brand-new node add, adopted
passes) — and incremental decode is bit-identical to the full
``decode_assignment`` (maps AND shortfall warnings).  Every
off-protocol event (divergent current, statics swap, shape drift,
cache eviction, pass-through states) demotes to a counted cold
re-encode, never a stale map; cold re-encodes are exactly attributable
(``encode_cold == first encodes + demotions + evictions``).  Through
the shared service, residency is a pure perf toggle: the fleet
simulator's event log, SLO summaries and final maps are byte-identical
with it on or off.
"""

import asyncio
import dataclasses
import random

import numpy as np
import pytest

from blance_tpu.core.encode import (
    encode_problem,
    pack_slot_rows,
    strip_prev_rows,
)
from blance_tpu.core.types import (
    HierarchyRule,
    Partition,
    PlanOptions,
    model,
)
from blance_tpu.fleetloop import FleetController, ServicePlanner
from blance_tpu.obs import Recorder, use_recorder
import blance_tpu.plan.fleet as fleet_mod
from blance_tpu.plan.carry import EncodeCache
from blance_tpu.plan.fleet import TenantProblem, solve_fleet
from blance_tpu.plan.resident import build_encoded_state
from blance_tpu.plan.service import PlanService
from blance_tpu.rebalance import ClusterDelta, _strip_nodes
from blance_tpu.testing.fleetsim import run_fleet_scenario, tenant_model
from blance_tpu.testing.scenarios import (
    fleet_noisy_neighbor,
    fleet_onboarding,
    fleet_zone_outage,
)
from blance_tpu.testing.sched import DeterministicLoop, FifoPolicy

M = model(primary=(0, 1), replica=(1, 1))

_ARRAYS = ("constraints", "prev", "partition_weights", "node_weights",
           "valid_node", "stickiness", "gids", "gid_valid")


def _cluster(nodes=12, parts=12, prefix="n"):
    names = [f"{prefix}{i}" for i in range(nodes)]
    pmap = {}
    for i in range(parts):
        p = f"p{i:03d}"
        pmap[p] = Partition(p, {"primary": [names[i % nodes]],
                                "replica": [names[(i + 1) % nodes]]})
    return names, pmap


def _nbs(pmap):
    return {name: {s: list(ns) for s, ns in p.nodes_by_state.items()}
            for name, p in pmap.items()}


def _assert_problem_equal(got, want, ctx=""):
    assert got.nodes == want.nodes, ctx
    assert got.partitions == want.partitions, ctx
    assert got.states == want.states, ctx
    assert got.rules == want.rules, ctx
    for f in _ARRAYS:
        a, b = np.asarray(getattr(got, f)), np.asarray(getattr(want, f))
        assert a.dtype == b.dtype and a.shape == b.shape \
            and np.array_equal(a, b), f"{ctx}: {f} drifted"


# -- array-kernel units -------------------------------------------------------


def test_strip_prev_rows_matches_strip_then_reencode():
    """strip_prev_rows ≡ _strip_nodes + encode_problem, bit-exactly,
    and untouched rows come back byte-identical in a NEW array."""
    nodes, pmap = _cluster(nodes=8, parts=16)
    opts = PlanOptions()
    problem = encode_problem(pmap, pmap, nodes, [], M, opts)
    dark = {"n2", "n5"}
    ids = np.array(sorted(i for i, n in enumerate(nodes) if n in dark),
                   np.int32)
    patched, dirty = strip_prev_rows(problem.prev, ids)
    stripped = _strip_nodes(pmap, dark)
    want = encode_problem(stripped, stripped, nodes, sorted(dark), M,
                          opts)
    assert np.array_equal(patched, want.prev)
    assert patched is not problem.prev  # identity-memo safety
    assert np.array_equal(dirty, (np.isin(problem.prev, ids)
                                  ).any(axis=(1, 2)))


def test_pack_slot_rows_matches_decode_pack():
    rng = np.random.default_rng(3)
    rows = rng.integers(-1, 6, size=(9, 2, 3)).astype(np.int32)
    packed, counts = pack_slot_rows(rows)
    for p in range(rows.shape[0]):
        for s in range(rows.shape[1]):
            row = rows[p, s]
            want = [x for x in row.tolist() if x >= 0]
            assert packed[p, s, :len(want)].tolist() == want
            assert counts[p, s] == len(want)
            # pad is whatever the stable argsort left; non-negative
            # prefix is the contract decode relies on
            assert (packed[p, s, len(want):] < 0).all() or \
                len(want) == rows.shape[2]


# -- the fuzz harness: resident vs never-resident twin ------------------------


class _Twin:
    """One planner + private service on the DeterministicLoop."""

    def __init__(self, rec, resident):
        self.svc = PlanService(admission_window_s=0.0,
                               inline_solve=True, recorder=rec,
                               batch_floor=16)
        self.planner = ServicePlanner(
            "t0", self.svc, recorder=rec,
            encode_residency=resident)
        self.current = None

    async def start(self, initial):
        await self.svc.start()
        self.current = initial

    async def cycle(self, nodes, removes, opts, adopt=True,
                    fresh_current=False):
        cur = self.current
        if fresh_current:
            # An equal-but-new map object: the divergence case.
            cur = {k: Partition(k, {s: list(ns) for s, ns in
                                    p.nodes_by_state.items()})
                   for k, p in cur.items()}
            self.current = cur
        nxt, warns = await self.planner.plan_cycle(
            cur, list(nodes), list(removes), M, opts)
        if adopt:
            self.current = nxt
        return nxt, warns

    def strip(self, dark):
        before = self.current
        self.current = _strip_nodes(self.current, set(dark))
        notify = getattr(self.planner, "notify_strip", None)
        if notify is not None:
            notify(set(dark), before, self.current)


def _run(loop, rec, coro):
    with use_recorder(rec):
        return loop.run_until_complete(coro)


def _check_resident_arrays(twin, nodes, removes, opts, ctx):
    """The resident arrays must equal a from-scratch re-encode of the
    planner's own inputs (its current view + this cycle's statics)."""
    st = twin.planner._encodes.get("t0")
    if st is None:
        return
    want = encode_problem(twin.current if st.expected is twin.current
                          else st.expected,
                          st.expected, list(nodes), list(removes), M,
                          opts)
    _assert_problem_equal(st.problem, want, ctx)


@pytest.mark.parametrize("seed", [7, 19, 83])
def test_fuzz_delta_families_patch_equals_reencode(seed):
    """Seeded random delta sequences over every family — fail+strip,
    graceful remove, re-add after fail, weight drift, brand-new node
    add, zero-delta repeats, forced divergence — with three invariants
    at every cycle: (a) the resident arrays are bit-equal to a full
    re-encode of the same inputs, (b) map + warnings are bit-identical
    to the never-resident twin's, (c) warm/cold SOLVE decisions match
    the twin's exactly (carry-hit/miss counter deltas)."""
    rng = random.Random(seed)
    loop = DeterministicLoop(FifoPolicy(), max_steps=2_000_000)
    rec = Recorder(clock=loop.time)

    async def drive():
        # 12 nodes / 12 partitions: the bucket class every fleet suite
        # compiles, so the fuzz pays no novel XLA programs beyond the
        # two node-add classes (N=13, N=14).
        nodes, pmap = _cluster(nodes=12, parts=12)
        spare = [f"x{i}" for i in range(2)]  # future brand-new adds
        res = _Twin(rec, resident=True)
        base = _Twin(rec, resident=False)
        await res.start(pmap)
        await base.start({k: p.copy() for k, p in pmap.items()})
        removes: set = set()
        failed: set = set()
        weights: dict = {}
        nweights: dict = {}

        def opts_now():
            return PlanOptions(
                partition_weights=dict(weights) or None,
                node_weights=dict(nweights) or None)

        opts = opts_now()
        for step in range(18):
            op = rng.choice(["fail", "remove", "readd", "drift",
                             "ndrift", "add", "noop", "diverge"])
            fresh = False
            if op == "fail":
                live = [n for n in nodes if n not in removes]
                if len(live) > 4:
                    dark = rng.choice(live)
                    failed.add(dark)
                    removes.add(dark)
                    res.strip({dark})
                    base.strip({dark})
            elif op == "remove":
                live = [n for n in nodes if n not in removes]
                if len(live) > 4:
                    removes.add(rng.choice(live))
            elif op == "readd":
                if removes:
                    back = rng.choice(sorted(removes))
                    removes.discard(back)
                    failed.discard(back)
            elif op == "drift":
                weights[f"p{rng.randrange(12):03d}"] = rng.randrange(
                    1, 9)
                opts = opts_now()
            elif op == "ndrift":
                nweights[rng.choice(nodes)] = rng.randrange(1, 5)
                opts = opts_now()
            elif op == "add" and spare:
                nodes = nodes + [spare.pop()]
            elif op == "diverge":
                fresh = True

            h0 = rec.counters.get("plan.solve.carry_hit", 0)
            m0 = rec.counters.get("plan.solve.carry_miss", 0)
            am, aw = await res.cycle(nodes, sorted(removes), opts,
                                     fresh_current=fresh)
            h1 = rec.counters.get("plan.solve.carry_hit", 0)
            m1 = rec.counters.get("plan.solve.carry_miss", 0)
            bm, bw = await base.cycle(nodes, sorted(removes), opts,
                                      fresh_current=fresh)
            h2 = rec.counters.get("plan.solve.carry_hit", 0)
            m2 = rec.counters.get("plan.solve.carry_miss", 0)
            ctx = f"seed={seed} step={step} op={op}"
            assert _nbs(am) == _nbs(bm), ctx
            assert aw == bw, ctx
            assert (h1 - h0, m1 - m0) == (h2 - h1, m2 - m1), \
                f"{ctx}: warm/cold solve decisions diverged"
            _check_resident_arrays(res, nodes, sorted(removes), opts,
                                   ctx)
        await res.svc.stop()
        await base.svc.stop()
        # Residency engaged for real across the run.
        assert rec.counters.get("fleet.encode_warm", 0) > 0
        assert rec.counters.get("fleet.encode_cold", 0) > 0

    _run(loop, rec, drive())


def test_fuzz_with_hierarchy_and_node_adds():
    """The gid-intern append path: rack hierarchy + same-rack rules,
    brand-new nodes joining existing and new racks — patched gid
    columns must equal the full re-encode's (first-seen interning can
    never renumber existing nodes)."""
    loop = DeterministicLoop(FifoPolicy(), max_steps=2_000_000)
    rec = Recorder(clock=loop.time)
    nodes = [f"n{i}" for i in range(8)]
    # x0 joins an existing rack (existing group id reused), x1 a brand
    # new rack (new group id appended) — the two intern paths, added in
    # ONE step so the whole test compiles only two bucket classes.
    extra = ["x0", "x1"]
    parents = {n: f"r{i % 4}" for i, n in enumerate(nodes)}
    parents.update({"x0": "r1", "x1": "r9"})
    for r in list(set(parents.values())):
        parents[r] = "dc"
    rules = {"replica": [HierarchyRule(include_level=2,
                                       exclude_level=1)]}
    _n, pmap = _cluster(nodes=8, parts=12)
    opts = PlanOptions(node_hierarchy=parents, hierarchy_rules=rules)

    async def drive():
        res = _Twin(rec, resident=True)
        base = _Twin(rec, resident=False)
        await res.start(pmap)
        await base.start({k: p.copy() for k, p in pmap.items()})
        seq = [list(nodes), list(nodes),
               list(nodes) + extra, list(nodes) + extra,
               list(nodes) + extra]
        removes = []
        for step, ns in enumerate(seq):
            if step == 3:
                removes = ["n3"]
            am, aw = await res.cycle(ns, removes, opts)
            bm, bw = await base.cycle(ns, removes, opts)
            ctx = f"hier step={step}"
            assert _nbs(am) == _nbs(bm), ctx
            assert aw == bw, ctx
            _check_resident_arrays(res, ns, removes, opts, ctx)
        assert rec.counters.get("fleet.encode_warm", 0) >= 3
        await res.svc.stop()
        await base.svc.stop()

    _run(loop, rec, drive())


def test_incremental_decode_warnings_bit_identical():
    """Constraint shortfalls (more constraint slots than live nodes)
    must produce the exact full-decode warnings from the incremental
    path — content AND dict construction order."""
    loop = DeterministicLoop(FifoPolicy(), max_steps=1_000_000)
    rec = Recorder(clock=loop.time)
    nodes, pmap = _cluster()  # the shared 12/12 bucket class
    dark = [f"n{i}" for i in range(11)]  # one live node: replica short

    async def drive():
        res = _Twin(rec, resident=True)
        base = _Twin(rec, resident=False)
        await res.start(pmap)
        await base.start({k: p.copy() for k, p in pmap.items()})
        opts = PlanOptions()
        for removes in ([], dark, dark):
            am, aw = await res.cycle(nodes, removes, opts)
            bm, bw = await base.cycle(nodes, removes, opts)
            assert _nbs(am) == _nbs(bm)
            assert aw == bw
            assert list(aw.keys()) == list(bw.keys())
        assert rec.counters.get("fleet.decode_patch", 0) > 0
        assert aw  # the shortfall rounds really warned
        await res.svc.stop()
        await base.svc.stop()

    _run(loop, rec, drive())


# -- class-batch decode shares (plan/fleet.py DecodeShare) -------------------


def _state_pair(parts):
    """Two resident states of one tenant over the same map: the first
    decodes from the class share, its twin by its own array path."""
    names, pmap = _cluster(parts=parts)
    opts = PlanOptions()
    return [build_encoded_state(
        encode_problem(pmap, pmap, names, [], M, opts), pmap, [], M, opts)
        for _ in range(2)]


def _dirty_rows(st, dark):
    """The planner's delta mask: the rows holding a dark node."""
    ids = [st.node_index[n] for n in dark]
    return np.isin(st.problem.prev, ids).any(axis=(1, 2))


# Each case: the tenants' partition counts (12 rides the module's shared
# 12/12 class; 17 and 18 share the 18/12 class, where 17 has a pad row),
# then per cycle the removed nodes, whether to run warm off the last
# carries, and which tenants' dirty masks lie (empty) so their repair
# ripples and is declined into the class's cold batch.
_SHARE_CASES = {
    "pad_rows": ((12, 17, 18), [([], False, ()), (["n3"], False, ()),
                                (["n3", "n7"], False, ())]),
    "shortfall": ((12, 12), [([], False, ()),
                             ([f"n{i}" for i in range(11)], False, ()),
                             ([f"n{i}" for i in range(11)], False, ())]),
    "declined_warm": ((12, 12), [([], False, ()), (["n5"], True, (1,))]),
    "adopt_cycles": ((12, 12, 12), [([], False, ()), (["n1"], True, ()),
                                    (["n1", "n4"], True, ()),
                                    (["n4"], False, ()),
                                    (["n4", "n9"], True, ())]),
    "stale_share": ((12, 17), [([], False, ()), (["n2"], False, ()),
                               (["n2", "n6"], False, ())]),
}


@pytest.mark.parametrize("case", list(_SHARE_CASES))
def test_class_share_decode_equals_own_decode(case, monkeypatch):
    """The class batch's decode share (diff, pack, counts and shortfall
    rows computed once per class, vectorized) decodes every tenant
    bit-identically to its own per-tenant array path: the same map in
    the same order, the same warnings in their order, and the same
    staged Proposal (``packed``, ``counts``, ``changed``).  A share not
    computed against the held ``prev`` (``stale_share``) takes the
    tenant's own path and still gives the same answer."""
    sizes, cycles = _SHARE_CASES[case]
    if case == "declined_warm":
        # Bypass the host precheck so the batched repair itself sees
        # the lying mask's ripple and declines (tests/test_fleet.py).
        monkeypatch.setattr(fleet_mod, "capacity_shrank",
                            lambda *a, **k: False)
    pairs = [_state_pair(p) for p in sizes]
    rec = Recorder()
    results = None
    for step, (dark, warm, lying) in enumerate(cycles):
        problems = []
        for j, (a, b) in enumerate(pairs):
            for st in (a, b):
                st.apply_removes(frozenset(dark))
            carry = dirty = None
            if warm:
                carry = results[j].carry
                dirty = _dirty_rows(a, dark)
                if j in lying:
                    dirty = np.zeros_like(dirty)
            problems.append(TenantProblem.from_dense(
                f"t{j}", a.problem, carry=carry, dirty=dirty))
        with use_recorder(rec):
            results = solve_fleet(problems, batch_floor=16, recorder=rec)
        for j, ((a, b), r) in enumerate(zip(pairs, results)):
            ctx = f"{case} step={step} tenant={j}"
            share = r.share
            assert share is not None and share.prev is problems[j].prev
            stale = case == "stale_share" and step == len(cycles) - 1
            if stale:
                share = dataclasses.replace(share, prev=share.prev.copy())
            held = a.map is not None
            ma, wa, fa, na, ba = a.decode(r.assign, a.expected, dark,
                                          share)
            mb, wb, fb, nb, bb = b.decode(r.assign, b.expected, dark)
            assert ba == (held and not stale) and not bb, ctx
            assert (fa, na) == (fb, nb) and fa == (not held), ctx
            assert list(ma) == list(mb) and _nbs(ma) == _nbs(mb), ctx
            assert wa == wb and list(wa) == list(wb), ctx
            pa, pb = a.pending, b.pending
            assert pa.changed == pb.changed, ctx
            for f in ("packed", "counts"):
                x, y = getattr(pa, f), getattr(pb, f)
                assert x.dtype == y.dtype and x.shape == y.shape \
                    and np.array_equal(x, y), f"{ctx}: {f}"
            for st in (a, b):
                st.adopt(st.pending, st.pending.map)
        if case == "shortfall" and step:
            assert all(r.share.short for r in results)
    assert all(a.map is not None for a, _b in pairs)
    if case == "declined_warm":
        assert rec.counters.get("plan.solve.warm_fallback", 0) >= 1
        assert not results[1].warm
    if case == "adopt_cycles":
        assert rec.counters.get("plan.solve.carry_hit", 0) > 0


# -- demotion paths -----------------------------------------------------------


def test_divergence_statics_shape_and_eviction_each_demote_cold():
    """Every off-protocol event costs exactly one counted demotion (or
    eviction) followed by one cold re-encode — never a stale map."""
    loop = DeterministicLoop(FifoPolicy(), max_steps=2_000_000)
    rec = Recorder(clock=loop.time)
    nodes, pmap = _cluster()

    async def drive():
        res = _Twin(rec, resident=True)
        base = _Twin(rec, resident=False)
        await res.start(pmap)
        await base.start({k: p.copy() for k, p in pmap.items()})
        opts = PlanOptions()
        cache = res.planner._encodes

        def cold():
            return int(rec.counters.get("fleet.encode_cold", 0))

        await res.cycle(nodes, [], opts)
        await base.cycle(nodes, [], opts)
        assert cold() == 1

        # (1) divergence: an equal-but-new current object.
        am, _ = await res.cycle(nodes, [], opts, fresh_current=True)
        bm, _ = await base.cycle(nodes, [], opts, fresh_current=True)
        assert _nbs(am) == _nbs(bm)
        assert cold() == 2
        assert cache.demotions.get("divergence") == 1

        # (2) statics: a swapped hierarchy object.
        hier = {n: "r0" for n in nodes}
        hopts = PlanOptions(node_hierarchy=hier)
        am, _ = await res.cycle(nodes, [], hopts)
        bm, _ = await base.cycle(nodes, [], hopts)
        assert _nbs(am) == _nbs(bm)
        assert cold() == 3
        assert cache.demotions.get("statics") == 1

        # (3) eviction: byte-budget pressure drops the live state
        # (budgets enforce at cold-build puts; simulate pressure by
        # re-enforcing directly) — the next cycle solves cold.
        cache.max_bytes = 0
        cache._enforce_budget()
        assert cache.evictions.get("bytes", 0) >= 1
        cache.max_bytes = None
        am, _ = await res.cycle(nodes, [], hopts)
        bm, _ = await base.cycle(nodes, [], hopts)
        assert _nbs(am) == _nbs(bm)
        assert cold() == 4

        # Attribution identity: every cold is a first encode, a
        # demotion or an eviction.
        demos = sum(cache.demotions.values())
        evs = sum(cache.evictions.values())
        assert cold() == 1 + demos + evs
        await res.svc.stop()
        await base.svc.stop()

    _run(loop, rec, drive())


def test_shape_drift_demotes():
    """An initial map wider than the constraints (R=2 for a 1-slot
    state) narrows after the first adopted proposal — fresh encode
    would pick a smaller R, so the resident state must demote with
    reason 'shape' instead of solving at a stale slot depth."""
    loop = DeterministicLoop(FifoPolicy(), max_steps=1_000_000)
    rec = Recorder(clock=loop.time)
    nodes = [f"n{i}" for i in range(12)]
    pmap = {}
    for i in range(12):
        p = f"p{i:03d}"
        extra = [nodes[(i + 2) % 12]] if i == 0 else []
        pmap[p] = Partition(p, {
            "primary": [nodes[i % 12]] + extra,
            "replica": [nodes[(i + 1) % 12]]})

    async def drive():
        res = _Twin(rec, resident=True)
        base = _Twin(rec, resident=False)
        await res.start(pmap)
        await base.start({k: p.copy() for k, p in pmap.items()})
        opts = PlanOptions()
        st0 = None
        for step in range(3):
            am, _ = await res.cycle(nodes, [], opts)
            bm, _ = await base.cycle(nodes, [], opts)
            assert _nbs(am) == _nbs(bm), step
            if step == 0:
                st0 = res.planner._encodes.get("t0")
                assert st0 is not None and st0.problem.R == 2
        assert res.planner._encodes.demotions.get("shape", 0) >= 1
        await res.svc.stop()
        await base.svc.stop()

    _run(loop, rec, drive())


def test_passthrough_states_stay_on_full_path():
    """A map carrying an unmodeled state is out of residency protocol:
    every cycle re-encodes/decodes fully (no resident state is built),
    and results still match the never-resident twin bit-exactly —
    including the pass-through placements."""
    loop = DeterministicLoop(FifoPolicy(), max_steps=1_000_000)
    rec = Recorder(clock=loop.time)
    nodes, pmap = _cluster()  # the shared 12/12 bucket class
    for p in pmap.values():
        p.nodes_by_state["archive"] = [nodes[3]]

    async def drive():
        res = _Twin(rec, resident=True)
        base = _Twin(rec, resident=False)
        await res.start(pmap)
        await base.start({k: p.copy() for k, p in pmap.items()})
        opts = PlanOptions()
        for _ in range(3):
            am, aw = await res.cycle(nodes, [], opts)
            bm, bw = await base.cycle(nodes, [], opts)
            assert _nbs(am) == _nbs(bm)
            assert aw == bw
        assert res.planner._encodes.get("t0") is None
        assert rec.counters.get("fleet.encode_warm", 0) == 0
        assert rec.counters.get("fleet.decode_patch", 0) == 0
        await res.svc.stop()
        await base.svc.stop()

    _run(loop, rec, drive())


# -- EncodeCache --------------------------------------------------------------


def test_encode_cache_lru_budgets_and_counters():
    rec = Recorder()

    class _Fake:
        def __init__(self, n):
            self._n = n

        def nbytes(self):
            return self._n

    c = EncodeCache(max_entries=2, recorder=rec)
    c.put("a", _Fake(10))
    c.put("b", _Fake(10))
    c.get("a")  # bump recency: "b" is now LRU
    c.put("c", _Fake(10))
    assert sorted(c.keys()) == ["a", "c"]
    assert c.evictions.get("entries") == 1
    assert rec.counters.get(
        'fleet.encode_evictions{reason="entries"}') == 1

    c = EncodeCache(max_bytes=25, recorder=rec)
    c.put("a", _Fake(10))
    c.put("b", _Fake(10))
    c.put("c", _Fake(10))  # 30 bytes: oldest goes
    assert sorted(c.keys()) == ["b", "c"]
    assert c.evictions.get("bytes") == 1

    c.invalidate("b", "divergence")
    assert c.keys() == ["c"]
    assert c.demotions.get("divergence") == 1
    c.invalidate("b", "divergence")  # gone: not double-counted
    assert c.demotions.get("divergence") == 1
    stats = c.stats()
    assert stats["entries"] == 1 and stats["bytes"] == 10
    with pytest.raises(ValueError):
        EncodeCache(max_entries=0)
    with pytest.raises(ValueError):
        EncodeCache(max_bytes=-1)


# -- through the shared service (controller + simulator) ----------------------


@pytest.mark.parametrize("family,kw", [
    (fleet_zone_outage, dict(seed=5, tenants=6)),
    (fleet_onboarding, dict(seed=13, tenants=8)),
    (fleet_noisy_neighbor, dict(seed=29, tenants=6)),
])
def test_residency_is_pure_perf_through_the_fleet(family, kw):
    """Residency on vs off across the scenario families: byte-identical
    event logs, equal SLO summaries and final maps — residency is a
    pure perf change — plus the cold-attribution identity on the
    resident run."""
    scn = family(**kw)
    on = run_fleet_scenario(scn)
    off = run_fleet_scenario(scn, encode_residency=False)
    assert on.log_text() == off.log_text()
    assert on.summaries == off.summaries
    assert {k: _nbs(m) for k, m in on.final_maps.items()} == \
        {k: _nbs(m) for k, m in off.final_maps.items()}
    assert on.encode_warm > 0
    assert off.encode_warm == 0 and off.encode_cold == 0
    # Two-sided attribution: one state-establishing cold per tenant,
    # every extra preceded by a counted demotion/eviction (a demotion
    # on a tenant's final cycle has no rebuilding cold, hence <=).
    attributable = on.tenants + sum(on.encode_demotions.values()) \
        + sum(on.encode_evictions.values())
    assert on.tenants <= on.encode_cold <= attributable
    # Steady-state warm cycles: no full re-encode, no full decode
    # beyond the attributable colds.
    assert on.decode_full == on.encode_cold
    assert on.decode_patch == on.encode_warm


def test_warm_patch_bytes_bounded_by_patched_rows():
    """A warm cycle uploads O(delta): the patch bytes of the resident
    run stay within the patched-row count times one row's ceiling (a
    strip/adopt row's prev scatter and counts, plus its weight row) and
    a per-warm-cycle scalar slack for node-add columns and dark-set
    flips."""
    scn = fleet_zone_outage(seed=5, tenants=8, partitions=(48, 64))
    res = run_fleet_scenario(scn)
    mdl = tenant_model(scn.tenants[0])
    s = len(mdl)
    r = max(st.constraints for st in mdl.values())
    row_cap = s * r * 4 + s * 8 + 4 + 4 * s
    assert res.encode_warm > 0 and res.encode_patch_rows > 0
    assert res.encode_patch_bytes <= \
        res.encode_patch_rows * row_cap + 256 * res.encode_warm


def test_supersede_divergence_demotes_and_recovers():
    """A delta landing mid-orchestration supersedes the pass; the
    achieved map diverges from the proposal, the planner demotes
    (reason divergence) and the next cycle re-encodes cold — final maps
    still identical to the never-resident controller."""

    def run(residency):
        loop = DeterministicLoop(FifoPolicy(), max_steps=2_000_000)
        rec = Recorder(clock=loop.time)

        async def drive():
            nodes, pmap = _cluster()

            async def slow_assign(stop_ch, node, partitions, states,
                                  ops):
                await asyncio.sleep(5.0)

            fc = FleetController(nodes, inline_solve=True,
                                 debounce_s=0.5, recorder=rec,
                                 encode_residency=residency)
            await fc.start()
            fc.add_tenant("t", M, pmap, slow_assign)
            fc.submit("t", ClusterDelta(fail=("n0",)))
            # Let the first pass start moving, then supersede it.
            await asyncio.sleep(2.0)
            fc.submit("t", ClusterDelta(fail=("n1",)))
            maps = await fc.quiesce_all()
            sup = fc.superseded
            demos = (dict(fc.encode_cache.demotions)
                     if fc.encode_cache is not None else {})
            await fc.stop()
            return maps, sup, demos

        with use_recorder(rec):
            return loop.run_until_complete(drive())

    on_maps, on_sup, demos = run(True)
    off_maps, off_sup, _ = run(False)
    assert on_sup == off_sup and on_sup >= 1
    assert demos.get("divergence", 0) >= 1
    assert {k: _nbs(m) for k, m in on_maps.items()} == \
        {k: _nbs(m) for k, m in off_maps.items()}


def test_fleet_loop_resident_emissions_are_registry_declared():
    """The residency plane's emissions (encode/decode counters,
    patch histograms, eviction/demotion labels, h2d bytes) are all
    declared in the registry."""
    from blance_tpu.obs.expo import default_registry

    scn = fleet_zone_outage(seed=5, tenants=4)
    loop = DeterministicLoop(FifoPolicy(), max_steps=scn.max_steps)
    rec = Recorder(clock=loop.time)
    from blance_tpu.testing.fleetsim import _fleet_main

    with use_recorder(rec):
        loop.run_until_complete(_fleet_main(scn, loop, rec, True))
    assert rec.counters.get("fleet.encode_warm", 0) > 0
    assert rec.counters.get("fleet.h2d_bytes", 0) > 0
    assert default_registry().undeclared(rec) == []
