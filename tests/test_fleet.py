"""Fleet tier: batched multi-tenant solves, carry cache, plan service.

The acceptance contract this module pins (ISSUE 7):

- batched ``[B, P, S, N]`` fleet solves are BIT-IDENTICAL to running
  each tenant through the existing single-problem path on the same
  padded arrays — across ≥ 2 bucket classes, cold AND warm-carry, on
  and off the batch-sharding mesh;
- bucket-boundary bit-neutrality: the inert-padding recipe cannot
  perturb real rows (unpadded-with-p_real == bucket-padded-with-p_real
  on the real rows), and tenants straddling a ``bucket_size`` boundary
  land in different classes yet each still matches its sequential
  solve;
- the keyed :class:`plan.carry.CarryCache` preserves the session's
  carry lifecycle (identity/value matching, pending promotion,
  dirty-mask routing, node padding) under an LRU byte budget whose
  evictions only ever cost a cold solve;
- the asyncio :class:`plan.service.PlanService` coalesces concurrent
  submits into per-class batches, reuses per-tenant carries across
  rounds (warm), applies backpressure via its bounded queue, fails
  cleanly on stop, and emits only registry-declared metrics.
"""

import asyncio
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from blance_tpu.core.encode import bucket_size, pad_problem_arrays, pad_to
from blance_tpu.obs import InMemorySink, Recorder, use_recorder
from blance_tpu.plan.carry import (
    CarryCache,
    capacity_shrank,
    effective_dirty,
    pad_carry_nodes,
)
from blance_tpu.plan import fleet as fleet_mod
from blance_tpu.plan import tensor as tensor_mod
from blance_tpu.plan.fleet import (
    TenantProblem,
    _normalized,
    batch_class_of,
    solve_fleet,
    tier_band_refusals,
)
from blance_tpu.plan.service import PlanService, PlanServiceClosed
from blance_tpu.plan.session import PlannerSession
from blance_tpu.plan.tensor import (
    SolveCarry,
    _solve_dense_converged_impl,
    solve_dense_converged,
    solve_dense_warm,
)

CONSTRAINTS = (1, 1)
RULES = ((), ((2, 1),))  # replica on another rack


def make_tenant(P, N, seed, key=None, weights=False):
    rng = np.random.default_rng(seed)
    prev = np.full((P, 2, 1), -1, np.int32)
    prev[:, 0, 0] = rng.integers(0, N, P)
    prev[:, 1, 0] = (prev[:, 0, 0] + 1 + rng.integers(0, N - 1, P)) % N
    pw = rng.integers(1, 3, P).astype(np.float32) if weights \
        else np.ones(P, np.float32)
    return TenantProblem(
        key=key or f"t{P}x{N}s{seed}", prev=prev,
        partition_weights=pw,
        node_weights=np.ones(N, np.float32),
        valid_node=np.ones(N, bool),
        stickiness=np.full((P, 2), 1.5, np.float32),
        gids=np.stack([np.arange(N, dtype=np.int32),
                       np.arange(N, dtype=np.int32) // 4,
                       np.zeros(N, np.int32)]),
        gid_valid=np.ones((3, N), bool),
        constraints=CONSTRAINTS, rules=RULES)


def solve_sequential(t):
    """The existing single-problem path on the tenant's class-padded
    arrays (bucketed solve_dense_converged + real-P fill denominator):
    the fleet solver's bit-identity reference.  Returns (real-row
    assign, padded carry)."""
    k = batch_class_of(t)
    arrs = pad_problem_arrays(
        t.prev, t.partition_weights, t.node_weights, t.valid_node,
        t.stickiness, t.gids, t.gid_valid, k.p, k.n)
    out, carry = solve_dense_converged(
        *[jnp.asarray(a) for a in arrs], t.constraints, t.rules,
        max_iterations=10, fused_score="off", record=False,
        return_carry=True,
        p_real=jax.device_put(np.float32(t.prev.shape[0])))
    return np.asarray(out)[:t.prev.shape[0]], carry


def delta_tenant(t, result, victim_rank=0):
    """Round-2 tenant: remove one held node, session-style dirty mask,
    carry from round 1."""
    held = np.unique(result.assign[result.assign >= 0])
    v = held[victim_rank % len(held)]
    valid2 = t.valid_node.copy()
    valid2[v] = False
    dirty = (result.assign == v).any(axis=(1, 2))
    return TenantProblem(
        key=t.key, prev=result.assign,
        partition_weights=t.partition_weights,
        node_weights=t.node_weights, valid_node=valid2,
        stickiness=t.stickiness, gids=t.gids, gid_valid=t.gid_valid,
        constraints=t.constraints, rules=t.rules,
        carry=result.carry, dirty=dirty), int(v)


# Two bucket classes ([16, 32) octave buckets are 2 wide): P 17/18 ->
# class 18, P 19/20 -> class 20.  Module-scoped so every test shares
# the compiled batch programs.
@pytest.fixture(scope="module")
def fleet_round1():
    tenants = [make_tenant(17 + (i % 4), 8, seed=i, weights=i % 3 == 0)
               for i in range(12)]
    results = solve_fleet(tenants)
    return tenants, results


# -- batch classes -----------------------------------------------------------


def test_batch_classes_follow_shape_buckets():
    same_a = batch_class_of(make_tenant(17, 8, 0))
    same_b = batch_class_of(make_tenant(18, 8, 1))
    other_p = batch_class_of(make_tenant(19, 8, 2))
    other_n = batch_class_of(make_tenant(17, 9, 3))
    assert same_a == same_b  # straddles nothing: one padded program
    assert same_a != other_p  # crosses the P bucket boundary
    assert same_a != other_n  # crosses the N bucket boundary
    assert same_a.p == bucket_size(17) == 18


def test_fleet_rejects_underdeep_slots():
    t = make_tenant(8, 4, 0)
    bad = TenantProblem(
        key="bad", prev=t.prev, partition_weights=t.partition_weights,
        node_weights=t.node_weights, valid_node=t.valid_node,
        stickiness=t.stickiness, gids=t.gids, gid_valid=t.gid_valid,
        constraints=(2, 1), rules=t.rules)  # R=1 < max constraint 2
    with pytest.raises(ValueError, match="slot depth"):
        solve_fleet([bad])


# -- cold bit-identity -------------------------------------------------------


def test_cold_batch_bit_identical_across_two_classes(fleet_round1):
    tenants, results = fleet_round1
    classes = {batch_class_of(t) for t in tenants}
    assert len(classes) == 2
    for t, r in zip(tenants, results):
        ref, ref_carry = solve_sequential(t)
        assert np.array_equal(ref, r.assign), t.key
        n = t.node_weights.shape[0]
        # The rebuilt carry must seed the next warm solve exactly like
        # the sequential path's: bit-equal used table (real columns).
        assert np.array_equal(np.asarray(ref_carry.used)[:, :n],
                              np.asarray(r.carry.used)), t.key
        assert not r.warm and r.sweeps >= 1


def test_fleet_results_keep_input_order_and_keys(fleet_round1):
    tenants, results = fleet_round1
    assert [r.key for r in results] == [t.key for t in tenants]


def test_degenerate_tenant_passes_through():
    t = make_tenant(6, 4, 0)
    empty = TenantProblem(
        key="empty", prev=np.zeros((0, 2, 1), np.int32),
        partition_weights=np.zeros(0, np.float32),
        node_weights=t.node_weights, valid_node=t.valid_node,
        stickiness=np.zeros((0, 2), np.float32), gids=t.gids,
        gid_valid=t.gid_valid, constraints=CONSTRAINTS, rules=RULES)
    res = solve_fleet([empty, t])
    assert res[0].klass is None and res[0].assign.shape == (0, 2, 1)
    assert np.array_equal(res[1].assign, solve_sequential(t)[0])


# -- bucket-boundary bit-neutrality ------------------------------------------


def test_bucket_padding_is_bit_neutral_on_real_rows():
    """The inert-padding recipe: solving the unpadded problem (with the
    traced real-P denominator) and the bucket-padded problem must agree
    bit-for-bit on the real rows — padding can never perturb a solve."""
    for P, N, seed in [(17, 9, 0), (19, 9, 1), (15, 10, 2)]:
        t = make_tenant(P, N, seed, weights=True)
        args_u = (t.prev, t.partition_weights, t.node_weights,
                  t.valid_node, t.stickiness, t.gids, t.gid_valid)
        out_u, _ = _solve_dense_converged_impl(
            *[jnp.asarray(a) for a in args_u], t.constraints, t.rules,
            max_iterations=10, fused_score="off",
            p_real=jax.device_put(np.float32(P)))
        k = batch_class_of(t)
        arrs_p = pad_problem_arrays(
            t.prev, t.partition_weights, t.node_weights, t.valid_node,
            t.stickiness, t.gids, t.gid_valid, k.p, k.n)
        out_p, _ = _solve_dense_converged_impl(
            *[jnp.asarray(a) for a in arrs_p], t.constraints, t.rules,
            max_iterations=10, fused_score="off",
            p_real=jax.device_put(np.float32(P)))
        assert np.array_equal(np.asarray(out_u),
                              np.asarray(out_p)[:P]), (P, N)


def test_boundary_straddling_tenants_solve_identically():
    """P just below vs just above a bucket boundary (16 | 17 -> buckets
    16 | 18) lands in different classes; the batched solve of BOTH must
    still match each tenant's sequential solve bit-for-bit."""
    below = make_tenant(16, 8, 5)
    above = make_tenant(17, 8, 6)
    kb, ka = batch_class_of(below), batch_class_of(above)
    assert (kb.p, ka.p) == (16, 18)
    for t, r in zip([below, above], solve_fleet([below, above])):
        assert np.array_equal(r.assign, solve_sequential(t)[0])


# -- warm bit-identity -------------------------------------------------------


def test_warm_batch_bit_identical_and_accepted(fleet_round1):
    tenants, results = fleet_round1
    round2 = [delta_tenant(t, r)[0] for t, r in zip(tenants, results)]
    res2 = solve_fleet(round2)
    assert all(r.warm for r in res2), "confined deltas must ride warm"
    for t, r in zip(round2, res2):
        k = batch_class_of(t)
        arrs = pad_problem_arrays(
            t.prev, t.partition_weights, t.node_weights, t.valid_node,
            t.stickiness, t.gids, t.gid_valid, k.p, k.n)
        cu = pad_to(np.asarray(t.carry.used, np.float32), 1, k.n, 0.0)
        dirty_p = pad_to(
            effective_dirty(t.dirty, t.prev, t.constraints), 0, k.p,
            True)
        wout, wcarry = solve_dense_warm(
            *arrs, t.constraints, t.rules,
            dirty=dirty_p,
            carry=SolveCarry(prices=cu.sum(axis=0), assign=arrs[0],
                             used=cu),
            fused_score="off", record=False, donate=False,
            p_real=jax.device_put(np.float32(t.prev.shape[0])))
        assert wout is not None, f"{t.key}: sequential warm declined"
        p, n = t.prev.shape[0], t.node_weights.shape[0]
        assert np.array_equal(wout[:p], r.assign), t.key
        assert np.array_equal(np.asarray(wcarry.used)[:, :n],
                              np.asarray(r.carry.used)), t.key
        assert r.sweeps == 1


def _under_marked(tenants, results):
    """A node-removal delta whose dirty mask lies (all-False): the
    removed node's holders MUST move, so a warm repair ripples."""
    t0, r0 = tenants[0], results[0]
    with_delta, _v = delta_tenant(t0, r0)
    return TenantProblem(
        key=t0.key, prev=with_delta.prev,
        partition_weights=with_delta.partition_weights,
        node_weights=with_delta.node_weights,
        valid_node=with_delta.valid_node,
        stickiness=with_delta.stickiness, gids=with_delta.gids,
        gid_valid=with_delta.gid_valid,
        constraints=with_delta.constraints, rules=with_delta.rules,
        carry=with_delta.carry,
        dirty=np.zeros(with_delta.prev.shape[0], bool))


def test_capacity_precheck_demotes_unmarkable_delta(fleet_round1):
    """Session parity: a shrink the dirty mask doesn't cover is caught
    by the host precheck BEFORE wasting a repair sweep (carry_miss,
    no warm attempt), and the cold result is the sequential one."""
    lying = _under_marked(*fleet_round1)
    rec = Recorder()
    with use_recorder(rec):
        res = solve_fleet([lying])[0]
    assert not res.warm
    assert rec.counters.get("plan.solve.carry_miss", 0) == 1
    assert rec.counters.get("plan.solve.warm_fallback", 0) == 0
    assert np.array_equal(res.assign, solve_sequential(lying)[0])


def test_warm_decline_falls_back_to_cold_identically(
        fleet_round1, monkeypatch):
    """The in-graph acceptance flags: with the host precheck bypassed,
    the batched repair itself must detect the ripple, decline per
    element, and fall back to the identical cold fixpoint — exactly
    like the sequential solve_dense_warm -> cold chain."""
    import blance_tpu.plan.fleet as fleet_mod

    lying = _under_marked(*fleet_round1)
    monkeypatch.setattr(fleet_mod, "capacity_shrank",
                        lambda *a, **k: False)
    rec = Recorder()
    with use_recorder(rec):
        res = solve_fleet([lying])[0]
    assert not res.warm
    assert rec.counters.get("plan.solve.warm_fallback", 0) == 1
    assert np.array_equal(res.assign, solve_sequential(lying)[0])


def test_mesh_sharded_fleet_bit_identical(fleet_round1):
    from blance_tpu.parallel.sharded import make_mesh

    tenants, results = fleet_round1
    res_m = solve_fleet(tenants, mesh=make_mesh())
    for r0, rm in zip(results, res_m):
        assert np.array_equal(r0.assign, rm.assign)
        assert np.array_equal(np.asarray(r0.carry.used),
                              np.asarray(rm.carry.used))


# -- CarryCache --------------------------------------------------------------


def _toy_carry(p=4, s=2, n=3, fill=1.0):
    used = np.full((s, n), fill, np.float32)
    return SolveCarry(prices=used.sum(axis=0),
                      assign=np.zeros((p, s, 1), np.int32), used=used)


def test_carry_cache_consume_matching_modes():
    cache = CarryCache()
    cur = np.zeros((4, 2, 1), np.int32)
    cache.store("a", _toy_carry(), cur)
    # Value-equal but different object: identity match misses, value
    # match hits (the service's mode — callers rebuild prev arrays).
    clone = cur.copy()
    carry, _ = cache.consume("a", clone, match="identity")
    assert carry is None
    cache.store("a", _toy_carry(), cur)
    carry, _ = cache.consume("a", clone, match="equal")
    assert carry is not None
    # Consumed: a second consume misses until the next store/promote.
    carry2, _ = cache.consume("a", clone, match="equal")
    assert carry2 is None


def test_carry_cache_pending_promotion_and_dirty_routing():
    cache = CarryCache()
    cur = np.zeros((4, 2, 1), np.int32)
    e = cache.entry("a", 4)
    cache.mark_dirty("a", np.array([1, 0, 0, 0], bool), pending=False)
    cache.store_pending("a", _toy_carry())
    # A delta landing while the proposal is pending must carry forward
    # through promote, not clear with the absorbed marks.
    cache.mark_dirty("a", np.array([0, 0, 1, 0], bool), pending=True)
    cache.promote("a", cur)
    assert e.carry is not None and e.pending is None
    carry, dirty = cache.consume("a", cur)
    assert carry is not None
    assert dirty.tolist() == [False, False, True, False]


def test_carry_cache_pad_nodes_grows_both_carries():
    cache = CarryCache()
    cur = np.zeros((4, 2, 1), np.int32)
    cache.store("a", _toy_carry(n=3), cur)
    cache.store_pending("a", _toy_carry(n=3, fill=2.0))
    cache.pad_nodes("a", 5)
    e = cache.peek("a")
    assert e.carry.used.shape == (2, 5)
    assert e.pending.used.shape == (2, 5)
    assert (np.asarray(e.carry.used)[:, 3:] == 0).all()
    assert np.allclose(np.asarray(e.carry.prices),
                       np.asarray(e.carry.used).sum(axis=0))
    assert pad_carry_nodes(None, 9) is None


def test_carry_cache_lru_byte_budget_evicts_oldest():
    one = _toy_carry()
    per_entry = sum(np.asarray(a).nbytes
                    for a in (one.prices, one.assign, one.used))
    cache = CarryCache(max_bytes=2 * per_entry)
    cur = np.zeros((4, 2, 1), np.int32)
    for key in ("a", "b", "c"):
        cache.store(key, _toy_carry(), cur)
    assert cache.nbytes() <= 2 * per_entry
    # Oldest ("a") lost its carry; the entry (and its masks) survive.
    assert cache.peek("a").carry is None
    assert cache.peek("b").carry is not None
    assert cache.peek("c").carry is not None
    # Touching "b" then adding "d" evicts "c" (LRU, not insertion).
    cache.consume("b", cur)
    cache.store("b", _toy_carry(), cur)
    cache.store("d", _toy_carry(), cur)
    assert cache.peek("c").carry is None
    assert cache.peek("b").carry is not None


def test_carry_cache_entry_resets_on_shape_change():
    cache = CarryCache()
    cache.store("a", _toy_carry(p=4), np.zeros((4, 2, 1), np.int32))
    e = cache.entry("a", 6)  # the tenant's P changed: stale by shape
    assert e.carry is None and e.dirty.shape == (6,)


def test_eviction_only_costs_a_cold_solve():
    """A budget-evicted carry demotes the tenant to cold — results stay
    identical to the never-cached run (eviction is always safe)."""
    t = make_tenant(18, 8, 11)
    r1 = solve_fleet([t])[0]
    t2, _ = delta_tenant(t, r1)
    # Warm (cache intact) vs cold (carry stripped) must agree because
    # the warm repair is bit-identical to the cold fixpoint by contract.
    warm_res = solve_fleet([t2])[0]
    cold_only = TenantProblem(
        key=t2.key, prev=t2.prev, partition_weights=t2.partition_weights,
        node_weights=t2.node_weights, valid_node=t2.valid_node,
        stickiness=t2.stickiness, gids=t2.gids, gid_valid=t2.gid_valid,
        constraints=t2.constraints, rules=t2.rules)
    cold_res = solve_fleet([cold_only])[0]
    assert warm_res.warm and not cold_res.warm
    assert np.array_equal(warm_res.assign, cold_res.assign)


def test_sessions_share_a_keyed_cache():
    """Two sessions on one CarryCache under distinct keys: both carry
    warm state independently (the ROADMAP refactor unlock)."""
    nodes = [f"n{i:02d}" for i in range(8)]
    parts = [str(i) for i in range(24)]
    from blance_tpu import model

    m = model(primary=(0, 1), replica=(1, 1))
    cache = CarryCache()
    s1 = PlannerSession(m, nodes, parts, carry_cache=cache,
                        cache_key="tenant-1")
    s2 = PlannerSession(m, nodes, parts, carry_cache=cache,
                        cache_key="tenant-2")
    for s in (s1, s2):
        s.replan()
        s.apply()
    assert set(cache.keys()) == {"tenant-1", "tenant-2"}
    rec = Recorder()
    with use_recorder(rec):
        s1.remove_nodes([nodes[0]])
        s1.replan()
        s2.remove_nodes([nodes[1]])
        s2.replan()
    assert rec.counters.get("plan.solve.carry_hit", 0) == 2


# -- capacity precheck parity ------------------------------------------------


def test_capacity_shrank_matches_session_behavior():
    used = np.array([[4.0, 0.0], [0.0, 4.0]], np.float32)
    current = np.zeros((4, 2, 1), np.int32)
    current[:, 1, 0] = 1
    pw = np.ones(4, np.float32)
    nw = np.ones(2, np.float32)
    valid = np.ones(2, bool)
    dirty = np.zeros(4, bool)
    # Balanced: rail = ceil(1*4*0.5) = 2, held 4 > 2 + allowance 1.
    assert capacity_shrank(used, current, pw, nw, valid, (1, 1), dirty)
    # Everything dirty: held weight cannot pin, no shrink.
    assert not capacity_shrank(used, current, pw, nw, valid, (1, 1),
                               np.ones(4, bool))


# -- the plan service --------------------------------------------------------


def _run(coro):
    return asyncio.run(coro)


def test_service_coalesces_and_matches_direct_solve():
    tenants = [make_tenant(17 + (i % 2), 8, seed=40 + i, key=f"svc{i}")
               for i in range(8)]
    rec = Recorder()

    async def drive():
        svc = PlanService(admission_window_s=0.05, recorder=rec)
        await svc.start()
        results = await asyncio.gather(
            *[svc.submit(t) for t in tenants])
        await svc.stop()
        return results

    with use_recorder(rec):
        results = _run(drive())
        direct = solve_fleet(tenants)
    for got, want in zip(results, direct):
        assert np.array_equal(got.assign, want.assign)
    # 8 concurrent submits coalesced into one batch per class.
    assert rec.counters["fleet.requests"] == 8
    assert rec.counters["fleet.batches"] <= 2
    assert rec._hist_stats["fleet.batch_tenants"][3] >= 4  # max
    assert rec._hist_stats["fleet.admission_latency_s"][0] == 8


def test_service_warm_carry_across_rounds():
    tenants = [make_tenant(18, 8, seed=60 + i, key=f"warm{i}")
               for i in range(4)]
    rec = Recorder()

    async def drive():
        svc = PlanService(admission_window_s=0.02, recorder=rec)
        await svc.start()
        r1 = await asyncio.gather(*[svc.submit(t) for t in tenants])
        round2 = []
        for t, r in zip(tenants, r1):
            t2, _ = delta_tenant(
                t, solve_fleet([t])[0])  # same delta derivation
            # Build the round-2 request WITHOUT a carry: the service's
            # cache must supply it (prev == cached assign by value).
            round2.append(TenantProblem(
                key=t.key, prev=r.assign,
                partition_weights=t.partition_weights,
                node_weights=t2.node_weights, valid_node=t2.valid_node,
                stickiness=t.stickiness, gids=t.gids,
                gid_valid=t.gid_valid, constraints=t.constraints,
                rules=t.rules, dirty=t2.dirty))
        r2 = await asyncio.gather(*[svc.submit(t) for t in round2])
        await svc.stop()
        return r1, r2

    with use_recorder(rec):
        _r1, r2 = _run(drive())
    assert all(r.warm for r in r2)
    assert rec.counters.get("plan.solve.carry_hit", 0) == 4


def test_service_without_dirty_mask_solves_cold():
    t = make_tenant(18, 8, seed=70, key="colder")
    rec = Recorder()

    async def drive():
        svc = PlanService(admission_window_s=0.0, recorder=rec)
        await svc.start()
        r1 = await svc.submit(t)
        # Same prev again, but no dirty statement: must not warm.
        r2 = await svc.submit(TenantProblem(
            key=t.key, prev=r1.assign,
            partition_weights=t.partition_weights,
            node_weights=t.node_weights, valid_node=t.valid_node,
            stickiness=t.stickiness, gids=t.gids, gid_valid=t.gid_valid,
            constraints=t.constraints, rules=t.rules))
        await svc.stop()
        return r2

    with use_recorder(rec):
        r2 = _run(drive())
    assert not r2.warm
    assert rec.counters.get("plan.solve.carry_hit", 0) == 0


def test_service_stop_and_closed_semantics():
    t = make_tenant(17, 8, seed=80, key="stopme")

    async def drive():
        svc = PlanService(admission_window_s=0.0)
        await svc.start()
        await svc.start()  # idempotent
        r = await svc.submit(t)
        await svc.stop()
        await svc.stop()  # idempotent
        with pytest.raises(PlanServiceClosed):
            await svc.submit(t)
        with pytest.raises(PlanServiceClosed):
            await svc.start()
        return r

    r = _run(drive())
    assert np.array_equal(r.assign, solve_sequential(t)[0])


def test_service_submit_before_start_raises():
    async def drive():
        svc = PlanService()
        with pytest.raises(PlanServiceClosed):
            await svc.submit(make_tenant(17, 8, 0))

    _run(drive())


def test_service_backpressure_bounds_queue():
    """With max_pending=2 and a dispatcher held busy, a third submit
    must block until the queue drains (bounded backlog)."""
    tenants = [make_tenant(17, 8, seed=90 + i, key=f"bp{i}")
               for i in range(6)]

    async def drive():
        svc = PlanService(admission_window_s=0.0, max_pending=2)
        await svc.start()
        subs = [asyncio.create_task(svc.submit(t)) for t in tenants]
        # The queue can hold at most 2 un-admitted requests at any
        # instant, so all six only complete because submits kept
        # unblocking as the dispatcher drained — and every future must
        # resolve despite the bound.
        results = await asyncio.gather(*subs)
        await svc.stop()
        assert len(results) == 6
        return results

    results = _run(drive())
    assert all(r.assign is not None for r in results)


def test_service_malformed_request_fails_alone():
    """A request that dies in batch preparation (here: prev as a plain
    list, which the cache lookup rejects) fails only its own future —
    co-batched neighbors still solve, and the service stays up."""
    good = make_tenant(17, 8, seed=95, key="good")
    bad = TenantProblem(
        key="bad", prev=[[0]],  # type: ignore[arg-type]
        partition_weights=good.partition_weights,
        node_weights=good.node_weights, valid_node=good.valid_node,
        stickiness=good.stickiness, gids=good.gids,
        gid_valid=good.gid_valid, constraints=good.constraints,
        rules=good.rules)

    async def drive():
        svc = PlanService(admission_window_s=0.05)
        await svc.start()
        good_fut = asyncio.ensure_future(svc.submit(good))
        bad_fut = asyncio.ensure_future(svc.submit(bad))
        done = await asyncio.gather(good_fut, bad_fut,
                                    return_exceptions=True)
        # Still serving after the failure.
        again = await svc.submit(make_tenant(17, 8, seed=96, key="ok2"))
        await svc.stop()
        return done, again

    (good_res, bad_res), again = _run(drive())
    assert isinstance(bad_res, Exception)
    assert np.array_equal(good_res.assign, solve_sequential(good)[0])
    assert again.assign is not None


def test_service_stop_after_dispatcher_crash_cleans_up(monkeypatch):
    """A crashed dispatcher must be observable (warning + counter) and
    a subsequent stop() must still release the executor thread."""
    rec = Recorder()

    async def drive():
        svc = PlanService(recorder=rec)
        await svc.start()

        async def boom():
            raise RuntimeError("boom")

        monkeypatch.setattr(svc._queue, "get", boom)
        with pytest.warns(UserWarning, match="dispatcher died"):
            for _ in range(10):
                await asyncio.sleep(0)  # let the crash + callback land
        with pytest.raises(PlanServiceClosed):
            await svc.submit(make_tenant(17, 8, 0))
        await svc.stop()  # cleanup must run despite _closed being set
        assert svc._executor is None and svc._task is None

    _run(drive())
    assert rec.counters.get("fleet.dispatcher_crashes", 0) == 1


def test_solve_fleet_record_false_emits_nothing():
    """record=False silences every counter/histogram the fleet path
    owns — the micro-timing contract solve_dense_converged documents."""
    t = make_tenant(18, 8, seed=97)
    rec = Recorder()
    with use_recorder(rec):
        r1 = solve_fleet([t], record=False)[0]
        t2, _ = delta_tenant(t, r1)
        solve_fleet([t2], record=False)
        solve_fleet([TenantProblem(  # carry-miss path (shape mismatch)
            key="m", prev=t.prev,
            partition_weights=t.partition_weights,
            node_weights=t.node_weights, valid_node=t.valid_node,
            stickiness=t.stickiness, gids=t.gids,
            gid_valid=t.gid_valid, constraints=t.constraints,
            rules=t.rules, carry=_toy_carry(p=18, s=2, n=5),
            dirty=np.zeros(18, bool))], record=False)
    assert rec.counters == {}
    assert rec._hist_stats == {}


def test_fleet_results_are_not_batch_tensor_views(fleet_round1):
    """Results copy out of the [B, ...] batch tensors: a per-tenant
    view would pin the whole batch in memory while the carry cache's
    byte accounting sees only the slice."""
    tenants, results = fleet_round1
    for r in results:
        assert r.assign.base is None
        assert np.asarray(r.carry.used).base is None


def test_service_invalid_tenant_fails_alone_not_the_batch():
    """Per-request validation runs before batching: a tenant whose
    slot depth cannot satisfy its constraints fails its own future,
    while the co-batched valid tenant still solves."""
    good = make_tenant(17, 8, seed=98, key="good2")
    t = make_tenant(8, 4, 0)
    bad = TenantProblem(
        key="bad2", prev=t.prev, partition_weights=t.partition_weights,
        node_weights=t.node_weights, valid_node=t.valid_node,
        stickiness=t.stickiness, gids=t.gids, gid_valid=t.gid_valid,
        constraints=(2, 1), rules=t.rules)  # R=1 < max constraint 2

    async def drive():
        svc = PlanService(admission_window_s=0.05)
        await svc.start()
        res = await asyncio.gather(svc.submit(good), svc.submit(bad),
                                   return_exceptions=True)
        await svc.stop()
        return res

    good_res, bad_res = _run(drive())
    assert isinstance(bad_res, ValueError)
    assert "slot depth" in str(bad_res)
    assert np.array_equal(good_res.assign, solve_sequential(good)[0])


# -- the tier-band scale guard, batched per class -----------------------------


def scalar_tier_band_bound(t):
    """The tier-band bound as the single-problem guard computes it on
    one tenant's own unpadded arrays, in float64 — the plain reference
    the batched bound must equal bit for bit."""
    prev = np.asarray(t.prev)
    pw = np.asarray(t.partition_weights, np.float64)
    nw = np.asarray(t.node_weights, np.float64)
    valid = np.asarray(t.valid_node, bool)
    stick = np.asarray(t.stickiness, np.float64)
    n = nw.shape[0]
    cap_w = np.where(valid & (nw >= 0), np.maximum(nw, 1.0), 0.0)
    w_div = np.where(nw > 0, nw, 1.0)
    k_total = float(sum(max(int(c), 0) for c in t.constraints))
    rail = k_total * float(pw.sum()) / max(float(cap_w.sum()), 1.0)
    ids = prev.reshape(prev.shape[0], -1)
    m = ids >= 0
    w_rep = np.broadcast_to(pw[:, None], ids.shape)
    fill = np.bincount(ids[m].ravel(), weights=w_rep[m].ravel(),
                       minlength=n)[:n]
    bound = max(rail, float((fill / w_div).max()))
    bound += float(stick.max())
    return bound + float(np.maximum(-nw, 0.0).max())


def band_tenant(P, N, seed, *, invalid=0, negative_weights=False,
                stick=None, rules=RULES, empty=False):
    """make_tenant with float weights over a wide range (so the sums
    round), and the node, stickiness and prev variants the guard reads
    (``empty``: an onboarding tenant, whose bound is the rail term)."""
    t = make_tenant(P, N, seed, key=f"b{P}x{N}s{seed}")
    if empty:
        t = dataclasses.replace(t, prev=np.full_like(t.prev, -1))
    rng = np.random.default_rng(seed + 1000)
    pw = (rng.random(P) * 10.0 ** rng.uniform(-9, 1, P)).astype(np.float32)
    nw = rng.uniform(0.5, 4.0, N).astype(np.float32)
    if negative_weights:  # cbgt's booster: negative weights boost
        nw[rng.choice(N, N // 3, replace=False)] *= -3
    valid = np.ones(N, bool)
    valid[rng.choice(N, invalid, replace=False)] = False
    st = rng.uniform(0.5, 3.0, (P, 2)).astype(np.float32) if stick is None \
        else np.full((P, 2), stick, np.float32)
    return dataclasses.replace(
        t, partition_weights=pw, node_weights=nw, valid_node=valid,
        stickiness=st, rules=rules)


# Each case holds several tenants; the ragged ones share one batch
# class with different P and N (bucket_size 208 for P 193..208, 18 for
# N 17..18), with enough partitions that their weight sums round
# differently in another summation order; the empty ones (no seeded
# fill, no stickiness) read that sum in their bound unrounded.
TIER_BAND_CASES = {
    "ragged": [band_tenant(p, n, seed=p + n, empty=n == 18,
                           stick=0.0 if n == 18 else None)
               for p in (193, 200, 208) for n in (17, 18)],
    "invalid_nodes": [band_tenant(18, n, seed=n, invalid=5)
                      for n in (17, 18)],
    "negative_weights": [band_tenant(p, 17, seed=p, negative_weights=True)
                         for p in (17, 18)],
    "negative_stickiness": [band_tenant(p, 18, seed=p, stick=-2.5)
                            for p in (17, 18)],
    # Ids past the tenant's last node (or below -1) count toward no
    # node, as in the scalar guard; they must not spill into the next
    # tenant's nodes.
    "stray_ids": [dataclasses.replace(
        band_tenant(18, 17, seed=7),
        prev=np.where(np.arange(18)[:, None, None] % 3 == 0,
                      np.array([17, -4])[None, :, None],
                      band_tenant(18, 17, seed=7).prev).astype(np.int32)),
        band_tenant(17, 18, seed=8, empty=True, stick=0.0)],
    "rule_less": [band_tenant(18, 17, seed=1, rules=((), ())),
                  dataclasses.replace(make_tenant(5000, 2, seed=2),
                                      rules=((), ())),
                  band_tenant(17, 18, seed=3)],
    # Unit weights: 5,000 and 4,600 partitions over 2 nodes put a
    # node's fill past 0.45 * _RULE_TIER = 4,500.
    "overflow": [make_tenant(5000, 2, seed=4), band_tenant(17, 18, seed=5),
                 make_tenant(4600, 2, seed=6)],
}


@pytest.mark.parametrize("case", sorted(TIER_BAND_CASES))
def test_batched_tier_band_bound_equals_scalar(case):
    """The batched bound (one pass per class over ragged tenants) is
    the scalar guard's bound on each tenant's own arrays, bit for bit;
    tier_band_refusals refuses exactly the tenants whose scalar guard
    raises, with the same message, and rule-less tenants stay exempt."""
    tenants = [_normalized(t) for t in TIER_BAND_CASES[case]]
    by_class = {}
    for t in tenants:
        by_class.setdefault(batch_class_of(t), []).append(t)
    if case == "ragged":
        assert len(by_class) == 1
    for k, group in by_class.items():
        got = tensor_mod._tier_band_bounds(
            [t.prev for t in group], [t.partition_weights for t in group],
            [t.node_weights for t in group], [t.valid_node for t in group],
            [t.stickiness for t in group], k.constraints)
        want = np.array([scalar_tier_band_bound(t) for t in group])
        assert got.dtype == np.float64
        assert np.array_equal(got.view(np.int64), want.view(np.int64))
    refused = tier_band_refusals(tenants, recorder=Recorder())
    expected = {}
    for i, t in enumerate(tenants):
        tensor_mod._tier_scale_memo.clear()
        try:
            tensor_mod._check_tier_band_scale(
                t.prev, t.partition_weights, t.node_weights, t.valid_node,
                t.stickiness, t.constraints, t.rules)
        except ValueError as e:
            expected[i] = str(e)
    assert {i: str(e) for i, e in refused.items()} == expected
    if case == "overflow":
        assert sorted(expected) == [0, 2]
        assert "tier band" in expected[0]
    if case == "rule_less":
        assert scalar_tier_band_bound(tenants[1]) >= 4500  # yet exempt
        assert expected == {}


def _overflow_pair():
    """A valid tenant and one whose tier-band bound overflows (5,000
    partitions on 2 nodes, with a hierarchy rule)."""
    good = make_tenant(17, 8, seed=96, key="good3")
    bad = make_tenant(5000, 2, seed=95, key="band3")
    return good, bad


def test_service_tier_band_overflow_fails_alone():
    """A tenant whose tier-band bound overflows fails its own future
    with the guard's ValueError, counted once; the co-batched valid
    tenant still solves."""
    good, bad = _overflow_pair()
    rec = Recorder()
    sink = InMemorySink()
    rec.add_sink(sink)

    async def drive():
        svc = PlanService(admission_window_s=0.05, recorder=rec)
        await svc.start()
        res = await asyncio.gather(svc.submit(good), svc.submit(bad),
                                   return_exceptions=True)
        await svc.stop()
        return res

    good_res, bad_res = _run(drive())
    assert isinstance(bad_res, ValueError)
    assert "tier band" in str(bad_res)
    assert np.array_equal(good_res.assign, solve_sequential(good)[0])
    assert rec.counters["fleet.validate_refused"] == 1
    (span,) = sink.by_name("fleet.validate")
    assert span.attrs == {"tenants": 2, "classes": 2, "refused": 1}


def test_solve_fleet_refuses_tier_band_overflow():
    """Called directly, solve_fleet still refuses the tenant, with the
    single-problem guard's message, before any dispatch."""
    good, bad = _overflow_pair()
    rec = Recorder()
    n = bad.prev.shape[0]
    tensor_mod._tier_scale_memo.clear()
    with pytest.raises(ValueError, match="tier band") as single:
        tensor_mod._check_tier_band_scale(
            bad.prev, bad.partition_weights, bad.node_weights,
            bad.valid_node, bad.stickiness, bad.constraints, bad.rules)
    with pytest.raises(ValueError, match="tier band") as fleet:
        solve_fleet([good, bad], recorder=rec)
    assert str(fleet.value) == str(single.value)
    assert f"P={n}" in str(fleet.value)
    assert rec.counters.get("fleet.batches", 0) == 0


def test_service_batch_checks_each_tier_band_once(monkeypatch):
    """One service batch computes each tenant's tier-band bound once —
    in its fleet.validate pass, one call per class — not again inside
    the solve."""
    tenants = [make_tenant(17 + (i % 4), 8, seed=60 + i, key=f"once{i}")
               for i in range(6)]
    calls = []
    real = fleet_mod._tier_band_bounds

    def counting(prevs, *a, **kw):
        calls.append(len(prevs))
        return real(prevs, *a, **kw)

    monkeypatch.setattr(fleet_mod, "_tier_band_bounds", counting)
    rec = Recorder()
    sink = InMemorySink()
    rec.add_sink(sink)

    async def drive():
        svc = PlanService(admission_window_s=0.05, recorder=rec)
        await svc.start()
        res = await asyncio.gather(*[svc.submit(t) for t in tenants])
        await svc.stop()
        return res

    results = _run(drive())
    assert [r.key for r in results] == [t.key for t in tenants]
    spans = sink.by_name("fleet.validate")
    assert sum(sp.attrs["tenants"] for sp in spans) == len(tenants)
    assert sum(calls) == len(tenants)
    assert len(calls) == sum(sp.attrs["classes"] for sp in spans)
    assert "fleet.validate_refused" not in rec.counters


def test_carry_cache_max_entries_drops_churned_keys():
    cache = CarryCache(max_entries=3)
    cur = np.zeros((4, 2, 1), np.int32)
    for i in range(10):
        cache.consume(f"k{i}", cur)  # consume-only churn creates entries
    assert len(cache.keys()) == 3
    # The most recent keys survive (LRU drop of the oldest).
    assert set(cache.keys()) == {"k7", "k8", "k9"}
    cache.store("k9", _toy_carry(), cur)
    assert cache.peek("k9").carry is not None


def test_carry_cache_incremental_bytes_track_ground_truth():
    """nbytes() is maintained incrementally (O(1) per store); it must
    equal the O(entries) recount after every lifecycle mutation."""
    cache = CarryCache(max_bytes=None, max_entries=4)
    cur = np.zeros((4, 2, 1), np.int32)

    def check(step):
        assert cache.nbytes() == cache._recount(), step

    for i in range(6):  # entry churn through the max_entries bound
        cache.store(f"k{i}", _toy_carry(), cur)
        check(f"store k{i}")
    cache.consume("k5", cur)
    check("consume")
    cache.store_pending("k5", _toy_carry(n=4))
    check("store_pending")
    cache.pad_nodes("k5", 7)
    check("pad_nodes")
    cache.promote("k5", cur)
    check("promote")
    cache.invalidate("k4")
    check("invalidate")
    cache.drop("k3")
    check("drop")
    cache.entry("k5", 9)  # shape reset replaces the entry
    check("entry reset")
    small = CarryCache(max_bytes=1)  # every store immediately evicts
    small.store("a", _toy_carry(), cur)
    assert small.nbytes() == small._recount() == 0


def test_submit_blocked_on_full_queue_fails_after_crash(monkeypatch):
    """A submit() suspended on a full queue when the dispatcher dies
    must resolve into PlanServiceClosed, not hang: the post-put closed
    check drains its own re-enqueued request."""
    t = make_tenant(17, 8, 0)

    async def drive():
        svc = PlanService(max_pending=1)
        await svc.start()
        gate = asyncio.Event()

        async def parked_get():
            await gate.wait()
            raise RuntimeError("parked dispatcher released")

        monkeypatch.setattr(svc._queue, "get", parked_get)
        t1 = asyncio.ensure_future(svc.submit(t))
        await asyncio.sleep(0)  # t1 enqueued; queue now full
        t2 = asyncio.ensure_future(svc.submit(t))
        await asyncio.sleep(0)  # t2 suspended inside queue.put
        # Simulate the dispatcher-crash callback's effect.
        svc._closed = True
        svc._drain_pending()
        for _ in range(5):
            await asyncio.sleep(0)
        with pytest.raises(PlanServiceClosed):
            await t1
        with pytest.raises(PlanServiceClosed):
            await t2
        task = svc._task
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)

    _run(drive())


def test_service_routes_solve_metrics_to_its_recorder():
    """A service built with its own Recorder gets ALL fleet/solve
    metrics on that recorder — including the ones emitted from the
    executor thread — and none leak to the process-global one."""
    from blance_tpu.obs import get_recorder

    t = make_tenant(18, 8, seed=99, key="routed")
    rec = Recorder()

    async def drive():
        svc = PlanService(admission_window_s=0.0, recorder=rec)
        await svc.start()
        r = await svc.submit(t)
        await svc.stop()
        return r

    global_before = dict(get_recorder().counters)
    _run(drive())  # NOT under use_recorder: the param must do the work
    assert rec.counters.get("fleet.batches", 0) >= 1
    assert rec.counters.get("plan.solve.calls", 0) >= 1
    assert "fleet.batch_tenants" in rec._hist_stats
    global_after = get_recorder().counters
    for name in ("fleet.batches", "fleet.requests"):
        assert global_after.get(name, 0) == global_before.get(name, 0)


def test_service_emissions_all_declared():
    """Everything the fleet tier emits is a declared registry metric
    (the PR-6 drift guard, extended over the new group)."""
    from blance_tpu.obs.expo import default_registry

    tenants = [make_tenant(17 + (i % 4), 8, seed=20 + i, key=f"reg{i}")
               for i in range(6)]
    rec = Recorder()

    async def drive():
        svc = PlanService(admission_window_s=0.01, recorder=rec)
        await svc.start()
        r1 = await asyncio.gather(*[svc.submit(t) for t in tenants])
        await svc.stop()
        return r1

    with use_recorder(rec):
        _run(drive())
    assert default_registry().undeclared(rec) == []


# -- cbgt's booster: negative node weights under vmap -----------------------


def _boosted_fleet(seed):
    """Three batch classes of six tenants each (classes 18, 20 and 22 on
    eight nodes); tenant 0 carries one negative-weight node, the node
    weight through which cbgt's booster steers placements."""
    tenants = [make_tenant(P, 8, seed=100 * seed + 10 * c + i,
                           key=f"boost{seed}.{P}.{i}")
               for c, P in enumerate((18, 20, 22)) for i in range(6)]
    rng = np.random.default_rng(seed)
    nw = tenants[0].node_weights.copy()
    nw[int(rng.integers(0, 8))] = -2.0  # boost 2 > stickiness 1.5
    tenants[0] = dataclasses.replace(tenants[0], node_weights=nw)
    return tenants


@pytest.mark.parametrize("seed", [1, 2])
def test_boosted_fleet_bit_identical_cold_and_warm(seed):
    """A fleet with a negative-weight node solves, cold and then warm,
    bit-identically to solve_dense_converged / solve_dense_warm on the
    same padded arrays — assignments and carries."""
    tenants = _boosted_fleet(seed)
    assert len({batch_class_of(t) for t in tenants}) == 3
    results = solve_fleet(tenants)
    for t, r in zip(tenants, results):
        ref, ref_carry = solve_sequential(t)
        n = t.node_weights.shape[0]
        assert np.array_equal(ref, r.assign), t.key
        assert np.array_equal(np.asarray(ref_carry.used)[:, :n],
                              np.asarray(r.carry.used)), t.key
    boosted = np.flatnonzero(tenants[0].node_weights < 0)[0]
    assert not (results[0].assign == boosted).any(), \
        "a boost over the stickiness pushes every holder off the node"

    round2 = [delta_tenant(t, r)[0] for t, r in zip(tenants, results)]
    res2 = solve_fleet(round2)
    assert sum(r.warm for r in res2) >= len(res2) // 2
    for t, r in zip(round2, res2):
        k = batch_class_of(t)
        arrs = pad_problem_arrays(
            t.prev, t.partition_weights, t.node_weights, t.valid_node,
            t.stickiness, t.gids, t.gid_valid, k.p, k.n)
        cu = pad_to(np.asarray(t.carry.used, np.float32), 1, k.n, 0.0)
        dirty_p = pad_to(
            effective_dirty(t.dirty, t.prev, t.constraints), 0, k.p,
            True)
        wout, wcarry = solve_dense_warm(
            *arrs, t.constraints, t.rules, dirty=dirty_p,
            carry=SolveCarry(prices=cu.sum(axis=0), assign=arrs[0],
                             used=cu),
            fused_score="off", record=False, donate=False,
            p_real=jax.device_put(np.float32(t.prev.shape[0])))
        p, n = t.prev.shape[0], t.node_weights.shape[0]
        if wout is None:  # declined alone: the fleet falls back to cold
            ref, ref_carry = solve_sequential(t)
            assert not r.warm, t.key
            assert np.array_equal(ref, r.assign), t.key
            assert np.array_equal(np.asarray(ref_carry.used)[:, :n],
                                  np.asarray(r.carry.used)), t.key
        else:
            assert r.warm, t.key
            assert np.array_equal(wout[:p], r.assign), t.key
            assert np.array_equal(np.asarray(wcarry.used)[:, :n],
                                  np.asarray(r.carry.used)), t.key
