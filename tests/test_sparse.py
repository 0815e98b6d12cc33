"""Sparse top-K shortlist solver (ISSUE 11).

Contracts pinned here:

- **Saturating-K bit-identity**: with K >= N the sparse engine's result
  equals the dense matrix engine's BIT-FOR-BIT — cold, carry-warm, on
  one device and under a sharded mesh, and at the plan/pipeline level
  (map + warnings + moves).  This is what keeps the two paths from
  drifting.
- **Audit contracts at realistic K**: K << N solves pass the full
  check_assignment audit (no duplicates, no removed-node placements,
  every feasible slot filled, zero feasible-tier hierarchy misses) on a
  randomized corpus, with balance within a pinned tolerance of dense.
- **The exhaustion escape hatch**: rows whose shortlist cannot serve a
  slot are flagged, re-placed by the per-row dense fallback, and
  counted (plan.sparse.* metrics) — shortlist quality is a performance
  knob, never a correctness surface.
- **Shortlist builder properties**, the fused sparse min2 kernel vs its
  XLA oracle (interpret mode), and the dense-memory guard's structured
  refusal.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from blance_tpu import HierarchyRule, Partition, PlanOptions, model
from blance_tpu.core.encode import encode_problem
from blance_tpu.core.shortlist import (
    auto_shortlist_k,
    build_shortlist,
    shortlist_rules_nest,
)
from blance_tpu.obs import get_recorder
from blance_tpu.plan.tensor import (
    DenseScoreMemoryError,
    carry_from_assignment,
    check_assignment,
    check_dense_memory,
    projected_score_bytes,
    set_dense_score_budget,
    solve_converged_resilient,
    solve_dense_converged,
    solve_dense_warm,
    solve_sparse,
    solve_sparse_warm,
)


def _dense_args(P, N, seed=0, rack=5, remove_frac=20, weights=False):
    """Solver arrays for the rack-rule delta shape, plus optional
    heterogeneous weights."""
    rng = np.random.default_rng(seed)
    S, R = 2, 1
    prev = np.full((P, S, R), -1, np.int32)
    prev[:, 0, 0] = rng.integers(0, N, P)
    prev[:, 1, 0] = (prev[:, 0, 0] + 1 + rng.integers(0, N - 1, P)) % N
    pw = np.ones(P, np.float32)
    nw = np.ones(N, np.float32)
    if weights:
        pw[::7] = rng.integers(2, 5, len(pw[::7]))
        nw[::5] = rng.integers(2, 4, len(nw[::5]))
    valid = np.ones(N, bool)
    if remove_frac:
        valid[rng.choice(N, max(N // remove_frac, 1),
                         replace=False)] = False
    stick = np.full((P, S), 1.5, np.float32)
    gids = np.stack([np.arange(N, dtype=np.int32),
                     np.arange(N, dtype=np.int32) // rack,
                     np.zeros(N, np.int32)])
    gv = np.ones((3, N), bool)
    constraints = (1, 1)
    rules = ((), ((2, 1),))
    return (prev, pw, nw, valid, stick, gids, gv, constraints, rules)


def _audit(a, valid, gids):
    a = np.asarray(a)
    prim, repl = a[:, 0, 0], a[:, 1, 0]
    held = a[a >= 0]
    rack = gids[1]
    co = int(((rack[np.clip(prim, 0, None)] == rack[np.clip(repl, 0, None)])
              & (prim >= 0) & (repl >= 0)).sum())
    return {"unassigned": int((a < 0).sum()),
            "removed": int((~valid[held]).sum()),
            "dup": int(((prim == repl) & (prim >= 0)).sum()),
            "co_racked": co}


# --- saturating-K bit-identity ----------------------------------------------


@pytest.mark.parametrize("seed", [0, 3, 9])
def test_saturating_k_bit_identity_cold(seed):
    P, N = 256, 32
    args = _dense_args(P, N, seed=seed, weights=(seed % 2 == 0))
    dense = np.asarray(solve_dense_converged(
        *[jnp.asarray(a) for a in args[:7]], args[7], args[8],
        record=False))
    sparse = solve_sparse(*args[:7], args[7], args[8], k=N, record=False)
    assert np.array_equal(dense, sparse)


def test_saturating_k_beyond_n_bit_identity():
    """K > N saturates to the identity permutation, same contract."""
    P, N = 128, 16
    args = _dense_args(P, N, seed=1)
    dense = np.asarray(solve_dense_converged(
        *[jnp.asarray(a) for a in args[:7]], args[7], args[8],
        record=False))
    sparse = solve_sparse(*args[:7], args[7], args[8], k=N + 7,
                          record=False)
    assert np.array_equal(dense, sparse)


def test_saturating_k_bit_identity_warm():
    """Carry-seeded one-sweep repair: sparse K=N accepts exactly when
    dense accepts and produces the identical assignment."""
    P, N = 256, 32
    args = _dense_args(P, N, seed=5)
    prev, pw, nw, valid, stick, gids, gv, cons, rules = args
    cold = solve_dense_converged(
        *[jnp.asarray(a) for a in args[:7]], cons, rules, record=False)
    cold_np = np.asarray(cold)
    victim = int(cold_np[0, 0, 0])
    valid2 = valid.copy()
    valid2[victim] = False
    dirty = (cold_np == victim).any(axis=(1, 2))
    c_dense = carry_from_assignment(cold, jnp.asarray(pw), jnp.asarray(nw))
    c_sparse = carry_from_assignment(cold, jnp.asarray(pw),
                                     jnp.asarray(nw))
    wd, cd = solve_dense_warm(
        cold_np, pw, nw, valid2, stick, gids, gv, cons, rules,
        dirty=dirty, carry=c_dense, record=False)
    ws, cs = solve_sparse_warm(
        cold_np, pw, nw, valid2, stick, gids, gv, cons, rules,
        dirty=dirty, carry=c_sparse, k=N, record=False)
    assert (wd is None) == (ws is None)
    if wd is not None:
        assert np.array_equal(wd, ws)
        assert np.array_equal(np.asarray(cd.used), np.asarray(cs.used))


def test_saturating_k_bit_identity_sharded():
    """Cold + warm sparse solves under an 8-shard partition mesh equal
    the dense sharded solves bit-for-bit at K=N."""
    from blance_tpu.parallel.sharded import (
        make_mesh,
        solve_dense_sharded,
        solve_sparse_sharded,
    )

    P, N = 256, 32
    args = _dense_args(P, N, seed=2)
    prev, pw, nw, valid, stick, gids, gv, cons, rules = args
    mesh = make_mesh(8)
    dense = solve_dense_sharded(mesh, *args[:7], cons, rules)
    sparse = solve_sparse_sharded(mesh, *args[:7], cons, rules, k=N)
    assert np.array_equal(dense, sparse)

    victim = int(dense[0, 0, 0])
    valid2 = valid.copy()
    valid2[victim] = False
    dirty = (dense == victim).any(axis=(1, 2))
    cd = carry_from_assignment(dense, jnp.asarray(pw), jnp.asarray(nw))
    cs = carry_from_assignment(dense, jnp.asarray(pw), jnp.asarray(nw))
    wd = solve_dense_sharded(
        mesh, dense, pw, nw, valid2, stick, gids, gv, cons, rules,
        dirty=dirty, carry=cd, warm_only=True)
    ws = solve_sparse_sharded(
        mesh, dense, pw, nw, valid2, stick, gids, gv, cons, rules, k=N,
        dirty=dirty, carry=cs, warm_only=True)
    assert (wd is None) == (ws is None)
    if wd is not None:
        assert np.array_equal(wd, ws)


def test_plan_level_saturating_identity_map_warnings_moves():
    """PlanOptions(sparse=True, sparse_k=N) through the fused pipeline:
    map, warnings AND move lists identical to the dense plan."""
    from blance_tpu.plan.tensor import plan_pipeline

    P, N = 192, 24
    rng = np.random.default_rng(4)
    nodes = [f"n{i:03d}" for i in range(N)]
    removed = [nodes[i] for i in rng.choice(N, 2, replace=False)]
    prev = {str(i): Partition(str(i), {
        "primary": [nodes[rng.integers(0, N)]],
        "replica": [nodes[rng.integers(0, N)]]}) for i in range(P)}
    m = model(primary=(0, 1), replica=(1, 1))
    hier = {n: f"r{i // 4}" for i, n in enumerate(nodes)}
    hier.update({f"r{i}": "z0" for i in range((N + 3) // 4)})
    base = dict(node_hierarchy=hier,
                hierarchy_rules={"replica": [HierarchyRule(2, 1)]})
    map_d, warn_d, moves_d = plan_pipeline(
        prev, prev, nodes, removed, [], m, PlanOptions(**base))
    map_s, warn_s, moves_s = plan_pipeline(
        prev, prev, nodes, removed, [], m,
        PlanOptions(sparse=True, sparse_k=N, **base))
    assert warn_d == warn_s
    assert {k: v.nodes_by_state for k, v in map_d.items()} == \
        {k: v.nodes_by_state for k, v in map_s.items()}
    assert moves_d == moves_s


# --- realistic K: audit contracts + balance tolerance -----------------------


@pytest.mark.parametrize("seed", range(8))
def test_fuzz_sparse_audit_contract(seed):
    """Randomized configs at K << N: the sparse solve (shortlist +
    exhaustion fallback) passes the full check_assignment audit — zero
    duplicates / removed-node placements / unfilled feasible slots /
    feasible-tier hierarchy misses — and keeps per-node load spread
    within a pinned tolerance of the dense solve (2x + 6: the shortlist
    trades a little balance tightness for the O(P*K) sweep)."""
    rng = np.random.default_rng(100 + seed)
    N = int(rng.integers(16, 64))
    P = int(rng.integers(64, 512))
    nodes = [f"n{i:03d}" for i in range(N)]
    parts = {str(i): Partition(str(i), {}) for i in range(P)}
    racks = int(rng.integers(2, 6))
    hier = {n: f"r{i % racks}" for i, n in enumerate(nodes)}
    hier.update({f"r{i}": "z0" for i in range(racks)})
    opts = PlanOptions(
        node_hierarchy=hier,
        hierarchy_rules={"replica": [HierarchyRule(2, 1)]},
        partition_weights=({str(i): int(rng.integers(1, 4))
                            for i in range(0, P, 5)}
                           if rng.random() < 0.5 else None))
    m = model(primary=(0, 1), replica=(1, 1))
    removed = (list(rng.choice(nodes, max(N // 10, 1), replace=False))
               if rng.random() < 0.7 else [])
    problem = encode_problem(parts, parts, nodes, removed, m, opts)
    cons = tuple(int(c) for c in problem.constraints)
    rules = tuple(tuple(problem.rules.get(si, ()))
                  for si in range(problem.S))
    k = auto_shortlist_k(problem.N, cons, rules)
    assert k < problem.N or problem.N <= 16

    sparse = solve_sparse(
        problem.prev, problem.partition_weights, problem.node_weights,
        problem.valid_node, problem.stickiness, problem.gids,
        problem.gid_valid, cons, rules, k=k, record=False)
    counts = check_assignment(problem, sparse)
    assert counts == {"duplicates": 0, "on_removed_nodes": 0,
                      "unfilled_feasible_slots": 0,
                      "hierarchy_misses": 0}, counts

    dense = np.asarray(solve_dense_converged(
        jnp.asarray(problem.prev), jnp.asarray(problem.partition_weights),
        jnp.asarray(problem.node_weights), jnp.asarray(problem.valid_node),
        jnp.asarray(problem.stickiness), jnp.asarray(problem.gids),
        jnp.asarray(problem.gid_valid), cons, rules, record=False))
    pw = problem.partition_weights

    def spread(a):
        w = np.zeros(problem.N, np.float64)
        ids = a.reshape(a.shape[0], -1)
        mask = ids >= 0
        np.add.at(w, ids[mask],
                  np.broadcast_to(pw[:, None], ids.shape)[mask])
        lv = w[problem.valid_node]
        return float(lv.max() - lv.min()) if lv.size else 0.0

    assert spread(sparse) <= 2.0 * spread(dense) + 6.0, (
        spread(sparse), spread(dense))


# --- shortlist edge cases ----------------------------------------------------


def test_k1_degenerate():
    """K=1 can never serve two exclusive slots: the fallback must fill
    them, audit-clean."""
    P, N = 96, 16
    args = _dense_args(P, N, seed=6)
    rec = get_recorder()
    before = rec.counters.get("plan.sparse.dense_fallback_rows", 0)
    sparse = solve_sparse(*args[:7], args[7], args[8], k=1)
    a = _audit(sparse, args[3], args[5])
    assert a == {"unassigned": 0, "removed": 0, "dup": 0, "co_racked": 0}
    assert rec.counters.get("plan.sparse.dense_fallback_rows", 0) > before


def test_all_candidates_excluded_row_falls_back_dense():
    """A row whose entire shortlist is removed nodes is flagged
    exhausted and re-placed densely; untouched rows keep their sparse
    result bit-for-bit."""
    P, N = 64, 16
    args = _dense_args(P, N, seed=8, remove_frac=0)
    prev, pw, nw, valid, stick, gids, gv, cons, rules = args
    valid = valid.copy()
    valid[0] = valid[1] = False
    k = 6
    shortlist = np.asarray(build_shortlist(
        prev, pw, nw, valid, gids, gv, cons, rules, k)).copy()
    # Row 0's candidates: only the two removed nodes (then pads).
    shortlist[0] = -1
    shortlist[0, :2] = [0, 1]
    rec = get_recorder()
    e0 = rec.counters.get("plan.sparse.shortlist_exhausted", 0)
    f0 = rec.counters.get("plan.sparse.dense_fallback_rows", 0)
    out = solve_sparse(prev, pw, nw, valid, stick, gids, gv, cons,
                       rules, shortlist=jnp.asarray(shortlist))
    assert rec.counters.get("plan.sparse.shortlist_exhausted", 0) > e0
    assert rec.counters.get("plan.sparse.dense_fallback_rows", 0) > f0
    a = _audit(out, valid, gids)
    assert a == {"unassigned": 0, "removed": 0, "dup": 0, "co_racked": 0}
    # Row 0 was re-placed onto live nodes.
    assert (out[0] >= 0).all() and valid[out[0].ravel()].all()


def test_sticky_row_with_removed_node():
    """Rows whose previous node was removed keep their OTHER sticky
    copy and move only the displaced one; the mover lands on a live
    node at the right rack tier."""
    P, N = 128, 20
    args = _dense_args(P, N, seed=12, remove_frac=0)
    prev, pw, nw, valid, stick, gids, gv, cons, rules = args
    valid = valid.copy()
    victim = int(prev[0, 0, 0])
    valid[victim] = False
    out = solve_sparse(prev, pw, nw, valid, stick, gids, gv, cons,
                       rules, k=8, record=False)
    a = _audit(out, valid, gids)
    assert a == {"unassigned": 0, "removed": 0, "dup": 0, "co_racked": 0}
    # Stickiness: rows NOT holding the victim keep their primary at
    # least as often as the dense engine does (the balance trim
    # legitimately displaces a few holders on both engines).
    dense = np.asarray(solve_dense_converged(
        jnp.asarray(prev), jnp.asarray(pw), jnp.asarray(nw),
        jnp.asarray(valid), jnp.asarray(stick), jnp.asarray(gids),
        jnp.asarray(gv), cons, rules, record=False))
    untouched = ~(prev == victim).any(axis=(1, 2))
    keep_sparse = (out[untouched, 0, 0] == prev[untouched, 0, 0]).mean()
    keep_dense = (dense[untouched, 0, 0] == prev[untouched, 0, 0]).mean()
    assert keep_sparse >= keep_dense - 0.05, (keep_sparse, keep_dense)
    assert keep_sparse > 0.8


def test_hierarchy_group_smaller_than_k():
    """A 2-node rack with K=8: the builder pads rather than invents
    candidates, and the solve stays audit-clean."""
    P, N = 64, 10
    args = _dense_args(P, N, seed=3, rack=2, remove_frac=0)
    prev, pw, nw, valid, stick, gids, gv, cons, rules = args
    sl = np.asarray(build_shortlist(
        prev, pw, nw, valid, gids, gv, cons, rules, 8))
    assert sl.shape == (P, 8)
    out = solve_sparse(prev, pw, nw, valid, stick, gids, gv, cons,
                       rules, k=8, record=False)
    a = _audit(out, valid, gids)
    assert a == {"unassigned": 0, "removed": 0, "dup": 0, "co_racked": 0}


# --- shortlist builder properties -------------------------------------------


def test_builder_rows_sorted_unique_padded():
    P, N = 200, 40
    args = _dense_args(P, N, seed=9, weights=True)
    prev, pw, nw, valid, stick, gids, gv, cons, rules = args
    k = 12
    sl = np.asarray(build_shortlist(
        prev, pw, nw, valid, gids, gv, cons, rules, k))
    assert sl.shape == (P, k) and sl.dtype == np.int32
    for row in sl[:32]:
        real = row[row >= 0]
        # ascending, unique, ids in range, pads only at the tail
        assert (np.diff(real) > 0).all()
        assert (real < N).all()
        assert (row[len(real):] == -1).all()
    # Sticky candidates (the previous placement) are always included.
    held = prev[:, :, 0]
    for pi in range(0, P, 17):
        for node in held[pi]:
            if node >= 0:
                assert node in sl[pi], (pi, node, sl[pi])


def test_builder_saturating_identity_permutation():
    P, N = 50, 12
    args = _dense_args(P, N, seed=2)
    prev, pw, nw, valid, stick, gids, gv, cons, rules = args
    for k in (N, N + 5):
        sl = np.asarray(build_shortlist(
            prev, pw, nw, valid, gids, gv, cons, rules, k))
        assert sl.shape == (P, N)
        assert (sl == np.arange(N)).all()


def test_auto_k_bounds():
    cons = (1, 2)
    rules = ((), ((2, 1), (2, 1)))
    k = auto_shortlist_k(1000, cons, rules)
    assert 16 <= k <= 64 and k % 8 == 0
    assert auto_shortlist_k(4, cons, rules) == 4  # clamped to N
    assert shortlist_rules_nest(rules)
    assert not shortlist_rules_nest(((), ((1, 2),)))


# --- the fused sparse min2 kernel -------------------------------------------


@pytest.mark.parametrize("shape", [(7, 3), (64, 16), (300, 48), (17, 1)])
def test_sparse_kernel_matches_reference(shape):
    """Interpret-mode kernel vs the XLA oracle, quantized scores so
    duplicate minima exercise the tie-break rules."""
    from blance_tpu.ops.sparse2 import (
        sparse_min2_reference,
        sparse_priced_min2,
    )

    p, k = shape
    rng = np.random.default_rng(p * 31 + k)
    score = jnp.asarray(
        rng.integers(0, 6, (p, k)).astype(np.float32) * 0.5)
    price = jnp.asarray(
        rng.integers(0, 4, (p, k)).astype(np.float32) * 0.25)
    got = sparse_priced_min2(score, price, interpret=True)
    want = sparse_min2_reference(score, price)
    for g, w, name in zip(got, want, ("best", "kidx", "second", "raw")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name


def test_sparse_kernel_rejects_empty_k():
    from blance_tpu.ops.sparse2 import sparse_priced_min2

    with pytest.raises(ValueError, match="K >= 1"):
        sparse_priced_min2(jnp.zeros((4, 0)), jnp.zeros((4, 0)),
                           interpret=True)


# --- dense-memory guard ------------------------------------------------------


def test_dense_memory_guard_structured_refusal():
    """Past budget, the matrix engine refuses at entry with a
    structured, actionable error naming the sparse way out — instead of
    an opaque XLA OOM."""
    P, N = 256, 32
    args = _dense_args(P, N, seed=0)
    try:
        set_dense_score_budget(projected_score_bytes(P, N) - 1)
        with pytest.raises(DenseScoreMemoryError) as ei:
            solve_converged_resilient(
                *[jnp.asarray(a) for a in args[:7]], args[7], args[8],
                max_iterations=4, mode="off", allow_fallback=False,
                context="test")
        err = ei.value
        assert err.projected_bytes > err.budget_bytes
        assert err.shape == (P, 2, N)
        assert "sparse" in str(err) and "PlanOptions" in str(err)
        # The sparse engine itself sails past the guard.
        out = solve_sparse(*args[:7], args[7], args[8], k=8,
                           record=False)
        assert (out >= 0).all()
    finally:
        set_dense_score_budget(None)
    # Back under budget: no refusal.
    check_dense_memory(P, 2, N, "off")


def test_sparse_program_peak_under_dense_projection():
    """The sparse engine's compiled program allocates less than the
    dense matrix engine's projected [P, N] working set — the one the
    memory guard refuses — from the AOT memory analysis of the program
    (abstract operands, no FLOPs run)."""
    from blance_tpu.analysis import membudget as mb
    from blance_tpu.analysis.shape_audit import Dims

    d = Dims(P=4096, S=2, N=256, R=2, L=2)
    peak = mb._measure_entry("sparse.cold", d,
                             mb._builders()["sparse.cold"])
    assert 0 < peak < projected_score_bytes(d.P, d.N)


def test_auto_routes_to_sparse_past_budget():
    """PlanOptions(sparse=None) auto-selects the sparse engine exactly
    when the dense projection exceeds the budget (and rules nest)."""
    from blance_tpu.plan.tensor import plan_next_map_tpu

    P, N = 96, 16
    rng = np.random.default_rng(3)
    nodes = [f"n{i:03d}" for i in range(N)]
    prev = {str(i): Partition(str(i), {
        "primary": [nodes[rng.integers(0, N)]],
        "replica": [nodes[rng.integers(0, N)]]}) for i in range(P)}
    m = model(primary=(0, 1), replica=(1, 1))
    hier = {n: f"r{i // 4}" for i, n in enumerate(nodes)}
    hier.update({f"r{i}": "z0" for i in range((N + 3) // 4)})
    opts = PlanOptions(node_hierarchy=hier,
                       hierarchy_rules={"replica": [HierarchyRule(2, 1)]})
    rec = get_recorder()
    try:
        set_dense_score_budget(projected_score_bytes(P, N) - 1)
        g0 = rec.gauges.get("plan.sparse.k_effective")
        next_map, warnings = plan_next_map_tpu(
            prev, prev, nodes, [], [], m, opts)
        assert not warnings
        assert rec.gauges.get("plan.sparse.k_effective") is not None
        assert rec.gauges.get("plan.sparse.k_effective") != g0 or \
            g0 is not None
    finally:
        set_dense_score_budget(None)


def test_sparse_requires_nesting_rules():
    P, N = 32, 8
    args = _dense_args(P, N, seed=0)
    bad_rules = ((), ((1, 2),))  # exclude coarser than include
    from blance_tpu.plan.tensor import _sparse_selected

    with pytest.raises(ValueError, match="nesting"):
        solve_sparse(*args[:7], args[7], bad_rules, k=4, record=False)
    with pytest.raises(ValueError, match="nesting"):
        _sparse_selected(PlanOptions(sparse=True), P, 2, N, bad_rules)
    # Auto (sparse=None) quietly declines exotic rules instead of raising.
    assert not _sparse_selected(PlanOptions(), P, 2, N, bad_rules)


# --- observability -----------------------------------------------------------


def test_sparse_metrics_registered():
    from blance_tpu.obs.expo import default_registry

    reg = default_registry()
    assert reg.declared("plan.sparse.shortlist_build_s", "histogram")
    assert reg.declared("plan.sparse.k_effective", "gauge")
    assert reg.declared("plan.sparse.shortlist_exhausted", "counter")
    assert reg.declared("plan.sparse.dense_fallback_rows", "counter")


def test_warm_sparse_counters_follow_dense_semantics():
    """A declined sparse repair counts warm_fallback + the spent sweep,
    exactly like the dense warm path."""
    P, N = 128, 16
    args = _dense_args(P, N, seed=7)
    prev, pw, nw, valid, stick, gids, gv, cons, rules = args
    cold = solve_dense_converged(
        *[jnp.asarray(a) for a in args[:7]], cons, rules, record=False)
    cold_np = np.asarray(cold)
    victim = int(cold_np[0, 0, 0])
    valid2 = valid.copy()
    valid2[victim] = False
    # An EMPTY dirty mask guarantees the repair ripples -> decline.
    dirty = np.zeros(P, bool)
    carry = carry_from_assignment(cold, jnp.asarray(pw), jnp.asarray(nw))
    rec = get_recorder()
    wf0 = rec.counters.get("plan.solve.warm_fallback", 0)
    out, nc = solve_sparse_warm(
        cold_np, pw, nw, valid2, stick, gids, gv, cons, rules,
        dirty=dirty, carry=carry, k=N)
    assert out is None and nc is None
    assert rec.counters.get("plan.solve.warm_fallback", 0) == wf0 + 1
