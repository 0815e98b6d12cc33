"""Fused in-kernel score engine (ops/score_fused.py) tests.

The fused path must produce the same AUCTION DECISIONS as the matrix
path.  Bit-identical scores are not the contract (the two paths add the
same terms in a slightly different order, which is allowed — each path
is deterministic on its own); the kernel IS bit-checked against a
reference that mirrors its own term order, and the full solve is held
to the same contract as the matrix engine: zero violations, rack-rule
conformance, fixpoint, and matching balance.
"""

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from blance_tpu import HierarchyRule, Partition, PlanOptions, model
from blance_tpu.core.encode import encode_problem
from blance_tpu.ops.score_fused import (
    ScoreInputs,
    fused_score_min2,
    score_at_columns,
)
from blance_tpu.plan.tensor import check_assignment, solve_dense_converged

CLEAN = {"duplicates": 0, "on_removed_nodes": 0,
         "unfilled_feasible_slots": 0, "hierarchy_misses": 0}
_INF = 1.0e9
_RULE_MISS = 1.0e6
_RULE_TIER = 1.0e4


def empty_parts(n):
    return {str(i): Partition(str(i), {}) for i in range(n)}


def _random_inputs(seed, P=37, N=23, R=2, T=3, A=2, nrules=2):
    """Random raw solver terms packed through the REAL pack_score_inputs
    (so the packer's anchor-gid encoding and column layout are covered
    by the bit-exact kernel test, not re-implemented here)."""
    from blance_tpu.ops.score_fused import pack_score_inputs

    rng = np.random.default_rng(seed)
    racks = 5
    rack_of = rng.integers(0, racks, N).astype(np.int32)
    zone_of_rack = rng.integers(0, 2, racks).astype(np.int32)
    gids = np.stack([np.arange(N, dtype=np.int32), rack_of,
                     zone_of_rack[rack_of]])
    gid_valid = rng.random((3, N)) < 0.9
    valid = rng.random(N) < 0.85
    anchors = rng.integers(-1, N, (P, A)).astype(np.int32)
    rules = ((2, 1), (1, 0))[:nrules]

    total = rng.random(N).astype(np.float32) * 40.0
    w_div = rng.integers(1, 4, N).astype(np.float32)
    neg_boost = np.where(rng.random(N) < 0.3,
                         rng.integers(1, 4, N), 0).astype(np.float32)
    stick = np.full(P, 1.5, np.float32)
    prev_slot = rng.integers(-1, N, P).astype(np.int32)
    prev_state = rng.integers(-1, N, (P, R)).astype(np.int32)
    taken = rng.integers(-1, N, (P, T)).astype(np.int32)

    si = pack_score_inputs(
        total_l=jnp.asarray(total), total_p=jnp.float32(P),
        w_div_l=jnp.asarray(w_div), neg_boost_l=jnp.asarray(neg_boost),
        valid_l=jnp.asarray(valid),
        stickiness_si=jnp.asarray(stick),
        prev_slot=jnp.asarray(prev_slot),
        prev_state=jnp.asarray(prev_state),
        taken_ids=[jnp.asarray(taken[:, t]) for t in range(T)],
        anchors=jnp.asarray(anchors),
        gids_l=jnp.asarray(gids), gid_valid=jnp.asarray(gid_valid),
        gids=jnp.asarray(gids), rules=rules)
    price = (rng.random(N).astype(np.float32)
             + np.where(rng.random(N) < 0.2, _INF, 0)).astype(np.float32)
    aux = dict(gids=gids, gid_valid=gid_valid, valid=valid,
               anchors=anchors, rules=rules, P=P, N=N)
    return si, price, aux


def _reference_score(si: ScoreInputs, aux, pbase=0, noff=0):
    """The kernel's formula in ITS term order, dense jnp (pure f32, the
    same precision path as the interpreted kernel) — the oracle the
    kernel must match bit-for-bit."""
    P = si.stick.shape[0]
    N = si.base.shape[0]
    cols = jnp.arange(N, dtype=jnp.int32)[None, :] + noff
    base = si.base[None, :]
    nb = si.neg_boost[None, :]
    stick = si.stick[:, None]
    score = base + jnp.where(nb > 0, jnp.maximum(nb, stick), 0.0)
    score = score - 0.01 * (si.prev_slot[:, None] == cols
                            ).astype(jnp.float32)
    sticky = jnp.zeros((P, N), jnp.bool_)
    for r in range(si.prev_state.shape[1]):
        sticky |= si.prev_state[:, r:r + 1] == cols
    score = score - stick * sticky.astype(jnp.float32)
    rules = aux["rules"]
    if rules:
        nrules = len(rules)
        pen = jnp.full((P, N), _RULE_MISS, jnp.float32)
        for idx in range(nrules):
            sat = jnp.ones((P, N), jnp.bool_)
            for ai in range(si.present.shape[1]):
                col = ai * nrules + idx
                inc_same = si.a_inc_g[:, col:col + 1] == \
                    si.cand_g[idx][None, :]
                exc_same = si.a_exc_g[:, col:col + 1] == \
                    si.cand_g[nrules + idx][None, :]
                sat &= jnp.where(si.present[:, ai:ai + 1] > 0,
                                 inc_same & ~exc_same, True)
            pen = jnp.where(sat, jnp.minimum(pen, idx * _RULE_TIER), pen)
        score = score + jnp.where(si.any_anchor[:, None] > 0, pen, 0.0)
    tk = jnp.zeros((P, N), jnp.bool_)
    for t in range(si.taken.shape[1]):
        tk |= si.taken[:, t:t + 1] == cols
    score = score + _INF * (tk | (si.validf[None, :] == 0.0)
                            ).astype(jnp.float32)
    from blance_tpu.ops.score_fused import jitter_hash

    pi = (pbase + jnp.arange(P, dtype=jnp.int32))[:, None]
    jit = jitter_hash(pi, cols.astype(jnp.int32))
    return np.asarray(score + jnp.float32(1.0e-5) * jit)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("nrules", [0, 1, 2])
def test_fused_kernel_matches_reference(seed, nrules):
    """Interpret-mode kernel == dense reference in the kernel's own term
    order: best/choice/second/raw, including tie-breaks and ragged
    tiles."""
    si, price, aux = _random_inputs(seed, nrules=nrules)
    best, choice, second, raw = (np.asarray(x) for x in fused_score_min2(
        jnp.asarray(price), si, 0, 0, nrules=nrules,
        jitter_scale=1.0e-5, tile_p=16, tile_n=8, interpret=True))
    ref = _reference_score(si, aux)
    eff = ref + price[None, :]
    P, N = ref.shape
    exp_best = eff.min(axis=1)
    exp_choice = eff.argmin(axis=1)
    masked = eff.copy()
    masked[np.arange(P), exp_choice] = np.inf
    exp_second = masked.min(axis=1)
    assert np.array_equal(best, exp_best.astype(np.float32))
    assert np.array_equal(choice, exp_choice.astype(np.int32))
    assert np.array_equal(second, exp_second.astype(np.float32))
    # raw = best - price[choice], computed in-kernel the same way.
    assert np.allclose(raw, best - price[exp_choice], atol=1e-3)

    # score_at_columns agrees with the reference at probe points (same
    # term order as the kernel; threshold-level agreement suffices).
    rng = np.random.default_rng(seed + 100)
    rows = rng.integers(0, P, 16).astype(np.int32)
    cols = rng.integers(0, N, 16).astype(np.int32)
    vals = np.asarray(score_at_columns(
        jnp.asarray(rows), jnp.asarray(cols),
        base_full=si.base, neg_boost_full=si.neg_boost,
        valid_full=jnp.asarray(aux["valid"]),
        gids=jnp.asarray(aux["gids"]),
        gid_valid=jnp.asarray(aux["gid_valid"]),
        anchors=jnp.asarray(aux["anchors"]),
        rules=aux["rules"][:nrules] if nrules else (),
        prev_slot=si.prev_slot, prev_state=si.prev_state,
        taken_ids=tuple(si.taken[:, t] for t in range(si.taken.shape[1])),
        stick=si.stick, jitter_scale=1.0e-5, pbase=jnp.zeros((1, 1),
                                                            jnp.int32)))
    ref_vals = ref[rows, cols]
    assert np.allclose(vals, ref_vals, atol=1e-3), (vals, ref_vals)


def _rack_problem(P=64, N=8):
    nodes = [f"n{i}" for i in range(N)]
    hier = {n: f"r{i // 2}" for i, n in enumerate(nodes)}
    hier.update({f"r{i}": "z0" for i in range(N // 2)})
    opts = PlanOptions(node_hierarchy=hier,
                       hierarchy_rules={"replica": [HierarchyRule(2, 1)]})
    m = model(primary=(0, 1), replica=(1, 2))
    problem = encode_problem({}, empty_parts(P), nodes, [], m, opts)
    return problem


def _solve(problem, fused):
    rules = tuple(tuple(problem.rules.get(i, ()))
                  for i in range(problem.S))
    return np.asarray(solve_dense_converged(
        jnp.asarray(problem.prev),
        jnp.asarray(problem.partition_weights),
        jnp.asarray(problem.node_weights),
        jnp.asarray(problem.valid_node),
        jnp.asarray(problem.stickiness),
        jnp.asarray(problem.gids),
        jnp.asarray(problem.gid_valid),
        tuple(int(c) for c in problem.constraints),
        rules,
        fused_score="interpret" if fused else "off"))


def test_fused_solve_matches_contract():
    """Full solve through the fused engine (interpret mode): same
    contract as the matrix engine — zero violations, rack conformance,
    identical per-state balance, own fixpoint."""
    problem = _rack_problem()
    a_fused = _solve(problem, fused=True)
    a_matrix = _solve(problem, fused=False)
    for a in (a_fused, a_matrix):
        assert check_assignment(problem, a) == CLEAN
        rack = problem.gids[1]
        pr = rack[a[:, 0, 0]]
        r0, r1 = rack[a[:, 1, 0]], rack[a[:, 1, 1]]
        bad = (pr == r0) | (pr == r1) | (r0 == r1)
        assert not bad.any()
    for si in range(2):
        for a in (a_fused, a_matrix):
            ids = a[:, si, :].ravel()
            loads = np.bincount(ids[ids >= 0], minlength=8)
            assert loads.max() - loads.min() <= 3, (si, loads)

    # Fused fixpoint: replanning the fused output through the fused
    # engine is a no-op.
    problem2 = _rack_problem()
    problem2.prev[...] = a_fused
    assert np.array_equal(_solve(problem2, fused=True), a_fused)


def test_fused_solve_node_removal():
    """Fused engine replan after removal: displaced copies move off the
    dead node, zero violations."""
    problem = _rack_problem()
    a1 = _solve(problem, fused=True)
    nodes = problem.nodes
    p2 = encode_problem({}, empty_parts(64), nodes, [],
                        model(primary=(0, 1), replica=(1, 2)),
                        PlanOptions(
                            node_hierarchy={
                                **{n: f"r{i // 2}" for i, n in
                                   enumerate(nodes)},
                                **{f"r{i}": "z0" for i in range(4)}},
                            hierarchy_rules={
                                "replica": [HierarchyRule(2, 1)]}))
    p2.prev[...] = a1
    p2.valid_node[0] = False
    a2 = _solve(p2, fused=True)
    assert not (a2 == 0).any()  # node 0 never used
    assert check_assignment(p2, a2) == CLEAN


def test_fused_default_plumbed_through_api(monkeypatch):
    """set_fused_score_default routes plan_next_map_tpu's pipeline
    program through the fused engine; the public result honors the same
    contract."""
    import warnings as w

    from blance_tpu import plan_next_map
    from blance_tpu.plan import tensor as T

    real = T._pipeline_cold_donating
    engines = []

    def spy(*args, **kwargs):
        engines.append(kwargs["fused_score"])
        return real(*args, **kwargs)

    monkeypatch.setattr(T, "_pipeline_cold_donating", spy)
    T.set_fused_score_default("interpret")
    try:
        nodes = [f"n{i}" for i in range(8)]
        hier = {n: f"r{i // 2}" for i, n in enumerate(nodes)}
        hier.update({f"r{i}": "z0" for i in range(4)})
        opts = PlanOptions(
            node_hierarchy=hier,
            hierarchy_rules={"replica": [HierarchyRule(2, 1)]})
        m = model(primary=(0, 1), replica=(1, 2))
        with w.catch_warnings():
            w.simplefilter("error")  # the validation gate must stay quiet
            result, warns = plan_next_map(
                empty_parts(48), empty_parts(48), nodes, [], nodes, m,
                opts, backend="tpu")
        assert not warns
        assert engines == ["interpret"]
        rackof = {n: i // 2 for i, n in enumerate(nodes)}
        for p in result.values():
            pr = rackof[p.nodes_by_state["primary"][0]]
            rs = [rackof[x] for x in p.nodes_by_state["replica"]]
            assert pr not in rs and len(set(rs)) == 2
    finally:
        T.set_fused_score_default("auto")


def test_resolve_fused_score_passthrough_and_auto(monkeypatch):
    """"auto" picks the engine from the matrix working-set estimate;
    explicit modes pass through; "auto" never reaches the jitted solver
    (solve_dense rejects it)."""
    from blance_tpu.plan import tensor as T

    for mode in ("off", "on", "interpret"):
        assert T.resolve_fused_score(mode, 100_000, 10_000) == mode

    # Auto without the compiled Pallas path (this CPU host): matrix
    # engine regardless of size.
    monkeypatch.setattr("blance_tpu.ops.reduce2.pallas_available",
                        lambda: False)
    assert T.resolve_fused_score("auto", 100_000, 10_000) == "off"

    # Auto with Pallas and a 16 GiB chip: small problems stay on the
    # matrix engine, the north-star shape must switch to fused.
    monkeypatch.setattr("blance_tpu.ops.reduce2.pallas_available",
                        lambda: True)
    monkeypatch.setattr(T, "_device_hbm_bytes", lambda: 16 * 2 ** 30)
    assert T.resolve_fused_score("auto", 100_000, 1_000) == "off"
    assert T.resolve_fused_score("auto", 100_000, 10_000) == "on"

    with pytest.raises(ValueError, match="unresolved fused-score"):
        T.solve_dense(
            jnp.full((4, 1, 1), -1, jnp.int32), jnp.ones(4), jnp.ones(3),
            jnp.ones(3, bool), jnp.full((4, 1), 1.5),
            jnp.zeros((1, 3), jnp.int32), jnp.ones((1, 3), bool),
            (1,), ((),), fused_score="auto")


@pytest.mark.parametrize("seed", range(6))
def test_fused_matrix_equivalence_fuzz(seed):
    """Randomized option-space sweep: warm prev maps, heterogeneous
    partition/node weights, NEGATIVE node weights (pin/boost), varied
    stickiness, node removals, 0-2 hierarchy rules.  The two engines
    need not be bit-equal (term order differs) but each must pass the
    production gate clean, respect every rule, and land within a small
    balance envelope of the other — the subtlest terms (boost, tiered
    rule penalty, exclusivity) are exactly where a drift would show."""
    rng = np.random.default_rng(seed)
    P = int(rng.integers(24, 72))
    N = int(rng.choice([8, 12, 16]))
    nodes = [f"n{i}" for i in range(N)]
    racks = max(2, N // int(rng.choice([2, 3, 4])))
    hier = {n: f"r{i % racks}" for i, n in enumerate(nodes)}
    hier.update({f"r{i}": "z0" for i in range(racks)})
    nrules = int(rng.integers(0, 3))
    # Tiered rule list: rule 0 (different rack) is preferred, rule 1
    # (same rack, different node) is the fallback tier — nrules=2
    # genuinely exercises the multi-rule penalty tiers.
    rules = {"replica": [HierarchyRule(2, 1), HierarchyRule(1, 0)][:nrules]}
    n_replicas = int(rng.choice([1, 2]))
    opts = PlanOptions(
        node_hierarchy=hier,
        hierarchy_rules=rules if nrules else None,
        partition_weights={str(i): int(rng.integers(1, 4))
                           for i in range(0, P, 3)},
        node_weights={nodes[0]: float(rng.choice([-2.0, 2.0]))},
        state_stickiness={"primary": int(rng.choice([1, 2, 3]))},
        state_stickiness_standalone=True,
    )
    m = model(primary=(0, 1), replica=(1, n_replicas))
    problem = encode_problem({}, empty_parts(P), nodes, [], m, opts)
    # Warm half the partitions onto random nodes; remove one node (the
    # gate's on_removed_nodes counter asserts nothing lands there).
    problem.prev[: P // 2, 0, 0] = rng.integers(0, N, P // 2)
    problem.valid_node[N - 1] = False

    a_f = _solve(problem, fused=True)
    a_m = _solve(problem, fused=False)
    for tag, a in (("fused", a_f), ("matrix", a_m)):
        gate = check_assignment(problem, a)
        assert not any(gate.values()), (tag, gate)
        if nrules:
            rack = problem.gids[1]  # rule-less encodes build level 0 only
            pr, rp = a[:, 0, 0], a[:, 1, 0]
            both = (pr >= 0) & (rp >= 0)
            # Tier 0 (different rack) is always attainable here (>= 2
            # racks stay valid), so slot 0 must conform to it.
            assert not (rack[pr] == rack[rp])[both].any(), tag

    def spread(a):
        ids = a[a >= 0]
        loads = np.bincount(ids, minlength=N)[problem.valid_node]
        return int(loads.max() - loads.min())

    assert abs(spread(a_f) - spread(a_m)) <= 2, (spread(a_f), spread(a_m))


def test_jitter_hash_matches_unsigned_weyl_oracle():
    """The int32 spelling (required: Mosaic cannot lower uint32->f32)
    must equal the mathematical unsigned Weyl sequence bit-for-bit —
    two's-complement wraparound makes the masked low 16 bits identical.
    Guards against 'simplifying' the negative multiplier back to its
    unsigned form (which changes nothing numerically but regresses TPU
    compilation) or touching the mask/divisor."""
    from blance_tpu.ops.score_fused import jitter_hash

    rng = np.random.default_rng(0)
    pi = rng.integers(0, 2**31 - 1, 4096).astype(np.int32)
    ni = rng.integers(0, 2**20, 4096).astype(np.int32)
    got = np.asarray(jitter_hash(jnp.asarray(pi), jnp.asarray(ni)))
    with np.errstate(over="ignore"):
        want = ((pi.astype(np.uint32) * np.uint32(2654435761)
                 + ni.astype(np.uint32) * np.uint32(40503))
                & np.uint32(0xFFFF)).astype(np.float32) / 65536.0
    assert np.array_equal(got, want)
    assert got.min() >= 0.0 and got.max() < 1.0
