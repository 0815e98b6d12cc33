"""Incremental (warm-started) replanning: PlannerSession carry lifecycle.

The warm path's contract (docs/DESIGN.md "Incremental replanning"):
a delta replan seeded from the previous solve's carry must produce a map
BIT-IDENTICAL to a cold solve of the same problem, while executing
measurably fewer solver sweeps (the plan.solve.sweeps counter).  These
tests pin both halves property-style across delta shapes — node removal,
addition, combined, weight changes, rack rules — plus the carry
lifecycle rules (promotion on apply, invalidation on load_map/weights,
single-use consumption).
"""

import numpy as np
import pytest

from blance_tpu import HierarchyRule, PlanOptions, model
from blance_tpu.obs import Recorder, use_recorder
from blance_tpu.plan.session import PlannerSession
from blance_tpu.plan.tensor import check_assignment

MODEL = model(primary=(0, 1), replica=(1, 1))
NODES = [f"n{i}" for i in range(8)]
PARTS = [str(i) for i in range(64)]


def rack_opts(nodes, racks_of=4):
    hier = {n: f"r{i // racks_of}" for i, n in enumerate(nodes)}
    hier.update({f"r{i}": "z0"
                 for i in range((len(nodes) + racks_of - 1) // racks_of)})
    return PlanOptions(node_hierarchy=hier,
                       hierarchy_rules={"replica": [HierarchyRule(2, 1)]})


def warmed_session(opts=None, mesh=None, nodes=NODES, parts=PARTS):
    """A session whose next replan is warm (solve + apply promoted the
    carry)."""
    s = PlannerSession(MODEL, list(nodes), list(parts), opts=opts,
                       mesh=mesh)
    s.replan()
    s.apply()
    return s


def cold_twin(session, opts=None, mesh=None):
    """A fresh session holding the same map and removed-node set as
    ``session`` but NO carry — its replan is the cold reference."""
    m, _ = session.to_map()
    s = PlannerSession(MODEL, session.nodes, list(PARTS), opts=opts,
                       mesh=mesh)
    s.load_map(m)
    if session.removed_nodes:
        s.remove_nodes(session.removed_nodes)
    return s


def apply_delta(s, delta):
    if "remove" in delta:
        s.remove_nodes(delta["remove"])
    if "add" in delta:
        s.add_nodes(delta["add"])


DELTAS = [
    pytest.param({"remove": ["n3"]}, id="remove-1"),
    pytest.param({"remove": ["n1", "n6"]}, id="remove-2"),
    pytest.param({"add": ["x0"]}, id="add-1"),
    pytest.param({"remove": ["n2"], "add": ["x0", "x1"]}, id="mixed"),
]


@pytest.mark.parametrize("delta", DELTAS)
@pytest.mark.parametrize("opts_fn", [lambda n: None, rack_opts],
                         ids=["flat", "rack-rules"])
def test_warm_replan_identical_to_cold(delta, opts_fn):
    """Property: warm replan == cold solve of the same problem, for
    every delta shape, with and without hierarchy rules.  (Deltas the
    warm path declines — e.g. adds, where capacity shrinks under held
    load — must fall back to the cold solve and still match.)"""
    rec = Recorder()
    with use_recorder(rec):
        opts = opts_fn(NODES)
        s = warmed_session(opts=opts)
        apply_delta(s, delta)
        warm = s.replan().copy()

        # Same opts OBJECT: the problems must be identical (added nodes
        # outside the hierarchy stay outside it in both sessions).
        c = PlannerSession(MODEL, s.nodes, list(PARTS), opts=opts)
        m, _ = s.to_map()
        c.load_map(m)
        if s.removed_nodes:
            c.remove_nodes(s.removed_nodes)
        cold = c.replan()
    assert np.array_equal(warm, cold)
    report = check_assignment(s.problem, warm)
    assert not any(report.values()), report


def test_warm_remove_halves_sweeps():
    """The acceptance bar: a 1-node-remove warm replan records >= 2x
    fewer plan.solve.sweeps than the cold solve of the same delta, and
    still matches it bit-for-bit."""
    rec = Recorder()
    with use_recorder(rec):
        s = warmed_session()
        twin = cold_twin(s)
        s.remove_nodes(["n3"])
        twin.remove_nodes(["n3"])

        c0 = rec.counters.get("plan.solve.sweeps", 0)
        warm = s.replan().copy()
        warm_sweeps = rec.counters["plan.solve.sweeps"] - c0
        assert rec.counters.get("plan.solve.carry_hit", 0) == 1

        c1 = rec.counters["plan.solve.sweeps"]
        cold = twin.replan()
        cold_sweeps = rec.counters["plan.solve.sweeps"] - c1

    assert np.array_equal(warm, cold)
    assert warm_sweeps * 2 <= cold_sweeps, (warm_sweeps, cold_sweeps)


def test_weight_change_invalidates_carry_and_matches_cold():
    """A node-weight change re-prices everything: the carry must drop
    (cold replan), and the result must match a cold session configured
    with the same weights from scratch."""
    rec = Recorder()
    with use_recorder(rec):
        s = warmed_session()
        m0, _ = s.to_map()
        s.set_node_weights({"n0": 3})
        s.remove_nodes(["n5"])
        out = s.replan().copy()
        assert rec.counters.get("plan.solve.carry_hit", 0) == 0

        c = PlannerSession(MODEL, NODES, list(PARTS),
                           opts=PlanOptions(node_weights={"n0": 3}))
        c.load_map(m0)
        c.remove_nodes(["n5"])
        cold = c.replan()
    assert np.array_equal(out, cold)


def test_carry_promoted_only_on_apply():
    """replan() without apply() leaves ``current`` unchanged; the carry
    built by that replan must not activate until apply() adopts the
    proposal.  A second replan without apply still matches the cold
    answer (the consumed carry forces a cold solve)."""
    rec = Recorder()
    with use_recorder(rec):
        s = warmed_session()
        s.remove_nodes(["n2"])
        first = s.replan().copy()
        hits = rec.counters.get("plan.solve.carry_hit", 0)
        second = s.replan().copy()  # no apply in between
        # No NEW carry hit: the carry was consumed by the first replan.
        assert rec.counters.get("plan.solve.carry_hit", 0) == hits
    assert np.array_equal(first, second)


def test_carry_survives_steady_state_loop():
    """Successive delta cycles each warm-start from the previous apply:
    every replan after the first is a carry hit, and the audits stay
    clean throughout."""
    rec = Recorder()
    with use_recorder(rec):
        s = warmed_session()
        for i, victim in enumerate(["n1", "n4", "n6"]):
            s.remove_nodes([victim])
            s.replan()
            s.apply()
            assert rec.counters.get("plan.solve.carry_hit", 0) == i + 1
            report = check_assignment(s.problem, s.current)
            assert not any(report.values()), report
            vid = s.nodes.index(victim)
            assert not (s.current == vid).any()


def test_add_nodes_between_replan_and_apply():
    """Regression: a node added while a proposal is pending must pad the
    PENDING carry too — apply() promotes it into the grown problem, and
    the next replan used to crash on the [S, N_old] vs [N_new] shape
    mismatch inside the capacity precheck."""
    rec = Recorder()
    with use_recorder(rec):
        s = warmed_session()
        s.replan()               # pending proposal + pending carry
        s.add_nodes(["x0"])      # delta lands before apply()
        s.apply()
        out = s.replan()         # must not raise; falls back cleanly
        report = check_assignment(s.problem, out)
        assert not any(report.values()), report


def test_remove_between_replan_and_apply_keeps_dirty():
    """Regression: a removal recorded after replan() but before apply()
    is NOT absorbed by the adopted proposal — its dirty marks (computed
    against the proposal, which may have moved load onto the victim)
    must survive apply(), and the following replan must drain the
    victim and match a cold solve of the same problem."""
    s = warmed_session()
    s.replan()                   # proposal pending
    s.remove_nodes(["n4"])       # delta after the solve
    s.apply()                    # adopts a map that still uses n4
    out = s.replan().copy()
    n4 = s.nodes.index("n4")
    assert not (out == n4).any()

    c = cold_twin(s)
    cold = c.replan()
    assert np.array_equal(out, cold)


def test_load_map_invalidates_carry():
    rec = Recorder()
    with use_recorder(rec):
        s = warmed_session()
        m, _ = s.to_map()
        s.load_map(m)
        s.remove_nodes(["n3"])
        s.replan()
        assert rec.counters.get("plan.solve.carry_hit", 0) == 0
        assert rec.counters.get("plan.solve.carry_miss", 0) >= 1


def test_dirty_fraction_histogram_records():
    rec = Recorder()
    with use_recorder(rec):
        s = warmed_session()
        s.remove_nodes(["n3"])
        s.replan()
    hist = rec.histogram_summary("plan.solve.dirty_fraction")
    assert hist is not None and hist["count"] == 1
    # A 1-of-8-node removal dirties a minority of partitions.
    assert 0.0 < hist["max"] < 1.0


def test_warm_replan_on_mesh_matches_cold():
    """The sharded path threads the carry (prices/used replicated,
    assignment partition-sharded): warm mesh replan == cold mesh replan,
    with a recorded carry hit."""
    from blance_tpu.parallel.sharded import make_mesh

    rec = Recorder()
    with use_recorder(rec):
        s = warmed_session(mesh=make_mesh(8))
        twin = cold_twin(s, mesh=make_mesh(8))
        s.remove_nodes(["n3"])
        twin.remove_nodes(["n3"])
        warm = s.replan().copy()
        assert rec.counters.get("plan.solve.carry_hit", 0) == 1
        cold = twin.replan()
    assert np.array_equal(warm, cold)


def test_sweeps_counter_drops_across_full_loop():
    """End-to-end sweep accounting through repeated deltas: the steady
    warm loop spends 1 sweep per replan where the cold baseline spends
    >= 2."""
    rec = Recorder()
    with use_recorder(rec):
        s = warmed_session()
        base = rec.counters.get("plan.solve.sweeps", 0)
        for victim in ["n0", "n7"]:
            s.remove_nodes([victim])
            s.replan()
            s.apply()
        warm_total = rec.counters["plan.solve.sweeps"] - base
    assert warm_total == 2  # one sweep per delta replan


def test_shape_bucketing_contract_equivalent():
    """PlanOptions.shape_bucketing pads P and N to the next bucket with
    inert rows/columns: the solve must stay deterministic, audit-clean,
    never emit a pad node, and balance as tightly as the unbucketed
    solve.  (Bit-identity with the unbucketed program is explicitly NOT
    the contract: the traced real-P fill denominator compiles to
    different low-bit arithmetic than the unbucketed constant —
    docs/DESIGN.md "Incremental replanning".)"""
    from blance_tpu import Partition, plan_next_map
    from blance_tpu.core.encode import encode_problem

    nodes = [f"n{i}" for i in range(13)]  # deliberately off-bucket
    parts = {str(i): Partition(str(i), {}) for i in range(100)}
    opts_b = PlanOptions(shape_bucketing=True)
    bucketed, warn = plan_next_map(
        parts, parts, nodes, [], [], MODEL, opts_b, backend="tpu")
    assert not warn
    # Determinism: same call, same map.
    again, _ = plan_next_map(
        parts, parts, nodes, [], [], MODEL, opts_b, backend="tpu")
    assert {p: m.nodes_by_state for p, m in bucketed.items()} == \
        {p: m.nodes_by_state for p, m in again.items()}
    # Pad nodes are inert: only real node names appear.
    placed = {n for p in bucketed.values()
              for ns in p.nodes_by_state.values() for n in ns}
    assert placed <= set(nodes)
    # Audit-clean, and balance as tight as the unbucketed solve's.
    prob = encode_problem(parts, parts, nodes, [], MODEL, PlanOptions())
    nidx = {n: i for i, n in enumerate(nodes)}
    assign = np.full((100, prob.S, prob.R), -1, np.int32)
    order = {p: i for i, p in enumerate(prob.partitions)}
    for pname, part in bucketed.items():
        for s, ns in part.nodes_by_state.items():
            si = prob.states.index(s)
            for ri, node in enumerate(ns):
                assign[order[pname], si, ri] = nidx[node]
    report = check_assignment(prob, assign)
    assert not any(report.values()), report
    counts = np.bincount(assign[assign >= 0], minlength=13)
    plain, _ = plan_next_map(
        parts, parts, nodes, [], [], MODEL, PlanOptions(), backend="tpu")
    pc = np.zeros(13, int)
    for p in plain.values():
        for ns in p.nodes_by_state.values():
            for n in ns:
                pc[nidx[n]] += 1
    assert counts.max() - counts.min() <= (pc.max() - pc.min()) + 2


def test_bucket_size_ladder():
    from blance_tpu.core.encode import bucket_size

    # Values within one bucket collapse to the same padded size...
    assert bucket_size(1000) == bucket_size(1007) == bucket_size(998)
    # ...the padding overhead stays within one octave step (12.5%)...
    for x in (9, 100, 513, 12_345, 100_000):
        b = bucket_size(x)
        assert x <= b <= x * 1.125 + 1, (x, b)
    # ...and tiny/degenerate sizes pass through untouched.
    assert bucket_size(0) == 0
    assert bucket_size(5) == 5


def test_auto_threshold_override_routes_backend():
    """PlanOptions.auto_tpu_threshold steers backend="auto": a threshold
    at/below P*N routes to the batched device solver, one above it to
    the exact path — visible through the phase spans each path emits."""
    from blance_tpu import Partition, plan_next_map

    nodes = [f"n{i}" for i in range(4)]
    parts = {str(i): Partition(str(i), {}) for i in range(8)}

    def spans_for(opts):
        rec = Recorder()
        with use_recorder(rec):
            plan_next_map(parts, parts, nodes, [], [], MODEL, opts,
                          backend="auto")
        return set(rec.summary()["spans"])

    low = spans_for(PlanOptions(auto_tpu_threshold=1))
    high = spans_for(PlanOptions(auto_tpu_threshold=10 ** 9))
    # Low threshold: device path (encode/dispatch/decode phase spans).
    assert "plan.pipeline.dispatch" in low
    # High threshold: exact path — no device phase spans.
    assert "plan.pipeline.dispatch" not in high and \
        "plan.encode" not in high
