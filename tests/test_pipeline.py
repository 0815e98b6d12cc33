"""Fused single-dispatch plan pipeline (ISSUE 9, ROADMAP item 3).

Contracts pinned here:

- the device decode pack (core/encode.pack_assignment) is bit-equivalent
  to decode_assignment's numpy pack, and the device prev scatter
  (prev_from_entries) to encode_problem's host fill;
- plan_pipeline produces a bit-identical map, equal warnings AND equal
  move lists vs the unfused solver it fuses (encode_problem ->
  solve_dense_converged -> host read -> decode_assignment, plus
  calc_all_moves), cold and bucketed, rules and rule-free, and
  plan_next_map(backend="tpu") returns plan_pipeline's map;
- PlannerSession.replan_with_moves ≡ replan() followed by moves(), cold
  AND warm, single-device and mesh-sharded, with the same carry/counter
  semantics;
- the warm one-sweep repair runs bit-identically through the fused
  Pallas score kernel (interpret mode) — delta replans cover the fused
  scoring path;
- the cold and sparse dispatch programs carry every host-bound output in
  one flat int32 buffer that splits back bit for bit, read in one wait;
- donated input buffers are actually invalidated after dispatch;
- mesh_shape_for/make_mesh_auto factorization invariants and the
  declarative shard-layout tables the runtime and the shape audit share.

The module runs under the autouse jax.transfer_guard("disallow")
fixture (tests/conftest.py): any IMPLICIT host<->device transfer inside
the pipeline paths fails the test — the zero-intermediate-transfers
guarantee is enforced, not assumed.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from blance_tpu import HierarchyRule, Partition, PlanOptions, model
from blance_tpu.core.encode import (
    bucket_size,
    decode_assignment,
    encode_problem,
    pack_assignment,
    pad_problem_arrays,
    prev_from_entries,
)
from blance_tpu.moves.batch import calc_all_moves
from blance_tpu.obs import Recorder, use_recorder
from blance_tpu.plan.session import PlannerSession
from blance_tpu.plan.tensor import (
    _PIPE_COLD_STATICS,
    _PIPE_SPARSE_STATICS,
    _dispatch_pipeline_cold,
    _dispatch_pipeline_sparse,
    _fetch_outputs,
    _host_shapes,
    _pipeline_cold_donating,
    _pipeline_cold_impl,
    _pipeline_cold_jit,
    _pipeline_sparse_cold_impl,
    _pipeline_sparse_jit,
    _pipeline_warm_donating,
    _pipeline_warm_jit,
    carry_from_assignment,
    plan_pipeline,
    solve_dense_converged,
)

M2 = model(primary=(0, 1), replica=(1, 1))


def _mk_map(P, N, seed=0):
    rng = np.random.default_rng(seed)
    nodes = [f"n{i:03d}" for i in range(N)]
    p_ids = rng.integers(0, N, P)
    r_ids = (p_ids + 1 + rng.integers(0, N - 1, P)) % N
    prev = {str(i): Partition(str(i), {"primary": [nodes[p_ids[i]]],
                                       "replica": [nodes[r_ids[i]]]})
            for i in range(P)}
    return prev, nodes


def _rack_opts(nodes, **kw):
    hier = {n: f"r{i // 4}" for i, n in enumerate(nodes)}
    hier.update({f"r{i}": "z0" for i in range((len(nodes) + 3) // 4)})
    return PlanOptions(node_hierarchy=hier,
                       hierarchy_rules={"replica": [HierarchyRule(2, 1)]},
                       **kw)


def _dense(P, N, seed=0, invalid=0):
    rng = np.random.default_rng(seed)
    S, R = 2, 1
    prev = np.full((P, S, R), -1, np.int32)
    prev[:, 0, 0] = rng.integers(0, N, P)
    prev[:, 1, 0] = (prev[:, 0, 0] + 1 + rng.integers(0, N - 1, P)) % N
    pw = np.ones(P, np.float32)
    nw = np.ones(N, np.float32)
    valid = np.ones(N, bool)
    if invalid:
        valid[:invalid] = False
    stick = np.full((P, S), 1.5, np.float32)
    gids = np.stack([np.arange(N, dtype=np.int32),
                     np.arange(N, dtype=np.int32) // 4,
                     np.zeros(N, np.int32)])
    gv = np.ones((3, N), bool)
    return (prev, pw, nw, valid, stick, gids, gv, (1, 1), ((), ((2, 1),)))


def _maps_equal(a, b):
    return {k: v.nodes_by_state for k, v in a.items()} == \
        {k: v.nodes_by_state for k, v in b.items()}


# ---------------------------------------------------------------------------
# device integer cores
# ---------------------------------------------------------------------------


def test_pack_assignment_matches_numpy_pack():
    rng = np.random.default_rng(3)
    assign = rng.integers(-1, 6, (37, 3, 4)).astype(np.int32)
    packed, counts = (np.asarray(x)
                      for x in pack_assignment(jnp.asarray(assign)))
    for si in range(assign.shape[1]):
        ids = assign[:, si, :]
        mask = ids >= 0
        order = np.argsort(~mask, axis=1, kind="stable")
        np_packed = np.take_along_axis(ids, order, axis=1)
        assert np.array_equal(packed[:, si, :], np_packed)
        assert np.array_equal(counts[:, si], mask.sum(axis=1))


def test_prev_from_entries_matches_encode_fill():
    prev_map, nodes = _mk_map(29, 7, seed=5)
    problem = encode_problem(prev_map, prev_map, nodes, None, M2,
                             PlanOptions())
    state_index = {s: i for i, s in enumerate(problem.states)}
    node_index = {n: i for i, n in enumerate(problem.nodes)}
    pis, sis, ris, nids = [], [], [], []
    for pi, pname in enumerate(problem.partitions):
        for sname, ns in prev_map[pname].nodes_by_state.items():
            for ri, node in enumerate(ns):
                pis.append(pi)
                sis.append(state_index[sname])
                ris.append(ri)
                nids.append(node_index[node])
    got = np.asarray(prev_from_entries(
        jnp.asarray(np.asarray(pis, np.int32)),
        jnp.asarray(np.asarray(sis, np.int32)),
        jnp.asarray(np.asarray(ris, np.int32)),
        jnp.asarray(np.asarray(nids, np.int32)),
        p=problem.P, s=problem.S, r=problem.R))
    assert np.array_equal(got, problem.prev)


# ---------------------------------------------------------------------------
# plan_pipeline ≡ the unfused solver
# ---------------------------------------------------------------------------


def _unfused(prev_map, nodes, removed, opts, favor_min_nodes=False):
    """Reference for the fused program: the same encode, the dense
    engine's converged solve as its own dispatch, a host read, the host
    decode, and the host move diff of the two maps."""
    problem = encode_problem(prev_map, prev_map, nodes, removed, M2, opts)
    rules = tuple(tuple(problem.rules.get(si, ()))
                  for si in range(problem.S))
    constraints = tuple(int(c) for c in problem.constraints)
    arrays = (problem.prev, problem.partition_weights,
              problem.node_weights, problem.valid_node,
              problem.stickiness, problem.gids, problem.gid_valid)
    p_real = None
    if opts.shape_bucketing:
        arrays = pad_problem_arrays(*arrays, bucket_size(problem.P),
                                    bucket_size(problem.N))
        p_real = jax.device_put(np.float32(problem.P))
    assign = np.asarray(solve_dense_converged(
        *(jnp.asarray(a) for a in arrays), constraints, rules,
        p_real=p_real))[:problem.P]
    next_map, warnings = decode_assignment(problem, assign, prev_map,
                                           removed)
    return next_map, warnings, calc_all_moves(
        prev_map, next_map, M2, favor_min_nodes=favor_min_nodes)


@pytest.mark.parametrize("with_rules", [True, False])
def test_plan_pipeline_identical_to_staged(with_rules):
    prev_map, nodes = _mk_map(96, 12, seed=1)
    removed = [nodes[3]]
    opts = _rack_opts(nodes) if with_rules else PlanOptions()
    smap, swarn, smoves = _unfused(prev_map, nodes, removed, opts)
    fmap, fwarn, fmoves = plan_pipeline(prev_map, prev_map, nodes,
                                        removed, [], M2, opts)
    assert _maps_equal(smap, fmap)
    assert swarn == fwarn
    assert fmoves == smoves


def test_plan_pipeline_bucketed_identical_to_staged():
    prev_map, nodes = _mk_map(70, 11, seed=2)
    opts = _rack_opts(nodes, shape_bucketing=True)
    smap, swarn, smoves = _unfused(prev_map, nodes, [nodes[1]], opts)
    fmap, fwarn, fmoves = plan_pipeline(prev_map, prev_map, nodes,
                                        [nodes[1]], [], M2, opts)
    assert _maps_equal(smap, fmap)
    assert swarn == fwarn
    assert fmoves == smoves


def test_plan_pipeline_favor_min_nodes_order():
    prev_map, nodes = _mk_map(48, 8, seed=7)
    _m, _w, fmoves = plan_pipeline(prev_map, prev_map, nodes,
                                   [nodes[0]], [], M2, PlanOptions(),
                                   favor_min_nodes=True)
    *_map, smoves = _unfused(prev_map, nodes, [nodes[0]], PlanOptions(),
                             favor_min_nodes=True)
    assert fmoves == smoves


def test_plan_pipeline_unsupported_opts_falls_back_exact():
    """Custom placement hooks keep the exact path, moves included."""
    prev_map, nodes = _mk_map(24, 6, seed=9)
    opts = PlanOptions(node_sorter=lambda ctx, ns: list(ns))
    from blance_tpu.plan.api import plan_next_map

    smap, swarn = plan_next_map(prev_map, prev_map, nodes, [], [], M2,
                                opts, backend="native")
    fmap, fwarn, fmoves = plan_pipeline(prev_map, prev_map, nodes, [],
                                        [], M2, opts)
    assert _maps_equal(smap, fmap)
    assert swarn == fwarn
    assert fmoves == calc_all_moves(prev_map, smap, M2)


def test_plan_next_map_fused_pipeline_option():
    """backend="tpu" returns plan_pipeline's map and warnings, bit for
    bit."""
    from blance_tpu.plan.api import plan_next_map

    prev_map, nodes = _mk_map(64, 8, seed=4)
    fmap, fwarn, _moves = plan_pipeline(prev_map, prev_map, nodes,
                                        [nodes[2]], [], M2,
                                        _rack_opts(nodes))
    tmap, twarn = plan_next_map(prev_map, prev_map, nodes, [nodes[2]],
                                [], M2, _rack_opts(nodes), backend="tpu")
    assert _maps_equal(tmap, fmap)
    assert twarn == fwarn


# ---------------------------------------------------------------------------
# session fast path ≡ replan() + moves()
# ---------------------------------------------------------------------------


def _fresh_sessions(P, N, seed, mesh=None, opts_fn=_rack_opts):
    prev_map, nodes = _mk_map(P, N, seed=seed)
    parts = [str(i) for i in range(P)]
    s_staged = PlannerSession(M2, nodes, parts, opts=opts_fn(nodes),
                              mesh=mesh)
    s_fused = PlannerSession(M2, nodes, parts, opts=opts_fn(nodes),
                             mesh=mesh)
    s_staged.load_map(prev_map)
    s_fused.load_map(prev_map)
    return s_staged, s_fused, nodes


def test_session_fast_path_cold_warm_identity():
    s1, s2, nodes = _fresh_sessions(96, 12, seed=11)
    a1 = s1.replan()
    mv1 = s1.moves()
    a2, mv2 = s2.replan_with_moves()
    assert np.array_equal(a1, a2)
    assert all(np.array_equal(x, y) for x, y in zip(mv1, mv2))
    s1.apply()
    s2.apply()

    for delta in ([nodes[5]], [nodes[7], nodes[8]]):
        s1.remove_nodes(delta)
        s2.remove_nodes(delta)
        w1 = s1.replan()
        wm1 = s1.moves()
        w2, wm2 = s2.replan_with_moves()
        assert np.array_equal(w1, w2)
        assert all(np.array_equal(x, y) for x, y in zip(wm1, wm2))
        s1.apply()
        s2.apply()


def test_session_fast_path_warm_counters():
    from blance_tpu.obs import get_recorder

    # 96x12: large enough that removing one node stays inside the
    # capacity-shrink precheck's allowance, so the warm path really runs
    # (tiny 8-node clusters legitimately route the removal to cold).
    s1, s2, nodes = _fresh_sessions(96, 12, seed=13)
    del s1
    rec = get_recorder()
    base_hit = rec.counters.get("plan.solve.carry_hit", 0)
    base_warm = rec.counters.get("plan.pipeline.warm", 0)
    s2.replan_with_moves()
    s2.apply()
    s2.remove_nodes([nodes[2]])
    s2.replan_with_moves()
    assert rec.counters.get("plan.solve.carry_hit", 0) == base_hit + 1
    assert rec.counters.get("plan.pipeline.warm", 0) == base_warm + 1


def test_session_fast_path_add_nodes_delta():
    s1, s2, nodes = _fresh_sessions(48, 8, seed=17)
    for s in (s1, s2):
        s.replan()
        s.apply()
    s1.add_nodes(["zz0", "zz1"])
    s2.add_nodes(["zz0", "zz1"])
    w1 = s1.replan()
    wm1 = s1.moves()
    w2, wm2 = s2.replan_with_moves()
    assert np.array_equal(w1, w2)
    assert all(np.array_equal(x, y) for x, y in zip(wm1, wm2))


def test_session_fast_path_sharded():
    from blance_tpu.parallel.sharded import make_mesh, make_mesh_2d

    for mesh in (make_mesh(8), make_mesh_2d(4, 2)):
        s1, s2, nodes = _fresh_sessions(64, 8, seed=19, mesh=mesh)
        a1 = s1.replan()
        mv1 = s1.moves()
        a2, mv2 = s2.replan_with_moves()
        assert np.array_equal(a1, a2)
        assert all(np.array_equal(x, y) for x, y in zip(mv1, mv2))
        s1.apply()
        s2.apply()
        s1.remove_nodes([nodes[1]])
        s2.remove_nodes([nodes[1]])
        w1 = s1.replan()
        wm1 = s1.moves()
        w2, wm2 = s2.replan_with_moves()
        assert np.array_equal(w1, w2)
        assert all(np.array_equal(x, y) for x, y in zip(wm1, wm2))


# ---------------------------------------------------------------------------
# warm repair through the fused Pallas kernel
# ---------------------------------------------------------------------------


def test_warm_pipeline_fused_interpret_matches_matrix():
    args = _dense(48, 8, seed=21)
    dev = [jnp.asarray(a) for a in args[:7]]
    out_np = np.asarray(solve_dense_converged(*dev, args[7], args[8],
                                              record=False))
    dirty = np.zeros(48, bool)
    dirty[0] = True

    def run(mode):
        carry = carry_from_assignment(jnp.asarray(out_np), dev[1], dev[2])
        return _pipeline_warm_jit(
            jnp.asarray(out_np), *dev[1:7], jnp.asarray(dirty),
            jnp.asarray(carry.used), args[7], args[8], fused_score=mode)

    r_matrix = run("off")
    r_fused = run("interpret")
    assert bool(r_matrix[3]) and bool(r_fused[3])  # both accepted
    for a, b in zip(r_matrix, r_fused):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_host_buffer_splits_back_to_every_output(sparse):
    """The dispatch program's one flat host buffer, split on the host,
    gives back bit for bit every output of the pipeline body the host
    reads, each read on its own; and the dispatch hands the host those
    arrays, with the carry's tables left on the device."""
    args = _dense(48, 8, seed=29)
    if sparse:
        kw = dict(max_iterations=10, shortlist_k=4, sparse_impl="xla",
                  favor_min_nodes=False)
        body = jax.jit(_pipeline_sparse_cold_impl,
                       static_argnames=_PIPE_SPARSE_STATICS)
        flat, extra = _pipeline_sparse_jit, ((48,),)
        got = _dispatch_pipeline_sparse(*args, entry="sparse.pipeline",
                                        **kw)
    else:
        kw = dict(max_iterations=10, fused_score="off",
                  favor_min_nodes=False)
        body = jax.jit(_pipeline_cold_impl,
                       static_argnames=_PIPE_COLD_STATICS)
        flat, extra = _pipeline_cold_jit, ()
        got = _dispatch_pipeline_cold(*args, allow_fallback=False,
                                      entry="pipeline.cold", **kw)
    want = [np.asarray(x) for x in body(
        *(jnp.asarray(a) for a in args[:7]), args[7], args[8], **kw)]
    w_assign, w_sweeps, w_prices, w_used, *w_host = want
    assign, prices, used, host = flat(
        *(jnp.asarray(a) for a in args[:7]), args[7], args[8], **kw)
    split = _fetch_outputs(host, _host_shapes(args[0].shape) + extra)
    for g, w in zip(split, [w_assign, w_sweeps, *w_host], strict=True):
        assert g.dtype == np.int32 and g.shape == w.shape
        assert np.array_equal(g, w.astype(np.int32))
    assert np.array_equal(np.asarray(assign), w_assign)
    assert np.array_equal(np.asarray(prices), w_prices)
    assert np.array_equal(np.asarray(used), w_used)

    assign, sweeps, carry, darrs, packs = got
    for g, w in zip((assign, sweeps, *darrs, *packs),
                    [w_assign, w_sweeps, *w_host[:5]], strict=True):
        assert np.array_equal(g, w)
    assert np.array_equal(np.asarray(carry.prices), w_prices)
    assert np.array_equal(np.asarray(carry.used), w_used)


def test_sparse_dispatch_rediffs_patched_rows_in_a_second_wait():
    """K=1 exhausts rows; the host fallback patches them, and the diff
    and pack handed back are those of the patched assignment, read in
    one more host wait."""
    from blance_tpu.moves.batch import diff_assignments

    args = _dense(96, 16, seed=6)
    rec = Recorder()
    with use_recorder(rec):
        assign, _sweeps, carry, darrs, (packed, counts) = \
            _dispatch_pipeline_sparse(
                *args, max_iterations=10, shortlist_k=1, sparse_impl="xla",
                favor_min_nodes=False, entry="sparse.pipeline")
    assert rec.counters["plan.sparse.dense_fallback_rows"] > 0
    assert rec.counters["plan.pipeline.d2h_waits"] == 2
    dev = jnp.asarray(assign)
    want = diff_assignments(jnp.asarray(args[0]), dev)
    for g, w in zip(darrs, want, strict=True):
        assert np.array_equal(g, np.asarray(w))
    w_packed, w_counts = pack_assignment(dev)
    assert np.array_equal(packed, np.asarray(w_packed))
    assert np.array_equal(counts, np.asarray(w_counts))
    assert np.array_equal(np.asarray(carry.assign), assign)


# ---------------------------------------------------------------------------
# donation discipline
# ---------------------------------------------------------------------------


def test_donated_buffers_invalidated_after_dispatch():
    """The donation contract is real: prev (cold) and prev+carry_used
    (warm) are consumed by the dispatch — reuse must fail loudly, and
    XLA is free to alias them into the outputs."""
    args = _dense(48, 8, seed=23)
    dev = [jnp.asarray(a) for a in args[:7]]
    out_np = np.asarray(solve_dense_converged(*dev, args[7], args[8],
                                              record=False))

    prev_cold = jnp.asarray(args[0])
    res = _pipeline_cold_donating(prev_cold, *dev[1:7], args[7], args[8],
                                  fused_score="off")
    jax.block_until_ready(res[0])
    assert prev_cold.is_deleted()

    dirty = np.zeros(48, bool)
    dirty[0] = True
    carry = carry_from_assignment(jnp.asarray(out_np), dev[1], dev[2])
    prev_warm = jnp.asarray(out_np)
    cu = jnp.asarray(np.asarray(carry.used))
    res_w = _pipeline_warm_donating(prev_warm, *dev[1:7],
                                    jnp.asarray(dirty), cu,
                                    args[7], args[8], fused_score="off")
    jax.block_until_ready(res_w[0])
    assert prev_warm.is_deleted()
    assert cu.is_deleted()
    # The non-donated operands must survive.
    assert not dev[1].is_deleted()


# ---------------------------------------------------------------------------
# sharded pipeline + mesh generalization
# ---------------------------------------------------------------------------


def test_sharded_pipeline_matches_staged_sharded():
    from blance_tpu.moves.batch import diff_assignments
    from blance_tpu.parallel.sharded import (
        make_mesh,
        make_mesh_2d,
        solve_dense_sharded,
        solve_pipeline_sharded,
    )

    args = _dense(64, 8, seed=25, invalid=1)
    for mesh in (make_mesh(8), make_mesh(2), make_mesh_2d(2, 4)):
        s_assign = solve_dense_sharded(mesh, *args[:7], args[7], args[8])
        with jax.transfer_guard("allow"):
            s_diff = tuple(np.asarray(a) for a in diff_assignments(
                jnp.asarray(args[0]), jnp.asarray(s_assign)))
        p_assign, p_carry, p_diff = solve_pipeline_sharded(
            mesh, *args[:7], args[7], args[8])
        assert np.array_equal(s_assign, p_assign)
        assert all(np.array_equal(a, b) for a, b in zip(s_diff, p_diff))
        # The carry matches a host rebuild off the same assignment.
        ref = carry_from_assignment(
            jnp.asarray(p_assign), jnp.asarray(args[1]),
            jnp.asarray(args[2]))
        assert np.allclose(np.asarray(ref.used), np.asarray(p_carry.used))


def test_sharded_pipeline_warm_fixpoint():
    from blance_tpu.parallel.sharded import (
        make_mesh,
        solve_dense_sharded,
        solve_pipeline_sharded,
    )

    args = _dense(64, 8, seed=27)
    mesh = make_mesh(8)
    b_assign, b_carry = solve_dense_sharded(
        mesh, *args[:7], args[7], args[8], return_carry=True)
    dirty = np.zeros(64, bool)
    dirty[:4] = True
    w = solve_pipeline_sharded(mesh, b_assign, *args[1:7], args[7],
                               args[8], dirty=dirty, carry=b_carry,
                               warm_only=True)
    assert w is not None, "fixpoint warm repair should be accepted"
    assert np.array_equal(w[0], b_assign)
    # moves of an unchanged map are empty
    assert (w[2][2] < 0).all()


def test_mesh_shape_for_invariants():
    from blance_tpu.parallel.sharded import mesh_shape_for

    for nd in (1, 2, 3, 5, 6, 8, 12, 16, 64, 256, 1024):
        for (p, n) in ((0, 0), (512, 64), (100_000, 1_000),
                       (100_000, 10_000), (1_000_000, 100_000),
                       (1_000_000, 1_000_000)):
            ps, ns = mesh_shape_for(nd, p, n)
            assert ps >= 1 and ns >= 1 and ps * ns == nd
    # Small problems prefer the pure partition mesh on any fleet.
    assert mesh_shape_for(8, 512, 64) == (8, 1)
    assert mesh_shape_for(256, 100_000, 10_000) == (256, 1)
    # Huge node counts engage the node axis.
    ps, ns = mesh_shape_for(8, 1_000_000, 100_000)
    assert ns > 1
    # Beyond-fleet problems still use every chip, balanced.
    ps, ns = mesh_shape_for(64, 1_000_000, 1_000_000)
    assert ps * ns == 64 and ns > 1
    with pytest.raises(ValueError):
        mesh_shape_for(0, 1, 1)


def test_make_mesh_auto_small_problem_is_1d():
    from blance_tpu.parallel.sharded import (
        PARTITION_AXIS,
        make_mesh_auto,
    )

    mesh = make_mesh_auto(512, 64)
    assert mesh.axis_names == (PARTITION_AXIS,)
    assert mesh.devices.size == len(jax.devices())


def test_layout_tables_cover_solver_args():
    """The declarative layout tables (the audit's source of truth) stay
    in lockstep with the impl signatures."""
    import inspect

    from blance_tpu.parallel.sharded import (
        PIPELINE_COLD_OUT_LAYOUT,
        PIPELINE_WARM_OUT_LAYOUT,
        SOLVER_IN_LAYOUT,
        WARM_EXTRA_LAYOUT,
        layout_specs,
    )
    from blance_tpu.plan.tensor import (
        _pipeline_cold_impl,
        _pipeline_warm_impl,
    )

    cold_params = list(inspect.signature(
        _pipeline_cold_impl).parameters)
    warm_params = list(inspect.signature(
        _pipeline_warm_impl).parameters)
    assert [n for n, _ in SOLVER_IN_LAYOUT] == cold_params[:7]
    assert [n for n, _ in SOLVER_IN_LAYOUT + WARM_EXTRA_LAYOUT] == \
        warm_params[:9]
    assert len(layout_specs(PIPELINE_COLD_OUT_LAYOUT)) == 9
    assert len(layout_specs(PIPELINE_WARM_OUT_LAYOUT)) == 9
    with pytest.raises(ValueError):
        layout_specs((("x", "diagonal"),))


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------


def test_pipeline_emissions_all_declared():
    from blance_tpu.obs.expo import default_registry

    rec = Recorder()
    with use_recorder(rec):
        prev_map, nodes = _mk_map(96, 12, seed=31)
        parts = [str(i) for i in range(96)]
        s = PlannerSession(M2, nodes, parts, opts=_rack_opts(nodes))
        s.load_map(prev_map)
        s.replan_with_moves()
        s.apply()
        s.remove_nodes([nodes[1]])
        s.replan_with_moves()
        plan_pipeline(prev_map, prev_map, nodes, [nodes[2]], [], M2,
                      _rack_opts(nodes))
    assert default_registry().undeclared(rec) == []
    assert rec.counters.get("plan.pipeline.calls", 0) >= 3
    assert rec.counters.get("plan.pipeline.warm", 0) >= 1
