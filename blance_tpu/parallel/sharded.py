"""Multi-chip sharded planning: shard_map over the partition axis.

This is the framework's distributed backbone (the analog of the reference's
"scale" story, which is single-threaded Go — SURVEY.md §2.6).  The planning
problem shards cleanly over partitions: scores[P, N] are embarrassingly
parallel in P, and the only cross-shard state is per-node aggregate weight
(counts, capacity usage), which rides XLA collectives (psum) over ICI.

Design (SURVEY.md §5 long-context analog): the (P x S x N) cost tensor is
sharded over P with a jax.sharding.Mesh; each device runs the same auction
rounds on its partition shard with 1/n of every node's capacity, and psums
its per-node accepted weight so the price/counts every shard sees stay
globally consistent.  No gather of [P, N] ever materializes on one chip.

For >> 10k-node problems a SECOND mesh axis shards the node dimension of
every [P, N] intermediate (make_mesh_2d): per-row (min, argmin, second)
stats combine across node shards via all_gather + index arithmetic, remote
column reads ride a masked psum, and the [N]-sized vectors (counts,
capacity, prices — kilobytes) stay replicated along the node axis so the
capacity/acceptance logic is identical math everywhere (see
plan/tensor.py solve_dense's node_axis docs).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.encode import DenseProblem, pad_to
from ..plan.tensor import (
    SolveCarry,
    _apply_sparse_fallback,
    _pipeline_cold_impl,
    _pipeline_warm_impl,
    _record_sweeps,
    _solve_sparse_converged_impl,
    _warm_repair,
    _warm_repair_sparse,
    carry_from_assignment,
    resolve_sparse_impl,
    solve_dense_converged,
    sparse_rules_supported,
)
from ..obs import device as _obs_device
from ..obs import get_recorder

__all__ = ["make_mesh", "make_mesh_2d", "make_hybrid_mesh",
           "make_mesh_auto", "mesh_shape_for", "slice_major_order",
           "solve_dense_sharded", "solve_pipeline_sharded",
           "solve_sparse_sharded",
           "pad_partitions", "pad_nodes", "SOLVER_IN_LAYOUT",
           "WARM_EXTRA_LAYOUT", "SPARSE_EXTRA_LAYOUT", "layout_specs"]

PARTITION_AXIS = "parts"
NODE_AXIS = "nodes"

# --- declarative shard layouts ----------------------------------------------
#
# THE one table of how solver operands lay out on a mesh: each entry is
# (operand name, "parts" = sharded over the partition axis | "replicated").
# Every shard_map dispatch here derives its in_specs from these rows, and
# the shape audit (analysis/shape_audit.py) builds its sharded contracts
# from the SAME table — so the audited layout and the dispatched layout
# cannot drift apart.  The node axis of a 2-D mesh never appears in the
# specs: [N]-shaped operands stay REPLICATED along it by design (see the
# module docstring) and the [P, N] splits happen inside solve_dense.

SOLVER_IN_LAYOUT: tuple[tuple[str, str], ...] = (
    ("prev", "parts"),
    ("pweights", "parts"),
    ("nweights", "replicated"),
    ("valid", "replicated"),
    ("stickiness", "parts"),
    ("gids", "replicated"),
    ("gid_valid", "replicated"),
)
WARM_EXTRA_LAYOUT: tuple[tuple[str, str], ...] = (
    ("dirty", "parts"),
    ("carry_used", "replicated"),
)
# Sparse solve: the [P, K] shortlist rides the partition axis with its
# prev rows; every [N]-shaped table stays replicated exactly like the
# dense layout (the sparse engine's fill/price/capacity are full-width
# by design).
SPARSE_EXTRA_LAYOUT: tuple[tuple[str, str], ...] = (
    ("shortlist", "parts"),
)
# Sparse solve outputs: assign + exhaustion flags are row-wise in P;
# the executed-sweep count is globally agreed.
SPARSE_COLD_OUT_LAYOUT: tuple[tuple[str, str], ...] = (
    ("assign", "parts"), ("sweeps", "replicated"),
    ("exhausted", "parts"),
)
SPARSE_WARM_OUT_LAYOUT: tuple[tuple[str, str], ...] = (
    ("assign", "parts"), ("used", "replicated"),
    ("ok", "replicated"), ("exhausted", "parts"),
)
# Pipeline outputs: assign + the diff/pack tensors are row-wise in P
# (shardable with zero collectives); the carry tables and scalars are
# psum'd/globally-agreed inside the body, hence replicated.
PIPELINE_COLD_OUT_LAYOUT: tuple[tuple[str, str], ...] = (
    ("assign", "parts"), ("sweeps", "replicated"),
    ("prices", "replicated"), ("used", "replicated"),
    ("d_nodes", "parts"), ("d_states", "parts"), ("d_ops", "parts"),
    ("packed", "parts"), ("counts", "parts"),
)
PIPELINE_WARM_OUT_LAYOUT: tuple[tuple[str, str], ...] = (
    ("assign", "parts"), ("prices", "replicated"),
    ("used", "replicated"), ("ok", "replicated"),
    ("d_nodes", "parts"), ("d_states", "parts"), ("d_ops", "parts"),
    ("packed", "parts"), ("counts", "parts"),
)


def layout_specs(layout: tuple) -> tuple:
    """Rows of a layout table -> PartitionSpecs for shard_map."""
    specs = []
    for name, kind in layout:
        if kind == "parts":
            specs.append(P(PARTITION_AXIS))
        elif kind == "replicated":
            specs.append(P())
        else:
            raise ValueError(
                f"layout row {name!r}: unknown kind {kind!r}")
    return tuple(specs)


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D device mesh over the partition axis."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (PARTITION_AXIS,))


def slice_major_order(slice_ids: list) -> list:
    """Stable slice-major device ordering: indices sorted by slice id,
    original order preserved within a slice.  Pure so the multi-slice
    path is unit-testable without multi-slice hardware."""
    return sorted(range(len(slice_ids)), key=lambda i: (slice_ids[i], i))


def make_hybrid_mesh(
    devices: Optional[list] = None,
    slice_ids: Optional[list] = None,
) -> Mesh:
    """Multi-slice (multi-host) 1-D mesh, DCN-aware.

    The solver's only cross-shard traffic is per-node [N] psums, so a 1-D
    partition axis works across slices — but the DEVICE ORDER matters:
    XLA lowers a psum over a flat axis hierarchically when devices that
    share ICI are contiguous in the mesh, keeping the heavy intra-slice
    hops on ICI and only one reduced copy per slice on DCN.  This helper
    orders devices slice-major (stable within a slice, preserving the
    runtime's topology order) to guarantee that contiguity; on a single
    slice it is equivalent to :func:`make_mesh`.

    ``devices`` / ``slice_ids`` default to the runtime's enumeration and
    each device's ``slice_index``; passing them explicitly lets tests
    (and exotic topologies) drive the multi-slice ordering with synthetic
    slice assignments — tests/test_sharded.py solves end-to-end on a
    2-slice hybrid mesh built from the 8 virtual CPU devices this way.

    Caveat: within a slice the runtime's enumeration order is trusted as
    ICI-reasonable.  On multi-host slices where jax.devices() enumerates
    by (process, local ordinal) but the physical torus differs,
    jax.experimental.mesh_utils.create_hybrid_device_mesh can arrange
    intra-slice devices by physical coordinates — worth benchmarking
    there; this helper prefers the simple order that is provably
    slice-contiguous and unit-testable (slice_major_order).
    """
    if devices is None:
        devices = jax.devices()
    if slice_ids is None:
        slice_ids = [getattr(d, "slice_index", 0) for d in devices]
    if len(slice_ids) != len(devices):
        raise ValueError(
            f"{len(slice_ids)} slice ids for {len(devices)} devices")
    if len(set(slice_ids)) > 1:
        order = slice_major_order(slice_ids)
        return Mesh(np.asarray([devices[i] for i in order]),
                    (PARTITION_AXIS,))
    return Mesh(np.asarray(list(devices)), (PARTITION_AXIS,))


def make_mesh_2d(
    parts_shards: int, node_shards: int,
    devices: Optional[list] = None,
) -> Mesh:
    """2-D (parts x nodes) device mesh for node-axis sharding.

    Lay the node axis minor (fastest-varying over adjacent devices): its
    per-round all_gather/psum of [P_l]-sized stats is the latency-bound
    traffic, so it should ride the shortest ICI hops.
    """
    devs = list(jax.devices() if devices is None else devices)
    need = parts_shards * node_shards
    if len(devs) < need:
        raise ValueError(f"need {need} devices, have {len(devs)}")
    arr = np.asarray(devs[:need]).reshape(parts_shards, node_shards)
    return Mesh(arr, (PARTITION_AXIS, NODE_AXIS))


# Per-axis shard caps for mesh_shape_for.  The per-shard [P_l, N_l]
# block is P*N/n_devices for EVERY factorization (memory cannot prefer
# one), so the factorization is chosen by per-AXIS extents instead:
# _PART_CAP is the partition rows one chip handles comfortably
# (calibrated: 100k x 10k solves on one v5e, so 128k rows per shard is
# conservative), _NODE_CAP the column width past which the ">> 10k
# nodes" guidance (module docstring) wants the node axis engaged — [N]
# replicated vectors and psums stay kilobytes-to-small below it.
_PART_CAP = 1 << 17  # 131072 partition rows per shard
_NODE_CAP = 1 << 14  # 16384 node columns per shard


def mesh_shape_for(
    n_devices: int,
    p: int,
    n: int,
    *,
    part_cap: int = _PART_CAP,
    node_cap: int = _NODE_CAP,
) -> tuple[int, int]:
    """(parts_shards, node_shards) for ANY device count — the mesh
    factorization rule that replaces the hand-picked 8-chip meshes.

    Pure and deterministic (unit-testable without devices).  Preference
    order: the partition axis (its only collectives are [N]-sized
    psums; the node axis adds per-round all_gathers of row stats), so
    among factorizations keeping both per-shard axis extents within
    their caps the fewest node shards wins — small problems on any
    fleet resolve to the plain 1-D partition mesh.  When no divisor of
    ``n_devices`` fits both caps (beyond-fleet problems: 1M x 1M on 8
    chips), the factorization minimizing the worst RELATIVE axis
    overload is returned, ties toward fewer node shards — both axes
    degrade together instead of one exploding.
    parts_shards * node_shards == n_devices always: every chip works.
    """
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if p < 0 or n < 0:
        raise ValueError(f"negative problem dims ({p}, {n})")
    divisors = [d for d in range(1, n_devices + 1) if n_devices % d == 0]

    def overload(node_shards: int) -> float:
        parts = n_devices // node_shards
        p_l = -(-max(p, 1) // parts)
        n_l = -(-max(n, 1) // node_shards)
        return max(p_l / part_cap, n_l / node_cap)

    fitting = [d for d in divisors if overload(d) <= 1.0]
    if fitting:
        best = fitting[0]  # smallest node_shards: prefer the parts axis
    else:
        best = min(divisors, key=lambda d: (overload(d), d))
    return n_devices // best, best


def make_mesh_auto(
    p: int,
    n: int,
    devices: Optional[list] = None,
    slice_ids: Optional[list] = None,
) -> Mesh:
    """Problem-shaped mesh over ALL available devices: 1-D partition
    mesh (slice-major ordered across slices, like make_hybrid_mesh)
    while a partition-only split fits, 2-D (parts x nodes) beyond that
    — the beyond-8-chip entry point: 4, 8, 64 or 256 chips all resolve
    to a working factorization with no hand-tuned mesh shape."""
    if devices is None:
        devices = list(jax.devices())
    if slice_ids is None:
        slice_ids = [getattr(d, "slice_index", 0) for d in devices]
    if len(set(slice_ids)) > 1:
        order = slice_major_order(slice_ids)
        devices = [devices[i] for i in order]
    parts, nodes = mesh_shape_for(len(devices), p, n)
    if nodes == 1:
        return Mesh(np.asarray(devices), (PARTITION_AXIS,))
    return make_mesh_2d(parts, nodes, devices=devices)


def pad_partitions(arr: np.ndarray, multiple: int,
                   fill: float | int | bool) -> np.ndarray:
    """Pad axis 0 to a multiple of the mesh size.

    Padding rows use weight 0 so they bid without consuming capacity or
    affecting counts; their assignments are discarded at decode.
    """
    p = arr.shape[0]
    return pad_to(arr, 0, p + (-p) % multiple, fill)


def pad_nodes(arr: np.ndarray, multiple: int,
              fill: float | int | bool) -> np.ndarray:
    """Pad the trailing (node) axis to a multiple of the node-shard count.

    Padding nodes are invalid (valid=False ⇒ zero capacity, +INF score,
    gid_valid=False), so they can never be chosen; assignments therefore
    only ever reference real node ids.
    """
    n = arr.shape[-1]
    return pad_to(arr, arr.ndim - 1, n + (-n) % multiple, fill)


def solve_dense_sharded(
    mesh: Mesh,
    prev: np.ndarray,
    pweights: np.ndarray,
    nweights: np.ndarray,
    valid: np.ndarray,
    stickiness: np.ndarray,
    gids: np.ndarray,
    gid_valid: np.ndarray,
    constraints: tuple,
    rules: tuple,
    max_iterations: int = 10,
    fused_score: Optional[str] = None,
    dirty: Optional[np.ndarray] = None,
    carry: Optional[SolveCarry] = None,
    return_carry: bool = False,
    warm_only: bool = False,
):
    """Run the converged solve under shard_map, partition axis sharded.

    Accepts a 1-D ("parts",) or 2-D ("parts", "nodes") mesh (make_mesh /
    make_mesh_2d).  On a 2-D mesh the [P, N] intermediates inside the
    solver are sharded on BOTH axes; inputs here stay partition-sharded +
    node-replicated ([N] vectors are small — the memory that matters is
    the solver's internal [P, N] score, which is what the node axis
    splits).  Returns assign[P_original, S, R] (padding stripped), or
    (assign, SolveCarry) with ``return_carry``.

    With ``dirty`` + ``carry`` (both matching ``prev``) the solve runs
    the WARM path first: one carry-seeded repair sweep under shard_map —
    the carry's prices/used tables ride replicated along the node axis
    while the assignment stays sharded over partitions — accepted when
    the repair stayed inside the dirty mask (plan/tensor.py
    solve_dense_warm semantics), else falling back to the cold fixpoint
    below — or, with ``warm_only``, returning (None, None) so the
    caller owns the fallback (and its metrics/audit gates, matching the
    single-device solve_dense_warm contract).  Like the single-device
    warm path, the carry is consumed either way.  ``carry_hit`` is not
    counted here for the same reason as solve_dense_warm: the caller's
    gates decide what a hit is.
    """
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_shards = axes[PARTITION_AXIS]
    node_shards = axes.get(NODE_AXIS, 1)
    node_axis = NODE_AXIS if node_shards > 1 else None
    p_orig = prev.shape[0]
    # Module-attribute access keeps the default and resolver
    # monkeypatch-visible (tests patch tensor-module attributes).
    from ..plan import tensor as _tensor

    # The per-shard solves run under shard_map (traced), where the tier-
    # band scale guard must skip — assert it here on the concrete host
    # values instead, once for the whole mesh.
    _tensor._check_tier_band_scale(
        prev, pweights, nweights, valid, stickiness, constraints, rules)

    # Resolve against the PER-SHARD slice: each device holds P/n_shards
    # rows (x N/node_shards columns) of every [P, N] intermediate, so
    # that is the working set the chip must fit.  None = follow the
    # module default, same as the single-chip entry points
    # (plan_next_map_tpu, PlannerSession.replan) — a caller who never
    # touches knobs gets "auto" on every path; both resolvers pass
    # explicit modes through untouched.
    shard_p = -(-prev.shape[0] // n_shards)
    shard_n = -(-np.asarray(nweights).shape[-1] // node_shards)
    if fused_score is None:
        fused_score = _tensor.resolve_default_fused_score(shard_p, shard_n)
    else:
        fused_score = _tensor.resolve_fused_score(
            fused_score, shard_p, shard_n)

    prev_p = pad_partitions(np.asarray(prev), n_shards, -1)
    pw_p = pad_partitions(np.asarray(pweights), n_shards, 0.0)
    st_p = pad_partitions(np.asarray(stickiness), n_shards, 0.0)
    nw_p = np.asarray(nweights)
    valid_p = np.asarray(valid)
    gids_p = np.asarray(gids)
    gv_p = np.asarray(gid_valid)
    if node_shards > 1:
        nw_p = pad_nodes(nw_p, node_shards, 1.0)
        valid_p = pad_nodes(valid_p, node_shards, False)
        gids_p = pad_nodes(gids_p, node_shards, -1)
        gv_p = pad_nodes(gv_p, node_shards, False)

    shard = P(PARTITION_AXIS)
    rep = P()
    # The varying-axes checker stays on for the plain 1-D matrix path.
    # Off it: the output is node-replicated by construction — every
    # node shard derives identical assignments from
    # the all_gathered stats, a property tests/test_sharded_2d.py proves
    # empirically (solves are bit-identical across node-shard counts) —
    # but the varying-axes checker can't see through the all_gather/psum
    # combine, so disable it on 2-D meshes.  The fused engine needs the
    # same disable on ANY mesh: the checker's per-op vma propagation
    # inside pallas_call rejects the kernel's mix of node-replicated [N]
    # tables and partition-varying columns (its outputs carry correct
    # vma annotations; the per-op walk is what can't see through).
    checked_ok = not node_axis and fused_score == "off"
    device_put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))

    dev_args = (
        device_put(jnp.asarray(prev_p), shard),
        device_put(jnp.asarray(pw_p), shard),
        device_put(jnp.asarray(nw_p), rep),
        device_put(jnp.asarray(valid_p), rep),
        device_put(jnp.asarray(st_p), shard),
        device_put(jnp.asarray(gids_p), rep),
        device_put(jnp.asarray(gv_p), rep),
    )

    rec = get_recorder()
    if dirty is not None and carry is not None:
        # Warm repair sweep: dirty rides the partition axis (padding
        # rows are marked dirty — their synthetic assignments must not
        # read as a ripple), the carry's [S, N] fill table is replicated
        # like every other [N]-shaped vector.
        dirty_p = pad_partitions(
            np.asarray(dirty, bool), n_shards, True)
        cu = np.asarray(carry.used, np.float32)
        if node_shards > 1:
            cu = pad_nodes(cu, node_shards, 0.0)
        rec.observe("plan.solve.dirty_fraction",
                    float(np.asarray(dirty, bool).mean())
                    if np.asarray(dirty).size else 0.0)
        body_w = partial(
            _warm_repair,
            constraints=constraints, rules=rules,
            axis_name=PARTITION_AXIS, node_axis=node_axis,
            node_shards=node_shards, fused_score=fused_score)
        fn_w = jax.shard_map(body_w, mesh=mesh,
                             in_specs=layout_specs(
                                 SOLVER_IN_LAYOUT + WARM_EXTRA_LAYOUT),
                             out_specs=(shard, rep, rep),
                             check_vma=checked_ok)
        with rec.span("plan.solve.attempt", warm=True, sharded=True), \
                _obs_device.entry("sharded.warm"):
            # transfer_guard allowlist: dispatching a fresh shard_map
            # executable uploads its jaxpr closure constants as
            # replicated buffers — an IMPLICIT transfer by jax's
            # classification, but intrinsic to compilation, not an
            # accidental per-call sync.  All operands above are explicit
            # device_puts; only the dispatch itself is exempted.
            with jax.transfer_guard("allow"):
                out, new_used, ok = fn_w(
                    *dev_args,
                    device_put(jnp.asarray(dirty_p), shard),
                    device_put(jnp.asarray(cu), rep))
            accepted = bool(ok)
        if accepted:
            _record_sweeps(1)
            rec.set_attr("warm", True)
            assign = np.asarray(out)[:p_orig]
            if not return_carry:
                return assign
            # Strip node padding: pad columns are invalid nodes with
            # zero fill, and the session's carry is unpadded-N shaped.
            n_orig = np.asarray(nweights).shape[-1]
            used = jnp.asarray(np.asarray(new_used)[:, :n_orig])
            return assign, SolveCarry(
                prices=jnp.sum(used, axis=0), assign=jnp.asarray(assign),
                used=used)
        rec.count("plan.solve.warm_fallback")
        rec.count("plan.solve.sweeps", 1)  # the executed repair pass
        if warm_only:
            return (None, None) if return_carry else None

    body = partial(
        solve_dense_converged,
        constraints=constraints,
        rules=rules,
        axis_name=PARTITION_AXIS,
        max_iterations=max_iterations,
        node_axis=node_axis,
        node_shards=node_shards,
        fused_score=fused_score,
    )
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=layout_specs(SOLVER_IN_LAYOUT),
                       out_specs=shard, check_vma=checked_ok)
    # Same dispatch-time constant-upload exemption as the warm path.
    # The observatory attribution is first-wins, so the body's inner
    # solve_dense_converged labels stay subordinate to "sharded.cold".
    with jax.transfer_guard("allow"), _obs_device.entry("sharded.cold"):
        out = fn(*dev_args)
    assign = np.asarray(out)[:p_orig]
    if return_carry:
        return assign, carry_from_assignment(
            assign, np.asarray(pweights, np.float32),
            np.asarray(nweights, np.float32))
    return assign


def solve_sparse_sharded(
    mesh: Mesh,
    prev: np.ndarray,
    pweights: np.ndarray,
    nweights: np.ndarray,
    valid: np.ndarray,
    stickiness: np.ndarray,
    gids: np.ndarray,
    gid_valid: np.ndarray,
    constraints: tuple,
    rules: tuple,
    *,
    k: Optional[int] = None,
    shortlist: Optional[np.ndarray] = None,
    max_iterations: int = 10,
    sparse_impl: Optional[str] = None,
    dirty: Optional[np.ndarray] = None,
    carry: Optional[SolveCarry] = None,
    return_carry: bool = False,
    warm_only: bool = False,
):
    """The sparse shortlist solve under shard_map, partition axis
    sharded — the [P, K] score tables (and the shortlist itself) ride
    the partition axis via the declarative layout rows
    (``SPARSE_EXTRA_LAYOUT``) while every [N]-shaped fill/price table
    stays replicated, exactly like the dense layout.  1-D partition
    meshes only: the shortlist already bounds the column working set,
    so a node axis would shard kilobytes.

    The shortlist is derived on the PADDED problem (pad rows are
    weight-0 bidders with the same global candidates as the dense
    engine's pads see), or adopted from ``shortlist`` and padded.  With
    ``dirty`` + ``carry`` the warm one-sweep sparse repair runs first
    under the solve_dense_sharded warm contract (``warm_only``
    included); exhausted rows of an accepted result are re-placed by
    the host-side per-row dense fallback, after padding strips."""
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_shards = axes[PARTITION_AXIS]
    if axes.get(NODE_AXIS, 1) > 1:
        raise ValueError(
            "solve_sparse_sharded: node-axis meshes are not supported "
            "(the [P, K] shortlist already bounds the column working "
            "set); use a 1-D partition mesh")
    p_orig = prev.shape[0]
    from ..plan import tensor as _tensor

    constraints = tuple(int(c) for c in constraints)
    rules = tuple(tuple(r) for r in rules)
    if not sparse_rules_supported(rules):
        raise ValueError(
            "sparse solve requires nesting hierarchy rules "
            "(exclude_level < include_level); use solve_dense_sharded")
    _tensor._check_tier_band_scale(
        prev, pweights, nweights, valid, stickiness, constraints, rules)
    impl = resolve_sparse_impl(sparse_impl)

    prev_p = pad_partitions(np.asarray(prev), n_shards, -1)
    pw_p = pad_partitions(np.asarray(pweights), n_shards, 0.0)
    st_p = pad_partitions(np.asarray(stickiness), n_shards, 0.0)

    rec = get_recorder()
    sl_in = None if shortlist is None \
        else pad_partitions(np.asarray(shortlist), n_shards, -1)
    sl_p = _tensor._build_or_adopt_shortlist(
        prev_p, pw_p, nweights, valid, gids, gid_valid, constraints,
        rules, sl_in, k, True)

    shard = P(PARTITION_AXIS)
    rep = P()
    # The pallas sparse2 kernel needs the checker off for the same
    # reason as the fused dense engine on any mesh (see
    # solve_dense_sharded): the per-op vma propagation inside
    # pallas_call rejects the kernel's mix of node-replicated tables
    # and partition-varying columns, even though its outputs carry
    # correct annotations.
    checked_ok = impl != "pallas"
    device_put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    dev_args = (
        device_put(jnp.asarray(prev_p), shard),
        device_put(jnp.asarray(pw_p), shard),
        device_put(jnp.asarray(nweights), rep),
        device_put(jnp.asarray(valid), rep),
        device_put(jnp.asarray(st_p), shard),
        device_put(jnp.asarray(gids), rep),
        device_put(jnp.asarray(gid_valid), rep),
        device_put(jnp.asarray(sl_p), shard),
    )

    def finish(assign_np, exh_np, used=None):
        """Strip padding, run the host fallback on flagged REAL rows,
        rebuild the carry when asked (always from the final patched
        assignment — the fallback invalidates any device-side used)."""
        assign_np = assign_np[:p_orig]
        patched, replaced = _apply_sparse_fallback(
            assign_np, exh_np[:p_orig], np.asarray(prev), pweights,
            nweights, valid, stickiness, gids, gid_valid, constraints,
            rules)
        if not return_carry:
            return patched
        if replaced or used is None:
            return patched, carry_from_assignment(
                patched, np.asarray(pweights, np.float32),
                np.asarray(nweights, np.float32))
        used_j = jnp.asarray(np.asarray(used))
        return patched, SolveCarry(
            prices=jnp.sum(used_j, axis=0),
            assign=jnp.asarray(patched), used=used_j)

    if dirty is not None and carry is not None:
        dirty_p = pad_partitions(np.asarray(dirty, bool), n_shards, True)
        cu = np.asarray(carry.used, np.float32)
        rec.observe("plan.solve.dirty_fraction",
                    float(np.asarray(dirty, bool).mean())
                    if np.asarray(dirty).size else 0.0)
        sparse_body_w = partial(
            _warm_repair_sparse, constraints=constraints, rules=rules,
            axis_name=PARTITION_AXIS, sparse_impl=impl)
        fn_w = jax.shard_map(
            sparse_body_w, mesh=mesh,
            in_specs=layout_specs(SOLVER_IN_LAYOUT + SPARSE_EXTRA_LAYOUT
                                  + WARM_EXTRA_LAYOUT),
            out_specs=layout_specs(SPARSE_WARM_OUT_LAYOUT),
            check_vma=checked_ok)
        with rec.span("plan.solve.attempt", warm=True, sharded=True,
                      engine="sparse"), \
                _obs_device.entry("sparse.sharded.warm"):
            # Same dispatch-time constant-upload exemption as the dense
            # sharded paths (see solve_dense_sharded).
            with jax.transfer_guard("allow"):
                out, new_used, ok, exh = fn_w(
                    *dev_args,
                    device_put(jnp.asarray(dirty_p), shard),
                    device_put(jnp.asarray(cu), rep))
            accepted = bool(ok)
        if accepted:
            _record_sweeps(1)
            rec.set_attr("warm", True)
            return finish(np.asarray(out), np.asarray(exh), new_used)
        rec.count("plan.solve.warm_fallback")
        rec.count("plan.solve.sweeps", 1)  # the executed repair pass
        if warm_only:
            return (None, None) if return_carry else None

    sparse_body = partial(
        _solve_sparse_converged_impl, constraints=constraints,
        rules=rules, axis_name=PARTITION_AXIS,
        max_iterations=max_iterations, sparse_impl=impl)
    fn = jax.shard_map(
        sparse_body, mesh=mesh,
        in_specs=layout_specs(SOLVER_IN_LAYOUT + SPARSE_EXTRA_LAYOUT),
        out_specs=layout_specs(SPARSE_COLD_OUT_LAYOUT),
        check_vma=checked_ok)
    with rec.span("plan.solve.attempt", sharded=True, engine="sparse"), \
            jax.transfer_guard("allow"), \
            _obs_device.entry("sparse.sharded.cold"):
        out, sweeps, exh = fn(*dev_args)
    _record_sweeps(sweeps)
    return finish(np.asarray(out), np.asarray(exh))


@lru_cache(maxsize=64)
def _pipeline_sharded_fn(
    mesh: Mesh,
    constraints: tuple,
    rules: tuple,
    max_iterations: int,
    fused_score: str,
    favor_min_nodes: bool,
    node_axis: Optional[str],
    node_shards: int,
    warm: bool,
):
    """Build-and-jit one sharded pipeline dispatch, memoized on (mesh,
    statics).  The eager shard_map spelling recompiles its sub-programs
    on EVERY call (the builder closures are fresh objects, so nothing
    keys the cache); jitting the built fn and caching it here makes
    repeat dispatches hit the jit cache — the bounded-compilation
    contract the retrace budget (analysis/retrace.py, sharded.pipeline)
    pins."""
    if warm:
        pipe_body = partial(
            _pipeline_warm_impl,
            constraints=constraints, rules=rules,
            axis_name=PARTITION_AXIS, node_axis=node_axis,
            node_shards=node_shards, fused_score=fused_score,
            favor_min_nodes=favor_min_nodes)
        in_layout = SOLVER_IN_LAYOUT + WARM_EXTRA_LAYOUT
        out_layout = PIPELINE_WARM_OUT_LAYOUT
    else:
        pipe_body = partial(
            _pipeline_cold_impl,
            constraints=constraints, rules=rules,
            axis_name=PARTITION_AXIS, max_iterations=max_iterations,
            node_axis=node_axis, node_shards=node_shards,
            fused_score=fused_score, favor_min_nodes=favor_min_nodes)
        in_layout = SOLVER_IN_LAYOUT
        out_layout = PIPELINE_COLD_OUT_LAYOUT
    return jax.jit(jax.shard_map(pipe_body, mesh=mesh,
                                 in_specs=layout_specs(in_layout),
                                 out_specs=layout_specs(out_layout),
                                 check_vma=False))


def solve_pipeline_sharded(
    mesh: Mesh,
    prev: np.ndarray,
    pweights: np.ndarray,
    nweights: np.ndarray,
    valid: np.ndarray,
    stickiness: np.ndarray,
    gids: np.ndarray,
    gid_valid: np.ndarray,
    constraints: tuple,
    rules: tuple,
    max_iterations: int = 10,
    fused_score: Optional[str] = None,
    favor_min_nodes: bool = False,
    dirty: Optional[np.ndarray] = None,
    carry: Optional[SolveCarry] = None,
    warm_only: bool = False,
):
    """The fused plan pipeline (solve -> diff -> pack) under shard_map.

    The diff and the decode pack are row-wise in P — they shard over the
    partition axis with ZERO additional collectives, so the pipeline
    scales exactly as far as the solve does (any mesh mesh_shape_for /
    make_mesh_auto produces, 1-D or 2-D, beyond the fixed 8-chip
    layouts).  Returns (assign, SolveCarry, (d_nodes, d_states, d_ops))
    with padding stripped — the tuple PlannerSession.replan_with_moves
    consumes — or None when ``warm_only`` and the repair declined.

    With ``dirty`` + ``carry`` the warm one-sweep repair runs first,
    accepted under the solve_dense_warm contract; declined repairs fall
    through to the cold fixpoint unless ``warm_only``.  The replication
    checker stays off for the pipeline bodies: the psum'd carry tables
    and globally-agreed scalars come back through replicated out_specs
    the per-op vma walk cannot see through (same class of disable as the
    2-D/fused paths above).
    """
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_shards = axes[PARTITION_AXIS]
    node_shards = axes.get(NODE_AXIS, 1)
    node_axis = NODE_AXIS if node_shards > 1 else None
    p_orig = prev.shape[0]
    n_orig = np.asarray(nweights).shape[-1]
    from ..plan import tensor as _tensor

    _tensor._check_tier_band_scale(
        prev, pweights, nweights, valid, stickiness, constraints, rules)
    shard_p = -(-prev.shape[0] // n_shards)
    shard_n = -(-n_orig // node_shards)
    if fused_score is None:
        fused_score = _tensor.resolve_default_fused_score(shard_p, shard_n)
    else:
        fused_score = _tensor.resolve_fused_score(
            fused_score, shard_p, shard_n)

    prev_p = pad_partitions(np.asarray(prev), n_shards, -1)
    pw_p = pad_partitions(np.asarray(pweights), n_shards, 0.0)
    st_p = pad_partitions(np.asarray(stickiness), n_shards, 0.0)
    nw_p = np.asarray(nweights)
    valid_p = np.asarray(valid)
    gids_p = np.asarray(gids)
    gv_p = np.asarray(gid_valid)
    if node_shards > 1:
        nw_p = pad_nodes(nw_p, node_shards, 1.0)
        valid_p = pad_nodes(valid_p, node_shards, False)
        gids_p = pad_nodes(gids_p, node_shards, -1)
        gv_p = pad_nodes(gv_p, node_shards, False)

    shard = P(PARTITION_AXIS)
    device_put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    dev_args = tuple(
        device_put(jnp.asarray(a), spec)
        for a, spec in zip(
            (prev_p, pw_p, nw_p, valid_p, st_p, gids_p, gv_p),
            layout_specs(SOLVER_IN_LAYOUT)))

    rec = get_recorder()

    def strip(out, new_used, darrs):
        # The padded run's prices/packed ride along unused: the carry is
        # rebuilt off the node-stripped used table, and the session's
        # decode runs off ``current``/``proposed``, not the batch.
        assign = np.asarray(out)[:p_orig]
        used = jnp.asarray(np.asarray(new_used)[:, :n_orig])
        carry_out = SolveCarry(
            prices=jnp.sum(used, axis=0), assign=jnp.asarray(assign),
            used=used)
        d_nodes, d_states, d_ops = (np.asarray(a)[:p_orig] for a in darrs)
        return assign, carry_out, (d_nodes, d_states, d_ops)

    if dirty is not None and carry is not None:
        dirty_p = pad_partitions(np.asarray(dirty, bool), n_shards, True)
        cu = np.asarray(carry.used, np.float32)
        if node_shards > 1:
            cu = pad_nodes(cu, node_shards, 0.0)
        rec.observe("plan.solve.dirty_fraction",
                    float(np.asarray(dirty, bool).mean())
                    if np.asarray(dirty).size else 0.0)
        fn_w = _pipeline_sharded_fn(
            mesh, constraints, rules, max_iterations, fused_score,
            favor_min_nodes, node_axis, node_shards, warm=True)
        t0 = rec.now()
        with rec.span("plan.pipeline.dispatch", warm=True, sharded=True), \
                _obs_device.entry("sharded.pipeline"):
            # Same dispatch-time constant-upload exemption as
            # solve_dense_sharded's paths.
            with jax.transfer_guard("allow"):
                (out, prices, new_used, ok, d_nodes, d_states, d_ops,
                 packed, counts) = fn_w(
                    *dev_args,
                    device_put(jnp.asarray(dirty_p), shard),
                    device_put(jnp.asarray(cu), P()))
            accepted = bool(ok)
        rec.observe("plan.pipeline.dispatch_s", rec.now() - t0)
        if accepted:
            _record_sweeps(1)
            rec.set_attr("warm", True)
            return strip(out, new_used, (d_nodes, d_states, d_ops))
        rec.count("plan.solve.warm_fallback")
        rec.count("plan.solve.sweeps", 1)  # the executed repair pass
        if warm_only:
            return None

    fn = _pipeline_sharded_fn(
        mesh, constraints, rules, max_iterations, fused_score,
        favor_min_nodes, node_axis, node_shards, warm=False)
    t0 = rec.now()
    with rec.span("plan.pipeline.dispatch", sharded=True), \
            jax.transfer_guard("allow"), \
            _obs_device.entry("sharded.pipeline"):
        (out, sweeps, prices, new_used, d_nodes, d_states, d_ops,
         packed, counts) = fn(*dev_args)
    rec.observe("plan.pipeline.dispatch_s", rec.now() - t0)
    _record_sweeps(sweeps)
    return strip(out, new_used, (d_nodes, d_states, d_ops))


def solve_problem_sharded(
    mesh: Mesh, problem: DenseProblem, fused_score: Optional[str] = None
) -> np.ndarray:
    """Convenience: solve an encoded DenseProblem on a mesh."""
    rules = tuple(tuple(problem.rules.get(si, ())) for si in range(problem.S))
    constraints = tuple(int(c) for c in problem.constraints)
    return solve_dense_sharded(
        mesh,
        problem.prev,
        problem.partition_weights,
        problem.node_weights,
        problem.valid_node,
        problem.stickiness,
        problem.gids,
        problem.gid_valid,
        constraints,
        rules,
        fused_score=fused_score,
    )
