"""Fleet-scale multi-tenant batch planning: vmapped bucket-class solves.

Production deployments of the paper's scenario (cbgt/FTS-style) rebalance
hundreds of tenant *indexes* concurrently — each its own small, fully
independent planning problem — yet every ``solve_dense`` call is
one-at-a-time, so a fleet replan pays hundreds of device dispatches for
work that fits in one.  This module is the batch tier:

- tenants are admitted as :class:`TenantProblem`\\ s and grouped into
  **batch classes**: the PR-2 shape buckets (core/encode.py
  ``bucket_size``) on (P, N) plus the solver statics (S, R, constraints,
  rules).  Same class == same compiled program, the GSPMD bucketed-
  compilation insight (arXiv:2105.04663) lifted from "repeated calls"
  to "concurrent tenants".
- each class stacks its tenants' padded arrays into ``[B, P, S, R]`` /
  ``[B, S, N]`` batch tensors (core/encode.py ``pad_problem_arrays`` +
  ``stack_problem_arrays`` — the same inert-padding contract the
  bucketed single-problem path uses, so pad rows provably cannot
  perturb real rows) and runs the dense auction solver under
  ``jax.vmap``: one device dispatch per class, per-element results
  bit-identical to the single-problem path (pinned by
  tests/test_fleet.py).
- warm tenants (a caller-provided :class:`plan.tensor.SolveCarry` +
  dirty mask, typically via a :class:`plan.carry.CarryCache`) run the
  one-sweep carry-seeded repair under vmap, with the same per-element
  acceptance flags as ``solve_dense_warm``; declined elements fall back
  into the class's cold batch.
- with a 1-D ``jax.sharding.Mesh`` the batch axis is sharded over the
  mesh via ``shard_map`` — tenant solves are embarrassingly parallel
  (no cross-tenant collectives), so every device solves its slice of
  the class concurrently.  This composes with, rather than replaces,
  parallel/sharded.py: a tenant too large to batch still takes the
  partition-sharded single-problem path.

The per-element arithmetic is exactly the single-problem bucketed
path's: padded shapes, the real partition count threaded as the traced
``p_real`` fill denominator.  The sequential reference for every fleet
solve is therefore ``solve_dense_converged`` / ``solve_dense_warm`` on
the same padded arrays — and the results match those bit-for-bit.

The asyncio front door (request coalescing, backpressure, per-tenant
carry cache) lives in plan/service.py; this module is the synchronous
compute core.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core.encode import (
    DenseProblem,
    NPArray,
    bucket_size,
    pack_slot_rows,
    pad_problem_arrays,
    pad_to,
    stack_problem_arrays,
)
from ..obs import device as _device
from ..obs import get_recorder, phase_span
from .carry import capacity_shrank, effective_dirty
from .tensor import (
    Constraints,
    Rules,
    SolveCarry,
    _solve_dense_converged_impl,
    _tier_band_bounds,
    _tier_band_error,
    _used_by_state,
    _warm_repair,
    resolve_default_fused_score,
    resolve_fused_score,
)

__all__ = ["TenantProblem", "BatchClass", "FleetResult", "DecodeShare",
           "batch_class_of",
           "validate_tenant", "tier_band_refusals", "refused_tenants",
           "solve_fleet", "FLEET_AXIS"]

# Default mesh axis name for fleet batch sharding (make_mesh's "parts"
# axis is accepted too — any 1-D mesh works, the axis carries no
# collectives).
FLEET_AXIS = "fleet"


class BatchClass(NamedTuple):
    """One compiled-program equivalence class of tenant problems."""

    p: int  # bucketed partition count (bucket_size(P_real))
    n: int  # bucketed node count (bucket_size(N_real))
    s: int  # states
    r: int  # slot depth
    levels: int  # hierarchy levels (gids rows)
    constraints: tuple[int, ...]
    rules: tuple[tuple[tuple[int, int], ...], ...]


@dataclass(frozen=True)
class TenantProblem:
    """One tenant's dense planning problem, ready to batch.

    Arrays follow plan/tensor.py solve_dense's positional layout.  The
    optional ``carry``/``dirty`` pair requests the warm path: ``carry``
    must match ``prev`` exactly (the solve_dense_warm contract — the
    CarryCache's consume() validates this for service callers) and
    ``dirty`` marks the partitions the delta since the carry may move.
    """

    key: str
    prev: NPArray  # [P, S, R] int32, -1 empty
    partition_weights: NPArray  # [P] float32
    node_weights: NPArray  # [N] float32
    valid_node: NPArray  # [N] bool
    stickiness: NPArray  # [P, S] float32
    gids: NPArray  # [L, N] int32
    gid_valid: NPArray  # [L, N] bool
    constraints: tuple[int, ...]
    rules: tuple[tuple[tuple[int, int], ...], ...]
    carry: Optional[SolveCarry] = None
    dirty: Optional[NPArray] = None

    @classmethod
    def from_dense(cls, key: str, problem: DenseProblem,
                   carry: Optional[SolveCarry] = None,
                   dirty: Optional[NPArray] = None,
                   prev: Optional[NPArray] = None) -> "TenantProblem":
        """Wrap an encoded DenseProblem (``prev`` overrides the encode-
        time seed — pass a session's live ``current``)."""
        return cls(
            key=key,
            prev=np.asarray(problem.prev if prev is None else prev,
                            np.int32),
            partition_weights=np.asarray(problem.partition_weights,
                                         np.float32),
            node_weights=np.asarray(problem.node_weights, np.float32),
            valid_node=np.asarray(problem.valid_node, bool),
            stickiness=np.asarray(problem.stickiness, np.float32),
            gids=np.asarray(problem.gids, np.int32),
            gid_valid=np.asarray(problem.gid_valid, bool),
            constraints=tuple(int(c) for c in problem.constraints),
            rules=tuple(tuple(problem.rules.get(si, ()))
                        for si in range(problem.S)),
            carry=carry,
            dirty=dirty,
        )


@dataclass
class DecodeShare:
    """One tenant's share of the decode's array work, computed once per
    class batch (:func:`_decode_shares`), so a resident decode
    (plan/resident.py ``EncodedState.decode``) only builds the
    ``Partition`` objects of its changed rows.  Every field diffs the
    solve's ``assign`` against ``prev``; the modeled states are those
    with a constraint above 0, in state order."""

    prev: NPArray  # the tenant's submitted [P, S, R] prev (the object)
    changed: list[int]  # rows whose assignment differs from prev
    ids: list[int]  # node ids of the changed rows, flat: per changed
    #                 row, per modeled state, R slots packed left
    fill: list[int]  # filled slots per changed row and modeled state
    packed: NPArray  # [P, S, R] int32: prev with the changed rows packed
    counts: NPArray  # [P, S] int64 filled slots per row of ``packed``
    short: list[list[int]]  # per modeled state: rows under its
    #                         constraint ([] when no row is short)


@dataclass
class FleetResult:
    """One tenant's solve outcome (arrays at the REAL, unpadded shape)."""

    key: str
    assign: NPArray  # [P, S, R] int32
    carry: Optional[SolveCarry]  # rebuilt warm-start state, real-N used
    warm: bool  # solved by an accepted one-sweep repair
    sweeps: int  # converged-loop passes executed
    klass: Optional[BatchClass]  # None for degenerate (empty) problems
    share: Optional[DecodeShare] = None  # None for degenerate problems


def batch_class_of(t: TenantProblem) -> BatchClass:
    """The tenant's batch class: bucketed shape + solver statics."""
    p, s, r = t.prev.shape
    n = t.node_weights.shape[0]
    return BatchClass(
        p=bucket_size(p), n=bucket_size(n), s=s, r=r,
        levels=t.gids.shape[0],
        constraints=tuple(int(c) for c in t.constraints),
        rules=tuple(tuple(rl) for rl in t.rules))


def validate_tenant(t: TenantProblem) -> None:
    """Raise ValueError when one tenant's arrays are malformed: the
    structural checks — cross-array shape consistency (a malformed
    array would otherwise only explode inside the batched solve) and
    slot depth against the constraints — each a keyed diagnostic.  The
    tier-band scale guard needs well-shaped arrays and runs after these,
    once per batch class over many tenants (:func:`tier_band_refusals`).
    """
    prev = np.asarray(t.prev)
    if prev.ndim != 3:
        raise ValueError(
            f"tenant {t.key!r}: prev must be [P, S, R], got shape "
            f"{prev.shape}")
    p, s, r = prev.shape
    n = np.asarray(t.node_weights).shape[0]
    shapes = {
        "partition_weights": (np.asarray(t.partition_weights).shape,
                              (p,)),
        "stickiness": (np.asarray(t.stickiness).shape, (p, s)),
        "valid_node": (np.asarray(t.valid_node).shape, (n,)),
        "gids": (np.asarray(t.gids).shape[-1:], (n,)),
        "gid_valid": (np.asarray(t.gid_valid).shape,
                      np.asarray(t.gids).shape),
    }
    if t.dirty is not None:
        shapes["dirty"] = (np.asarray(t.dirty).shape, (p,))
    for name, (got, want) in shapes.items():
        if tuple(got) != tuple(want):
            raise ValueError(
                f"tenant {t.key!r}: {name} shape {tuple(got)} does not "
                f"match prev/nodes (want {tuple(want)})")
    if t.constraints and max(t.constraints) > r:
        raise ValueError(
            f"tenant {t.key!r}: prev slot depth R={r} "
            f"< max constraints {max(t.constraints)}")


def tier_band_refusals(tenants: Sequence[TenantProblem], *,
                       recorder=None,
                       record: bool = True) -> dict[int, ValueError]:
    """The single-problem entry points' tier-band scale guard
    (plan/tensor.py ``_check_tier_band_scale``) over structurally valid,
    dtype-normalized tenants: one vectorized pass per batch class
    (``_tier_band_bounds``), every tenant's bound checked against the
    same limit.  Returns ``{index: ValueError}`` for the tenants whose
    bound overflows, each with the single-problem guard's message.
    Rule-less and empty tenants are exempt, as there.

    obs: one ``fleet.validate`` span (``tenants``, ``classes``,
    ``refused`` attrs; also a host annotation in a profiler trace) and
    the ``fleet.validate_refused`` counter."""
    rec = recorder if recorder is not None else get_recorder()
    refused: dict[int, ValueError] = {}
    with phase_span("fleet.validate", recorder=rec,
                    tenants=len(tenants)) as span:
        by_class: dict[BatchClass, list[int]] = {}
        for i, t in enumerate(tenants):
            if any(rl for rl in t.rules) and t.prev.size and \
                    t.node_weights.shape[0]:
                by_class.setdefault(batch_class_of(t), []).append(i)
        for k, idxs in by_class.items():
            group = [tenants[i] for i in idxs]
            bounds = _tier_band_bounds(
                [t.prev for t in group],
                [t.partition_weights for t in group],
                [t.node_weights for t in group],
                [t.valid_node for t in group],
                [t.stickiness for t in group], k.constraints)
            for i, t, bound in zip(idxs, group, bounds.tolist()):
                err = _tier_band_error(bound, t.prev.shape[0],
                                       t.node_weights, t.valid_node,
                                       k.constraints)
                if err is not None:
                    refused[i] = err
        span.attrs.update(classes=len(by_class), refused=len(refused))
    if record and refused:
        rec.count("fleet.validate_refused", len(refused))
    return refused


def refused_tenants(tenants: Sequence[TenantProblem], *, recorder=None,
                    record: bool = True) -> dict[int, ValueError]:
    """Why each refused tenant cannot be solved, ``{index: error}``:
    :func:`validate_tenant`'s structural checks per tenant, then
    :func:`tier_band_refusals` once per batch class over the tenants
    that passed them.  ``tenants`` are dtype-normalized
    (``_normalized``).  solve_fleet raises the first; the plan service
    fails each refused request alone."""
    refused: dict[int, ValueError] = {}
    shaped: list[int] = []
    for i, t in enumerate(tenants):
        try:
            validate_tenant(t)
        except ValueError as e:
            refused[i] = e
        else:
            shaped.append(i)
    bands = tier_band_refusals([tenants[i] for i in shaped],
                               recorder=recorder, record=record)
    refused.update({shaped[j]: e for j, e in bands.items()})
    return refused


# -- batched device programs -------------------------------------------------
#
# Module-level jits with static (constraints, rules, ...) so every batch
# class compiles exactly once and every later dispatch of the class hits
# the jit cache (the whole point of bucketed batching).  The per-element
# body is the SAME traced code as the single-problem path —
# _solve_dense_converged_impl / _warm_repair with the traced p_real fill
# denominator — so vmap only adds the batch dimension, and per-element
# outputs are bit-identical to single solves (tests/test_fleet.py pins
# this, cold and warm).


@partial(jax.jit, static_argnames=("constraints", "rules",
                                   "max_iterations", "fused_score"))
def _fleet_cold_batch(
    prev: jnp.ndarray,  # [B, P, S, R]
    pweights: jnp.ndarray,  # [B, P]
    nweights: jnp.ndarray,  # [B, N]
    valid: jnp.ndarray,  # [B, N]
    stickiness: jnp.ndarray,  # [B, P, S]
    gids: jnp.ndarray,  # [B, L, N]
    gid_valid: jnp.ndarray,  # [B, L, N]
    p_real: jnp.ndarray,  # [B] f32 — real partition counts
    constraints: Constraints,
    rules: Rules,
    max_iterations: int = 10,
    fused_score: str = "off",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Batched cold fixpoint: (assign[B,P,S,R], sweeps[B], used[B,S,N]).

    ``used`` is each element's carry table (_used_by_state, the same
    scatter the single-problem carry_from_assignment runs) so the next
    warm solve seeds bit-identically without B little host jits."""
    def one(prev1, pw1, nw1, valid1, stick1, gids1, gv1, p1):
        out, sweeps = _solve_dense_converged_impl(
            prev1, pw1, nw1, valid1, stick1, gids1, gv1, constraints,
            rules, max_iterations=max_iterations, fused_score=fused_score,
            p_real=p1)
        used = _used_by_state(out, pw1, nw1.shape[0], out.shape[1])
        return out, sweeps, used

    return jax.vmap(one)(prev, pweights, nweights, valid, stickiness,
                         gids, gid_valid, p_real)


@partial(jax.jit, static_argnames=("constraints", "rules", "fused_score"))
def _fleet_warm_batch(
    prev: jnp.ndarray,  # [B, P, S, R]
    pweights: jnp.ndarray,
    nweights: jnp.ndarray,
    valid: jnp.ndarray,
    stickiness: jnp.ndarray,
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    dirty: jnp.ndarray,  # [B, P] bool (pad rows True: not a ripple)
    carry_used: jnp.ndarray,  # [B, S, N]
    p_real: jnp.ndarray,  # [B]
    constraints: Constraints,
    rules: Rules,
    fused_score: str = "off",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Batched one-sweep warm repair: (assign, used, ok) per element."""
    def one(prev1, pw1, nw1, valid1, stick1, gids1, gv1, dirty1, cu1, p1):
        return _warm_repair(
            prev1, pw1, nw1, valid1, stick1, gids1, gv1, dirty1, cu1,
            constraints, rules, fused_score=fused_score, p_real=p1)

    return jax.vmap(one)(prev, pweights, nweights, valid, stickiness,
                         gids, gid_valid, dirty, carry_used, p_real)


# Mesh-sharded variants, built lazily per (mesh, statics) and cached —
# rebuilding jax.jit(shard_map(...)) per call would defeat the jit
# cache.  Bounded: a fleet deployment has a handful of classes and one
# mesh.
_MESH_FN_CACHE: dict[tuple[object, ...], Any] = {}
_MESH_FN_CACHE_MAX = 128


def _mesh_callable(mesh, warm: bool, constraints: Constraints, rules: Rules,
                   max_iterations: int, fused_score: str):
    """jit(shard_map(vmap(solver))) with the batch axis sharded.

    Tenant solves are independent — no collectives ride the mesh axis —
    so in/out specs shard every operand's leading (batch) dimension and
    nothing is replicated, so the varying-axes checker has nothing to
    verify and stays off.
    """
    from jax.sharding import PartitionSpec

    key = (mesh, warm, constraints, rules, max_iterations, fused_score)
    fn = _MESH_FN_CACHE.get(key)
    if fn is not None:
        # Move-to-end: insertion order doubles as LRU recency.
        _MESH_FN_CACHE[key] = _MESH_FN_CACHE.pop(key)
        return fn
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"fleet batch sharding wants a 1-D mesh, got axes "
            f"{mesh.axis_names}")
    axis = mesh.axis_names[0]
    sh = PartitionSpec(axis)
    if warm:
        body = partial(_fleet_warm_batch, constraints=constraints,
                       rules=rules, fused_score=fused_score)
        n_in = 10
    else:
        body = partial(_fleet_cold_batch, constraints=constraints,
                       rules=rules, max_iterations=max_iterations,
                       fused_score=fused_score)
        n_in = 8
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(sh,) * n_in,
                               out_specs=(sh, sh, sh), check_vma=False))
    while len(_MESH_FN_CACHE) >= _MESH_FN_CACHE_MAX:
        # Evict the least-recently-used wrapper only — clearing the
        # whole table would force every hot class to retrace.
        _MESH_FN_CACHE.pop(next(iter(_MESH_FN_CACHE)))
    _MESH_FN_CACHE[key] = fn
    return fn


# -- host orchestration ------------------------------------------------------


def _normalized(t: TenantProblem) -> TenantProblem:
    """Dtype-normalize a tenant's arrays (solver dtypes, C-contiguous)."""
    return TenantProblem(
        key=t.key,
        prev=np.ascontiguousarray(t.prev, np.int32),
        partition_weights=np.ascontiguousarray(t.partition_weights,
                                               np.float32),
        node_weights=np.ascontiguousarray(t.node_weights, np.float32),
        valid_node=np.ascontiguousarray(t.valid_node, bool),
        stickiness=np.ascontiguousarray(t.stickiness, np.float32),
        gids=np.ascontiguousarray(t.gids, np.int32),
        gid_valid=np.ascontiguousarray(t.gid_valid, bool),
        constraints=tuple(int(c) for c in t.constraints),
        rules=tuple(tuple(rl) for rl in t.rules),
        carry=t.carry,
        dirty=None if t.dirty is None
        else np.ascontiguousarray(t.dirty, bool),
    )


def _padded_solver_arrays(t: TenantProblem,
                          k: BatchClass) -> tuple[NPArray, ...]:
    """One tenant's arrays padded to its class shape (inert padding)."""
    return pad_problem_arrays(
        t.prev, t.partition_weights, t.node_weights, t.valid_node,
        t.stickiness, t.gids, t.gid_valid, k.p, k.n)


def _warm_eligible(t: TenantProblem, rec,
                   record: bool) -> Optional[NPArray]:
    """The tenant's effective dirty mask when the warm path may run,
    else None (demoted to cold).  Mirrors PlannerSession.replan's
    gating: a carry + dirty mask must be present, the carry must match
    prev's shape, and the host capacity precheck must not predict a
    clean-holder displacement (which the repair could never accept)."""
    if t.carry is None or t.dirty is None:
        return None
    carry_assign = np.asarray(t.carry.assign)
    used = np.asarray(t.carry.used)
    if carry_assign.shape != t.prev.shape or \
            used.shape != (t.prev.shape[1], t.node_weights.shape[0]):
        if record:
            rec.count("plan.solve.carry_miss")
        return None
    dirty = effective_dirty(t.dirty, t.prev, t.constraints)
    if capacity_shrank(used, t.prev, t.partition_weights,
                       t.node_weights, t.valid_node, t.constraints,
                       dirty):
        # Grown cluster: the trim pass would displace clean holders —
        # the repair could never be accepted, so skip straight to cold
        # instead of wasting a sweep (PlannerSession parity).
        if record:
            rec.count("plan.solve.carry_miss")
        return None
    return dirty


def _pad_batch(stacked: Sequence[NPArray],
               b_target: int) -> tuple[list[NPArray], int]:
    """Pad the batch axis to ``b_target`` by replicating the last
    element (a real problem solves to a real answer, discarded) —
    returns (padded arrays, padded B)."""
    b = stacked[0].shape[0]
    if b_target <= b:
        return list(stacked), b
    reps = np.full(b_target - b, b - 1, np.intp)
    return [np.concatenate([a, a[reps]]) for a in stacked], b_target


def _dispatch(fn_args: list[NPArray], mesh, warm: bool,
              k: BatchClass, max_iterations: int, fused_score: str,
              rec, record: bool,
              batch_floor: int = 1) -> tuple[NPArray, ...]:
    """Run one class batch on device (vmapped; mesh-sharded when given);
    returns host arrays, batch padding stripped.

    The batch axis is itself a static jit shape, so it gets the same
    bucketing treatment as P and N: B pads up to ``bucket_size(B)``
    (and to mesh divisibility), so a service whose coalesced batch
    sizes drift round to round reuses one compiled program per bucket
    instead of recompiling per size.  ``batch_floor`` additionally
    rounds B UP to a minimum before bucketing: at small B the buckets
    step by 1, so a fleet of control loops whose coalesced sizes
    wander 1..N would compile one program per size — flooring them
    onto one shared program trades a few inert pad elements for a
    bounded compiled-program count (docs/FLEET.md)."""
    b_real = fn_args[0].shape[0]
    b_target = bucket_size(max(b_real, batch_floor))
    ent = "fleet.warm" if warm else "fleet.cold"
    launch = phase_span("fleet.launch", recorder=rec)
    if mesh is not None:
        n_dev = int(np.prod(mesh.devices.shape))
        b_target += (-b_target) % n_dev
        fn_args, b_padded = _pad_batch(fn_args, b_target)
        fn = _mesh_callable(mesh, warm, k.constraints, k.rules,
                            max_iterations, fused_score)
        from jax.sharding import NamedSharding, PartitionSpec

        spec = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
        with launch:
            # device_put straight off the host arrays: shards
            # host->devices in one placement (jnp.asarray first would
            # commit every operand to the default device and then
            # reshard — double transfer).
            dev_args = [jax.device_put(a, spec) for a in fn_args]
            _device.maybe_publish_cost(
                ent, f"{k.p}x{k.n}xB{b_padded}", fn, *dev_args)
            # Dispatch-time jaxpr-constant uploads are implicit
            # transfers by jax's classification but intrinsic to
            # compilation — the same scoped allow parallel/sharded.py
            # documents.
            with jax.transfer_guard("allow"), _device.entry(ent):
                outs = fn(*dev_args)
    else:
        fn_args, b_padded = _pad_batch(fn_args, b_target)
        batch_fn = _fleet_warm_batch if warm else _fleet_cold_batch
        statics = dict(constraints=k.constraints, rules=k.rules,
                       fused_score=fused_score)
        if not warm:
            statics["max_iterations"] = max_iterations
        with launch:
            dev_args = [jnp.asarray(a) for a in fn_args]
            _device.maybe_publish_cost(
                ent, f"{k.p}x{k.n}xB{b_padded}", batch_fn, *dev_args,
                **statics)
            with _device.entry(ent):
                outs = batch_fn(*dev_args, **statics)
    if record:
        rec.observe("fleet.batch_tenants", float(b_real))
        rec.observe("fleet.batch_occupancy",
                    b_real / b_padded if b_padded else 0.0)
        # Host->device transfer accounting: the stacked batch tensors
        # this dispatch ships (deterministic — a pure function of the
        # batch's shapes, so exposition text stays replay-identical).
        rec.count("fleet.h2d_bytes",
                  sum(int(np.asarray(a).nbytes) for a in fn_args))
    with phase_span("fleet.fetch", recorder=rec):
        # One blocking read per output (fleet.d2h_waits counts them).
        host = tuple(np.asarray(o)[:b_real] for o in outs)
    if record:
        rec.count("fleet.d2h_waits", len(outs))
    return host


def _count_solve(rec, sweeps: int) -> None:
    """One solved element's plan.solve.* accounting — the
    tensor._record_sweeps spelling, routed to THIS recorder (the
    executor-thread path must not fall back to the process global)."""
    rec.count("plan.solve.calls")
    rec.count("plan.solve.sweeps", sweeps)
    rec.observe("plan.solve.sweeps", sweeps)


def _real_carry(assign: NPArray, used_padded: NPArray,
                n_real: int) -> SolveCarry:
    """Strip node padding off a batched element's carry table.  Pad
    columns are invalid nodes with zero fill (inert-padding contract),
    so the slice is exact; prices re-derive as the per-node sum.  The
    slice is COPIED (explicitly — at bucket-exact sizes it is already
    contiguous): a view would pin the whole [B, S, N] batch tensor
    alive per tenant while CarryCache's byte accounting sees only the
    slice."""
    used = used_padded[:, :n_real].copy()
    return SolveCarry(prices=used.sum(axis=0), assign=assign, used=used)


def _decode_shares(prevs: Sequence[NPArray], prev_b: NPArray,
                   assign_b: NPArray, constraints: tuple[int, ...],
                   keep: Optional[NPArray] = None,
                   ) -> list[Optional[DecodeShare]]:
    """The decode's array half for a whole class batch, vectorized over
    its tenants: the changed-row diff (pad rows masked out), one
    :func:`~blance_tpu.core.encode.pack_slot_rows` over every changed
    row, the counts, the shortfall rows and one flat ``tolist`` (flat:
    a nested list per row would cost an object per row and state, and
    the collector's passes over them).  ``prev_b``
    and ``assign_b`` are the batch's padded ``[B, P, S, R]`` prev and
    solve; ``prevs`` the tenants' own ``prev`` arrays.  Tenants that
    ``keep`` marks False (declined warm repairs) get None.

    ``counts`` covers every row of ``packed``; a row the solve left
    alone keeps prev's fill, which is what a resident state's counts
    hold for it (plan/resident.py)."""
    b, p = assign_b.shape[:2]
    p_real = [pv.shape[0] for pv in prevs]
    kept = [True] * b if keep is None else np.asarray(keep, bool).tolist()
    real = (np.arange(p) < np.asarray(p_real)[:, None]) \
        & np.asarray(kept)[:, None]  # [B, P]
    changed = (assign_b != prev_b).any(axis=(2, 3)) & real
    bi, pi = np.nonzero(changed)
    rows_c, counts_c = pack_slot_rows(
        assign_b[bi, pi].astype(np.int32, copy=False))
    packed_b = prev_b.copy()
    packed_b[bi, pi] = rows_c
    counts_b = (packed_b >= 0).sum(axis=3).astype(np.int64)
    mod = [si for si, c in enumerate(constraints) if c > 0]
    want = np.asarray([constraints[si] for si in mod], np.int64)
    short_b = (counts_b[:, :, mod] < want) & real[:, :, None]
    any_short = short_b.any(axis=(1, 2)).tolist()
    ids = rows_c[:, mod, :].ravel().tolist()
    fill = counts_c[:, mod].ravel().tolist()
    m_r, m_n = len(mod) * rows_c.shape[2], len(mod)
    bounds = np.searchsorted(bi, np.arange(b + 1)).tolist()
    changed_rows = pi.tolist()
    out: list[Optional[DecodeShare]] = []
    for j, (lo, hi, pr, ok, short_any) in enumerate(zip(
            bounds, bounds[1:], p_real, kept, any_short)):
        if not ok:
            out.append(None)
            continue
        short = [np.flatnonzero(short_b[j, :, m]).tolist()
                 for m in range(m_n)] if short_any else []
        # Copies off the batch tensors, as assign's: a view would pin
        # the whole batch alive per tenant.
        out.append(DecodeShare(
            prevs[j], changed_rows[lo:hi], ids[lo * m_r:hi * m_r],
            fill[lo * m_n:hi * m_n], packed_b[j, :pr].copy(),
            counts_b[j, :pr].copy(), short))
    return out


def _trace_attrs(trace_ids: Optional[dict[str, str]],
                 keys: Sequence[str]) -> dict[str, str]:
    """Span attrs carrying the batch members' trace ids (capped: a
    thousand-tenant batch must not serialize a novel per span)."""
    if not trace_ids:
        return {}
    ids = [str(trace_ids[k]) for k in keys if k in trace_ids]
    if not ids:
        return {}
    shown = ",".join(ids[:16])
    if len(ids) > 16:
        shown += f",+{len(ids) - 16}"
    return {"trace_ids": shown}


def solve_fleet(
    problems: Sequence[TenantProblem],
    *,
    mesh=None,
    max_iterations: int = 10,
    fused_score: Optional[str] = None,
    record: bool = True,
    recorder=None,
    trace_ids: Optional[dict[str, str]] = None,
    batch_floor: int = 1,
) -> list[FleetResult]:
    """Solve every tenant, batched by bucket class: one device dispatch
    per (class, warm/cold) instead of one per tenant.

    Results are returned in input order, each bit-identical to running
    that tenant through the single-problem path on the same padded
    arrays (``solve_dense_converged`` / ``solve_dense_warm`` with the
    class shape and the tenant's real-P fill denominator).  Tenants
    with a ``carry`` + ``dirty`` pair attempt the one-sweep warm repair
    first; declined elements (ripple / fresh over-capacity — the same
    per-element flags the single warm path checks) fall back into the
    class's cold batch, exactly like a session's warm decline.

    Every tenant is checked before any dispatch
    (:func:`refused_tenants`): the structural checks per tenant, then
    the tier-band scale guard once per batch class, vectorized.  The
    first refused tenant in input order raises its ValueError and
    nothing is solved.

    ``mesh`` (1-D) shards each class's batch axis over the devices via
    shard_map — tenant solves are independent, so this is pure
    data-parallel scale-out.  ``fused_score`` None resolves the module
    default per class shape, like every other solve entry point.

    obs: the ``fleet.validate`` span of the tier-band pass; per-batch
    ``fleet.batch_tenants`` / ``fleet.batch_occupancy``
    histograms and a ``fleet.dispatch`` span per device dispatch
    (``warm``, ``engine`` = the class's resolved ``fused_score``,
    ``tenants``, ``klass`` attrs) with ``fleet.launch`` (uploads and
    the jitted call, up to its return) and ``fleet.fetch`` (the reads
    back to the host, counted by ``fleet.d2h_waits``) inside it, all
    three also host annotations in a profiler trace, and the
    ``fleet.dispatch_s`` histogram; per-tenant ``plan.solve.*``
    carry/sweep counters mirror the single-problem spellings.  Each
    solved result carries its :class:`DecodeShare`, computed for the
    whole class batch inside its ``fleet.dispatch`` span, after the
    reads.
    ``recorder`` overrides the process recorder (the plan service
    passes its own so executor-thread solves report to the right one).
    ``trace_ids`` (tenant key → trace id, the plan service's
    :class:`obs.tracectx.TraceContext` ids) rides into each
    ``fleet.dispatch`` span's attrs so a request's device dispatch is
    findable from its trace id in Perfetto and the JSONL sink.
    """
    tenants = [_normalized(t) for t in problems]
    # Validate FIRST: a malformed prev must surface as the keyed
    # per-tenant diagnostic, not an opaque shape-unpack error.
    refused = refused_tenants(tenants, recorder=recorder, record=record)
    if refused:
        raise refused[min(refused)]
    return _solve_checked(
        tenants, mesh=mesh, max_iterations=max_iterations,
        fused_score=fused_score, record=record, recorder=recorder,
        trace_ids=trace_ids, batch_floor=batch_floor)


def _solve_checked(
    tenants: Sequence[TenantProblem],
    *,
    mesh=None,
    max_iterations: int = 10,
    fused_score: Optional[str] = None,
    record: bool = True,
    recorder=None,
    trace_ids: Optional[dict[str, str]] = None,
    batch_floor: int = 1,
) -> list[FleetResult]:
    """:func:`solve_fleet` past its checks: dtype-normalized tenants
    that :func:`refused_tenants` admitted.  The plan service calls this
    directly once it has checked its batch and failed the refused
    requests alone."""
    rec = recorder if recorder is not None else get_recorder()
    results: dict[int, FleetResult] = {}
    by_class: dict[BatchClass, list[int]] = {}
    for i, t in enumerate(tenants):
        p, s, _r = t.prev.shape
        n = t.node_weights.shape[0]
        if p == 0 or n == 0 or s == 0:
            # Degenerate problem: nothing to place (PlannerSession
            # returns current unchanged for these).
            results[i] = FleetResult(
                key=t.key, assign=t.prev.copy(), carry=None, warm=False,
                sweeps=0, klass=None)
            continue
        by_class.setdefault(batch_class_of(t), []).append(i)

    for k, idxs in by_class.items():
        mode = fused_score
        if mode is None:
            mode = resolve_default_fused_score(k.p, k.n)
        else:
            mode = resolve_fused_score(mode, k.p, k.n)

        warm_idx: list[int] = []
        warm_dirty: dict[int, NPArray] = {}
        cold_idx: list[int] = []
        for i in idxs:
            dirty = _warm_eligible(tenants[i], rec, record)
            if dirty is None:
                cold_idx.append(i)
            else:
                warm_idx.append(i)
                warm_dirty[i] = dirty

        if warm_idx:
            batch = []
            for i in warm_idx:
                t = tenants[i]
                arrs = _padded_solver_arrays(t, k)
                # Pad rows are marked dirty (their synthetic assignments
                # must not read as a ripple) and the carry table's pad
                # columns are zero-fill — the parallel/sharded.py warm
                # layout, element-wise.
                dirty_p = pad_to(warm_dirty[i], 0, k.p, True)
                cu = pad_to(np.asarray(t.carry.used, np.float32), 1,
                            k.n, 0.0)
                batch.append(arrs + (dirty_p, cu,
                                     np.float32(t.prev.shape[0])))
                if record:
                    rec.observe(
                        "plan.solve.dirty_fraction",
                        float(warm_dirty[i].mean())
                        if warm_dirty[i].size else 0.0)
            stacked = list(stack_problem_arrays(batch))
            t0 = rec.now()
            with phase_span("fleet.dispatch", recorder=rec,
                            warm=True, engine=mode,
                            tenants=len(warm_idx),
                            klass=f"{k.p}x{k.n}",
                            **_trace_attrs(trace_ids,
                                           [tenants[i].key
                                            for i in warm_idx])):
                out_b, used_b, ok_b = _dispatch(
                    stacked, mesh, True, k, max_iterations, mode, rec,
                    record, batch_floor=batch_floor)
                shares = _decode_shares(
                    [tenants[i].prev for i in warm_idx], stacked[0],
                    out_b, k.constraints, keep=ok_b)
            if record:
                rec.observe("fleet.dispatch_s", rec.now() - t0)
                rec.count("fleet.batches")
            for j, i in enumerate(warm_idx):
                t = tenants[i]
                if bool(ok_b[j]):
                    p_real = t.prev.shape[0]
                    n_real = t.node_weights.shape[0]
                    # Copy off the batch tensor: a view per tenant would
                    # pin the whole [B, P, S, R] array alive (row
                    # slices are contiguous, so ascontiguousarray
                    # would no-op into a view).
                    assign = out_b[j][:p_real].copy()
                    if record:
                        _count_solve(rec, 1)
                        rec.count("plan.solve.carry_hit")
                    results[i] = FleetResult(
                        key=t.key, assign=assign,
                        carry=_real_carry(assign, used_b[j], n_real),
                        warm=True, sweeps=1, klass=k, share=shares[j])
                else:
                    # Declined repair: same accounting as
                    # solve_dense_warm's decline, then the cold batch
                    # picks the tenant up.
                    if record:
                        rec.count("plan.solve.warm_fallback")
                        rec.count("plan.solve.sweeps", 1)
                    cold_idx.append(i)

        if cold_idx:
            batch = []
            for i in cold_idx:
                t = tenants[i]
                arrs = _padded_solver_arrays(t, k)
                batch.append(arrs + (np.float32(t.prev.shape[0]),))
            stacked = list(stack_problem_arrays(batch))
            t0 = rec.now()
            with phase_span("fleet.dispatch", recorder=rec,
                            warm=False, engine=mode,
                            tenants=len(cold_idx),
                            klass=f"{k.p}x{k.n}",
                            **_trace_attrs(trace_ids,
                                           [tenants[i].key
                                            for i in cold_idx])):
                out_b, sweeps_b, used_b = _dispatch(
                    stacked, mesh, False, k, max_iterations, mode, rec,
                    record, batch_floor=batch_floor)
                shares = _decode_shares(
                    [tenants[i].prev for i in cold_idx], stacked[0],
                    out_b, k.constraints)
            if record:
                rec.observe("fleet.dispatch_s", rec.now() - t0)
                rec.count("fleet.batches")
            for j, i in enumerate(cold_idx):
                t = tenants[i]
                p_real = t.prev.shape[0]
                n_real = t.node_weights.shape[0]
                assign = out_b[j][:p_real].copy()
                if record:
                    _count_solve(rec, int(sweeps_b[j]))
                results[i] = FleetResult(
                    key=t.key, assign=assign,
                    carry=_real_carry(assign, used_b[j], n_real),
                    warm=False, sweeps=int(sweeps_b[j]), klass=k,
                    share=shares[j])

    return [results[i] for i in range(len(tenants))]
