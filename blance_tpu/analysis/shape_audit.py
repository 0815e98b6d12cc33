"""Abstract shape audit: the solver's [P, S, N, R] contracts, enforced.

The dense solver's shape conventions — assign[P, S, R] int32 with -1
empties, carry.used[S, N] float32, prices[N], the bucketed-pad and
shard_map layouts — live in docstrings and comments; nothing fails when
an entry point drifts.  This module pins them in a declarative contract
table checked with ``jax.eval_shape``: every public solver entry point is
traced abstractly across a (P, S, N, R) x bucketing x carry matrix, so
shape/dtype drift is caught in seconds with ZERO FLOPs and no device
(GSPMD's insight in reverse: if the shapes are static contracts, check
the contracts statically).

Covered entry points (acceptance contract):

- ``solve_dense``            — cold, carry-seeded, bucketed, bucketed+carry
- ``solve_dense_converged``  — via ``_solve_dense_converged_impl`` (the
  public wrapper adds host-side recording only), cold + carry
- ``solve_dense_warm``       — via ``_warm_repair`` (the public wrapper
  adds host gates around exactly this traced core)
- sharded solve              — ``solve_dense`` under ``shard_map`` with
  the partition axis sharded, the layout solve_dense_sharded builds
- fleet batch solves         — ``plan.fleet._fleet_cold_batch`` /
  ``_fleet_warm_batch``, the vmapped bucket-class programs the
  multi-tenant tier dispatches (stacked ``[B, ...]`` layouts)
- fused plan pipeline        — ``plan.tensor._pipeline_cold_impl`` /
  ``_pipeline_warm_impl`` (cold/carry/bucketed/warm: the one-dispatch
  solve→diff→pack programs), plus both under ``shard_map`` with specs
  derived from ``parallel/sharded``'s declarative layout tables
- sparse shortlist solve     — ``plan.tensor._solve_sparse_converged_impl``
  (cold + carry: (assign, sweeps, exhausted)), ``_warm_repair_sparse``
  ((assign, used, ok, exhausted)), the same body under ``shard_map``
  with specs from ``SOLVER_IN_LAYOUT + SPARSE_EXTRA_LAYOUT``, the
  shortlist builder (``core.shortlist.build_shortlist_core``: [P, K]
  int32, saturating K -> [P, N]), and a concrete host-side check of the
  per-row dense exhaustion fallback (fills flagged rows audit-clean)
- carry construction         — ``carry_from_assignment`` / ``_carry_used_jit``
- ``encode_problem`` / ``decode_assignment`` — dense-encoding dtypes and
  the decode round trip (tiny concrete problem; host-only, milliseconds)
- ``bucket_size`` / ``pad_to`` — the bucketing algebra (monotone, >= x,
  bounded overhead)

Failures surface as findings: SHP001 (shape/dtype mismatch), SHP002
(entry point raised under abstract evaluation), SHP003 (host-side
contract violation).  Add a new entry point by appending to CONTRACTS —
the table IS the documentation of the contract.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, NamedTuple

from . import Finding

__all__ = ["run_shape_audit", "CONTRACTS", "Dims"]

_PATH = "blance_tpu/analysis/shape_audit.py"


class Dims(NamedTuple):
    """One point in the audit matrix."""

    P: int
    S: int
    N: int
    R: int
    L: int = 1  # hierarchy levels (gids rows)

    @property
    def constraints(self) -> tuple[int, ...]:
        # Full-depth slots for every state; max(constraints) == R by
        # construction, the solver's own validity precondition.
        return (self.R,) * self.S

    @property
    def rules(self) -> tuple[tuple[tuple[int, int], ...], ...]:
        # One (include, exclude) rule on the last state when there is
        # more than one hierarchy level, else rule-free.
        if self.L < 2 or self.S < 2:
            return ((),) * self.S
        return ((),) * (self.S - 1) + (((1, 0),),)


@dataclass(frozen=True)
class ShapeContract:
    """One declarative entry-point contract.

    ``build(d)`` returns (callable, args, kwargs) with array arguments as
    ``jax.ShapeDtypeStruct``; ``expect(d)`` returns the expected output
    as a pytree of (shape, dtype) pairs.  The runner eval_shapes the
    callable and compares structurally.
    """

    entry: str  # reported entry-point name
    variant: str  # "cold" / "carry" / "bucketed" / ...
    build: Callable[..., object]
    expect: Callable[..., object]


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _solver_args(d: Dims, jnp):
    """The eight positional array args every solver entry shares."""
    import numpy as np

    return (
        _sds((d.P, d.S, d.R), np.int32),  # prev
        _sds((d.P,), np.float32),  # pweights
        _sds((d.N,), np.float32),  # nweights
        _sds((d.N,), np.bool_),  # valid
        _sds((d.P, d.S), np.float32),  # stickiness
        _sds((d.L, d.N), np.int32),  # gids
        _sds((d.L, d.N), np.bool_),  # gid_valid
    )


def _expect_assign(d: Dims):
    import numpy as np

    return ((d.P, d.S, d.R), np.int32)


def _expect_used(d: Dims):
    import numpy as np

    return ((d.S, d.N), np.float32)


# -- builders ---------------------------------------------------------------


def _build_solve_dense(d: Dims, carry: bool = False, bucketed: bool = False):
    import numpy as np

    import jax.numpy as jnp

    from ..plan.tensor import solve_dense

    kwargs = {"constraints": d.constraints, "rules": d.rules,
              "fused_score": "off"}
    if carry:
        kwargs["carry_used"] = _sds((d.S, d.N), np.float32)
    if bucketed:
        # Bucketed solves trace the REAL partition count as a scalar
        # operand so intra-bucket drift cannot retrigger compilation.
        kwargs["p_real"] = _sds((), np.float32)
    return solve_dense, _solver_args(d, jnp), kwargs


def _build_converged(d: Dims, carry: bool = False):
    import numpy as np

    import jax.numpy as jnp

    from ..plan.tensor import _solve_dense_converged_impl

    kwargs = {"constraints": d.constraints, "rules": d.rules,
              "fused_score": "off", "max_iterations": 4}
    if carry:
        kwargs["carry_used"] = _sds((d.S, d.N), np.float32)
    return _solve_dense_converged_impl, _solver_args(d, jnp), kwargs


def _build_warm(d: Dims):
    import numpy as np

    import jax.numpy as jnp

    from ..plan.tensor import _warm_repair

    args = _solver_args(d, jnp) + (
        _sds((d.P,), np.bool_),  # dirty
        _sds((d.S, d.N), np.float32),  # carry_used
    )
    return _warm_repair, args, {"constraints": d.constraints,
                                "rules": d.rules, "fused_score": "off"}


def _build_carry_used(d: Dims):
    import numpy as np

    import jax.numpy as jnp

    from ..plan.tensor import _carry_used_jit

    return _carry_used_jit, (
        _sds((d.P, d.S, d.R), np.int32),
        _sds((d.P,), np.float32),
        _sds((d.N,), np.float32),
    ), {}


def _build_sharded(d: Dims):
    """solve_dense under shard_map, the exact in/out layout
    solve_dense_sharded builds — in_specs derived from the SAME
    declarative layout table the runtime dispatch uses
    (parallel/sharded.SOLVER_IN_LAYOUT), so the audited layout cannot
    drift from the dispatched one."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    from ..parallel.sharded import (
        PARTITION_AXIS,
        SOLVER_IN_LAYOUT,
        layout_specs,
        make_mesh,
    )
    from ..plan.tensor import solve_dense

    n_dev = len(jax.devices())
    shards = n_dev if d.P % n_dev == 0 else 1
    mesh = make_mesh(shards)
    shard = PartitionSpec(PARTITION_AXIS)
    body = partial(solve_dense, constraints=d.constraints, rules=d.rules,
                   axis_name=PARTITION_AXIS, fused_score="off")
    # Same varying-axes checker policy as solve_dense_sharded.
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=layout_specs(SOLVER_IN_LAYOUT),
                       out_specs=shard, check_vma=True)
    return fn, _solver_args(d, jnp), {}


def _diff_len(d: Dims) -> int:
    """The device move-diff's padded op-list length (moves/batch.py)."""
    return 2 * d.S * d.R


def _expect_pipeline_cold(d: Dims):
    import numpy as np

    L = _diff_len(d)
    return (
        _expect_assign(d),  # assign
        ((), "int32"),  # sweeps
        ((d.N,), np.float32),  # prices
        _expect_used(d),  # used
        ((d.P, L), np.int32),  # d_nodes
        ((d.P, L), np.int32),  # d_states
        ((d.P, L), np.int32),  # d_ops
        _expect_assign(d),  # packed
        ((d.P, d.S), np.int32),  # counts
    )


def _expect_pipeline_warm(d: Dims):
    import numpy as np

    L = _diff_len(d)
    return (
        _expect_assign(d),
        ((d.N,), np.float32),  # prices
        _expect_used(d),
        ((), "bool"),  # ok
        ((d.P, L), np.int32),
        ((d.P, L), np.int32),
        ((d.P, L), np.int32),
        _expect_assign(d),
        ((d.P, d.S), np.int32),
    )


def _build_pipeline_cold(d: Dims, carry: bool = False,
                         bucketed: bool = False):
    import numpy as np

    from ..plan.tensor import _pipeline_cold_impl

    kwargs = {"constraints": d.constraints, "rules": d.rules,
              "fused_score": "off", "max_iterations": 4,
              "favor_min_nodes": False}
    if carry:
        kwargs["carry_used"] = _sds((d.S, d.N), np.float32)
    if bucketed:
        kwargs["p_real"] = _sds((), np.float32)
    return _pipeline_cold_impl, _solver_args(d, None), kwargs


def _build_pipeline_warm(d: Dims):
    import numpy as np

    from ..plan.tensor import _pipeline_warm_impl

    args = _solver_args(d, None) + (
        _sds((d.P,), np.bool_),  # dirty
        _sds((d.S, d.N), np.float32),  # carry_used
    )
    return _pipeline_warm_impl, args, {
        "constraints": d.constraints, "rules": d.rules,
        "fused_score": "off", "favor_min_nodes": False}


def _build_pipeline_sharded(d: Dims, warm: bool = False):
    """The fused pipeline under shard_map, in/out specs straight from
    the runtime's declarative layout tables — the exact dispatch
    solve_pipeline_sharded builds."""
    from functools import partial

    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..parallel.sharded import (
        PARTITION_AXIS,
        PIPELINE_COLD_OUT_LAYOUT,
        PIPELINE_WARM_OUT_LAYOUT,
        SOLVER_IN_LAYOUT,
        WARM_EXTRA_LAYOUT,
        layout_specs,
        make_mesh,
    )
    from ..plan.tensor import _pipeline_cold_impl, _pipeline_warm_impl

    n_dev = len(jax.devices())
    shards = n_dev if d.P % n_dev == 0 else 1
    mesh = make_mesh(shards)
    if warm:
        body = partial(_pipeline_warm_impl, constraints=d.constraints,
                       rules=d.rules, axis_name=PARTITION_AXIS,
                       fused_score="off", favor_min_nodes=False)
        in_layout = SOLVER_IN_LAYOUT + WARM_EXTRA_LAYOUT
        out_layout = PIPELINE_WARM_OUT_LAYOUT
        extra = (_sds((d.P,), np.bool_), _sds((d.S, d.N), np.float32))
    else:
        body = partial(_pipeline_cold_impl, constraints=d.constraints,
                       rules=d.rules, axis_name=PARTITION_AXIS,
                       max_iterations=4, fused_score="off",
                       favor_min_nodes=False)
        in_layout = SOLVER_IN_LAYOUT
        out_layout = PIPELINE_COLD_OUT_LAYOUT
        extra = ()
    # Checker off: psum'd replicated outs.
    fn = jax.shard_map(body, mesh=mesh, in_specs=layout_specs(in_layout),
                       out_specs=layout_specs(out_layout), check_vma=False)
    return fn, _solver_args(d, jnp) + extra, {}


def _sparse_k(d: Dims) -> int:
    """A K < N candidate width for the sparse contracts (saturation is
    covered separately by the builder contract)."""
    return max(1, min(d.N - 1, d.R + 2))


def _build_sparse_cold(d: Dims, carry: bool = False):
    import numpy as np

    from ..plan.tensor import _solve_sparse_converged_impl

    args = _solver_args(d, None) + (
        _sds((d.P, _sparse_k(d)), np.int32),)  # shortlist
    kwargs = {"constraints": d.constraints, "rules": d.rules,
              "max_iterations": 4, "sparse_impl": "xla"}
    if carry:
        kwargs["carry_used"] = _sds((d.S, d.N), np.float32)
    return _solve_sparse_converged_impl, args, kwargs


def _expect_sparse_cold(d: Dims):
    import numpy as np

    return (_expect_assign(d), ((), "int32"), ((d.P,), np.bool_))


def _build_sparse_warm(d: Dims):
    import numpy as np

    from ..plan.tensor import _warm_repair_sparse

    args = _solver_args(d, None) + (
        _sds((d.P, _sparse_k(d)), np.int32),  # shortlist
        _sds((d.P,), np.bool_),  # dirty
        _sds((d.S, d.N), np.float32),  # carry_used
    )
    return _warm_repair_sparse, args, {
        "constraints": d.constraints, "rules": d.rules,
        "sparse_impl": "xla"}


def _expect_sparse_warm(d: Dims):
    import numpy as np

    return (_expect_assign(d), _expect_used(d), ((), "bool"),
            ((d.P,), np.bool_))


def _build_sparse_sharded(d: Dims):
    """The sparse converged solve under shard_map, in/out specs from
    the runtime's declarative layout tables (SPARSE_EXTRA_LAYOUT /
    SPARSE_COLD_OUT_LAYOUT) — the exact dispatch solve_sparse_sharded
    builds."""
    from functools import partial

    import numpy as np

    import jax

    from ..parallel.sharded import (
        PARTITION_AXIS,
        SOLVER_IN_LAYOUT,
        SPARSE_COLD_OUT_LAYOUT,
        SPARSE_EXTRA_LAYOUT,
        layout_specs,
        make_mesh,
    )
    from ..plan.tensor import _solve_sparse_converged_impl

    n_dev = len(jax.devices())
    shards = n_dev if d.P % n_dev == 0 else 1
    mesh = make_mesh(shards)
    body = partial(_solve_sparse_converged_impl,
                   constraints=d.constraints, rules=d.rules,
                   axis_name=PARTITION_AXIS, max_iterations=4,
                   sparse_impl="xla")
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=layout_specs(SOLVER_IN_LAYOUT
                                             + SPARSE_EXTRA_LAYOUT),
                       out_specs=layout_specs(SPARSE_COLD_OUT_LAYOUT),
                       check_vma=True)
    return fn, _solver_args(d, None) + (
        _sds((d.P, _sparse_k(d)), np.int32),), {}


def _build_shortlist_builder(d: Dims, saturating: bool = False):
    import numpy as np

    from ..core.shortlist import build_shortlist_core

    k = d.N + 2 if saturating else _sparse_k(d)
    args = (
        _sds((d.P, d.S, d.R), np.int32),  # prev
        _sds((d.P,), np.float32),  # pweights
        _sds((d.N,), np.float32),  # nweights
        _sds((d.N,), np.bool_),  # valid
        _sds((d.L, d.N), np.int32),  # gids
        _sds((d.L, d.N), np.bool_),  # gid_valid
    )
    return build_shortlist_core, args, {
        "constraints": d.constraints, "rules": d.rules, "k": k}


def _expect_shortlist(d: Dims, saturating: bool = False):
    import numpy as np

    k = d.N if saturating else _sparse_k(d)
    return ((d.P, k), np.int32)


def _bucketed_dims(d: Dims) -> Dims:
    from ..core.encode import bucket_size

    return Dims(P=bucket_size(d.P), S=d.S, N=bucket_size(d.N), R=d.R,
                L=d.L)


_FLEET_B = 4  # batch width for the fleet contracts


def _fleet_args(d: Dims, b: int):
    """The stacked [B, ...] operands a fleet batch class solves."""
    import numpy as np

    return (
        _sds((b, d.P, d.S, d.R), np.int32),  # prev
        _sds((b, d.P), np.float32),  # pweights
        _sds((b, d.N), np.float32),  # nweights
        _sds((b, d.N), np.bool_),  # valid
        _sds((b, d.P, d.S), np.float32),  # stickiness
        _sds((b, d.L, d.N), np.int32),  # gids
        _sds((b, d.L, d.N), np.bool_),  # gid_valid
    )


def _build_fleet_cold(d: Dims, b: int = _FLEET_B):
    """plan.fleet._fleet_cold_batch: the vmapped converged fixpoint
    over one bucket class — (assign, sweeps, carry-used) per element."""
    import numpy as np

    from ..plan.fleet import _fleet_cold_batch

    db = _bucketed_dims(d)
    args = _fleet_args(db, b) + (_sds((b,), np.float32),)  # p_real
    return _fleet_cold_batch, args, {
        "constraints": db.constraints, "rules": db.rules,
        "max_iterations": 4, "fused_score": "off"}


def _build_fleet_warm(d: Dims, b: int = _FLEET_B):
    """plan.fleet._fleet_warm_batch: the vmapped one-sweep repair —
    (assign, new_used, accept flag) per element."""
    import numpy as np

    from ..plan.fleet import _fleet_warm_batch

    db = _bucketed_dims(d)
    args = _fleet_args(db, b) + (
        _sds((b, db.P), np.bool_),  # dirty
        _sds((b, db.S, db.N), np.float32),  # carry_used
        _sds((b,), np.float32),  # p_real
    )
    return _fleet_warm_batch, args, {
        "constraints": db.constraints, "rules": db.rules,
        "fused_score": "off"}


def _expect_fleet_cold(d: Dims, b: int = _FLEET_B):
    import numpy as np

    db = _bucketed_dims(d)
    return (((b, db.P, db.S, db.R), np.int32), ((b,), np.int32),
            ((b, db.S, db.N), np.float32))


def _expect_fleet_warm(d: Dims, b: int = _FLEET_B):
    import numpy as np

    db = _bucketed_dims(d)
    return (((b, db.P, db.S, db.R), np.int32),
            ((b, db.S, db.N), np.float32), ((b,), np.bool_))


# -- the table --------------------------------------------------------------

def _build_sched_ranks(d: Dims):
    import numpy as np

    from ..orchestrate.sched.ranks import rank_levels

    # The critical-path scheduler's device rank sweep: [P, L] per-move
    # costs (chains x levels, zero-padded) -> [P, L] upward ranks.  L
    # here is a representative 4-move chain depth (promote/add + del +
    # repair is 3; 4 covers a demote leg).
    return rank_levels, (_sds((d.P, 4), np.float32),), {}


def _expect_sched_ranks(d: Dims):
    import numpy as np

    return ((d.P, 4), np.float32)


# The audit matrix: small/typical/awkward sizes.  P values are multiples
# of 8 so the sharded variant exercises a real multi-shard mesh on the 8
# virtual CPU devices CI forces (a non-divisible P still audits, on a
# 1-shard mesh).
_MATRIX = (
    Dims(P=8, S=1, N=5, R=1),
    Dims(P=16, S=2, N=8, R=2, L=2),
    Dims(P=24, S=3, N=9, R=3, L=2),
)

CONTRACTS: tuple[ShapeContract, ...] = tuple(
    [
        ShapeContract(
            entry="solve_dense", variant=f"cold@{d.P}x{d.N}",
            build=(lambda d=d: _build_solve_dense(d)),
            expect=(lambda d=d: _expect_assign(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="solve_dense", variant=f"carry@{d.P}x{d.N}",
            build=(lambda d=d: _build_solve_dense(d, carry=True)),
            expect=(lambda d=d: _expect_assign(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="solve_dense", variant=f"bucketed@{d.P}x{d.N}",
            build=(lambda d=d: _build_solve_dense(
                _bucketed_dims(d), bucketed=True)),
            expect=(lambda d=d: _expect_assign(_bucketed_dims(d))))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="solve_dense", variant=f"bucketed+carry@{d.P}x{d.N}",
            build=(lambda d=d: _build_solve_dense(
                _bucketed_dims(d), carry=True, bucketed=True)),
            expect=(lambda d=d: _expect_assign(_bucketed_dims(d))))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="solve_dense_converged", variant=f"cold@{d.P}x{d.N}",
            build=(lambda d=d: _build_converged(d)),
            # (assign, executed-sweep count)
            expect=(lambda d=d: (_expect_assign(d), ((), "int32"))))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="solve_dense_converged", variant=f"carry@{d.P}x{d.N}",
            build=(lambda d=d: _build_converged(d, carry=True)),
            expect=(lambda d=d: (_expect_assign(d), ((), "int32"))))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="solve_dense_warm", variant=f"repair@{d.P}x{d.N}",
            build=(lambda d=d: _build_warm(d)),
            # (assign, new_used, accept flag)
            expect=(lambda d=d: (_expect_assign(d), _expect_used(d),
                                 ((), "bool"))))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="carry_from_assignment", variant=f"used@{d.P}x{d.N}",
            build=(lambda d=d: _build_carry_used(d)),
            expect=(lambda d=d: _expect_used(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="solve_dense_sharded", variant=f"1d@{d.P}x{d.N}",
            build=(lambda d=d: _build_sharded(d)),
            expect=(lambda d=d: _expect_assign(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="fleet_cold_batch",
            variant=f"B{_FLEET_B}@{d.P}x{d.N}",
            build=(lambda d=d: _build_fleet_cold(d)),
            expect=(lambda d=d: _expect_fleet_cold(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="fleet_warm_batch",
            variant=f"B{_FLEET_B}@{d.P}x{d.N}",
            build=(lambda d=d: _build_fleet_warm(d)),
            expect=(lambda d=d: _expect_fleet_warm(d)))
        for d in _MATRIX
    ] + [
        # -- sparse shortlist solve (ISSUE 11) -------------------------
        ShapeContract(
            entry="solve_sparse", variant=f"cold@{d.P}x{d.N}",
            build=(lambda d=d: _build_sparse_cold(d)),
            expect=(lambda d=d: _expect_sparse_cold(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="solve_sparse", variant=f"carry@{d.P}x{d.N}",
            build=(lambda d=d: _build_sparse_cold(d, carry=True)),
            expect=(lambda d=d: _expect_sparse_cold(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="solve_sparse_warm", variant=f"repair@{d.P}x{d.N}",
            build=(lambda d=d: _build_sparse_warm(d)),
            expect=(lambda d=d: _expect_sparse_warm(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="solve_sparse_sharded", variant=f"1d@{d.P}x{d.N}",
            build=(lambda d=d: _build_sparse_sharded(d)),
            expect=(lambda d=d: _expect_sparse_cold(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="build_shortlist", variant=f"topk@{d.P}x{d.N}",
            build=(lambda d=d: _build_shortlist_builder(d)),
            expect=(lambda d=d: _expect_shortlist(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="build_shortlist", variant=f"saturating@{d.P}x{d.N}",
            build=(lambda d=d: _build_shortlist_builder(
                d, saturating=True)),
            expect=(lambda d=d: _expect_shortlist(d, saturating=True)))
        for d in _MATRIX
    ] + [
        # -- fused single-dispatch plan pipeline (solve→diff→pack) -----
        ShapeContract(
            entry="plan_pipeline", variant=f"cold@{d.P}x{d.N}",
            build=(lambda d=d: _build_pipeline_cold(d)),
            expect=(lambda d=d: _expect_pipeline_cold(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="plan_pipeline", variant=f"carry@{d.P}x{d.N}",
            build=(lambda d=d: _build_pipeline_cold(d, carry=True)),
            expect=(lambda d=d: _expect_pipeline_cold(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="plan_pipeline", variant=f"bucketed@{d.P}x{d.N}",
            build=(lambda d=d: _build_pipeline_cold(
                _bucketed_dims(d), bucketed=True)),
            expect=(lambda d=d: _expect_pipeline_cold(_bucketed_dims(d))))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="plan_pipeline", variant=f"warm@{d.P}x{d.N}",
            build=(lambda d=d: _build_pipeline_warm(d)),
            expect=(lambda d=d: _expect_pipeline_warm(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="plan_pipeline_sharded", variant=f"cold@{d.P}x{d.N}",
            build=(lambda d=d: _build_pipeline_sharded(d)),
            expect=(lambda d=d: _expect_pipeline_cold(d)))
        for d in _MATRIX
    ] + [
        ShapeContract(
            entry="plan_pipeline_sharded", variant=f"warm@{d.P}x{d.N}",
            build=(lambda d=d: _build_pipeline_sharded(d, warm=True)),
            expect=(lambda d=d: _expect_pipeline_warm(d)))
        for d in _MATRIX
    ] + [
        # -- critical-path scheduler device rank kernel (ISSUE 12) -----
        ShapeContract(
            entry="sched_rank_levels", variant=f"chains@{d.P}",
            build=(lambda d=d: _build_sched_ranks(d)),
            expect=(lambda d=d: _expect_sched_ranks(d)))
        for d in _MATRIX
    ]
)


# -- runner -----------------------------------------------------------------


def _flatten_expect(exp):
    """(shape, dtype) | tuple thereof -> flat list, mirroring how
    eval_shape output tuples flatten."""
    if isinstance(exp, tuple) and len(exp) == 2 and \
            isinstance(exp[0], tuple) and \
            all(isinstance(x, int) for x in exp[0]):
        return [exp]
    out = []
    for e in exp:
        out.extend(_flatten_expect(e))
    return out


def _check_one(contract: ShapeContract) -> list[Finding]:
    import numpy as np

    import jax

    findings: list[Finding] = []
    label = f"{contract.entry}[{contract.variant}]"
    try:
        fn, args, kwargs = contract.build()
        # Static (non-array) kwargs ride a partial closure: eval_shape
        # abstracts every operand it is handed, and a tuple/str static
        # must stay a concrete Python value at trace time.
        from functools import partial

        statics = {k: v for k, v in kwargs.items()
                   if not isinstance(v, jax.ShapeDtypeStruct)}
        arrays = {k: v for k, v in kwargs.items()
                  if isinstance(v, jax.ShapeDtypeStruct)}
        out = jax.eval_shape(partial(fn, **statics), *args, **arrays)
    except Exception as e:
        first = (str(e).splitlines() or [""])[0][:200]
        findings.append(Finding(
            rule="SHP002", path=_PATH, line=0, symbol=label,
            message=f"entry point raised under jax.eval_shape "
                    f"({type(e).__name__}: {first})"))
        return findings

    got = jax.tree_util.tree_leaves(out)
    want = _flatten_expect(contract.expect())
    if len(got) != len(want):
        findings.append(Finding(
            rule="SHP001", path=_PATH, line=0, symbol=label,
            message=f"output arity drift: expected {len(want)} arrays, "
                    f"got {len(got)}"))
        return findings
    for i, (g, (shape, dtype)) in enumerate(zip(got, want)):
        if tuple(g.shape) != tuple(shape) or \
                np.dtype(g.dtype) != np.dtype(dtype):
            findings.append(Finding(
                rule="SHP001", path=_PATH, line=0, symbol=label,
                message=f"output #{i} drifted: expected "
                        f"{tuple(shape)} {np.dtype(dtype).name}, got "
                        f"{tuple(g.shape)} {np.dtype(g.dtype).name}"))
    return findings


def _check_encode_decode() -> list[Finding]:
    """Concrete (tiny) encode/decode round trip: dense dtypes + map
    shape.  Host-only, milliseconds."""
    import numpy as np

    from ..core.encode import decode_assignment, encode_problem
    from ..core.types import Partition, PartitionModelState, PlanOptions

    findings: list[Finding] = []
    label = "encode_problem/decode_assignment"
    try:
        model = {
            "primary": PartitionModelState(priority=0, constraints=1),
            "replica": PartitionModelState(priority=1, constraints=1),
        }
        nodes = ["a", "b", "c"]
        pmap = {
            "00": Partition("00", {"primary": ["a"], "replica": ["b"]}),
            "01": Partition("01", {"primary": ["b"], "replica": ["c"]}),
        }
        problem = encode_problem(pmap, pmap, nodes, None, model,
                                 PlanOptions())
        expect = {
            "prev": ((2, 2, 1), np.int32),
            "constraints": ((2,), np.int32),
            "partition_weights": ((2,), np.float32),
            "node_weights": ((3,), np.float32),
            "valid_node": ((3,), np.bool_),
            "stickiness": ((2, 2), np.float32),
            "gids": ((1, 3), np.int32),
            "gid_valid": ((1, 3), np.bool_),
        }
        for field_name, (shape, dtype) in expect.items():
            arr = getattr(problem, field_name)
            if tuple(arr.shape) != shape or \
                    np.dtype(arr.dtype) != np.dtype(dtype):
                findings.append(Finding(
                    rule="SHP001", path=_PATH, line=0, symbol=label,
                    message=f"DenseProblem.{field_name} drifted: "
                            f"expected {shape} {np.dtype(dtype).name}, "
                            f"got {tuple(arr.shape)} {arr.dtype}"))
        decoded, warns = decode_assignment(problem, problem.prev, pmap)
        if set(decoded) != set(pmap) or warns:
            findings.append(Finding(
                rule="SHP001", path=_PATH, line=0, symbol=label,
                message=f"decode(encode(m).prev) did not round-trip the "
                        f"partition set cleanly (warnings: {warns})"))
        elif decoded["00"].nodes_by_state != pmap["00"].nodes_by_state:
            findings.append(Finding(
                rule="SHP001", path=_PATH, line=0, symbol=label,
                message="decode(encode(m).prev) changed placements"))
    except Exception as e:
        first = (str(e).splitlines() or [""])[0][:200]
        findings.append(Finding(
            rule="SHP002", path=_PATH, line=0, symbol=label,
            message=f"encode/decode audit raised "
                    f"({type(e).__name__}: {first})"))
    return findings


def _check_bucketing_algebra() -> list[Finding]:
    """bucket_size/pad_to host contracts: result >= x, monotone,
    overhead bounded by 1/granularity, idempotent."""
    import numpy as np

    from ..core.encode import _BUCKET_GRANULARITY, bucket_size, pad_to

    findings: list[Finding] = []
    label = "bucket_size/pad_to"
    prev = 0
    for x in list(range(1, 200)) + [255, 256, 257, 1000, 1007, 4096,
                                    99_999, 100_001]:
        b = bucket_size(x)
        if b < x:
            findings.append(Finding(
                rule="SHP003", path=_PATH, line=0, symbol=label,
                message=f"bucket_size({x}) = {b} < x: padding would "
                        f"TRUNCATE the axis"))
            break
        if bucket_size(b) != b:
            findings.append(Finding(
                rule="SHP003", path=_PATH, line=0, symbol=label,
                message=f"bucket_size not idempotent at {x}: "
                        f"bucket_size({b}) = {bucket_size(b)}"))
            break
        if x > _BUCKET_GRANULARITY and \
                (b - x) * _BUCKET_GRANULARITY > b:
            findings.append(Finding(
                rule="SHP003", path=_PATH, line=0, symbol=label,
                message=f"bucket_size({x}) = {b}: padding overhead "
                        f"exceeds the 1/{_BUCKET_GRANULARITY} bound"))
            break
        if b < prev:
            findings.append(Finding(
                rule="SHP003", path=_PATH, line=0, symbol=label,
                message=f"bucket_size not monotone at {x}"))
            break
        prev = b
    arr = np.arange(6, dtype=np.int32).reshape(2, 3)
    padded = pad_to(arr, 1, 5, -1)
    if padded.shape != (2, 5) or not (padded[:, 3:] == -1).all() or \
            not (padded[:, :3] == arr).all():
        findings.append(Finding(
            rule="SHP003", path=_PATH, line=0, symbol=label,
            message="pad_to contract violated (shape/fill/prefix)"))
    if pad_to(arr, 1, 2, -1) is not arr:
        findings.append(Finding(
            rule="SHP003", path=_PATH, line=0, symbol=label,
            message="pad_to must be a no-op when already long enough"))
    return findings


def _check_sparse_fallback() -> list[Finding]:
    """Concrete host contract of the per-row dense exhaustion fallback:
    a row flagged exhausted (its shortlist was all removed nodes) must
    come back with every feasible slot filled, duplicate-free, off
    removed nodes, and untouched rows bit-unchanged.  Tiny problem,
    host + one small solve, milliseconds."""
    import numpy as np

    findings: list[Finding] = []
    label = "sparse_fallback"
    try:
        from ..plan.tensor import _sparse_fallback_rows

        P, S, R, N = 6, 2, 1, 8
        rng = np.random.default_rng(5)
        prev = np.full((P, S, R), -1, np.int32)
        prev[:, 0, 0] = rng.integers(0, N, P)
        prev[:, 1, 0] = (prev[:, 0, 0] + 1) % N
        assign = prev.copy()
        assign[0] = -1  # the exhausted row the sparse solve left empty
        valid = np.ones(N, bool)
        valid[prev[0, 0, 0]] = False
        gids = np.stack([np.arange(N, dtype=np.int32),
                         np.arange(N, dtype=np.int32) // 2,
                         np.zeros(N, np.int32)])
        out = _sparse_fallback_rows(
            assign, np.array([0]), prev, np.ones(P, np.float32),
            np.ones(N, np.float32), valid,
            np.full((P, S), 1.5, np.float32), gids,
            np.ones((3, N), bool), (1, 1), ((), ((2, 1),)))
        row = out[0]
        if (row < 0).any():
            findings.append(Finding(
                rule="SHP003", path=_PATH, line=0, symbol=label,
                message=f"fallback left feasible slots empty: {row}"))
        held = row[row >= 0]
        if held.size and (~valid[held]).any():
            findings.append(Finding(
                rule="SHP003", path=_PATH, line=0, symbol=label,
                message="fallback placed a copy on a removed node"))
        if held.size != np.unique(held).size:
            findings.append(Finding(
                rule="SHP003", path=_PATH, line=0, symbol=label,
                message=f"fallback duplicated a node in one row: {row}"))
        if not np.array_equal(out[1:], assign[1:]):
            findings.append(Finding(
                rule="SHP003", path=_PATH, line=0, symbol=label,
                message="fallback mutated rows it was not asked to"))
    except Exception as e:
        first = (str(e).splitlines() or [""])[0][:200]
        findings.append(Finding(
            rule="SHP002", path=_PATH, line=0, symbol=label,
            message=f"sparse fallback audit raised "
                    f"({type(e).__name__}: {first})"))
    return findings


def _check_encode_residency() -> list[Finding]:
    """Concrete host contract of the encode-residency delta kernels
    (plan/resident.py, ISSUE 14): strip_prev_rows must equal
    strip-the-map-then-re-encode bit-exactly (new array, untouched rows
    byte-identical), and pack_slot_rows must be the decode pack —
    non-negative prefix in original slot order with exact counts.
    Tiny problem, host-only, milliseconds."""
    import numpy as np

    findings: list[Finding] = []
    label = "encode_residency"
    try:
        from ..core.encode import (
            encode_problem,
            pack_slot_rows,
            strip_prev_rows,
        )
        from ..core.types import Partition, PartitionModelState, PlanOptions

        model = {
            "primary": PartitionModelState(priority=0, constraints=1),
            "replica": PartitionModelState(priority=1, constraints=2),
        }
        nodes = [f"n{i}" for i in range(5)]
        pmap = {
            f"{i:02d}": Partition(f"{i:02d}", {
                "primary": [nodes[i % 5]],
                "replica": [nodes[(i + 1) % 5], nodes[(i + 2) % 5]]})
            for i in range(7)
        }
        problem = encode_problem(pmap, pmap, nodes, [], model,
                                 PlanOptions())
        dark = {"n1"}
        ids = np.array([1], np.int32)
        patched, dirty = strip_prev_rows(problem.prev, ids)
        stripped = {
            name: Partition(name, {
                s: [n for n in ns if n not in dark]
                for s, ns in p.nodes_by_state.items()})
            for name, p in pmap.items()}
        want = encode_problem(stripped, stripped, nodes, sorted(dark),
                              model, PlanOptions())
        if patched.shape != want.prev.shape or \
                not np.array_equal(patched, want.prev):
            findings.append(Finding(
                rule="SHP003", path=_PATH, line=0, symbol=label,
                message="strip_prev_rows != strip-map-then-re-encode"))
        if patched is problem.prev:
            findings.append(Finding(
                rule="SHP003", path=_PATH, line=0, symbol=label,
                message="strip_prev_rows returned the input array — "
                        "identity memos would serve stale hits"))
        if dirty.shape != (problem.P,) or not dirty.any():
            findings.append(Finding(
                rule="SHP003", path=_PATH, line=0, symbol=label,
                message="strip_prev_rows dirty mask drifted"))
        rows = np.array([[[2, -1, 0], [-1, -1, 4]]], np.int32)
        packed, counts = pack_slot_rows(rows)
        if packed.tolist() != [[[2, 0, -1], [4, -1, -1]]] or \
                counts.tolist() != [[2, 1]]:
            findings.append(Finding(
                rule="SHP003", path=_PATH, line=0, symbol=label,
                message=f"pack_slot_rows drifted: {packed.tolist()} "
                        f"{counts.tolist()}"))
    except Exception as e:
        first = (str(e).splitlines() or [""])[0][:200]
        findings.append(Finding(
            rule="SHP002", path=_PATH, line=0, symbol=label,
            message=f"encode-residency audit raised "
                    f"({type(e).__name__}: {first})"))
    return findings


def run_shape_audit() -> tuple[list[Finding], int]:
    """Run the whole table.  Returns (findings, entries_checked)."""
    findings: list[Finding] = []
    for contract in CONTRACTS:
        findings.extend(_check_one(contract))
    findings.extend(_check_encode_decode())
    findings.extend(_check_bucketing_algebra())
    findings.extend(_check_sparse_fallback())
    findings.extend(_check_encode_residency())
    return findings, len(CONTRACTS) + 4
