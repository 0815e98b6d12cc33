"""Batched cost-tensor planner — the TPU backend.

Where the reference runs a sequential greedy loop over partitions
(reference plan.go:253-303, O(S*P*(S*N + N log N)) on one core), this
backend scores ALL partitions against ALL nodes at once and assigns each
state/replica slot in fully-vectorized auction rounds:

  score[P, N] = (holders_of_state[N] + 0.001 * fill[N] / P) / node_weight
              + negative-weight boost       (plan.go:675-684 semantics)
              - stickiness * held_previously (plan.go:654-662)
              + tiered hierarchy-rule penalty (api.go:76-105 semantics)
              + INF * forbidden              (same-partition exclusivity,
                                              removed nodes)

Assignment per slot runs capacity-constrained proposal rounds: every
unassigned partition bids on its best open node; each node accepts bidders
in most-urgent-first order (urgency = regret margin between best and
second-best) up to its remaining weighted capacity; accepted bids update
the counts that score the next round.  A deterministic per-(partition, node)
tie-break jitter — far below any real score term — spreads equal-score bids
across equally-good nodes, so a wave of identical partitions fills every
node in one round instead of herding onto the argmin.  A final force step
ignores capacity so constraint satisfaction never degrades below the greedy
planner's (shortfalls become warnings, exactly like plan.go:231-235).

Everything is jit-compiled with static (S, R, rules) structure: the slot
loops unroll at trace time, the auction is a lax.while_loop, and the only
cross-partition dependencies are per-node sums — which is what makes the
partition axis shardable across a TPU mesh (see blance_tpu.parallel).

Set axis_name to run under shard_map with the partition axis sharded:
per-node bid totals are then psum'd over the mesh so capacity and counts
stay globally consistent while scores stay local.
"""

from __future__ import annotations

import itertools
from functools import partial
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core.encode import (
    DenseProblem,
    NPArray,
    decode_assignment,
    encode_problem,
)
from ..core.types import PartitionMap, PartitionModel, PlanOptions
from ..obs import device as _device
from ..obs import get_recorder, phase_span
from ..ops.reduce2 import (
    min2_argmin_reference,
    pallas_available,
    priced_min2_argmin,
)
from ..ops.score_fused import (
    fused_score_min2,
    jitter_hash,
    pack_score_inputs,
    score_at_columns,
)
from ..ops.sparse2 import sparse_min2_reference, sparse_priced_min2

__all__ = ["plan_next_map_tpu", "plan_pipeline", "solve_dense",
           "solve_dense_converged", "solve_converged_resilient",
           "solve_dense_warm", "SolveCarry", "carry_from_assignment",
           "check_assignment", "maybe_validate",
           "solve_sparse", "solve_sparse_warm", "DenseScoreMemoryError",
           "projected_score_bytes", "set_dense_score_budget",
           "check_dense_memory", "sparse_rules_supported",
           "resolve_sparse_impl"]

# Static solver-entry shapes (see plan/session.py where both are built
# from the EncodedProblem): per-state slot counts, and per-state tuples
# of (include_level, exclude_level) hierarchy-rule pairs.  The _hier_*
# helpers below take ONE state's pair tuple (StateRules); every solve
# entry takes the full per-state Rules.
Constraints = tuple[int, ...]
StateRules = tuple[tuple[int, int], ...]
Rules = tuple[StateRules, ...]

_INF = 1.0e9  # hard-forbidden
_RULE_MISS = 1.0e6  # satisfies no hierarchy rule (uniform => flat fallback)
_RULE_TIER = 1.0e4  # penalty step per rule index (earlier rules win)
# SCALE ASSUMPTION (round-5 advisor finding): tier equality is decided by
# BAND tests — "same tier" means the raw score sits within _RULE_TIER/2 of
# the row's unpriced minimum (see rule_ok/soft_ok in _assign_slot and the
# pin pass's floor test).  That is only sound while every within-tier
# score term stays well below the band: the seeded per-node fill term
# (≈ sum(constraints) * total_weight / total_node_weight for balanced
# prevs, or max(seed_fill/node_weight) for skewed ones), stickiness, and
# the negative-weight boost.  At extreme P/N ratios (≳2k unit-weight
# partitions per node per slot) the fill term alone crosses the band and
# nodes stop being tier-comparable — placements would silently
# misclassify tiers.  _check_tier_band_scale below asserts the headroom
# at every host-side solve entry so such problems fail loudly instead.
_TIER_BAND_HEADROOM = 0.45  # max allowed within-tier mass, in tiers
# Passed-check memo for _check_tier_band_scale: (array id + shape +
# statics) -> weight fingerprint.  See the function for the safety
# argument; bounded at 256 entries.
_tier_scale_memo: dict[tuple[object, ...], object] = {}
_MAX_AUCTION_ROUNDS = 16
# Bid-spreading jitter: above the advisory fill factor (0.001/P) by design,
# below every decision-bearing term (stickiness >= 1.5 typical, rule tiers
# 1e4, price >= 1/node-weight per accepted unit).
_JITTER = 1.0e-5

# Score-engine default for plan_next_map_tpu: "off" materializes the
# [P, N_l] score matrix per slot; "on" computes the score inside the
# Pallas reduction kernel (ops/score_fused.py) so the matrix never
# exists; "interpret" runs the fused kernel under the pallas interpreter
# (CPU testing).  Passed into the jit as a static arg, so flipping the
# default takes effect on the next call.  "auto" resolves per problem
# size at the plan_next_map_tpu boundary (resolve_fused_score): the
# matrix engine wins below the chip's memory ceiling (fewer kernel
# launches), the fused engine is the only thing that fits above it.
_FUSED_SCORE_DEFAULT = "auto"

# Working-set model for the matrix engine: ~5 live [P, N] f32 copies
# through an auction round (score build, priced copy, reduction temps).
# Calibrated on v5e: 100k x 10k measured an 18.9 GB program requirement
# = ~19 bytes/cell.
_MATRIX_BYTES_PER_CELL = 20
_HBM_BUDGET_FRACTION = 0.6


def set_fused_score_default(mode: str) -> None:
    """Select the score engine for subsequent plan_next_map_tpu calls."""
    global _FUSED_SCORE_DEFAULT
    if mode not in ("off", "on", "interpret", "auto"):
        raise ValueError(f"unknown fused-score mode: {mode!r}")
    _FUSED_SCORE_DEFAULT = mode


def _device_hbm_bytes() -> int:
    """Accelerator memory per chip, as the runtime reports it.  The CPU
    platform reports no limit, and stands in for a 16 GiB (v5e) chip so
    CPU test meshes make the engine choices the chip would; any other
    device that reports no limit is an error, not a guess."""
    dev = jax.devices()[0]
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    if limit > 0:
        return limit
    if dev.platform == "cpu":
        return 16 * 2 ** 30
    raise RuntimeError(
        f"{dev.platform} device {dev.device_kind!r} reports no memory "
        f"limit (memory_stats()['bytes_limit']); the engine choice needs it")


def resolve_fused_score(mode: str, p: int, n: int) -> str:
    """Resolve "auto" to a concrete engine for a [P, N]-sized problem.

    "auto" -> "on" (in-kernel score, O(P + N) traffic per round) when
    the matrix engine's [P, N] working set would exceed the chip's
    memory budget and the compiled Pallas path is available; "off"
    (materialized score matrix) otherwise.  Explicit modes pass
    through untouched.  Must run BEFORE jit: fused_score is a static
    argument of solve_dense / solve_dense_converged, and "auto" there
    is an error by design.
    """
    if mode != "auto":
        return mode
    from ..ops.reduce2 import pallas_available

    if not pallas_available():
        return "off"
    if p * n * _MATRIX_BYTES_PER_CELL > \
            _HBM_BUDGET_FRACTION * _device_hbm_bytes():
        return "on"
    return "off"


def resolve_default_fused_score(p: int, n: int) -> str:
    """The session-default engine mode, resolved for a [P, N] problem.
    The one spelling every entry point (plan_next_map_tpu,
    PlannerSession.replan, future callers) uses to turn the module
    default into a concrete jit-safe mode."""
    return resolve_fused_score(_FUSED_SCORE_DEFAULT, p, n)


# --- dense-memory guard ------------------------------------------------------
#
# The matrix engine's score sweep materializes ~_MATRIX_BYTES_PER_CELL
# bytes per [P, N] cell.  Past the accelerator budget XLA dies with an
# opaque allocator error deep in compile (or the CPU backend swaps the
# host to death); this guard turns that into a structured, actionable
# error at solve ENTRY, naming the projected footprint and the ways out
# (the sparse shortlist engine, the in-kernel fused engine, sharding).
# None = derive from the device (the same 60%-of-HBM ceiling the engine
# auto-selection uses); configurable for deployments with different
# headroom — and for tests.

_DENSE_GUARD_BUDGET: Optional[int] = None


def set_dense_score_budget(n_bytes: Optional[int]) -> None:
    """Override the dense-memory guard's byte budget (None = derive
    from the device again)."""
    global _DENSE_GUARD_BUDGET
    if n_bytes is not None and int(n_bytes) <= 0:
        raise ValueError(f"budget must be positive, got {n_bytes}")
    _DENSE_GUARD_BUDGET = None if n_bytes is None else int(n_bytes)


def dense_score_budget_bytes() -> int:
    """The byte budget the dense-memory guard enforces."""
    if _DENSE_GUARD_BUDGET is not None:
        return _DENSE_GUARD_BUDGET
    return int(_HBM_BUDGET_FRACTION * _device_hbm_bytes())


def projected_score_bytes(p: int, n: int) -> int:
    """Projected matrix-engine working set for a [P, N] problem (the
    score sweep's live [P, N] f32 copies, calibrated on v5e — see
    _MATRIX_BYTES_PER_CELL)."""
    return int(p) * int(n) * _MATRIX_BYTES_PER_CELL


class DenseScoreMemoryError(ValueError):
    """The dense matrix engine's projected [P, N] score footprint
    exceeds the memory budget.  Structured so callers can act on it:
    ``projected_bytes`` / ``budget_bytes`` / ``shape`` (P, S, N)."""

    def __init__(self, projected_bytes: int, budget_bytes: int,
                 shape: tuple[int, ...]):
        self.projected_bytes = int(projected_bytes)
        self.budget_bytes = int(budget_bytes)
        self.shape = tuple(shape)
        p, s, n = shape
        super().__init__(
            f"dense score sweep would materialize ~"
            f"{projected_bytes / 2**30:.1f} GiB of [P, N] intermediates "
            f"(P={p}, S={s}, N={n}, ~{_MATRIX_BYTES_PER_CELL} B/cell) — "
            f"over the {budget_bytes / 2**30:.1f} GiB budget; refusing "
            f"before XLA OOMs opaquely.  Ways out: the sparse shortlist "
            f"engine (PlanOptions(sparse=True) or plan.tensor."
            f"solve_sparse, K candidates/partition instead of N), a "
            f"smaller K if already sparse, the in-kernel fused engine "
            f"on TPU (set_fused_score_default('on')), sharding the "
            f"partition axis (parallel.sharded), or raising the budget "
            f"(plan.tensor.set_dense_score_budget)")


def check_dense_memory(p: int, s: int, n: int, engine: str) -> None:
    """Raise :class:`DenseScoreMemoryError` when the MATRIX engine
    (``engine == "off"``) is about to materialize a [P, N] score sweep
    past the budget.  The fused/sparse engines never materialize it and
    pass untouched."""
    if engine != "off":
        return
    projected = projected_score_bytes(p, n)
    budget = dense_score_budget_bytes()
    if projected > budget:
        raise DenseScoreMemoryError(projected, budget, (p, s, n))


class SolveCarry(NamedTuple):
    """Auction state carried across delta replans (the warm start).

    A converged solve is a fixpoint: replaying it against the same
    problem re-derives the same per-node fill (the quantity that prices
    the score's balance term) from scratch.  The carry keeps that state
    alive between replans so a delta replan seeds the solver instead of
    re-deriving it, and — more importantly — so the fixpoint loop's
    confirming sweep can be skipped when the repair provably stayed
    inside the delta (see :func:`solve_dense_warm`).

    ``used`` is the ground truth; ``prices`` is its per-node sum (the
    total fill vector the balance term divides), kept explicit so
    callers can run O(N) host prechecks (capacity-shrink detection)
    without touching the [S, N] table.

    Fields
    ------
    prices: [N] f32 — total per-node weighted fill at convergence.
    assign: [P, S, R] i32 — the converged assignment the carry matches.
        A carry is only valid against a ``prev`` equal to this array;
        sessions enforce that by identity (plan/session.py).
    used:   [S, N] f32 — per-state per-node accepted weight, built with
        the SAME scatter the solver's seed pass uses, so seeding from it
        is bitwise identical to recomputing from ``assign``.
    """

    prices: jnp.ndarray
    assign: jnp.ndarray
    used: jnp.ndarray


def _used_by_state(assign: jnp.ndarray, pweights: jnp.ndarray, n: int,
                   s: int, axis_name: Optional[str] = None) -> jnp.ndarray:
    """[S, N] per-state weighted fill — the carry's ``used`` table.

    One :func:`_scatter_counts` per state followed by a psum, exactly
    the ops (and op order) of solve_dense's seed pass, so a warm solve
    seeded from this table computes bit-identical totals."""
    return jnp.stack([
        _psum(_scatter_counts(assign[:, si, :], pweights, n), axis_name)
        for si in range(s)])


@jax.jit
def _carry_used_jit(assign: jnp.ndarray, pweights: jnp.ndarray,
                    nweights: jnp.ndarray) -> jnp.ndarray:
    """Single-device spelling of :func:`_used_by_state` (for building a
    carry from a host-side assignment, e.g. after a cold solve)."""
    return _used_by_state(
        assign, pweights, nweights.shape[0], assign.shape[1])


def carry_from_assignment(assign: jnp.ndarray, pweights: jnp.ndarray,
                          nweights: jnp.ndarray) -> SolveCarry:
    """Package a converged assignment as a :class:`SolveCarry`.

    Use after any cold solve whose output will seed future delta
    replans.  ``used`` comes from the same device scatter the solver's
    seed pass runs, so the next warm solve's totals match a cold
    recompute bit-for-bit."""
    assign = jnp.asarray(assign)
    used = _carry_used_jit(assign, jnp.asarray(pweights),
                           jnp.asarray(nweights))
    return SolveCarry(prices=jnp.sum(used, axis=0), assign=assign,
                      used=used)


def _drop_empty(ids: jnp.ndarray, n: int) -> jnp.ndarray:
    """Map empty (-1) ids to n so scatters with mode='drop' discard them.

    NB: JAX .at[] wraps negative indices like NumPy — a raw -1 would
    silently scatter onto the LAST node."""
    return jnp.where(ids >= 0, ids, n)


def _scatter_counts(ids: jnp.ndarray, weights: jnp.ndarray, n: int) -> jnp.ndarray:
    """Weighted histogram of node ids [P, R] -> [N]; -1 entries dropped."""
    flat = _drop_empty(ids.reshape(-1), n)
    w = jnp.broadcast_to(weights[:, None], ids.shape).reshape(-1)
    return jnp.zeros(n, jnp.float32).at[flat].add(w, mode="drop")




def _anchor_rule_sat(
    anchor: jnp.ndarray,  # [P] global node ids, -1 = absent
    cand_inc: jnp.ndarray,  # candidates' include-level gids, [P] or [1, N_l]
    cand_exc: jnp.ndarray,  # candidates' exclude-level gids, same shape
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    inc: int,
    exc: int,
) -> jnp.ndarray:
    """Rule gate for ONE anchor column: candidate satisfies (inc, exc)
    iff it shares the anchor's include-level ancestor and NOT its
    exclude-level ancestor; absent anchors satisfy everything; validity
    gates on the anchor side only.  THE single spelling of the gate —
    both the [P, N] penalty matrix and the [P] point evaluation go
    through here, so the semantics cannot drift apart."""
    aa = jnp.maximum(anchor, 0)
    sh = (anchor.shape[0],) + (1,) * (cand_inc.ndim - 1)
    inc_same = (gids[inc][aa].reshape(sh) == cand_inc) & \
        gid_valid[inc][aa].reshape(sh)
    exc_same = (gids[exc][aa].reshape(sh) == cand_exc) & \
        gid_valid[exc][aa].reshape(sh)
    return jnp.where((anchor >= 0).reshape(sh), inc_same & ~exc_same, True)


def _hier_penalty(
    anchors: jnp.ndarray,  # [P, A] GLOBAL node ids, -1 = absent anchor
    gids: jnp.ndarray,  # [L, N] full (anchor lookups are global)
    gid_valid: jnp.ndarray,  # [L, N] full
    rules: StateRules,  # ((include_level, exclude_level), ...)
    gids_cand: Optional[jnp.ndarray] = None,  # [L, N_l] candidate columns
) -> jnp.ndarray:
    """Tiered rule penalty [P, N] anchored on EVERY prior pick at once.

    The reference anchors each hierarchy pick on the primary *plus all
    nodes picked so far for the partition* (the intersection at
    plan.go:185-191,738-753), which is what makes two replicas under a
    rule like (include 2, exclude 1) land on two *different* racks — not
    merely racks different from the primary's.  A rule is satisfied by
    node n iff, for every present anchor a: n shares a's include-level
    ancestor and NOT a's exclude-level ancestor.  First-satisfied rule
    index sets the tier; satisfying none costs _RULE_MISS.  Unsatisfiable
    rules penalize every node equally, which leaves the argmin order
    flat — the reference's fall-back-to-flat-candidates behavior
    (plan.go:214-220).  A ~ 1 + constraints, so the anchor loop unrolls
    into a handful of [P, N] comparisons that XLA fuses into the score
    expression — no [P, N, A] tensor materializes.

    Under node-axis sharding, ``gids_cand`` holds only this shard's
    candidate columns (the output is [P, N_local]) while anchor lookups
    still index the full replicated tables; validity gates on the anchor
    side only, exactly like the replicated path."""
    if gids_cand is None:
        gids_cand = gids
    p, a_width = anchors.shape
    n_l = gids_cand.shape[1]
    any_anchor = jnp.any(anchors >= 0, axis=1)
    pen = jnp.full((p, n_l), _RULE_MISS, jnp.float32)
    for idx, (inc, exc) in enumerate(rules):
        sat = jnp.ones((p, n_l), jnp.bool_)
        for ai in range(a_width):
            sat &= _anchor_rule_sat(
                anchors[:, ai], gids_cand[inc][None, :],
                gids_cand[exc][None, :], gids, gid_valid, inc, exc)
        pen = jnp.where(sat, jnp.minimum(pen, idx * _RULE_TIER), pen)
    return jnp.where(any_anchor[:, None], pen, 0.0)


def _psum(x: jnp.ndarray, axis_name: Optional[str]) -> jnp.ndarray:
    return lax.psum(x, axis_name) if axis_name else x


def _hier_tier_at(
    anchors: jnp.ndarray,  # [P, A] global node ids, -1 absent
    node: jnp.ndarray,  # [P] or [P, K] global node ids
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    rules: StateRules,
) -> jnp.ndarray:
    """_hier_penalty evaluated at gathered columns — O(rows * cols) ops.

    ``node`` may be [P] (one column per row: phase B's waterfall probe)
    or [P, K] (the sparse shortlist's candidate block); the anchor axis
    broadcasts against any trailing shape, and the [P] spelling is
    bit-identical to what it always was."""
    any_anchor = jnp.any(anchors >= 0, axis=1)
    sh = (node.shape[0],) + (1,) * (node.ndim - 1)
    nd = jnp.clip(node, 0, gids.shape[1] - 1)
    pen = jnp.full(node.shape, _RULE_MISS, jnp.float32)
    for idx, (inc, exc) in enumerate(rules):
        sat = jnp.ones(node.shape, jnp.bool_)
        for ai in range(anchors.shape[1]):
            sat &= _anchor_rule_sat(
                anchors[:, ai], gids[inc][nd], gids[exc][nd],
                gids, gid_valid, inc, exc)
        pen = jnp.where(sat, jnp.minimum(pen, idx * _RULE_TIER), pen)
    return jnp.where(any_anchor.reshape(sh), pen, 0.0)


def _hier_floor_counts(
    anchors: jnp.ndarray,  # [P, A] global node ids, -1 absent
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    valid: jnp.ndarray,  # [N] full
    rules: StateRules,
    taken_stack: Optional[jnp.ndarray] = None,  # [P, T] GLOBAL node ids
    # the row's partition already occupies; those columns are +INF in
    # the score, so a taken-aware floor must not count them attainable
) -> jnp.ndarray:
    """Best attainable rule tier over valid nodes, by GROUP COUNTING.

    Equivalent to ``min over valid n of _hier_penalty[:, n]`` without
    materializing [P, N]: because each rule's exclude level is strictly
    finer than its include level (caller checks this statically), an
    exclude group lies inside exactly one include group, so the number
    of rule-satisfying valid nodes is
        count(valid in shared include group g)
        - sum over DISTINCT anchor exclude groups of count(valid in e).
    Everything is [N]-histograms plus [P] gathers.  Anchor-side validity
    gates exactly like _hier_penalty: an anchor with an invalid include
    gid makes the rule unsatisfiable; an invalid exclude gid excludes
    nothing.  Returns the floor PENALTY value ([P], 0.0 when no anchor),
    matching what _hier_penalty's row-min over valid columns yields —
    with one deliberate difference: when no valid node exists at all the
    matrix row-min is +_INF while this returns _RULE_MISS, and every
    comparison made against the floor treats those identically (a
    _RULE_MISS-tier pin passes either way)."""
    p, a_width = anchors.shape
    n = gids.shape[1]
    any_anchor = jnp.any(anchors >= 0, axis=1)
    floor = jnp.full(p, _RULE_MISS, jnp.float32)
    for idx, (inc, exc) in enumerate(rules):
        # Valid-node histograms per group at each level (group ids are
        # dense per level, < N; invalid slots route to the drop bucket).
        gi = jnp.where(valid, gids[inc], -1)
        ge = jnp.where(valid, gids[exc], -1)
        cnt_inc = jnp.zeros(n, jnp.float32).at[
            jnp.where(gi >= 0, gi, n)].add(1.0, mode="drop")
        cnt_exc = jnp.zeros(n, jnp.float32).at[
            jnp.where(ge >= 0, ge, n)].add(1.0, mode="drop")

        # Shared include group across present anchors (else unsatisfiable).
        g = jnp.full(p, -1, jnp.int32)
        ok = jnp.ones(p, jnp.bool_)
        for ai in range(a_width):
            a = anchors[:, ai]
            aa = jnp.maximum(a, 0)
            a_g = jnp.where(gid_valid[inc][aa], gids[inc][aa], -2)
            present = a >= 0
            ok &= jnp.where(present & (g >= 0), a_g == g, True)
            ok &= jnp.where(present & (g < 0), a_g >= 0, True)
            g = jnp.where(present & (g < 0), a_g, g)

        # Exclusion mass: distinct exclude groups among present anchors.
        excl = jnp.zeros(p, jnp.float32)
        e_seen = []
        for ai in range(a_width):
            a = anchors[:, ai]
            aa = jnp.maximum(a, 0)
            e = jnp.where((a >= 0) & gid_valid[exc][aa], gids[exc][aa], -1)
            dup = jnp.zeros(p, jnp.bool_)
            for prev_e in e_seen:
                dup |= (e == prev_e) & (e >= 0)
            excl += jnp.where(
                (e >= 0) & ~dup, cnt_exc[jnp.clip(e, 0, n - 1)], 0.0)
            e_seen.append(e)

        count = jnp.where(
            ok & (g >= 0), cnt_inc[jnp.clip(g, 0, n - 1)] - excl, 0.0)

        # Taken-aware: subtract the row's own occupied nodes still
        # standing in the include group but OUTSIDE every counted
        # exclude group (those inside were subtracted with their group).
        # Mirrors the audit's attainable_count (_count_hier_misses_fast)
        # including its dedup of repeated ids, so the floor agrees with
        # the matrix row-min over score columns the taken mask +INFs.
        if taken_stack is not None:
            t_seen = []
            for ti in range(taken_stack.shape[1]):
                u = taken_stack[:, ti]
                uu = jnp.clip(u, 0, n - 1)
                ok_u = (u >= 0) & valid[uu]
                in_g = ok_u & (gids[inc][uu] == g) & (g >= 0)
                in_excl = jnp.zeros(p, jnp.bool_)
                for e in e_seen:
                    in_excl |= (e >= 0) & (gids[exc][uu] == e)
                dup = jnp.zeros(p, jnp.bool_)
                for prev_u in t_seen:
                    dup |= (u == prev_u) & (u >= 0)
                count = count - jnp.where(in_g & ~in_excl & ~dup, 1.0, 0.0)
                t_seen.append(u)

        floor = jnp.where(count > 0,
                          jnp.minimum(floor, idx * _RULE_TIER), floor)
    return jnp.where(any_anchor, floor, 0.0)


# --- node-axis sharding helpers ------------------------------------------
#
# Under a 2-D mesh (parts x nodes) every [N] vector (counts, capacity,
# prices) stays REPLICATED along the node axis — at the north-star 10k
# nodes that's kilobytes — while the [P, N] score (and the masks fused
# into it) holds only local columns.  Membership/exclusivity live as
# [P, small] GLOBAL id columns compared against the local column window
# (_member_ids), so they need no collectives at all.  Acceptance/capacity
# logic runs as identical replicated math on every node shard; the only
# node-axis collectives are (a) combining per-row (min, argmin, second)
# stats and (b) fetching a matrix value at a remote column.


def _node_off(node_axis: Optional[str], n_l: int):
    """Global column offset of this node shard."""
    return lax.axis_index(node_axis) * n_l if node_axis else 0


def _node_slice(vec: jnp.ndarray, node_axis: Optional[str], n_l: int):
    """Local [.., N_l] slice of a node-replicated [.., N] array."""
    if not node_axis:
        return vec
    return lax.dynamic_slice_in_dim(
        vec, _node_off(node_axis, n_l), n_l, axis=vec.ndim - 1)


def _member_ids(ids: jnp.ndarray, cols: jnp.ndarray) -> jnp.ndarray:
    """[P, K] GLOBAL node ids x [N_l] global column ids -> [P, N_l]
    membership, as K unrolled broadcast-compares ORed together.

    Deliberately NOT a scatter: scatters materialize the [P, N_l] bool in
    HBM, while compares fuse into whatever elementwise consumer follows
    (the score build) — at the north-star scale that removes ~1 GB of
    write+read traffic per mask.  -1 ids never match (cols >= 0), and
    column ids are global, so the result is node-shard invariant by
    construction."""
    out = None
    for k in range(ids.shape[1]):
        m = ids[:, k][:, None] == cols[None, :]
        out = m if out is None else (out | m)
    if out is None:  # K == 0
        return jnp.zeros((ids.shape[0], cols.shape[0]), jnp.bool_)
    return out


def _in_id_list(node: jnp.ndarray, id_list: list[jnp.ndarray]) -> jnp.ndarray:
    """[P] node id -> [P] bool: held by any of the [P] id columns."""
    out = jnp.zeros(node.shape[0], jnp.bool_)
    for ids in id_list:
        out = out | ((node == ids) & (node >= 0))
    return out


def _gather_cols(
    mat: jnp.ndarray,  # [P, N_l]
    rows: jnp.ndarray,  # [P] row ids
    cols_global: jnp.ndarray,  # [P] GLOBAL column ids (>= 0)
    node_axis: Optional[str],
) -> jnp.ndarray:
    """mat[rows, cols] with global column ids: the owner shard supplies the
    value, a masked psum over the node axis delivers it everywhere."""
    n_l = mat.shape[1]
    loc = cols_global - _node_off(node_axis, n_l)
    ok = (loc >= 0) & (loc < n_l)
    vals = mat[rows, jnp.clip(loc, 0, n_l - 1)]
    if not node_axis:
        return vals
    return lax.psum(jnp.where(ok, vals, 0.0), node_axis)


def _row_min_global(mat: jnp.ndarray, node_axis: Optional[str]):
    """Per-row min over the full (sharded) column axis."""
    m = jnp.min(mat, axis=1)
    return lax.pmin(m, node_axis) if node_axis else m


def _combine_min2(
    best_l: jnp.ndarray,  # [P] local best (priced)
    choice_g: jnp.ndarray,  # [P] GLOBAL id of local argmin
    second_l: jnp.ndarray,  # [P] local second-best
    raw_l: jnp.ndarray,  # [P] unpriced score at the local argmin
    node_axis: Optional[str],
):
    """Merge per-shard (min, argmin, second, raw-at-min) into global stats.

    Global second = min(second of the winning shard, best of every other
    shard).  Ties in best break toward the lowest shard index = lowest
    global node id, preserving the replicated-node tie-break order."""
    if not node_axis:
        return best_l, choice_g, second_l, raw_l
    bests = lax.all_gather(best_l, node_axis)  # [ns, P]
    choices = lax.all_gather(choice_g, node_axis)
    seconds = lax.all_gather(second_l, node_axis)
    raws = lax.all_gather(raw_l, node_axis)
    ns = bests.shape[0]
    k_star = jnp.argmin(bests, axis=0)  # [P]

    def take(a):
        return jnp.take_along_axis(a, k_star[None, :], axis=0)[0]

    others = jnp.where(
        jnp.arange(ns)[:, None] == k_star[None, :], jnp.inf, bests)
    second = jnp.minimum(take(seconds), jnp.min(others, axis=0))
    return take(bests), take(choices), second, take(raws)


def _ensure_varying(x: jnp.ndarray, *axes: Optional[str]) -> jnp.ndarray:
    """Mark ``x`` varying over each named mesh axis it does not already
    vary over.  Under shard_map, values created inside the body (zeros,
    constants) are axis-invariant, so loop carries and cond branches
    that mix them with shard-local values must be cast up front for
    their varying-axes types to agree."""
    missing = tuple(a for a in axes if a and a not in jax.typeof(x).vma)
    return lax.pcast(x, missing, to="varying") if missing else x


def _shard_capacity(cap: jnp.ndarray, axis_name: Optional[str]) -> jnp.ndarray:
    """Split global per-node capacity into integral per-shard shares.

    Fractional caps + the first-bidder progress rule would overshoot, so
    each shard gets floor(cap/ns) with the remainder rotated by node index
    so no shard systematically holds the extras.
    """
    if not axis_name:
        return cap
    ns = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    base_cap = jnp.floor(cap / ns)
    rem = cap - base_cap * ns
    node_ids = jnp.arange(cap.shape[0], dtype=jnp.int32)
    extra = ((node_ids + idx) % ns) < rem.astype(jnp.int32)
    return base_cap + extra.astype(jnp.float32)


def _segment_accept(
    node_s: jnp.ndarray,  # [K] node ids, sorted so equal nodes are adjacent
    ok_s: jnp.ndarray,  # [K] participating entries
    w_s: jnp.ndarray,  # [K] weights (0 where not participating)
    cap_here: jnp.ndarray,  # [K] per-entry capacity budget (node's cap)
) -> jnp.ndarray:
    """Per-node prefix acceptance: keep entries while the running weight on
    their node fits ``cap_here``; the first entry per node always fits if
    the node has any capacity (the auction's progress rule).  The single
    capacity-acceptance idiom shared by the auction rounds and the
    warm-start pins — one accept rule, enforced identically in both."""
    csum = jnp.cumsum(w_s)
    ecs = csum - w_s  # exclusive prefix over ALL entries
    seg_start = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), node_s[1:] != node_s[:-1]])
    seg_base = lax.cummax(jnp.where(seg_start, ecs, -jnp.inf))
    before_me = ecs - seg_base  # weight of earlier entries on my node
    return ok_s & (
        (before_me + w_s <= cap_here) | (before_me == 0.0) & (cap_here > 0))


def _pin_prev_holders(
    prev_slot: jnp.ndarray,  # [P] node id or -1
    pin_ok: jnp.ndarray,  # [P] eligible to keep its previous node
    pweights: jnp.ndarray,  # [P]
    cap: jnp.ndarray,  # [N] GLOBAL capacity for this state
    slack: jnp.ndarray,  # [P] per-holder capacity tolerance (stickiness)
    axis_name: Optional[str],
    load_div: Optional[jnp.ndarray] = None,  # [N] node weight (>= 1) —
    # converts held weight into the score's count/weight units
    taken_stack: Optional[jnp.ndarray] = None,  # [P, T] GLOBAL node ids
    # this row's partition already occupies (other states / ordinals)
) -> jnp.ndarray:
    """Capacity-capped warm start: returns pinned[P] bool.

    The keep-ceiling per node is  max(fair-share quota,
    (least-loaded-open-node's score-load + stickiness) * node_weight) —
    the batch spelling of the reference's marginal rule (plan.go:654-662
    + the traced self-inclusive count: a holder keeps its node iff its
    node's load minus stickiness still beats the emptiest candidate).
    Consequences, each pinned by a test: a fresh node pulls load only
    from nodes more than ``stickiness`` above it (2 copies + 1 fresh
    node -> one moves); +-1 capacity-quantization fixpoints replan
    unchanged (ceil-cap overshoot sits inside the lmin+stickiness
    band); delta rebalances shed only the load above the band instead
    of trimming every over-quota node to its exact share (churn stays
    near the sequential oracle's).  Holders are kept in partition order
    (deterministic), except that holders barred from the emptiest node
    by same-partition exclusivity keep their place first (see trim).
    The first holder per node always stays (auction progress rule).
    Everything else goes to the auction.
    """
    p = prev_slot.shape[0]
    n = cap.shape[0]
    safe = _drop_empty(prev_slot, n)
    pin_w = jnp.where(pin_ok, pweights, 0.0)
    node_w_local = jnp.zeros(n, jnp.float32).at[safe].add(pin_w, mode="drop")
    # Load and over-capacity are GLOBAL questions — under shard_map each
    # shard holds an arbitrary subset of a node's holders, so the shard-
    # local weight says nothing about whether the node is full.
    node_w = _psum(node_w_local, axis_name)
    # Least-loaded OPEN node in score units (held weight / node weight);
    # the minimum runs over nodes that can accept load (cap > 0 —
    # removed nodes can't fake an empty target).  Anchors the marginal
    # keep-ceiling below.
    div = load_div if load_div is not None else jnp.ones(n, jnp.float32)
    load = node_w / div
    lmin = jnp.min(jnp.where(cap > 0, load, jnp.inf)) if n else jnp.inf

    # The trim quota must be shard-local (each shard admits only its
    # integral share of a node's capacity, remainder rotated — the same
    # split the auction uses) or every shard would admit up to the global
    # cap and overshoot by the shard count.
    cap_quota = _shard_capacity(cap, axis_name)

    def keep_all(_):
        # Common case (shrinking/steady cluster: caps only grew): every
        # eligible holder fits — no ordering pass needed.
        return pin_ok

    def trim(_):
        # Some node over-caps (cluster grew, its share shrank): keep
        # holders up to the marginal ceiling.  Within a node group, holders
        # whose partition already occupies the EMPTIEST open node are
        # kept FIRST: exclusivity bars their displaced copy from the one
        # node that needs load, so displacing them instead of a free
        # holder strands the deficit (seen on a 2-node + fresh-node
        # grow: both capacity-displaced primaries landed on the new
        # node, so the replica wave's partition-order trim displaced
        # exactly the two replicas that could not follow).  Ties keep
        # partition order (deterministic).
        if taken_stack is not None:
            deficit_node = jnp.argmin(jnp.where(cap > 0, load, jnp.inf))
            blocked = jnp.any(taken_stack == deficit_node, axis=1)
            perm1 = jnp.argsort((~blocked).astype(jnp.int32), stable=True)
        else:
            perm1 = jnp.arange(p)
        sort_node = jnp.where(pin_ok, prev_slot, n)
        perm2 = jnp.argsort(sort_node[perm1], stable=True)  # groups by node
        perm = perm1[perm2]
        node_s = sort_node[perm]
        ok_s = pin_ok[perm]
        w_s = jnp.where(ok_s, pweights[perm], 0.0)
        # Marginal keep-ceiling (docstring): fair-share quota, or the
        # emptiest open node's load plus the holder's stickiness in the
        # node's weight units — whichever is larger.  The lmin band is
        # divided by the shard count like the quota: it is a GLOBAL
        # allowance, and each shard orders only its own holders.
        ns = lax.axis_size(axis_name) if axis_name else 1
        nclip = jnp.clip(node_s, 0, n - 1)
        band = (lmin + slack[perm]) * div[nclip] / ns
        cap_here = jnp.maximum(cap_quota[nclip], band)
        keep_s = _segment_accept(node_s, ok_s, w_s, cap_here)
        return jnp.zeros(p, jnp.bool_).at[perm].set(keep_s)

    return lax.cond(jnp.any(node_w > cap), trim, keep_all, None)


def _sparse_score_cols(
    cols: jnp.ndarray,  # [M, K] GLOBAL node ids; -1 = pad (scores +_INF)
    rows: jnp.ndarray,  # [M] local row ids
    pbase,  # global partition index of local row 0 (jitter)
    *,
    total: jnp.ndarray,  # [N] full fill vector
    total_p: jnp.ndarray,
    w_div: jnp.ndarray,  # [N]
    neg_boost: jnp.ndarray,  # [N]
    valid: jnp.ndarray,  # [N] bool
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    stick_si: jnp.ndarray,  # [P]
    prev_slot: jnp.ndarray,  # [P] global ids
    prev_state: jnp.ndarray,  # [P, R]
    taken_ids: tuple[jnp.ndarray, ...],
    anchors: Optional[jnp.ndarray],  # [P, A] (rules only)
    rules: StateRules,
    jitter_scale: float,
) -> jnp.ndarray:
    """The MATRIX engine's score formula evaluated at gathered columns.

    This is the sparse path's score: term order mirrors run_auction's
    matrix build EXACTLY, so with a saturating shortlist (row r's
    columns = 0..N-1) the [P, N] result is bitwise the dense matrix —
    the foundation of the K = N bit-identity contract.  Pad columns
    (id -1) score +_INF like any forbidden node.  O(M * K) ops and
    HBM traffic; no [P, N] tensor exists."""
    n = w_div.shape[0]
    c = jnp.clip(cols, 0, n - 1)
    okc = cols >= 0
    st = stick_si[rows][:, None]
    score = 0.001 * total[c] / jnp.maximum(total_p, 1.0)
    score = score / w_div[c]
    # Same-ordinal alignment (matrix: -0.01 * _member_ids(prev_slot)).
    score = score - 0.01 * ((prev_slot[rows][:, None] == cols) & okc)
    nb = neg_boost[c]
    score = score + jnp.maximum(nb, jnp.where(nb > 0, st, 0.0))
    sticky = jnp.zeros(cols.shape, jnp.bool_)
    for r in range(prev_state.shape[1]):
        sticky = sticky | ((prev_state[rows, r][:, None] == cols) & okc)
    score = score - st * sticky
    if rules:
        score = score + _hier_tier_at(
            anchors[rows], c, gids, gid_valid, rules)
    taken = jnp.zeros(cols.shape, jnp.bool_)
    for tid in taken_ids:
        taken = taken | ((tid[rows][:, None] == cols) & okc)
    score = score + _INF * (taken | ~valid[c] | ~okc)
    pi = (pbase + rows)[:, None].astype(jnp.int32)
    return score + jitter_scale * jitter_hash(pi, c.astype(jnp.int32))


def _assign_slot(
    min2_fn,  # price_vec[N] -> (best, choice GLOBAL, second, raw-at-choice)
    score_at_fn,  # (rows[K], cols_global[K]) -> unpriced score values [K]
    p: int,
    pweights: jnp.ndarray,  # [P]
    cap: jnp.ndarray,  # [N] weighted capacity for this slot (global)
    price_scale: jnp.ndarray,  # [N] converts accepted weight into score units
    axis_name: Optional[str],
    init_assign: Optional[jnp.ndarray] = None,  # [P] warm-start (or -1)
    init_used: Optional[jnp.ndarray] = None,  # [N] weight behind the warm start
    node_axis: Optional[str] = None,
    topup_share: Optional[jnp.ndarray] = None,  # [N] per-node share for
    # capacity top-ups when rule-constrained demand exceeds the rail
    has_rules: bool = True,  # static: state carries hierarchy rules
    feasible_hint: Optional[jnp.ndarray] = None,  # [P] bool, required when
    # has_rules=False and topup_share is set: any allowed node exists
    allow: Optional[jnp.ndarray] = None,  # [P] bool — rows the caller
    # permits to take a slot here at all.  The sparse path gates rows
    # whose shortlist cannot reach the globally attainable rule tier:
    # they neither bid nor get forced, staying -1 for the per-row dense
    # fallback instead of silently accepting a worse-tier placement.
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Auction: returns (slot_assign[P] int32 GLOBAL node id or -1, used[N]).

    The score is reached ONLY through the two callables, so the caller
    chooses the engine: a materialized [P, N_l] matrix (min2_fn = the
    priced Pallas reduction or the XLA reference over it), or the fused
    in-kernel score (ops/score_fused.py) where the matrix never exists.
    Both must include the deterministic tie-break jitter and fold
    forbidden columns in as +_INF.

    Each round: bid on the best open node, accept most-urgent bidders up to
    remaining capacity (at least the first bidder per node, to guarantee
    progress), repeat.  Ends when everyone is assigned or nothing moved.
    ``init_assign``/``init_used`` seed the loop with pre-pinned placements
    (the warm start); pinned partitions never rebid.

    When a round accepts NOTHING while bidders remain — hierarchy rules can
    owe one rack far more copies than the global capacity rail allots it
    (e.g. every heavy node on one rack: the light racks then owe most
    replicas) — the rail is raised instead of abandoned: every node gains
    its ``topup_share`` of the remaining unassigned weight and the priced
    rounds continue.  This keeps per-node acceptance discipline for
    rule-constrained overflow, where the one-shot force step would herd
    stragglers onto the locally-cheapest node (measured within-rack
    replica spread 16..29 vs the greedy oracle's 20..21 on a weighted
    3-rack fuzz seed; with top-up both sit at ~1).

    Partition axis: entirely shard-local — the caller hands each shard its
    slice of capacity and psums the returned per-node usage afterwards, so
    shards may take different round counts.  Node axis: the callables see
    only this shard's columns while cap/price/used stay replicated [N];
    each round runs one all_gather (per-row min stats) inside min2_fn —
    everything else is identical replicated math on every node shard.
    """
    n = cap.shape[0]

    # Loop-invariant: phase B consults the unpriced per-row best to decide
    # whether a straggler still has rule-satisfying options.  Computed once
    # here (min2 at price 0) — XLA cannot hoist a [P, N] reduction out of
    # the while_loop body on its own.  Rule-LESS states have no tiers to
    # reason about (the boost term is a preference, not a constraint), so
    # their gates are structurally pass-through and this whole pass is
    # skipped; hard feasibility then comes from the caller's id-column
    # count (feasible_hint) instead of a row-min.
    if has_rules:
        raw_best_all, _, _, _ = min2_fn(jnp.zeros(n, jnp.float32))
        hard_feasible = raw_best_all < _INF / 2
    else:
        raw_best_all = None
        hard_feasible = feasible_hint
    if allow is not None and hard_feasible is not None:
        hard_feasible = hard_feasible & allow

    def round_body(carry):
        slot_assign, unassigned, rem_cap, used, _progress, it = carry

        # Price: weight already accepted this slot raises a node's cost as
        # if the counts term had updated, so bids keep spreading even
        # within one slot wave; closed nodes cost +_INF.
        # The fused (min, argmin, second-min) over score + price runs in
        # one HBM pass with the price row folded in VMEM via the Pallas
        # kernel on TPU (blance_tpu/ops/reduce2.py); the XLA spelling
        # (priced [P, N] materialization + 3 reductions) elsewhere.
        price_vec = used * price_scale + jnp.where(rem_cap > 0, 0.0, _INF)
        best, choice, second, raw_choice = min2_fn(price_vec)
        margin = jnp.clip(jnp.nan_to_num(second - best, posinf=10.0), 0.0, 10.0)

        # Rules-first gate (mirrors phase B's soft_ok): when every node
        # at the partition's best attainable rule TIER is priced closed
        # — common under shard_map, where each shard holds only 1/ns of
        # a node's capacity — the priced argmin falls through to a
        # worse-tier node.  Don't bid it: wait for top-up/force, which
        # prefer the best-tier nodes (rule conformance beats balance,
        # like the reference's hierarchy-pass-first ordering,
        # plan.go:174-226).  Tier equality is a band test against the
        # unpriced row-min: within-tier terms stay far below the
        # _RULE_TIER step.  Unattainable rules (row-min at _RULE_MISS)
        # fall back flat and accept any feasible node.
        rule_ok = ((raw_choice < raw_best_all + _RULE_TIER * 0.5)
                   | (raw_best_all >= _RULE_MISS / 2)) if has_rules else True
        active = unassigned & (best < _INF / 2) & rule_ok
        if allow is not None:
            active = active & allow

        # Sort bidders by (node, urgency desc) via two stable argsorts —
        # avoids packing into int64, which is x64-gated.  Inactive bidders
        # sort to the end.
        inv_margin = jnp.where(active, -margin, jnp.inf)
        sort_choice = jnp.where(active, choice, n)
        perm1 = jnp.argsort(inv_margin, stable=True)
        perm2 = jnp.argsort(sort_choice[perm1], stable=True)
        perm = perm1[perm2]

        choice_s = choice[perm]
        w_s = pweights[perm]
        active_s = active[perm]

        accept_s = _segment_accept(
            choice_s, active_s, jnp.where(active_s, w_s, 0.0),
            rem_cap[choice_s])

        accept = jnp.zeros(p, jnp.bool_).at[perm].set(accept_s)
        slot_assign = jnp.where(accept, choice, slot_assign)
        unassigned = unassigned & ~accept

        used_round = jnp.zeros(n, jnp.float32).at[choice].add(
            jnp.where(accept, pweights, 0.0))
        rem_cap = rem_cap - used_round
        used = used + used_round

        # Phase B — waterfall: stragglers rejected above would all rebid on
        # the single cheapest node next round (converging linearly), so
        # instead rank them by urgency and pour them into the remaining
        # capacity of nodes ordered by price.  Hard-forbidden matches and
        # rule-missing matches (when the partition still has rule-satisfying
        # options) are skipped and retry next round.
        price = used * price_scale
        node_order = jnp.argsort(price)
        # Clamp: the first-bidder progress rule can drive rem_cap negative
        # (oversize partition into a capacity remainder); cum_rem must stay
        # non-decreasing for searchsorted to be meaningful.
        rem_sorted = jnp.maximum(rem_cap, 0.0)[node_order]
        cum_rem = jnp.cumsum(rem_sorted)

        straggler = active & ~accept
        skey = jnp.where(straggler, -margin, jnp.inf)
        sperm = jnp.argsort(skey, stable=True)
        s_mask = straggler[sperm]
        s_w = jnp.where(s_mask, pweights[sperm], 0.0)
        s_excl = jnp.cumsum(s_w) - s_w
        pos = jnp.searchsorted(cum_rem, s_excl + 0.5 * s_w, side="right")
        in_range = pos < n
        choice2 = node_order[jnp.clip(pos, 0, n - 1)]

        raw2 = score_at_fn(sperm, choice2)
        hard_ok = raw2 < _INF / 2
        # Same tier-aware gate as phase A: the waterfall may only place a
        # partition at its best attainable tier — a capacity-ordered
        # target at a worse tier is skipped and retried next round (the
        # audit counts any tier downgrade as a hierarchy miss).
        soft_ok = ((raw2 < raw_best_all[sperm] + _RULE_TIER * 0.5)
                   | (raw_best_all[sperm] >= _RULE_MISS / 2)) \
            if has_rules else True
        accept2_s = s_mask & in_range & hard_ok & soft_ok

        accept2 = jnp.zeros(p, jnp.bool_).at[sperm].set(accept2_s)
        choice2_un = jnp.zeros(p, jnp.int32).at[sperm].set(choice2)
        slot_assign = jnp.where(accept2, choice2_un, slot_assign)
        unassigned = unassigned & ~accept2

        used2 = jnp.zeros(n, jnp.float32).at[choice2_un].add(
            jnp.where(accept2, pweights, 0.0))
        rem_cap = rem_cap - used2
        used = used + used2

        progress = jnp.any(accept | accept2)
        if topup_share is not None:
            # Stalled with FEASIBLE bidders left: raise the rail by each
            # node's share of their remaining weight and keep the priced
            # rounds going (see docstring).  Hard-infeasible stragglers
            # (no valid node at any price — raw_best_all >= _INF/2) must
            # not force extra rounds: only the force step can resolve
            # them, so without a feasible bidder the loop still exits on
            # the first stalled round.  Share-0 (invalid) nodes get no
            # top-up and stay closed.
            rem_w = jnp.sum(jnp.where(
                unassigned & hard_feasible, pweights, 0.0))
            stalled = ~progress & (rem_w > 0)
            topup = jnp.ceil(rem_w * topup_share)
            rem_cap = jnp.where(stalled, rem_cap + topup, rem_cap)
            progress = progress | (stalled & jnp.any(topup > 0))
        return (slot_assign, unassigned, rem_cap, used, progress, it + 1)

    def round_cond(carry):
        _, unassigned, _, _, progress, it = carry
        return jnp.any(unassigned) & progress & (it < _MAX_AUCTION_ROUNDS)

    if init_assign is None:
        init_assign = jnp.full(p, -1, jnp.int32)
    if init_used is None:
        init_used = jnp.zeros(n, jnp.float32)
    init = (
        init_assign,
        init_assign < 0,
        cap - init_used,
        init_used,
        jnp.array(True),
        jnp.array(0, jnp.int32),
    )
    # Freshly-created carries are axis-invariant until the (shard-local)
    # loop body makes them varying.
    init = tuple(_ensure_varying(x, axis_name, node_axis) for x in init)
    slot_assign, unassigned, _rem, used, _, _ = lax.while_loop(
        round_cond, round_body, init)

    # Force step: remaining partitions take their best feasible node,
    # ignoring capacity (constraint satisfaction beats balance).  Price on
    # the GLOBAL usage (one [N] psum): each shard's force sees every
    # shard's accepted weight, or all shards would pile their stragglers
    # onto the same locally-cheapest node.  Skipped entirely (a full
    # [P, N] pass saved) when the rounds assigned everyone — the common
    # case.  The psum runs unconditionally; inside the branch only
    # node-axis collectives can occur, and ``unassigned`` is replicated
    # along the node axis, so every participant of those collectives
    # agrees on the branch.
    used_global = _psum(used, axis_name)

    def do_force(args):
        slot_assign, unassigned, used = args
        best, choice, _second, _raw = min2_fn(
            used_global * price_scale)
        feasible = best < _INF / 2
        forced = unassigned & feasible
        if allow is not None:
            forced = forced & allow
        slot_assign = jnp.where(forced, choice, slot_assign)
        used_forced = jnp.zeros(n, jnp.float32).at[choice].add(
            jnp.where(forced, pweights, 0.0))
        return slot_assign, used + used_forced

    def skip_force(args):
        slot_assign, _unassigned, used = args
        return slot_assign, used

    slot_assign, used = lax.cond(
        jnp.any(unassigned), do_force, skip_force,
        (slot_assign, unassigned, used))

    return slot_assign, used


def _solve_assign(
    prev: jnp.ndarray,  # [P, S, R] int32 (GLOBAL node ids)
    pweights: jnp.ndarray,  # [P] float32
    nweights: jnp.ndarray,  # [N] float32 (full, node-replicated)
    valid: jnp.ndarray,  # [N] bool (full)
    stickiness: jnp.ndarray,  # [P, S] float32
    gids: jnp.ndarray,  # [L, N] int32 (full)
    gid_valid: jnp.ndarray,  # [L, N] bool (full)
    constraints: Constraints,  # static, per-state slot counts
    rules: Rules,  # static, per-state tuple of (inc, exc) pairs
    axis_name: Optional[str] = None,  # static; set under shard_map
    node_axis: Optional[str] = None,  # static; second mesh axis over nodes
    node_shards: int = 1,  # static; size of the node axis (N must divide)
    fused_score: str = "off",  # static; "off" = materialized score matrix,
    # "on" = in-kernel score (ops/score_fused.py, TPU), "interpret" =
    # in-kernel via the pallas interpreter (CPU tests)
    carry_used: Optional[jnp.ndarray] = None,  # [S, N] warm-start seed:
    # per-state per-node fill from the previous converged solve
    # (SolveCarry.used).  MUST equal the scatter of ``prev`` (the session
    # invalidates the carry whenever prev drifts); seeding replaces the
    # S + 1 seed scatters with lookups, bit-identically.
    p_real: Optional[jnp.ndarray] = None,  # traced scalar: the GLOBAL
    # count of REAL partitions when the arrays carry inert padding rows
    # (shape bucketing).  Keeps the advisory fill factor's denominator —
    # the one place the partition COUNT (not weight) enters the score —
    # identical to the unpadded solve, so bucketing is bit-neutral.
    # Traced, not static: drifting real sizes inside one bucket must not
    # retrigger compilation.
    shortlist: Optional[jnp.ndarray] = None,  # [P, K] GLOBAL candidate
    # node ids per partition (-1 pads), ascending per row — the SPARSE
    # engine.  Scores are evaluated only at these columns ([P, S, K]
    # work per sweep) while fill/price/capacity stay full [S, N] width,
    # so acceptance and the audit contracts run against real global
    # state.  A saturating shortlist (row r = 0..N-1) is bit-identical
    # to the dense engines.
    sparse_impl: str = "xla",  # static: "xla" reference reduction,
    # "pallas" = the fused ops/sparse2.py kernel, "interpret" = that
    # kernel under the pallas interpreter (CPU tests)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One assignment sweep; returns (assign[P, S, R], exhausted[P]).

    ``exhausted`` is all-False on the dense engines; on the sparse
    engine it flags rows whose shortlist could not reach the globally
    attainable rule tier (or had no feasible candidate) for some slot —
    the rows the caller must re-place through the per-row dense
    fallback.

    With ``node_axis`` set (a 2-D parts x nodes mesh), every [P, N]
    intermediate — score, penalties, stickiness/taken masks — holds only
    this shard's N/node_shards columns, while [N] vectors (counts,
    capacity, prices) stay replicated along the node axis: at the
    north-star scale those are kilobytes and keeping them replicated makes
    all capacity/acceptance logic identical math on every node shard.
    Node ids in prev/assign are global throughout."""
    p, s, r_max = prev.shape
    n = nweights.shape[0]
    if fused_score not in ("off", "on", "interpret"):
        # "auto" must be resolved by resolve_fused_score BEFORE jit; a
        # silent passthrough here would select the compiled kernel on
        # hosts that can't run it.
        raise ValueError(f"unresolved fused-score mode: {fused_score!r}")
    if shortlist is not None:
        if node_axis:
            raise ValueError(
                "sparse solve does not support node-axis sharding: the "
                "[P, K] shortlist already bounds the column working set; "
                "shard the partition axis instead")
        if sparse_impl not in ("xla", "pallas", "interpret"):
            raise ValueError(f"unknown sparse_impl: {sparse_impl!r}")
        if not all(exc < inc for rl in rules for (inc, exc) in rl):
            # The shortlist-exhaustion gate needs the group-counting
            # attainability floor, which only exists for nesting rules
            # (exclude strictly finer than include — the tree shape).
            raise ValueError(
                "sparse solve requires nesting hierarchy rules "
                "(exclude_level < include_level for every rule); use the "
                "dense engines for exotic rule shapes")
    if constraints and max(constraints) > r_max:
        # JAX drops out-of-bounds scatter writes silently; without this the
        # slots beyond R would vanish while still consuming capacity.
        raise ValueError(
            f"prev slot depth R={r_max} < max constraints {max(constraints)}")
    if n % node_shards:
        raise ValueError(
            f"N={n} not divisible by node_shards={node_shards}; pad nodes")
    n_l = n // node_shards
    noff = _node_off(node_axis, n_l)
    valid_l = _node_slice(valid, node_axis, n_l)
    gids_l = _node_slice(gids, node_axis, n_l)

    if p_real is not None:
        total_p = jnp.asarray(p_real, jnp.float32)  # global: no psum
    else:
        total_p = _psum(jnp.array(p, jnp.float32), axis_name)
    total_w = _psum(jnp.sum(pweights), axis_name)

    w_div = jnp.where(nweights > 0, nweights, 1.0)
    neg_boost = jnp.where(nweights < 0, -nweights, 0.0)  # [N]

    # Jitter sits deliberately ABOVE the 0.001/P fill factor: the fill term
    # is an advisory nudge (as in the reference's 0.001 filled-factor,
    # plan.go:647-651), while real balance is owned by the capacity rail and
    # the in-slot price.  Letting jitter dominate the fill signal keeps
    # bids spread across near-equal nodes; herding bids by the fill
    # ordering fragments capacity and forces cap overflows (measured: slot
    # spread 12-20 vs 15-17 at 256x16).
    jitter_scale = jnp.float32(_JITTER)

    # Negative-weight (booster-steered) nodes get NO fair-share capacity:
    # the cbgt booster semantics make them last-resort targets (greedy
    # adds max(-w, stickiness) to their score, plan.go:675-684), so the
    # rail must not reserve a share for them — new load overflows onto
    # them only through the capacity-ignoring force step.  Their existing
    # sticky holders survive via pin slack when -w <= stickiness (the
    # same marginal rule the greedy applies).
    cap_w = jnp.where(valid & (nweights >= 0), jnp.maximum(nweights, 1.0),
                      0.0)
    cap_share = cap_w / jnp.maximum(jnp.sum(cap_w), 1.0)

    # Seed the total-fill factor from prev (plan.go:94).  Per-state counts
    # are NOT part of the batch score: every partition of a state reassigns
    # simultaneously, so the state's own counts are zero at wave start, and
    # carrying intra-wave counts across slots lets +-cap quantization noise
    # (several units) swamp the 1.5 stickiness bonus and cause churn.  The
    # capacity rail + in-slot price own balance instead.
    # A warm start reads the seed straight off the carry (which was built
    # with the same per-state scatters, in the same summation order, from
    # the same assignment) instead of re-scattering prev.
    if carry_used is not None:
        total = jnp.sum(carry_used, axis=0)
    else:
        total = jnp.sum(
            jnp.stack([_scatter_counts(prev[:, si, :], pweights, n)
                       for si in range(s)]), axis=0)
        total = _psum(total, axis_name)

    assign = jnp.full((p, s, r_max), -1, jnp.int32)
    # Sparse-engine escape hatch: rows whose shortlist could not serve
    # some slot (all-False on the dense engines, and on fully-pinned
    # slots — a pinned copy proved its tier through the pin pass).
    exhausted = jnp.zeros(p, jnp.bool_)
    # Nodes already holding this partition at an equal-or-higher priority
    # state in this pass (excludeHigherPriorityNodes, plan.go:146-156).
    # Kept as a LIST of [P] global-id columns, not a [P, N] bitmap: the
    # list stays kilobytes, membership tests become fusable compares (see
    # _member_ids), and global ids make every test node-shard invariant
    # with no psum gathers.
    taken_ids: list[jnp.ndarray] = []
    # Global column ids of this shard's node window (noff = 0 unsharded).
    cols_l = jnp.arange(n_l, dtype=jnp.int32) + noff

    top_anchor = prev[:, 0, 0]  # previous primary, until slot (0,0) assigns

    for si in range(s):
        k = constraints[si]
        if k <= 0:
            continue

        # All of this state's prev holders re-assign in this wave: remove
        # their seed contribution up front (the batch analog of the
        # per-partition decrement at plan.go:290-297).  Warm starts read
        # the per-state row off the carry (same psum-of-scatter, bitwise).
        if carry_used is not None:
            state_prev = carry_used[si]
        else:
            state_prev = _psum(_scatter_counts(prev[:, si, :], pweights, n),
                               axis_name)
        total = total - state_prev

        # Held this state before (fusable compares, no scatter).
        prev_state_ids = prev[:, si, :]  # [P, R]

        anchor = jnp.where(assign[:, 0, 0] >= 0, assign[:, 0, 0], top_anchor) \
            if si > 0 else top_anchor

        # Warm start, decided per STATE across all k ordinals: a previous
        # holder whose node survives, isn't taken by a higher-priority
        # state, and sits at the best attainable rule tier keeps its place
        # up to the node's state-level capacity — churn becomes structural,
        # not a price-dynamics accident (the batch analog of stickiness,
        # plan.go:654-662).  State-level, because ordinal packing within a
        # state is arbitrary (a node legitimately holds many slot-1 copies
        # if it holds few slot-0 copies); judging pins per slot would trim
        # balanced placements and break the replan fixpoint.
        kk = min(k, r_max)
        prev_k = prev[:, si, :kk]  # [P, kk]
        safe_k = jnp.clip(prev_k, 0, n - 1)
        taken_prev = jnp.stack(
            [_in_id_list(prev_k[:, j], taken_ids) for j in range(kk)],
            axis=1)
        # Booster-steered nodes: a holder stays only while the boost does
        # not exceed its stickiness (greedy: +max(-w, stick) - stick <= 0
        # keeps, > 0 pushes off, plan.go:675-684 + the cbgt booster).
        pin_ok_k = (prev_k >= 0) & valid[safe_k] & ~taken_prev & \
            (neg_boost[safe_k] <= stickiness[:, si][:, None])
        # An externally supplied prev map can repeat a node within one
        # state's row; only the first occurrence may pin, or both copies
        # would keep the same node — a duplicate the auction's exclusivity
        # mask can no longer prevent (the converged loop would then carry
        # it forever).  kk is small, so the pairwise check unrolls.
        for j in range(1, kk):
            dup = jnp.zeros(p, jnp.bool_)
            for i in range(j):
                dup |= (prev_k[:, j] == prev_k[:, i]) & (prev_k[:, j] >= 0)
            pin_ok_k = pin_ok_k.at[:, j].set(pin_ok_k[:, j] & ~dup)
        # Rule anchors for this state: column 0 is the primary anchor;
        # column 1+j is ordinal j's node once pinned/assigned.  Grown
        # ordinal-by-ordinal so every pick's penalty sees all prior picks
        # (reference plan.go:185-191) — this is what spreads replica pairs
        # across racks, not just replicas away from the primary.
        anchors = (jnp.full((p, 1 + k), -1, jnp.int32).at[:, 0].set(anchor)
                   if rules[si] else None)
        if rules[si]:
            # Pin eligibility, decided sequentially: a pin must sit at the
            # best attainable rule tier GIVEN the copies already kept
            # (primary + earlier ordinals' pin candidates) — the 1e4 tier
            # gap outweighs stickiness in the auction, and pinning must not
            # override that; nor may two surviving replicas stay co-racked.
            # Deliberately pre-capacity-trim: if the earlier pin is later
            # trimmed, a co-racked later ordinal loses its pin too — but the
            # anchors re-seed below drops the trimmed rack, and stickiness
            # steers the displaced copy back to its own node in the auction,
            # so the corner costs at most one extra converge pass, never a
            # rule violation.
            # Exclude groups nest inside include groups whenever the rule's
            # exclude level is strictly finer (the normal tree shape), and
            # then the attainable-tier floor reduces to group counting —
            # [P] gathers instead of a [P, N] penalty matrix + row-min.
            # Exotic rules (exc >= inc) keep the matrix path.
            counts_ok = all(exc < inc for (inc, exc) in rules[si])
            rows1 = jnp.arange(p)
            for j in range(kk):
                if counts_ok:
                    floor_j = _hier_floor_counts(
                        anchors[:, :1 + j], gids, gid_valid, valid,
                        rules[si])
                    hier_at_prev = _hier_tier_at(
                        anchors[:, :1 + j], safe_k[:, j], gids, gid_valid,
                        rules[si])
                else:
                    hier_j = _hier_penalty(
                        anchors[:, :1 + j], gids, gid_valid, rules[si],
                        gids_cand=gids_l)
                    floor_j = _row_min_global(
                        jnp.where(valid_l[None, :], hier_j, _INF), node_axis)
                    hier_at_prev = _gather_cols(
                        hier_j, rows1, safe_k[:, j], node_axis)
                ok_j = pin_ok_k[:, j] & (
                    hier_at_prev < floor_j + _RULE_TIER * 0.5)
                pin_ok_k = pin_ok_k.at[:, j].set(ok_j)
                anchors = anchors.at[:, 1 + j].set(
                    jnp.where(ok_j, prev_k[:, j], -1))
        state_cap = jnp.ceil(k * total_w * cap_share)
        pins_flat = _pin_prev_holders(
            prev_k.reshape(-1),
            pin_ok_k.reshape(-1),
            jnp.repeat(pweights, kk),
            state_cap,
            jnp.repeat(stickiness[:, si], kk),
            axis_name,
            load_div=w_div,
            taken_stack=(jnp.repeat(jnp.stack(taken_ids, axis=1), kk, axis=0)
                         if taken_ids else None),
        )
        pins = pins_flat.reshape(p, kk)
        # Same-partition exclusivity: later ordinals' pins must be visible
        # to earlier ordinals' auctions, or a displaced slot-0 copy could
        # land on the node slot-1 keeps pinned.  Each pin column is later
        # OVERWRITTEN by its ordinal's slot assignment (a superset: the
        # slot result keeps every pin), so the list stays one column per
        # slot instead of two.
        pin_base = len(taken_ids)
        for j in range(kk):
            taken_ids.append(jnp.where(pins[:, j], prev_k[:, j], -1))
        if rules[si]:
            # Re-seed anchors from the capacity-trimmed pins: a trimmed pin
            # must not keep excluding its rack from the auction, while a
            # surviving pin must exclude its rack from EVERY ordinal's
            # auction (including earlier ones — a displaced slot-0 copy may
            # not land in the rack slot-1 keeps pinned).
            anchors = jnp.full((p, 1 + k), -1, jnp.int32).at[:, 0].set(anchor)
            for j in range(kk):
                anchors = anchors.at[:, 1 + j].set(
                    jnp.where(pins[:, j], prev_k[:, j], -1))

        for ri in range(k):
            # This ordinal's share of the state-level pins; only displaced
            # or over-capacity copies enter the auction below.
            if ri < kk:
                init_assign = jnp.where(pins[:, ri], prev[:, si, ri], -1)
            else:
                init_assign = jnp.full(p, -1, jnp.int32)
            pin_used = jnp.zeros(n, jnp.float32).at[
                _drop_empty(init_assign, n)].add(
                jnp.where(init_assign >= 0, pweights, 0.0), mode="drop")

            all_pinned = jnp.all(init_assign >= 0)
            if axis_name:
                all_pinned = lax.psum(
                    (~all_pinned).astype(jnp.int32), axis_name) == 0

            def run_auction(_, *, ri=ri, anchors=anchors,
                            taken_ids=tuple(taken_ids)):
                """Score + auction + force for this slot — the expensive
                path, skipped entirely when every copy pinned (converged
                passes of solve_dense_converged land here for every slot,
                so the confirming pass never touches a [P, N] tensor).
                Two engines behind _assign_slot's callables: the default
                MATRIX path builds score[P, N_l] from fusable compares
                (scatter-free — the compares fuse into the elementwise
                build) and reduces it with the priced Pallas kernel; the
                FUSED path (ops/score_fused.py) computes the score
                in-kernel from the same id columns, so the matrix never
                exists and every round's HBM traffic is O(P + N)."""
                total_l = _node_slice(total, node_axis, n_l)
                w_div_l = _node_slice(w_div, node_axis, n_l)
                neg_boost_l = _node_slice(neg_boost, node_axis, n_l)
                stick_si = stickiness[:, si]
                prev_slot = prev[:, si, ri] if ri < r_max else \
                    jnp.full(p, -1, jnp.int32)
                pbase = lax.axis_index(axis_name) * p if axis_name else 0
                anchors_k = anchors if rules[si] else \
                    jnp.full((p, 1), -1, jnp.int32)

                if shortlist is not None:
                    # SPARSE engine: evaluate the matrix formula only at
                    # the [P, K] shortlist columns; fill/price/capacity
                    # stay full [S, N] width.  min2 reduces the gathered
                    # block (fused kernel on TPU); phase B's waterfall
                    # probes return +INF outside the row's shortlist, so
                    # stragglers never leak past their candidate set.
                    cand = shortlist
                    cand_c = jnp.clip(cand, 0, n - 1)
                    rows_p = jnp.arange(p)
                    score_pk = _sparse_score_cols(
                        cand, rows_p, pbase, total=total, total_p=total_p,
                        w_div=w_div, neg_boost=neg_boost, valid=valid,
                        gids=gids, gid_valid=gid_valid, stick_si=stick_si,
                        prev_slot=prev_slot, prev_state=prev_state_ids,
                        taken_ids=taken_ids, anchors=anchors_k,
                        rules=rules[si], jitter_scale=float(_JITTER))

                    def min2_fn(price_vec, *, score_pk=score_pk,
                                cand=cand, cand_c=cand_c):
                        price_pk = price_vec[cand_c]
                        if sparse_impl == "xla":
                            b, kidx, s2, raw = sparse_min2_reference(
                                score_pk, price_pk)
                        else:
                            b, kidx, s2, raw = sparse_priced_min2(
                                score_pk, price_pk,
                                interpret=(sparse_impl == "interpret"))
                        choice = jnp.maximum(jnp.take_along_axis(
                            cand, kidx[:, None], axis=1)[:, 0], 0)
                        return b, choice, s2, raw

                    def score_at_fn(rows, cols_global, *, cand=cand):
                        vals = _sparse_score_cols(
                            cols_global[:, None], rows, pbase,
                            total=total, total_p=total_p, w_div=w_div,
                            neg_boost=neg_boost, valid=valid, gids=gids,
                            gid_valid=gid_valid, stick_si=stick_si,
                            prev_slot=prev_slot,
                            prev_state=prev_state_ids,
                            taken_ids=taken_ids, anchors=anchors_k,
                            rules=rules[si],
                            jitter_scale=float(_JITTER))[:, 0]
                        in_sl = jnp.any(
                            cand[rows] == cols_global[:, None], axis=1)
                        return jnp.where(in_sl, vals, _INF)
                elif fused_score != "off":
                    si_pack = pack_score_inputs(
                        total_l=total_l, total_p=total_p, w_div_l=w_div_l,
                        neg_boost_l=neg_boost_l, valid_l=valid_l,
                        stickiness_si=stick_si, prev_slot=prev_slot,
                        prev_state=prev_state_ids,
                        taken_ids=list(taken_ids), anchors=anchors_k,
                        gids_l=gids_l, gid_valid=gid_valid, gids=gids,
                        rules=rules[si])

                    vma = tuple(a for a in (axis_name, node_axis) if a)

                    def min2_fn(price_vec):
                        price_l = _node_slice(price_vec, node_axis, n_l)
                        b, cl, s2, raw = fused_score_min2(
                            price_l, si_pack, pbase, noff,
                            nrules=len(rules[si]),
                            jitter_scale=float(_JITTER),
                            interpret=(fused_score == "interpret"),
                            vma=vma)
                        return _combine_min2(
                            b, cl + noff, s2, raw, node_axis)

                    base_full = (0.001 * total
                                 / jnp.maximum(total_p, 1.0)) / w_div

                    def score_at_fn(rows, cols_global):
                        return score_at_columns(
                            rows, cols_global, base_full=base_full,
                            neg_boost_full=neg_boost, valid_full=valid,
                            gids=gids, gid_valid=gid_valid,
                            anchors=anchors_k, rules=rules[si],
                            prev_slot=prev_slot,
                            prev_state=prev_state_ids,
                            taken_ids=taken_ids, stick=stick_si,
                            jitter_scale=float(_JITTER), pbase=pbase)
                else:
                    balance = 0.001 * total_l[None, :] / \
                        jnp.maximum(total_p, 1.0)
                    score = balance / w_div_l[None, :]
                    # Same-ordinal alignment: slot ri mildly prefers prev
                    # slot ri's node (above jitter, below every real
                    # term), so sticky bids don't scramble ordinals and
                    # leftovers stay spread.
                    score = score - 0.01 * _member_ids(
                        prev_slot[:, None], cols_l)
                    score = score + jnp.maximum(
                        neg_boost_l[None, :],
                        jnp.where(neg_boost_l[None, :] > 0,
                                  stick_si[:, None], 0.0))
                    score = score - stick_si[:, None] * _member_ids(
                        prev_state_ids, cols_l)
                    # Per-slot rule penalty: anchored on the primary,
                    # every pinned ordinal, and every slot already
                    # assigned this state — so consecutive replicas
                    # spread across exclusion groups.
                    if rules[si]:
                        score = score + _hier_penalty(
                            anchors, gids, gid_valid, rules[si],
                            gids_cand=gids_l)
                    taken = _member_ids(
                        jnp.stack(taken_ids, axis=1), cols_l) if taken_ids \
                        else jnp.zeros((p, n_l), jnp.bool_)
                    score = score + _INF * (taken | ~valid_l[None, :])
                    # Deterministic tie-break jitter (Weyl hash of GLOBAL
                    # (partition, node) — shard-local indices would make
                    # every shard bid on the same jitter-preferred
                    # columns in lockstep, and break node-shard-count
                    # invariance).
                    pi = (pbase + jnp.arange(p))[:, None].astype(jnp.int32)
                    ni = cols_l[None, :].astype(jnp.int32)
                    score = score + jitter_scale * jitter_hash(pi, ni)

                    def min2_fn(price_vec):
                        price_l = _node_slice(price_vec, node_axis, n_l)
                        if pallas_available():
                            b_l, c_l, s_l = priced_min2_argmin(
                                score, price_l)
                        else:
                            b_l, c_l, s_l = min2_argmin_reference(
                                score + price_l[None, :])
                        raw_l = jnp.take_along_axis(
                            score, c_l[:, None], axis=1)[:, 0]
                        return _combine_min2(
                            b_l, c_l + noff, s_l, raw_l, node_axis)

                    def score_at_fn(rows, cols_global):
                        return _gather_cols(
                            score, rows, cols_global, node_axis)

                if rules[si]:
                    feasible_hint = None
                else:
                    # Rule-less hard feasibility without a [P, N] row-min:
                    # the taken ids are distinct per partition (exclusivity
                    # invariant), so an allowed node exists iff the count
                    # of taken VALID nodes is below the valid-node total.
                    n_valid_total = jnp.sum(valid.astype(jnp.int32))
                    tkn = jnp.zeros(p, jnp.int32)
                    for tid in taken_ids:
                        tkn += ((tid >= 0)
                                & valid[jnp.clip(tid, 0, n - 1)]
                                ).astype(jnp.int32)
                    feasible_hint = tkn < n_valid_total

                allow = None
                exh_slot = jnp.zeros(p, jnp.bool_)
                if shortlist is not None:
                    # Shortlist adequacy, judged against GLOBAL state: a
                    # row may take this slot only when its shortlist
                    # best reaches the globally attainable rule tier
                    # (group-counting floor, taken-aware — [P] ops, no
                    # [P, N] row-min) or, rule-less, offers any feasible
                    # candidate while one exists anywhere.  Inadequate
                    # rows sit out the whole slot (no bid, no force) and
                    # are flagged for the per-row dense fallback; at a
                    # saturating K the shortlist best IS the global
                    # best, so the gate passes exactly when the dense
                    # engines would have placed the row.
                    raw_best_sl = jnp.min(score_pk, axis=1)
                    if rules[si]:
                        floor_sl = _hier_floor_counts(
                            anchors, gids, gid_valid, valid, rules[si],
                            taken_stack=(jnp.stack(taken_ids, axis=1)
                                         if taken_ids else None))
                        allow = raw_best_sl < floor_sl + _RULE_TIER * 0.5
                    else:
                        sl_feas = raw_best_sl < _INF / 2
                        allow = sl_feas | ~feasible_hint
                        # Top-up must weigh shortlist-feasible rows, not
                        # globally-feasible ones the gate excluded.
                        feasible_hint = sl_feas
                    exh_slot = (init_assign < 0) & ~allow

                # Exact ceil capacity: the binding rail that yields tight
                # balance; exclusivity stragglers rebid under the in-slot
                # price and, in the worst case, the force step places them.
                cap = _shard_capacity(
                    jnp.ceil(total_w * cap_share), axis_name)
                slot_assign, used = _assign_slot(
                    min2_fn, score_at_fn, p, pweights, cap, 1.0 / w_div,
                    axis_name, init_assign=init_assign, init_used=pin_used,
                    node_axis=node_axis, topup_share=cap_share,
                    has_rules=bool(rules[si]), feasible_hint=feasible_hint,
                    allow=allow)
                return slot_assign, used, exh_slot

            def keep_pins(_):
                return init_assign, pin_used, jnp.zeros(p, jnp.bool_)

            def varying(branch):
                # Both branches' outputs must carry the same varying-axes
                # types; keep_pins' fresh zeros are partition-invariant.
                return lambda op: tuple(
                    _ensure_varying(x, axis_name) for x in branch(op))

            # NB: no collectives run inside either branch (_assign_slot is
            # shard-local by design), so a cond on the globally-agreed
            # all_pinned flag is safe under shard_map.
            slot_assign, used, exh_slot = lax.cond(
                all_pinned, varying(keep_pins), varying(run_auction), None)
            exhausted = exhausted | exh_slot
            used = _psum(used, axis_name)  # global per-node accepted weight

            assign = assign.at[:, si, ri].set(slot_assign)
            total = total + used
            if ri < kk:
                taken_ids[pin_base + ri] = slot_assign  # supersedes the pin
            else:
                taken_ids.append(slot_assign)
            if rules[si]:
                anchors = anchors.at[:, 1 + ri].set(slot_assign)

    return assign, exhausted


_SOLVE_STATICS = ("constraints", "rules", "axis_name", "node_axis",
                  "node_shards", "fused_score")


@partial(jax.jit, static_argnames=_SOLVE_STATICS)
def solve_dense(
    prev: jnp.ndarray,
    pweights: jnp.ndarray,
    nweights: jnp.ndarray,
    valid: jnp.ndarray,
    stickiness: jnp.ndarray,
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    constraints: Constraints,
    rules: Rules,
    axis_name: Optional[str] = None,
    node_axis: Optional[str] = None,
    node_shards: int = 1,
    fused_score: str = "off",
    carry_used: Optional[jnp.ndarray] = None,
    p_real: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Solve the whole placement problem on device; returns assign[P, S, R].

    The jitted dense spelling of :func:`_solve_assign` (see its
    docstring for the full parameter/sharding contract); the sparse
    engine enters through :func:`solve_sparse` instead."""
    return _solve_assign(
        prev, pweights, nweights, valid, stickiness, gids, gid_valid,
        constraints, rules, axis_name, node_axis, node_shards,
        fused_score, carry_used=carry_used, p_real=p_real)[0]


@partial(jax.jit, static_argnames=("constraints", "rules", "axis_name",
                                   "max_iterations", "node_axis",
                                   "node_shards", "fused_score",
                                   "trace_sweeps"))
def _solve_dense_converged_impl(
    prev: jnp.ndarray,
    pweights: jnp.ndarray,
    nweights: jnp.ndarray,
    valid: jnp.ndarray,
    stickiness: jnp.ndarray,
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    constraints: Constraints,
    rules: Rules,
    axis_name: Optional[str] = None,
    max_iterations: int = 10,
    node_axis: Optional[str] = None,
    node_shards: int = 1,
    fused_score: str = "off",
    carry_used: Optional[jnp.ndarray] = None,
    p_real: Optional[jnp.ndarray] = None,
    trace_sweeps: bool = False,
) -> tuple[jnp.ndarray, ...]:
    """Jitted fixpoint body; returns (assign, sweeps-executed).

    ``carry_used`` seeds the FIRST sweep only — like cluster deltas
    (plan.go:49-55), the carry describes the state the loop starts from;
    later sweeps re-derive their seed from their own input.

    ``trace_sweeps`` (static) additionally accumulates each sweep's
    accepted-bid fraction — the share of REAL partitions whose
    assignment that sweep changed — in-graph, returning
    (assign, sweeps, fracs[max_iterations]) so the device observatory
    (obs/device.py) can export a convergence track without per-sweep
    host round-trips.  Off (the default) the trace and outputs are
    byte-identical to before the flag existed."""
    def solve(x, cu=None):
        return solve_dense(x, pweights, nweights, valid, stickiness,
                           gids, gid_valid, constraints, rules, axis_name,
                           node_axis, node_shards, fused_score,
                           carry_used=cu, p_real=p_real)

    first = solve(prev, carry_used)

    if not trace_sweeps:
        def cond(carry):
            out, prev_i, it = carry
            changed = jnp.any(out != prev_i)
            if axis_name:
                changed = lax.psum(changed.astype(jnp.int32), axis_name) > 0
            return changed & (it < max_iterations)

        def body(carry):
            out, _prev, it = carry
            return solve(out), out, it + 1

        out, _, it = lax.while_loop(cond, body, (first, prev, jnp.array(1)))
        return out, it

    # Traced variant: same fixpoint, plus a [max_iterations] accumulator
    # of per-sweep changed-row fractions.  The denominator is the REAL
    # partition count (p_real under bucketing — pad rows are inert and
    # never change), psum'd across partition shards like every other
    # global count.
    if p_real is not None:
        denom = jnp.maximum(jnp.asarray(p_real, jnp.float32), 1.0)
    else:
        denom = jnp.maximum(
            _psum(jnp.array(prev.shape[0], jnp.float32), axis_name), 1.0)

    def frac(a, b):
        changed = jnp.any(a != b, axis=(1, 2))
        total = jnp.sum(changed.astype(jnp.float32))
        return _psum(total, axis_name) / denom

    fracs0 = jnp.zeros(max_iterations, jnp.float32) \
        .at[0].set(frac(first, prev))

    def cond_t(carry):
        out, prev_i, it, _f = carry
        changed = jnp.any(out != prev_i)
        if axis_name:
            changed = lax.psum(changed.astype(jnp.int32), axis_name) > 0
        return changed & (it < max_iterations)

    def body_t(carry):
        out, _prev, it, f = carry
        new = solve(out)
        return new, out, it + 1, f.at[it].set(frac(new, out))

    out, _, it, fracs = lax.while_loop(
        cond_t, body_t, (first, prev, jnp.array(1), fracs0))
    return out, it, fracs


def _record_sweeps(sweeps: object) -> None:
    """Publish a converged solve's pass count to the obs Recorder.

    Silently skipped when ``sweeps`` is a tracer (solve_dense_converged
    runs under shard_map / an outer jit: there is no concrete value at
    trace time, and a host callback would be the wrong cost to pay)."""
    if isinstance(sweeps, jax.core.Tracer):
        return
    try:
        n = int(sweeps)
    except (TypeError, ValueError):
        # A non-scalar/non-numeric sweeps value (an exotic tracer the
        # isinstance above missed, an aborted transfer) — recording is
        # best-effort, correctness errors propagate elsewhere.
        return
    rec = get_recorder()
    rec.count("plan.solve.calls")
    rec.count("plan.solve.sweeps", n)
    rec.observe("plan.solve.sweeps", n)
    rec.set_attr("sweeps", n)


def _tier_band_bounds(prevs, pweights, nweights, valid, stickiness,
                      constraints) -> np.ndarray:
    """bound[B]: the within-tier score mass estimate of
    _check_tier_band_scale for each of B problems of one batch class.

    Each argument is a length-B sequence of one problem's host arrays,
    every problem with P > 0, N > 0, S > 0 and the same S, R and
    ``constraints``; P and N may differ.  The problems lie end to end,
    not padded, so no pad row or node enters any term: node ids are
    offset by each problem's first node, one bincount fills every
    problem's nodes, and the maxima reduce over each problem's own
    segment.  The two sums run on each problem's own contiguous slice,
    so every bound equals, bit for bit in float64, the arithmetic on
    that problem's arrays alone.  The bound is the larger of the
    capacity-rail fill and the prev map's seeded fill per node weight,
    plus max stickiness, plus the largest negative-weight boost."""
    p = [np.shape(x)[0] for x in prevs]
    n = [np.shape(w)[0] for w in nweights]
    p_at = [0, *itertools.accumulate(p)]  # each problem's first row, ...
    n_at = [0, *itertools.accumulate(n)]  # ... first node; then the totals
    pw = np.concatenate(pweights, dtype=np.float64)
    nw = np.concatenate(nweights, dtype=np.float64)
    ids = np.concatenate([np.ravel(x) for x in prevs], dtype=np.int64)
    width = ids.size // p_at[-1]  # S * R slots per partition
    per_problem = [k * width for k in p]
    # Skewed seed: each prev map's weighted fill per node.  Ids outside
    # [0, N) (negative ones wrap high as unsigned) count toward no node:
    # they land in the trash bin past the last one.
    bins = ids + np.repeat(n_at[:-1], per_problem)
    bins[ids.view(np.uint64) >= np.repeat(n, per_problem)] = n_at[-1]
    fill = np.bincount(bins, weights=np.repeat(pw, width),
                       minlength=n_at[-1] + 1)[:-1]
    w_div = np.where(nw > 0, nw, 1.0)
    seed_term = np.maximum.reduceat(fill / w_div, n_at[:-1])
    # Balanced ceiling: every constrained slot's capacity rail lands
    # ~K * total_w * share on a node; dividing by the node's weight
    # cancels the share for uniform shares.
    cap_w = np.where(np.concatenate(valid, dtype=bool) & (nw >= 0),
                     np.maximum(nw, 1.0), 0.0)
    total_w = np.array([pw[a:b].sum() for a, b in zip(p_at, p_at[1:])])
    cap_total = np.array([cap_w[a:b].sum() for a, b in zip(n_at, n_at[1:])])
    k_total = float(sum(max(int(c), 0) for c in constraints))
    rail_term = k_total * total_w / np.maximum(cap_total, 1.0)
    bound = np.where(seed_term > rail_term, seed_term, rail_term)
    # Stickiness is [P, S] per problem: its rows start where prev's do.
    stick = np.concatenate([np.ravel(s) for s in stickiness],
                           dtype=np.float64)
    s_at = [a * (stick.size // p_at[-1]) for a in p_at[:-1]]
    bound = bound + np.maximum.reduceat(stick, s_at)
    return bound + np.maximum.reduceat(np.maximum(-nw, 0.0), n_at[:-1])


def _tier_band_error(bound: float, p: int, nweights, valid,
                     constraints) -> Optional[ValueError]:
    """The tier-band overflow error for one problem whose bound (from
    _tier_band_bounds) eats into the band, else None."""
    if bound < _TIER_BAND_HEADROOM * _RULE_TIER:
        return None
    nw = np.asarray(nweights, np.float64)
    usable = int(np.count_nonzero(np.asarray(valid, bool) & (nw >= 0)))
    k_total = float(sum(max(int(c), 0) for c in constraints))
    return ValueError(
        f"hierarchy tier band overflow: within-tier score mass "
        f"~{bound:.0f} >= {_TIER_BAND_HEADROOM:.2f} * _RULE_TIER "
        f"({_RULE_TIER:.0f}) — at this partitions-per-node scale "
        f"(P={p}, usable N={usable}, "
        f"slots={k_total:.0f}) the band test that separates hierarchy "
        f"tiers would misclassify rule conformance.  Add nodes, split "
        f"the problem, or raise _RULE_TIER in concert with "
        f"_RULE_MISS/_INF (blance_tpu/plan/tensor.py)")


def _check_tier_band_scale(prev, pweights, nweights, valid, stickiness,
                           constraints, rules) -> None:
    """Assert the tier-equality band's scale assumption (see _RULE_TIER).

    Estimates the largest within-tier score mass a node can carry —
    the per-node fill term at its capacity rail AND at the prev map's
    seeded skew, plus max stickiness and max negative-weight boost
    (_tier_band_bounds, here on a batch of one) — and raises ValueError
    when it eats into the _RULE_TIER/2 band (headroom
    _TIER_BAND_HEADROOM).  Rule-less problems never consult the band
    and are exempt.  Host-side only: silently skipped under a
    jit/shard_map trace (the host entry already checked concrete
    values).  Cost: one vectorized bincount over prev (a few ms at
    the 100k-partition north star), memoized per (prev identity,
    weight/stickiness fingerprint) so the steady-state warm-replan loop
    — which passes the SAME adopted ``current`` array replan after
    replan — pays it once, not per solve."""
    if not any(rl for rl in rules):
        return
    from jax import core as _jax_core

    args = (prev, pweights, nweights, valid, stickiness)
    if any(isinstance(a, _jax_core.Tracer) for a in args):
        return
    prev_in = prev
    prev = np.asarray(prev)
    pw = np.asarray(pweights, np.float64)
    nw = np.asarray(nweights, np.float64)
    valid = np.asarray(valid, bool)
    stick = np.asarray(stickiness, np.float64)
    n = nw.shape[0]
    if prev.size == 0 or n == 0:
        return
    # Memo key: array identity + cheap O(P+N) fingerprint.  The
    # fingerprint (not identity alone) guards against id() reuse after
    # gc and against in-place weight edits; a stale hit can only skip a
    # re-check of an already-validated shape, never corrupt a solve.
    key = (id(prev_in), prev.shape, n, tuple(constraints),
           tuple(tuple(r) for r in rules))
    fingerprint = (float(pw.sum()), float(stick.max()) if stick.size else 0.0,
                   float(nw.min()), float(nw.max()), int(valid.sum()))
    if _tier_scale_memo.get(key) == fingerprint:
        return
    bound = float(_tier_band_bounds([prev], [pw], [nw], [valid], [stick],
                                    constraints)[0])
    err = _tier_band_error(bound, prev.shape[0], nw, valid, constraints)
    if err is not None:
        raise err
    if len(_tier_scale_memo) >= 256:  # bound a long-lived process's memo
        _tier_scale_memo.clear()
    _tier_scale_memo[key] = fingerprint


def solve_dense_converged(
    prev: jnp.ndarray,
    pweights: jnp.ndarray,
    nweights: jnp.ndarray,
    valid: jnp.ndarray,
    stickiness: jnp.ndarray,
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    constraints: Constraints,
    rules: Rules,
    axis_name: Optional[str] = None,
    max_iterations: int = 10,
    node_axis: Optional[str] = None,
    node_shards: int = 1,
    fused_score: str = "off",
    record: bool = True,
    carry_used: Optional[jnp.ndarray] = None,
    return_carry: bool = False,
    p_real: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """solve_dense iterated to a fixpoint (reference plan.go:23-58).

    The reference replans on its own output until stable (≤ 10 passes,
    "usually 1 or 2"): the first pass does the work, later passes converge
    because the warm-start pins hold everything the capacity rail accepts.
    A converged pass short-circuits the auction (every copy pins), so the
    confirming iteration costs a fraction of the first.  Like the
    reference, cluster deltas apply only to the first pass — subsequent
    passes re-balance on the stable node set (plan.go:49-55; removed nodes
    hold nothing after pass 1, so a constant valid mask is equivalent).

    The executed pass count surfaces as the ``plan.solve.sweeps``
    counter/histogram on the obs Recorder (the loop itself is fused into
    one device program, so per-sweep host spans cannot exist).  Reading it
    costs one scalar device-to-host sync; ``record=False`` skips that —
    for micro-timed loops where an extra host round-trip would perturb
    the measurement (under jit/shard_map tracing it is skipped anyway).

    ``carry_used`` (SolveCarry.used matching ``prev``) seeds the first
    sweep's fill totals bit-identically instead of re-scattering them;
    ``return_carry`` additionally packages the converged output as a
    :class:`SolveCarry` for the next delta replan — returns
    (assign, carry) instead of assign.  (Not usable under an outer
    jit/shard_map trace; the sharded entry point builds its carry
    host-side instead.)
    """
    _check_tier_band_scale(prev, pweights, nweights, valid, stickiness,
                           constraints, rules)
    # An enclosing dispatch site's entry scope (the bucketed plan path)
    # owns BOTH instruments — compile attribution is first-wins anyway,
    # and the cost gauges must agree with it, or the documented
    # device.flops{entry="solve_dense.bucketed"} series would never
    # exist while "cold" silently absorbed bucketed-shape classes.
    ent = _device.ambient_entry() or (
        "solve_dense.carry" if carry_used is not None
        else "solve_dense.cold")
    # Device observatory (obs/device.py), all opt-in: the sweep trace
    # compiles a sibling program with the convergence accumulator, and
    # cost analysis AOT-compiles the dispatched program once per
    # (entry, shape).  Both are host-side only — under an outer
    # jit/shard_map trace the args are tracers and everything below is
    # skipped, so the sharded dispatch keeps owning its own scope.
    concrete = not isinstance(prev, jax.core.Tracer)
    want_trace = (record and concrete and
                  _device.sweep_trace_enabled())
    if concrete:
        # Lower the ACTUAL dispatched unit — the converged fixpoint
        # program, not one solve_dense sweep — so the gauge's unit
        # ("FLOPs per dispatch") is consistent with the fleet/warm
        # entries, which also publish their real dispatched programs.
        _device.maybe_publish_cost(
            ent, f"{prev.shape[0]}x{nweights.shape[0]}",
            _solve_dense_converged_impl,
            prev, pweights, nweights, valid, stickiness, gids, gid_valid,
            constraints, rules, axis_name, max_iterations, node_axis,
            node_shards, fused_score, carry_used, p_real)
    rec = get_recorder()
    t0 = rec.now()
    with _device.entry(ent):
        res = _solve_dense_converged_impl(
            prev, pweights, nweights, valid, stickiness, gids, gid_valid,
            constraints, rules, axis_name, max_iterations, node_axis,
            node_shards, fused_score, carry_used, p_real,
            trace_sweeps=want_trace)
    out, sweeps = res[0], res[1]
    if record:
        _record_sweeps(sweeps)
    if want_trace:
        _device.record_sweep_trace(rec, t0, rec.now(), int(sweeps),
                                   np.asarray(res[2]))
    if return_carry:
        return out, carry_from_assignment(out, pweights, nweights)
    return out


def _warm_repair(
    prev: jnp.ndarray,
    pweights: jnp.ndarray,
    nweights: jnp.ndarray,
    valid: jnp.ndarray,
    stickiness: jnp.ndarray,
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    dirty: jnp.ndarray,  # [P] bool — partitions the delta may move
    carry_used: jnp.ndarray,  # [S, N] SolveCarry.used matching prev
    constraints: Constraints,
    rules: Rules,
    axis_name: Optional[str] = None,
    node_axis: Optional[str] = None,
    node_shards: int = 1,
    fused_score: str = "off",
    p_real: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """ONE carry-seeded repair sweep + in-graph acceptance flags.

    The repair sweep is ``solve_dense`` itself (same trace, totals seeded
    bit-identically from the carry), so its output equals a cold solve's
    first sweep exactly; only re-bidding partitions — dirty rows, plus
    anything the pin pass displaces — do any auction work, while
    untouched rows keep their pinned placement.  What a warm replan
    SKIPS is the fixpoint loop's confirming sweep(s), and that skip is
    only sound when the repair provably stayed inside the delta.  Two
    device-side checks decide, without host round-trips per condition:

    - ripple: any row OUTSIDE the dirty mask changed — the delta leaked
      (capacity trim displaced clean holders, a tier floor shifted); a
      second sweep could move more, so the caller must cold-solve.
    - fresh over-capacity: a node's new fill exceeds its state rail by
      more than the quantization allowance AND exceeds its previous
      fill — a sign the repair force-packed displaced copies where a
      confirming sweep would re-balance them.  The allowance is one
      max-weight partition per shard: the auction's first-bidder
      progress rule legitimately overshoots the ceil'd rail by up to
      that much, and such fixpoints replan unchanged (the overshoot
      sits inside the pin pass's lmin+stickiness band — see
      _pin_prev_holders), so flagging them would demote every
      steady-state sharded replan to cold.  Rails the PREVIOUS solution
      already exceeded (rule-constrained overflow the top-up
      deliberately grants) don't trip this either.

    Returns (assign, new_used[S, N], ok) where ``ok`` (scalar bool,
    globally agreed under shard_map) means "accept this as converged".
    """
    p, s, _ = prev.shape
    n = nweights.shape[0]
    out = solve_dense(prev, pweights, nweights, valid, stickiness, gids,
                      gid_valid, constraints, rules, axis_name, node_axis,
                      node_shards, fused_score, carry_used=carry_used,
                      p_real=p_real)
    new_used = _used_by_state(out, pweights, n, s, axis_name)
    ok = _repair_ok(prev, out, new_used, carry_used, dirty, pweights,
                    nweights, valid, constraints, axis_name)
    return out, new_used, ok


def _repair_ok(prev, out, new_used, carry_used, dirty, pweights, nweights,
               valid, constraints, axis_name):
    """The warm repair's acceptance gates (ripple + fresh over-capacity;
    see :func:`_warm_repair`'s docstring) — extracted so the sparse
    repair judges itself with the identical device-side checks."""
    p = prev.shape[0]
    rippled = jnp.any((out != prev) & ~dirty[:, None, None])
    if axis_name:
        rippled = lax.psum(rippled.astype(jnp.int32), axis_name) > 0

    total_w = _psum(jnp.sum(pweights), axis_name)
    cap_w = jnp.where(valid & (nweights >= 0), jnp.maximum(nweights, 1.0),
                      0.0)
    cap_share = cap_w / jnp.maximum(jnp.sum(cap_w), 1.0)
    ns = lax.axis_size(axis_name) if axis_name else 1
    max_w = jnp.max(pweights) if p else jnp.float32(0.0)
    if axis_name:
        max_w = lax.pmax(max_w, axis_name)
    allowance = ns * max_w  # first-bidder quantization, one per shard
    overcap = jnp.array(False)
    for si, k in enumerate(constraints):
        if k <= 0:
            continue
        rail = jnp.ceil(k * total_w * cap_share)
        overcap |= jnp.any((new_used[si] > rail + allowance)
                           & (new_used[si] > carry_used[si]))
    return ~rippled & ~overcap


_WARM_STATICS = ("constraints", "rules", "axis_name", "node_axis",
                 "node_shards", "fused_score")
_warm_repair_jit = partial(jax.jit, static_argnames=_WARM_STATICS)(
    _warm_repair)
# Donating prev + carry_used lets XLA alias them into the outputs (same
# shapes/dtypes), so a steady-state warm replan reuses the previous
# carry's buffers instead of allocating: the carry is single-use by
# contract (sessions drop theirs after every attempt).  CPU buffers are
# not donatable (dispatch would warn every call), so the plain jit backs
# host runs and tests.
_warm_repair_donating = jax.jit(
    _warm_repair, static_argnames=_WARM_STATICS,
    donate_argnames=("prev", "carry_used"))


def solve_dense_warm(
    prev, pweights, nweights, valid, stickiness, gids, gid_valid,
    constraints, rules, *, dirty, carry: SolveCarry,
    fused_score: str = "off", record: bool = True,
    donate: Optional[bool] = None, p_real=None,
) -> tuple[Optional[NPArray], Optional[SolveCarry]]:
    """Warm delta replan: repair sweep from the carry, or decline.

    Returns (assign, next_carry) when the repair is accepted as
    converged — one sweep instead of the cold fixpoint's two-plus — or
    (None, None) when the delta leaked outside the dirty mask and the
    caller must run the cold path (:func:`solve_converged_resilient`).
    The carry is CONSUMED either way (its device buffers may be donated
    into the repair); callers must replace it with ``next_carry`` or the
    cold solve's rebuilt carry, never reuse it.

    obs: records ``plan.solve.dirty_fraction`` (histogram), a
    ``plan.solve.warm_fallback`` counter on decline, the executed sweep
    in ``plan.solve.sweeps``, and a ``warm`` span attribute on
    acceptance.  ``plan.solve.carry_hit`` is deliberately NOT counted
    here: the caller may still reject the accepted repair (the
    session's audit gate), and a hit must mean the replan really did
    cost one sweep end-to-end — callers count it once their own gates
    pass.
    """
    rec = get_recorder()
    _check_tier_band_scale(prev, pweights, nweights, valid, stickiness,
                           constraints, rules)
    check_dense_memory(np.asarray(prev).shape[0], np.asarray(prev).shape[1],
                       np.asarray(nweights).shape[-1], fused_score)
    dirty_np = np.asarray(dirty)
    if record:
        rec.observe("plan.solve.dirty_fraction",
                    float(dirty_np.mean()) if dirty_np.size else 0.0)
    if donate is None:
        donate = jax.default_backend() != "cpu"
    impl = _warm_repair_donating if donate else _warm_repair_jit
    dev_args = (
        jnp.asarray(prev), jnp.asarray(pweights), jnp.asarray(nweights),
        jnp.asarray(valid), jnp.asarray(stickiness), jnp.asarray(gids),
        jnp.asarray(gid_valid), jnp.asarray(dirty_np),
        jnp.asarray(carry.used))
    # Cost gauges BEFORE the dispatch: with donation on, the live call
    # consumes its operand buffers and a later lower() could not touch
    # them.  Memoized per (entry, shape) — steady state pays nothing.
    _device.maybe_publish_cost(
        "solve_dense.warm",
        f"{dev_args[0].shape[0]}x{dev_args[2].shape[0]}", _warm_repair_jit,
        *dev_args, constraints=constraints, rules=rules,
        fused_score=fused_score, p_real=p_real)
    with rec.span("plan.solve.attempt", warm=True,
                  engine={"off": "matrix", "on": "fused",
                          "interpret": "fused-interpret"}[fused_score]), \
            _device.entry("solve_dense.warm"):
        out, new_used, ok = impl(
            *dev_args, constraints=constraints, rules=rules,
            fused_score=fused_score, p_real=p_real)
        accepted = bool(ok)
    if not accepted:
        if record:
            rec.count("plan.solve.warm_fallback")
            rec.count("plan.solve.sweeps", 1)  # the executed repair pass
        return None, None
    if record:
        _record_sweeps(1)
        rec.set_attr("warm", True)
    return np.asarray(out), SolveCarry(
        prices=jnp.sum(new_used, axis=0), assign=out, used=new_used)


# --- sparse shortlist solve --------------------------------------------------
#
# ROADMAP item 2: the dense score sweep is f32 [P, N] per slot — 1M
# partitions x 10k nodes is a ~40 GB intermediate no fusing fixes.  The
# sparse engine scores only a [P, K] candidate shortlist (K << N,
# derived statically in core/shortlist.py from stickiness + hierarchy
# groups + weights) while the fill/price/capacity tables stay full
# [S, N] width, so acceptance, tie-breaks and the audit contracts are
# evaluated against real global state.  Rows whose shortlist cannot
# reach the globally attainable rule tier (or has no feasible candidate)
# are flagged in-graph and re-placed by a per-row dense fallback on the
# host — the observable escape hatch (plan.sparse.* counters) that makes
# audit contracts hold for ANY shortlist.  A saturating K = N shortlist
# is bit-identical to the dense matrix engine, cold and warm.


def sparse_rules_supported(rules: Rules) -> bool:
    """True when the sparse engine can solve these rules (every
    exclude level strictly finer than its include level — the nesting
    tree shape the group-counting attainability floor requires)."""
    from ..core.shortlist import shortlist_rules_nest

    return shortlist_rules_nest(rules)


def resolve_sparse_impl(impl: Optional[str]) -> str:
    """None -> the fused ops/sparse2.py kernel on TPU, the XLA
    reference elsewhere; explicit modes pass through validated."""
    if impl is None:
        return "pallas" if pallas_available() else "xla"
    if impl not in ("xla", "pallas", "interpret"):
        raise ValueError(f"unknown sparse_impl: {impl!r}")
    return impl


_SPARSE_STATICS = ("constraints", "rules", "axis_name", "max_iterations",
                   "sparse_impl")


@partial(jax.jit, static_argnames=_SPARSE_STATICS)
def _solve_sparse_converged_impl(
    prev: jnp.ndarray,
    pweights: jnp.ndarray,
    nweights: jnp.ndarray,
    valid: jnp.ndarray,
    stickiness: jnp.ndarray,
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    shortlist: jnp.ndarray,  # [P, K] ascending candidate ids, -1 pads
    constraints: Constraints,
    rules: Rules,
    axis_name: Optional[str] = None,
    max_iterations: int = 10,
    sparse_impl: str = "xla",
    carry_used: Optional[jnp.ndarray] = None,
    p_real: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, ...]:
    """Jitted sparse fixpoint; returns (assign, sweeps, exhausted[P]).

    The same converged loop as ``_solve_dense_converged_impl`` (carry
    seeds the FIRST sweep only), over the shortlist engine.  The
    exhaustion flags are the LAST executed sweep's — rows still
    unservable at the fixpoint, which the host fallback re-places."""
    def solve(x, cu=None):
        return _solve_assign(
            x, pweights, nweights, valid, stickiness, gids, gid_valid,
            constraints, rules, axis_name, None, 1, "off",
            carry_used=cu, p_real=p_real, shortlist=shortlist,
            sparse_impl=sparse_impl)

    first, exh0 = solve(prev, carry_used)

    def cond(carry):
        out, prev_i, it, _exh = carry
        changed = jnp.any(out != prev_i)
        if axis_name:
            changed = lax.psum(changed.astype(jnp.int32), axis_name) > 0
        return changed & (it < max_iterations)

    def body(carry):
        out, _prev, it, _exh = carry
        new, exh = solve(out)
        return new, out, it + 1, exh

    out, _, it, exh = lax.while_loop(
        cond, body, (first, prev, jnp.array(1), exh0))
    return out, it, exh


def _warm_repair_sparse(
    prev: jnp.ndarray,
    pweights: jnp.ndarray,
    nweights: jnp.ndarray,
    valid: jnp.ndarray,
    stickiness: jnp.ndarray,
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    shortlist: jnp.ndarray,
    dirty: jnp.ndarray,
    carry_used: jnp.ndarray,
    constraints: Constraints,
    rules: Rules,
    axis_name: Optional[str] = None,
    sparse_impl: str = "xla",
    p_real: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, ...]:
    """ONE carry-seeded sparse repair sweep; returns
    (assign, new_used[S, N], ok, exhausted[P]) with the exact
    acceptance gates of :func:`_warm_repair` (shared ``_repair_ok``),
    so ``PlannerSession``/``CarryCache`` semantics carry over
    unchanged.  Exhausted rows come back -1 and, being changed rows,
    are only acceptable when the dirty mask covers them — the caller
    then routes them through the per-row dense fallback."""
    p, s, _ = prev.shape
    n = nweights.shape[0]
    out, exh = _solve_assign(
        prev, pweights, nweights, valid, stickiness, gids, gid_valid,
        constraints, rules, axis_name, None, 1, "off",
        carry_used=carry_used, p_real=p_real, shortlist=shortlist,
        sparse_impl=sparse_impl)
    new_used = _used_by_state(out, pweights, n, s, axis_name)
    ok = _repair_ok(prev, out, new_used, carry_used, dirty, pweights,
                    nweights, valid, constraints, axis_name)
    return out, new_used, ok, exh


_WARM_SPARSE_STATICS = ("constraints", "rules", "axis_name", "sparse_impl")
_warm_repair_sparse_jit = partial(
    jax.jit, static_argnames=_WARM_SPARSE_STATICS)(_warm_repair_sparse)
# Same donation contract as _warm_repair_donating: the carry is
# single-use and prev aliases into the same-shaped assign output.
_warm_repair_sparse_donating = jax.jit(
    _warm_repair_sparse, static_argnames=_WARM_SPARSE_STATICS,
    donate_argnames=("prev", "carry_used"))


def _sparse_fallback_rows(
    assign: NPArray,  # [P, S, R] the sparse result (NOT mutated)
    rows: NPArray,  # indices of exhausted rows
    prev: NPArray,
    pweights: NPArray,
    nweights: NPArray,
    valid: NPArray,
    stickiness: NPArray,
    gids: NPArray,
    gid_valid: NPArray,
    constraints: Constraints,
    rules: Rules,
) -> NPArray:
    """Per-row DENSE fallback for shortlist-exhausted partitions.

    Discards the flagged rows' sparse placements entirely and re-places
    every slot in order against the full node axis — anchors, taken-set
    and rule tiers evaluated exactly as the audit judges them, priced by
    the real global fill so the handful of fallback rows spread instead
    of herding.  Host numpy over a [B, N] block (B = exhausted rows,
    rare by design): the whole point of the flag is that only these
    rows ever pay dense cost.  Returns a patched copy."""
    assign = np.array(np.asarray(assign), copy=True)
    rows = np.asarray(rows)
    P, S, R = assign.shape
    nw = np.asarray(nweights, np.float32)
    n = nw.shape[0]
    if rows.size == 0 or n == 0:
        return assign
    pw = np.asarray(pweights, np.float32)
    valid = np.asarray(valid, bool)
    gids = np.asarray(gids)
    gid_valid = np.asarray(gid_valid)
    w_div = np.where(nw > 0, nw, 1.0)
    neg_boost = np.maximum(-nw, 0.0)

    kept = assign.copy()
    kept[rows] = -1
    used_s = np.zeros((S, n), np.float32)
    for si in range(S):
        ids = kept[:, si, :]
        m = ids >= 0
        if m.any():
            w_rep = np.broadcast_to(pw[:, None], ids.shape)
            used_s[si] = np.bincount(
                ids[m].ravel(), weights=w_rep[m].ravel(),
                minlength=n)[:n].astype(np.float32)
    total = used_s.sum(axis=0)

    B = rows.size
    prev_b = np.asarray(prev)[rows]
    stick_b = np.asarray(stickiness, np.float32)[rows]
    pw_b = pw[rows]
    top_anchor = prev_b[:, 0, 0]
    new_rows = np.full((B, S, R), -1, np.int32)
    taken: list[NPArray] = []
    ar = np.arange(B)
    for si in range(S):
        kcon = int(constraints[si])
        if kcon <= 0:
            continue
        rules_si = list(rules[si]) if si < len(rules) else []
        if rules_si:
            base = top_anchor if si == 0 else np.where(
                new_rows[:, 0, 0] >= 0, new_rows[:, 0, 0], top_anchor)
            anchors = [base]
        for ri in range(min(kcon, R)):
            score = (0.001 * total[None, :] / max(float(P), 1.0)) \
                / w_div[None, :]
            prev_slot = prev_b[:, si, ri] if ri < prev_b.shape[2] \
                else np.full(B, -1, np.int32)
            align = np.zeros((B, n), bool)
            hold = prev_slot >= 0
            align[ar[hold], prev_slot[hold]] = True
            score = score - 0.01 * align
            score = score + np.maximum(
                neg_boost[None, :],
                np.where(neg_boost[None, :] > 0,
                         stick_b[:, si][:, None], 0.0))
            sticky = np.zeros((B, n), bool)
            for r in range(prev_b.shape[2]):
                ps = prev_b[:, si, r]
                hold = ps >= 0
                sticky[ar[hold], ps[hold]] = True
            score = score - stick_b[:, si][:, None] * sticky
            if rules_si:
                pen = np.full((B, n), _RULE_MISS, np.float32)
                for idx, (inc, exc) in enumerate(rules_si):
                    sat = np.ones((B, n), bool)
                    for a in anchors:
                        aa = np.clip(a, 0, n - 1)
                        inc_same = (gids[inc][aa][:, None]
                                    == gids[inc][None, :]) \
                            & gid_valid[inc][aa][:, None]
                        exc_same = (gids[exc][aa][:, None]
                                    == gids[exc][None, :]) \
                            & gid_valid[exc][aa][:, None]
                        sat &= np.where((a >= 0)[:, None],
                                        inc_same & ~exc_same, True)
                    pen = np.where(sat, np.minimum(pen, idx * _RULE_TIER),
                                   pen)
                any_anchor = np.zeros(B, bool)
                for a in anchors:
                    any_anchor |= a >= 0
                score = score + np.where(any_anchor[:, None], pen, 0.0)
            tk = np.zeros((B, n), bool)
            for t in taken:
                held = t >= 0
                tk[ar[held], t[held]] = True
            score = score + _INF * (tk | ~valid[None, :])
            # Price by the state's live global fill so concurrent
            # fallback rows spread (the force step's pricing idiom).
            score = score + used_s[si][None, :] / w_div[None, :]
            choice = np.argmin(score, axis=1).astype(np.int32)
            feas = score[ar, choice] < _INF / 2
            pick = np.where(feas, choice, -1).astype(np.int32)
            new_rows[:, si, ri] = pick
            placed = pick[feas]
            np.add.at(used_s[si], placed, pw_b[feas])
            np.add.at(total, placed, pw_b[feas])
            taken.append(pick)
            if rules_si:
                anchors.append(pick)
    assign[rows] = new_rows
    return assign


def _apply_sparse_fallback(
    assign: NPArray, exhausted: NPArray, prev, pweights, nweights,
    valid, stickiness, gids, gid_valid, constraints, rules,
    record: bool = True,
) -> tuple[NPArray, int]:
    """Route flagged rows through the dense fallback; returns
    (patched assign, rows re-placed).  Publishes the
    ``plan.sparse.shortlist_exhausted`` / ``dense_fallback_rows``
    counters so the escape hatch is observable."""
    rows = np.nonzero(np.asarray(exhausted))[0]
    if rows.size == 0:
        return np.asarray(assign), 0
    rec = get_recorder()
    if record:
        rec.count("plan.sparse.shortlist_exhausted", int(rows.size))
    patched = _sparse_fallback_rows(
        assign, rows, np.asarray(prev), pweights, nweights, valid,
        stickiness, gids, gid_valid, constraints, rules)
    replaced = int(np.any(
        patched[rows] != np.asarray(assign)[rows], axis=(1, 2)).sum())
    if record and replaced:
        rec.count("plan.sparse.dense_fallback_rows", replaced)
    return patched, replaced


def _build_or_adopt_shortlist(
    prev, pweights, nweights, valid, gids, gid_valid, constraints, rules,
    shortlist, k, record: bool,
):
    """The host entries' shared shortlist step: adopt a caller-built
    [P, K] table or derive one (timed as plan.sparse.shortlist_build_s),
    and publish the k_effective gauge."""
    from ..core.shortlist import auto_shortlist_k, build_shortlist

    rec = get_recorder()
    if shortlist is None:
        n = np.asarray(nweights).shape[-1]
        kk = int(k) if k is not None \
            else auto_shortlist_k(n, constraints, rules)
        t0 = rec.now()
        shortlist = build_shortlist(
            prev, pweights, nweights, valid, gids, gid_valid,
            constraints, rules, kk)
        if record:
            rec.observe("plan.sparse.shortlist_build_s", rec.now() - t0)
    shortlist = jnp.asarray(shortlist)
    if record:
        rec.set_gauge("plan.sparse.k_effective",
                      float(shortlist.shape[1] if shortlist.ndim == 2
                            else 0))
    return shortlist


def solve_sparse(
    prev, pweights, nweights, valid, stickiness, gids, gid_valid,
    constraints, rules, *, shortlist=None, k: Optional[int] = None,
    max_iterations: int = 10, record: bool = True, carry_used=None,
    return_carry: bool = False, p_real=None,
    sparse_impl: Optional[str] = None,
):
    """Sparse converged solve: shortlist -> [P, S, K] auction ->
    per-row dense fallback for exhausted rows.  The sparse sibling of
    :func:`solve_dense_converged` — same positional contract, returns
    the assignment as numpy (plus the rebuilt :class:`SolveCarry` with
    ``return_carry``).

    ``shortlist`` adopts a caller-built [P, K] table; otherwise one is
    derived (``k`` columns, auto-sized when None — see
    core/shortlist.py).  A saturating K >= N is bit-identical to the
    dense matrix engine (map, warnings and moves), the pinned contract
    that keeps the two paths from drifting.  ``carry_used`` seeds the
    first sweep exactly like the dense loop, so warm sessions ride it
    unchanged.
    """
    constraints = tuple(int(c) for c in constraints)
    rules = tuple(tuple(r) for r in rules)
    if not sparse_rules_supported(rules):
        raise ValueError(
            "sparse solve requires nesting hierarchy rules "
            "(exclude_level < include_level); use the dense engines")
    _check_tier_band_scale(prev, pweights, nweights, valid, stickiness,
                           constraints, rules)
    impl = resolve_sparse_impl(sparse_impl)
    rec = get_recorder()
    ent = _device.ambient_entry() or (
        "sparse.carry" if carry_used is not None else "sparse.cold")
    # The entry scope opens before the shortlist step: the cold entry
    # owns TWO programs (the jitted builder + the converged fixpoint),
    # and the retrace budget (analysis/retrace.py) is sized for both —
    # a builder retrace must land in THIS bucket, not "other".
    with _device.entry(ent):
        shortlist = _build_or_adopt_shortlist(
            prev, pweights, nweights, valid, gids, gid_valid,
            constraints, rules, shortlist, k, record)
        with rec.span("plan.solve.attempt", engine="sparse"):
            out, sweeps, exh = _solve_sparse_converged_impl(
                jnp.asarray(prev), jnp.asarray(pweights),
                jnp.asarray(nweights), jnp.asarray(valid),
                jnp.asarray(stickiness), jnp.asarray(gids),
                jnp.asarray(gid_valid), shortlist,
                constraints=constraints, rules=rules,
                max_iterations=max(int(max_iterations), 1),
                sparse_impl=impl, carry_used=carry_used, p_real=p_real)
            out_np = np.asarray(out)
            exh_np = np.asarray(exh)
    if record:
        _record_sweeps(sweeps)
    out_np, _replaced = _apply_sparse_fallback(
        out_np, exh_np, prev, pweights, nweights, valid, stickiness,
        gids, gid_valid, constraints, rules, record=record)
    if return_carry:
        return out_np, carry_from_assignment(
            jnp.asarray(out_np), jnp.asarray(pweights),
            jnp.asarray(nweights))
    return out_np


def solve_sparse_warm(
    prev, pweights, nweights, valid, stickiness, gids, gid_valid,
    constraints, rules, *, dirty, carry: SolveCarry, shortlist=None,
    k: Optional[int] = None, record: bool = True,
    donate: Optional[bool] = None, p_real=None,
    sparse_impl: Optional[str] = None,
) -> tuple[Optional[NPArray], Optional[SolveCarry]]:
    """Warm delta replan on the sparse engine: one carry-seeded repair
    sweep over the shortlist, or decline — the exact
    :func:`solve_dense_warm` contract ((None, None) on decline, carry
    consumed either way, same obs counters), so sessions and the
    CarryCache ride the sparse path unchanged.  Exhausted rows in an
    ACCEPTED repair go through the per-row dense fallback and the
    returned carry is rebuilt from the patched assignment."""
    constraints = tuple(int(c) for c in constraints)
    rules = tuple(tuple(r) for r in rules)
    if not sparse_rules_supported(rules):
        raise ValueError(
            "sparse solve requires nesting hierarchy rules "
            "(exclude_level < include_level); use the dense engines")
    rec = get_recorder()
    _check_tier_band_scale(prev, pweights, nweights, valid, stickiness,
                           constraints, rules)
    impl = resolve_sparse_impl(sparse_impl)
    dirty_np = np.asarray(dirty)
    if record:
        rec.observe("plan.solve.dirty_fraction",
                    float(dirty_np.mean()) if dirty_np.size else 0.0)
    if donate is None:
        donate = jax.default_backend() != "cpu"
    impl_fn = _warm_repair_sparse_donating if donate \
        else _warm_repair_sparse_jit
    # The donating dispatch consumes prev's device buffer (aliased into
    # the repair output), but the exhaustion fallback below still needs
    # the pre-repair placement — snapshot it host-side first.
    prev_fb = np.asarray(prev) if donate else prev
    with _device.entry("sparse.warm"):
        shortlist = _build_or_adopt_shortlist(
            prev, pweights, nweights, valid, gids, gid_valid,
            constraints, rules, shortlist, k, record)
        with rec.span("plan.solve.attempt", warm=True, engine="sparse"):
            out, new_used, ok, exh = impl_fn(
                jnp.asarray(prev), jnp.asarray(pweights),
                jnp.asarray(nweights), jnp.asarray(valid),
                jnp.asarray(stickiness), jnp.asarray(gids),
                jnp.asarray(gid_valid), shortlist,
                jnp.asarray(dirty_np), jnp.asarray(carry.used),
                constraints=constraints, rules=rules, sparse_impl=impl,
                p_real=p_real)
            accepted = bool(ok)
    if not accepted:
        if record:
            rec.count("plan.solve.warm_fallback")
            rec.count("plan.solve.sweeps", 1)  # the executed repair pass
        return None, None
    if record:
        _record_sweeps(1)
        rec.set_attr("warm", True)
    out_np = np.asarray(out)
    patched, replaced = _apply_sparse_fallback(
        out_np, np.asarray(exh), prev_fb, pweights, nweights, valid,
        stickiness, gids, gid_valid, constraints, rules, record=record)
    if replaced:
        return patched, carry_from_assignment(
            jnp.asarray(patched), jnp.asarray(pweights),
            jnp.asarray(nweights))
    return patched, SolveCarry(
        prices=jnp.sum(new_used, axis=0), assign=out, used=new_used)


# --- fused single-dispatch plan pipeline ------------------------------------
#
# ROADMAP item 3: at the north star the device solve is ~1/3 of
# end-to-end wall-clock — host encode/decode and the separate move-diff
# dispatch own the rest.  These impls chain solve -> on-device move diff
# -> on-device decode pack into ONE jitted program, so a plan round trip
# pays one dispatch and no intermediate host transfer: the solver output
# feeds the diff and the pack as device values inside the same trace.
# Buffer donation (prev, and the warm path's carry table) lets XLA alias
# the inputs into the same-shaped outputs (assign/packed are prev-shaped,
# new_used is carry_used-shaped), so the steady-state replan loop
# allocates no fresh [P, S, R] buffers.  Host work shrinks to the
# id->name materialization (decode_assignment's gather + NextMoves
# lists), which is irreducibly string-typed.


def _host_flat(outs: tuple) -> tuple:
    """A cold or sparse pipeline body's outputs as a dispatch returns
    them: (assign, prices, used, host).  ``host`` holds every output the
    host reads, all but the carry's prices and used, in the body's order
    (assign, sweeps, d_nodes, d_states, d_ops, packed, counts[, exh]),
    raveled into ONE flat int32 buffer, so they cross to the host in one
    transfer (_fetch_outputs splits it).  Every one is an id, a count or
    a flag that int32 holds.  ``assign`` also stays a device output, for
    the carry."""
    assign, sweeps, prices, used, *rest = outs
    return assign, prices, used, jnp.concatenate(
        [jnp.ravel(x).astype(jnp.int32) for x in (assign, sweeps, *rest)])


def _pipeline_cold_impl(
    prev: jnp.ndarray,
    pweights: jnp.ndarray,
    nweights: jnp.ndarray,
    valid: jnp.ndarray,
    stickiness: jnp.ndarray,
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    constraints: Constraints,
    rules: Rules,
    axis_name: Optional[str] = None,
    max_iterations: int = 10,
    node_axis: Optional[str] = None,
    node_shards: int = 1,
    fused_score: str = "off",
    favor_min_nodes: bool = False,
    carry_used: Optional[jnp.ndarray] = None,
    p_real: Optional[jnp.ndarray] = None,
    host_flat: bool = False,
) -> tuple[jnp.ndarray, ...]:
    """Cold pipeline body: converged solve + diff(prev, out) + pack.

    Returns (assign, sweeps, prices, used, d_nodes, d_states, d_ops,
    packed, counts), or with ``host_flat`` (the dispatch's program) the
    :func:`_host_flat` form.  ``prices``/``used`` are the next
    SolveCarry's tables, computed with the carry builder's exact ops so
    the packaged carry is bitwise what carry_from_assignment would build
    —  without a second dispatch.  The solve is the UNCHANGED converged
    fixpoint trace, so ``assign`` is bit-identical to
    solve_dense_converged's.
    """
    from ..core.encode import pack_assignment_core
    from ..moves.batch import diff_assignments

    out, sweeps = _solve_dense_converged_impl(
        prev, pweights, nweights, valid, stickiness, gids, gid_valid,
        constraints, rules, axis_name, max_iterations, node_axis,
        node_shards, fused_score, carry_used, p_real)
    used = _used_by_state(out, pweights, nweights.shape[0], prev.shape[1],
                          axis_name)
    prices = jnp.sum(used, axis=0)
    d_nodes, d_states, d_ops = diff_assignments(
        prev, out, favor_min_nodes=favor_min_nodes)
    packed, counts = pack_assignment_core(out)
    outs = (out, sweeps, prices, used, d_nodes, d_states, d_ops, packed,
            counts)
    return _host_flat(outs) if host_flat else outs


def _pipeline_warm_impl(
    prev: jnp.ndarray,
    pweights: jnp.ndarray,
    nweights: jnp.ndarray,
    valid: jnp.ndarray,
    stickiness: jnp.ndarray,
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    dirty: jnp.ndarray,
    carry_used: jnp.ndarray,
    constraints: Constraints,
    rules: Rules,
    axis_name: Optional[str] = None,
    node_axis: Optional[str] = None,
    node_shards: int = 1,
    fused_score: str = "off",
    favor_min_nodes: bool = False,
    p_real: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, ...]:
    """Warm pipeline body: one carry-seeded repair sweep (_warm_repair,
    acceptance flags included) + diff + pack in the same program.

    Returns (assign, prices, used, ok, d_nodes, d_states, d_ops,
    packed, counts); ``ok`` False means the repair leaked and the
    caller must run the cold pipeline — the diff/pack work is then
    wasted, which is fine: declines are the rare path by design."""
    from ..core.encode import pack_assignment_core
    from ..moves.batch import diff_assignments

    out, new_used, ok = _warm_repair(
        prev, pweights, nweights, valid, stickiness, gids, gid_valid,
        dirty, carry_used, constraints, rules, axis_name, node_axis,
        node_shards, fused_score, p_real)
    prices = jnp.sum(new_used, axis=0)
    d_nodes, d_states, d_ops = diff_assignments(
        prev, out, favor_min_nodes=favor_min_nodes)
    packed, counts = pack_assignment_core(out)
    return (out, prices, new_used, ok, d_nodes, d_states, d_ops,
            packed, counts)


def _pipeline_sparse_cold_impl(
    prev: jnp.ndarray,
    pweights: jnp.ndarray,
    nweights: jnp.ndarray,
    valid: jnp.ndarray,
    stickiness: jnp.ndarray,
    gids: jnp.ndarray,
    gid_valid: jnp.ndarray,
    constraints: Constraints,
    rules: Rules,
    axis_name: Optional[str] = None,
    max_iterations: int = 10,
    shortlist_k: int = 16,
    sparse_impl: str = "xla",
    favor_min_nodes: bool = False,
    carry_used: Optional[jnp.ndarray] = None,
    p_real: Optional[jnp.ndarray] = None,
    host_flat: bool = False,
) -> tuple[jnp.ndarray, ...]:
    """Sparse pipeline body: shortlist build -> sparse converged solve
    -> diff(prev, out) -> pack, ONE traced program — the sparse variant
    of :func:`_pipeline_cold_impl` (donation preserved: prev aliases
    into assign).  Returns the cold pipeline tuple plus the
    exhaustion flags (``host_flat``: see the cold body); the dispatcher
    re-places flagged rows host-side and re-derives diff/pack for them
    (rare by design)."""
    from ..core.encode import pack_assignment_core
    from ..core.shortlist import build_shortlist_core
    from ..moves.batch import diff_assignments

    shortlist = build_shortlist_core(
        prev, pweights, nweights, valid, gids, gid_valid, constraints,
        rules, shortlist_k)
    out, sweeps, exh = _solve_sparse_converged_impl(
        prev, pweights, nweights, valid, stickiness, gids, gid_valid,
        shortlist, constraints=constraints, rules=rules,
        axis_name=axis_name, max_iterations=max_iterations,
        sparse_impl=sparse_impl, carry_used=carry_used, p_real=p_real)
    used = _used_by_state(out, pweights, nweights.shape[0], prev.shape[1],
                          axis_name)
    prices = jnp.sum(used, axis=0)
    d_nodes, d_states, d_ops = diff_assignments(
        prev, out, favor_min_nodes=favor_min_nodes)
    packed, counts = pack_assignment_core(out)
    outs = (out, sweeps, prices, used, d_nodes, d_states, d_ops, packed,
            counts, exh)
    return _host_flat(outs) if host_flat else outs


_PIPE_COLD_STATICS = ("constraints", "rules", "axis_name",
                      "max_iterations", "node_axis", "node_shards",
                      "fused_score", "favor_min_nodes")
_PIPE_SPARSE_STATICS = ("constraints", "rules", "axis_name",
                        "max_iterations", "shortlist_k", "sparse_impl",
                        "favor_min_nodes")
_PIPE_WARM_STATICS = ("constraints", "rules", "axis_name", "node_axis",
                      "node_shards", "fused_score", "favor_min_nodes")

_pipeline_cold_jit = partial(
    jax.jit, static_argnames=_PIPE_COLD_STATICS)(
    partial(_pipeline_cold_impl, host_flat=True))
# Donation: prev aliases into the same-shaped assign output; the
# warm path additionally donates the consumed carry table (single-use by
# contract — sessions replace theirs after every attempt).  Donation is
# supported on every backend under the pinned jax (tests assert the
# donated buffers really are invalidated), so there is no CPU split like
# _warm_repair_donating's.
_pipeline_cold_donating = jax.jit(
    partial(_pipeline_cold_impl, host_flat=True),
    static_argnames=_PIPE_COLD_STATICS,
    donate_argnames=("prev",))
_pipeline_warm_jit = partial(
    jax.jit, static_argnames=_PIPE_WARM_STATICS)(_pipeline_warm_impl)
_pipeline_warm_donating = jax.jit(
    _pipeline_warm_impl, static_argnames=_PIPE_WARM_STATICS,
    donate_argnames=("prev", "carry_used"))
_pipeline_sparse_jit = partial(
    jax.jit, static_argnames=_PIPE_SPARSE_STATICS)(
    partial(_pipeline_sparse_cold_impl, host_flat=True))
_pipeline_sparse_donating = jax.jit(
    partial(_pipeline_sparse_cold_impl, host_flat=True),
    static_argnames=_PIPE_SPARSE_STATICS,
    donate_argnames=("prev",))


def _seeded_beg_map(prev_map: PartitionMap,
                    partitions_to_assign: PartitionMap) -> PartitionMap:
    """The beginning state the planner actually diffs against: prev_map
    entries where present, partitions_to_assign seeds elsewhere — the
    same ``prev_map.get(p) or partitions_to_assign[p]`` rule
    encode_problem fills prev[P, S, R] with."""
    return {name: (prev_map.get(name) or partitions_to_assign[name])
            for name in partitions_to_assign}


def plan_pipeline(
    prev_map: PartitionMap,
    partitions_to_assign: PartitionMap,
    nodes_all: list[str],
    nodes_to_remove: Optional[list[str]],
    nodes_to_add: Optional[list[str]],
    model: PartitionModel,
    opts: Optional[PlanOptions] = None,
    timer=None,
    *,
    favor_min_nodes: bool = False,
    want_moves: bool = True,
):
    """Plan the next map and its move diff in ONE device dispatch: the
    tpu backend of ``plan_next_map`` (through :func:`plan_next_map_tpu`).

    Returns (next_map, warnings, moves).  ``moves`` matches
    ``moves.batch.calc_all_moves(seeded_beg, next_map, model,
    favor_min_nodes)`` (the per-partition ordered op lists the
    orchestrator consumes), where seeded_beg resolves missing prev
    entries from partitions_to_assign exactly like the encoder.  The
    encode stays host (string interning), then solve->diff->decode-pack
    run as one jitted, buffer-donated program — no intermediate host
    transfer between solve and diff, and decode's host share is only
    the id->name gather.  ``nodes_to_add`` is implicit (fresh nodes simply
    have zero counts, which attracts load).

    Caveat shared with PlannerSession.moves(): partitions whose
    beginning state holds one node in several states diff through the
    dense one-state-per-node encoding (calc_all_moves's irregular-host
    fallback does not apply); the solver's own outputs never do that.

    A score engine that fails to compile or run retries once on the
    other engine when the engine came from "auto"
    (:func:`_with_engine_fallback`).  Custom placement hooks the device
    score can't express run the native/greedy exact path instead — a
    cbgt-style app keeps its policy even when ``backend="auto"`` routes
    a large problem here.

    ``want_moves=False`` skips the host move materialization, returning
    ``{}`` as the third element (the device diff still runs inside the
    same program)."""
    from ..moves.batch import calc_all_moves
    from ..utils.trace import PhaseTimer

    opts = opts or PlanOptions()
    timer = timer if timer is not None else PhaseTimer()
    rec = get_recorder()
    if not _tpu_supported(opts):
        from .native import plan_next_map_native  # falls back to greedy

        # The exact path has no encode/solve/decode split; attribute it
        # all to "solve" so a caller's timer still sees the wall-clock.
        with phase_span("plan.solve", timer=timer,
                        engine="exact-fallback"):
            next_map, warnings = plan_next_map_native(
                prev_map, partitions_to_assign, nodes_all,
                nodes_to_remove, nodes_to_add, model, opts)
        moves = calc_all_moves(
            _seeded_beg_map(prev_map, partitions_to_assign), next_map,
            model, favor_min_nodes) if want_moves else {}
        return next_map, warnings, moves
    del nodes_to_add

    with phase_span("plan.pipeline", timer=timer,
                    partitions=len(partitions_to_assign),
                    nodes=len(nodes_all)):
        rec.count("plan.pipeline.calls")
        with phase_span("plan.encode", timer=timer):
            problem = encode_problem(
                prev_map, partitions_to_assign, nodes_all,
                nodes_to_remove, model, opts)
        if problem.P == 0 or problem.N == 0 or problem.S == 0:
            next_map, warnings = decode_assignment(
                problem,
                np.full((problem.P, problem.S, max(problem.R, 1)), -1,
                        np.int32),
                partitions_to_assign, nodes_to_remove)
            return next_map, warnings, {n: [] for n in problem.partitions}

        with phase_span("plan.pipeline.prepare", timer=timer):
            rules = tuple(
                tuple(problem.rules.get(si, ())) for si in range(problem.S))
            constraints = tuple(int(c) for c in problem.constraints)

            prev_a = problem.prev
            pw_a = problem.partition_weights
            nw_a = problem.node_weights
            valid_a = problem.valid_node
            stick_a = problem.stickiness
            gids_a = problem.gids
            gv_a = problem.gid_valid
            solve_p, solve_n = problem.P, problem.N
            # Opt-in static-shape bucketing: pad P and N up to the next
            # bucket so repeated calls against a drifting cluster hit the
            # jit cache instead of recompiling.  Pad partitions are
            # weight-0 bidders (sliced off below) and pad nodes invalid,
            # the inert-padding contract parallel/sharded.py relies on.
            if opts.shape_bucketing:
                from ..core.encode import bucket_size, pad_problem_arrays

                solve_p = bucket_size(problem.P)
                solve_n = bucket_size(problem.N)
                (prev_a, pw_a, nw_a, valid_a, stick_a, gids_a, gv_a) = \
                    pad_problem_arrays(prev_a, pw_a, nw_a, valid_a, stick_a,
                                       gids_a, gv_a, solve_p, solve_n)
            _check_tier_band_scale(prev_a, pw_a, nw_a, valid_a, stick_a,
                                   constraints, rules)
            mode = resolve_default_fused_score(solve_p, solve_n)
            use_sparse = _sparse_selected(opts, solve_p, problem.S, solve_n,
                                          rules)
            if not use_sparse:
                check_dense_memory(solve_p, problem.S, solve_n, mode)
            # Only under bucketing: p_real keeps the fill denominator at
            # the REAL partition count while sizes drift within a bucket.
            # Unbucketed solves keep total_p a compile-time constant — a
            # traced scalar changes how XLA strength-reduces the fill
            # division, and those low bits flip jitter-level ties (which
            # is also why bucketed output is contract-equivalent to the
            # unbucketed solve, not bit-identical).  device_put: the
            # scalar reaches the device as an EXPLICIT transfer, which
            # the tests' transfer guard permits.
            p_real = (jax.device_put(np.float32(problem.P))
                      if opts.shape_bucketing else None)

        if use_sparse:
            res = _dispatch_pipeline_sparse(
                prev_a, pw_a, nw_a, valid_a, stick_a, gids_a, gv_a,
                constraints, rules,
                max_iterations=max(int(opts.max_iterations), 1),
                shortlist_k=_opts_shortlist_k(
                    opts, solve_n, constraints, rules),
                sparse_impl=resolve_sparse_impl(None),
                favor_min_nodes=favor_min_nodes,
                entry="sparse.pipeline", timer=timer, p_real=p_real)
        else:
            res = _dispatch_pipeline_cold(
                prev_a, pw_a, nw_a, valid_a, stick_a, gids_a, gv_a,
                constraints, rules,
                max_iterations=max(int(opts.max_iterations), 1),
                fused_score=mode,
                allow_fallback=_FUSED_SCORE_DEFAULT == "auto",
                favor_min_nodes=favor_min_nodes,
                entry=("solve_dense.bucketed" if opts.shape_bucketing
                       else "pipeline.cold"),
                timer=timer, p_real=p_real)
        assign, _sweeps, _carry, (d_nodes, d_states, d_ops), \
            (packed, counts) = res
        assign = assign[:problem.P]
        maybe_validate(problem, assign, opts.validate_assignment,
                       "plan_pipeline", timer=timer)
        with phase_span("plan.decode", timer=timer):
            next_map, warnings = decode_assignment(
                problem, assign, partitions_to_assign, nodes_to_remove,
                packed=packed[:problem.P], counts=counts[:problem.P])
        if not want_moves:
            return next_map, warnings, {}
        with phase_span("plan.pipeline.materialize", timer=timer):
            from ..moves.batch import moves_from_arrays

            moves = moves_from_arrays(
                problem.partitions, problem.states, problem.nodes,
                d_nodes[:problem.P], d_states[:problem.P],
                d_ops[:problem.P])
        return next_map, warnings, moves


def _fetch_outputs(host, shapes: tuple) -> list:
    """Read a dispatch's flat host buffer (:func:`_host_flat`) in ONE
    host wait, counted as one ``plan.pipeline.d2h_waits``, and split it
    at the static offsets of ``shapes`` (a layout that disagrees with the
    buffer fails the reshape)."""
    get_recorder().count("plan.pipeline.d2h_waits")
    bounds = np.cumsum([int(np.prod(s)) for s in shapes])[:-1]
    return [part.reshape(s) for part, s in
            zip(np.split(np.asarray(host), bounds), shapes)]


def _host_shapes(prev_shape: tuple) -> tuple:
    """Shapes of the cold body's host outputs in :func:`_host_flat`'s
    order, from prev's [P, S, R]: the diff's rows are 2*S*R ops wide
    (moves.batch.diff_assignments)."""
    p, s, r = prev_shape
    diff = (p, 2 * s * r)
    return ((p, s, r), (), diff, diff, diff, (p, s, r), (p, s))


def _dispatch_pipeline_cold(
    prev_a, pw_a, nw_a, valid_a, stick_a, gids_a, gv_a,
    constraints: Constraints, rules: Rules, *, max_iterations: int,
    fused_score: str, allow_fallback: bool, favor_min_nodes: bool,
    entry: str, timer=None, carry_used=None, p_real=None, donate=True,
):
    """One cold pipeline dispatch, retried once on the other score
    engine when ``allow_fallback`` (:func:`_with_engine_fallback`).
    Returns (assign_np, sweeps, SolveCarry, (d_nodes, d_states, d_ops)
    np, (packed, counts) np) — everything off-device in one host wait,
    at the end."""
    rec = get_recorder()

    def run(m: str):
        if m != fused_score:
            # A retry onto the matrix engine passes the memory guard the
            # caller ran for the first engine.
            check_dense_memory(prev_a.shape[0], prev_a.shape[1],
                               nw_a.shape[-1], m)
        impl = _pipeline_cold_donating if donate else _pipeline_cold_jit
        t0 = rec.now()
        with phase_span("plan.pipeline.dispatch", timer=timer,
                        engine=m), \
                _device.entry(entry):
            # launch: the uploads and the call, up to its return (before
            # the first blocking read).
            with phase_span("plan.pipeline.launch", timer=timer):
                out = impl(
                    jnp.asarray(prev_a), jnp.asarray(pw_a),
                    jnp.asarray(nw_a), jnp.asarray(valid_a),
                    jnp.asarray(stick_a), jnp.asarray(gids_a),
                    jnp.asarray(gv_a), constraints, rules,
                    max_iterations=max_iterations, fused_score=m,
                    favor_min_nodes=favor_min_nodes, carry_used=carry_used,
                    p_real=p_real)
            assign, prices, used, host = out
            # One boundary crossing for the whole pipeline: everything
            # below is host-side materialization.
            (assign_np, sweeps_np, *darrs, packed_np,
             counts_np) = _fetch_outputs(host, _host_shapes(prev_a.shape))
        rec.observe("plan.pipeline.dispatch_s", rec.now() - t0)
        with phase_span("plan.pipeline.fetch", timer=timer):
            _record_sweeps(sweeps_np)
            carry = SolveCarry(prices=prices, assign=assign, used=used)
            return (assign_np, sweeps_np, carry, tuple(darrs),
                    (packed_np, counts_np))

    out, _mode = _with_engine_fallback(
        run, fused_score, allow_fallback=allow_fallback,
        context="plan_pipeline", timer=timer)
    return out


def _sparse_selected(opts: PlanOptions, p: int, s: int, n: int,
                     rules: Rules) -> bool:
    """Route a plan through the sparse shortlist engine?

    ``opts.sparse`` True/False forces it (True + non-nesting rules is
    an error); None = auto — sparse exactly when the dense matrix
    engine's projected [P, N] footprint exceeds the memory budget and
    the rules nest, i.e. when dense would be refused (CPU hosts) or
    forced into the fused engine's O(P*N) compute (TPU)."""
    sel = getattr(opts, "sparse", None)
    if sel is False:
        return False
    nest = sparse_rules_supported(rules)
    if sel:
        if not nest:
            raise ValueError(
                "PlanOptions(sparse=True) requires nesting hierarchy "
                "rules (exclude_level < include_level for every rule)")
        return True
    return nest and projected_score_bytes(p, n) > \
        dense_score_budget_bytes()


def _opts_shortlist_k(opts: PlanOptions, n: int, constraints: Constraints,
                      rules: Rules) -> int:
    """PlanOptions.sparse_k, or the auto-derived K."""
    from ..core.shortlist import auto_shortlist_k

    k = getattr(opts, "sparse_k", None)
    if k is not None:
        if int(k) < 1:
            raise ValueError(f"PlanOptions.sparse_k must be >= 1, got {k}")
        return min(int(k), max(n, 1))
    return auto_shortlist_k(n, constraints, rules)


def _dispatch_pipeline_sparse(
    prev_a, pw_a, nw_a, valid_a, stick_a, gids_a, gv_a,
    constraints: Constraints, rules: Rules, *, max_iterations: int,
    shortlist_k: int, sparse_impl: str, favor_min_nodes: bool,
    entry: str, timer=None, carry_used=None, p_real=None, donate=True,
):
    """One sparse pipeline dispatch (shortlist -> sparse solve -> diff
    -> pack in one program).  Returns the `_dispatch_pipeline_cold`
    tuple; exhausted rows are re-placed by the host fallback and their
    diff/pack re-derived in one small extra dispatch (the rare path)."""
    rec = get_recorder()
    impl = _pipeline_sparse_donating if donate else _pipeline_sparse_jit
    # The donating dispatch aliases prev's buffer into the outputs, but
    # the exhaustion fallback and its re-diff below still need the
    # pre-solve placement — snapshot it host-side first (zero-copy for
    # the numpy arrays the plan/session paths pass).
    prev_fb = np.asarray(prev_a) if donate else prev_a
    t0 = rec.now()
    with phase_span("plan.pipeline.dispatch", timer=timer,
                    engine="sparse"), \
            _device.entry(entry):
        with phase_span("plan.pipeline.launch", timer=timer):
            assign, prices, used, host = impl(
                jnp.asarray(prev_a), jnp.asarray(pw_a), jnp.asarray(nw_a),
                jnp.asarray(valid_a), jnp.asarray(stick_a),
                jnp.asarray(gids_a), jnp.asarray(gv_a),
                constraints, rules, max_iterations=max_iterations,
                shortlist_k=shortlist_k, sparse_impl=sparse_impl,
                favor_min_nodes=favor_min_nodes, carry_used=carry_used,
                p_real=p_real)
        # One boundary crossing for the whole pipeline, the exhaustion
        # flags (which gate the host escape hatch) included.
        (assign_np, sweeps_np, *darrs, packed_np, counts_np,
         exh_np) = _fetch_outputs(
            host, _host_shapes(prev_a.shape) + (prev_a.shape[:1],))
    rec.observe("plan.pipeline.dispatch_s", rec.now() - t0)
    rec.set_gauge("plan.sparse.k_effective", float(shortlist_k))
    (timer.annotate if timer is not None else rec.set_attr)(
        "engine", "sparse")
    patched, replaced = _apply_sparse_fallback(
        assign_np, exh_np, prev_fb, pw_a, nw_a, valid_a, stick_a,
        gids_a, gv_a, constraints, rules)
    if replaced:
        # The fused diff/pack ran before the host fallback patched the
        # flagged rows: re-derive both against the final assignment and
        # rebuild the carry from it (one small extra dispatch, and one
        # more host wait, on the escape-hatch path only).
        from ..core.encode import pack_assignment
        from ..moves.batch import diff_assignments

        assign_np = patched
        dev_assign = jnp.asarray(assign_np)
        d_nodes, d_states, d_ops = diff_assignments(
            jnp.asarray(prev_fb), dev_assign,
            favor_min_nodes=favor_min_nodes)
        packed, counts = pack_assignment(dev_assign)
        carry = carry_from_assignment(
            dev_assign, jnp.asarray(pw_a), jnp.asarray(nw_a))
        rec.count("plan.pipeline.d2h_waits")
        (*darrs, packed_np, counts_np) = jax.device_get(
            (d_nodes, d_states, d_ops, packed, counts))
    else:
        carry = SolveCarry(prices=prices, assign=assign, used=used)
    with phase_span("plan.pipeline.fetch", timer=timer):
        _record_sweeps(sweeps_np)
        return (assign_np, sweeps_np, carry, tuple(darrs),
                (packed_np, counts_np))


def solve_converged_resilient(
    prev, pweights, nweights, valid, stickiness, gids, gid_valid,
    constraints, rules, *, max_iterations: int, mode: str,
    allow_fallback: bool, context: str, timer=None,
    carry_used=None, return_carry: bool = False, p_real=None,
):
    """solve_dense_converged with engine-failure degradation
    (:func:`_with_engine_fallback`).  Returns (assignment,
    engine-that-ran), plus the rebuilt :class:`SolveCarry` when
    ``return_carry`` is set.  ``carry_used`` seeds the first sweep (see
    solve_dense_converged)."""
    rec = get_recorder()

    def run(m: str) -> NPArray:
        # Structured refusal instead of an opaque XLA OOM when the
        # matrix engine's projected [P, N] working set is over budget
        # (checked per attempt: an auto-fallback onto the matrix engine
        # must not sneak past the guard either).
        check_dense_memory(prev.shape[0], prev.shape[1],
                           np.asarray(nweights).shape[-1], m)
        # np.asarray inside the guarded region: async dispatch can defer
        # a runtime failure to the first host read.
        with rec.span("plan.solve.attempt", engine=m):
            return np.asarray(solve_dense_converged(
                prev, pweights, nweights, valid, stickiness, gids,
                gid_valid, constraints, rules,
                max_iterations=max_iterations, fused_score=m,
                carry_used=carry_used, p_real=p_real))

    out, mode = _with_engine_fallback(
        run, mode, allow_fallback=allow_fallback, context=context,
        timer=timer)
    if return_carry:
        return out, mode, carry_from_assignment(out, pweights, nweights)
    return out, mode


def _with_engine_fallback(run, mode: str, *, allow_fallback: bool,
                          context: str, timer=None):
    """``run(mode)``, retried once on the opposite score engine.

    The auto-selected engine is a prediction from a working-set model
    (_MATRIX_BYTES_PER_CELL / _HBM_BUDGET_FRACTION, calibrated on one
    chip generation); when the prediction is wrong the matrix engine can
    die in compile (HBM over-subscription) — or, more rarely, a fused
    kernel can hit a Mosaic lowering gap on a new toolchain.  With
    ``allow_fallback`` (set iff the mode came from "auto", never for an
    explicit user choice) a failed engine retries once on the opposite
    one, surfacing the switch as a UserWarning, the
    ``plan.engine_fallback`` counter and an ``engine_fallback``
    annotation.  The engine that ran is annotated as ``engine`` either
    way.  Returns (run's result, the mode that ran)."""
    import warnings as _warnings

    rec = get_recorder()
    # timer.annotate forwards to rec.set_attr (PhaseTimer is a shim over
    # the Recorder), so write directly only when there is no timer —
    # never both.
    annotate = timer.annotate if timer is not None else rec.set_attr
    try:
        out = run(mode)
    except (ValueError, TypeError):
        # Deterministic input/validation errors fail identically on every
        # engine — retrying would double the failure and surface the
        # wrong traceback.  The fallback is for the documented runtime
        # cases only (HBM over-subscription, Mosaic lowering gaps).
        raise
    except Exception as e:
        alt = {"off": "on", "on": "off"}.get(mode)
        if not allow_fallback or alt is None or \
                (alt == "on" and not pallas_available()):
            raise
        first = (str(e).splitlines() or [""])[0][:200]
        _warnings.warn(
            f"blance_tpu {context}: score engine {mode!r} failed to "
            f"compile/run ({type(e).__name__}: {first}); retrying with "
            f"{alt!r}", UserWarning, stacklevel=4)
        rec.count("plan.engine_fallback")
        out = run(alt)
        mode = alt
        annotate("engine_fallback", f"-> {alt}")
    annotate("engine", {"off": "matrix", "on": "fused",
                        "interpret": "fused-interpret"}[mode])
    return out, mode


def _anchor_sat_np(
    anchor: NPArray,  # [P] node ids, -1 = absent
    gids: NPArray,  # [L, N]
    gid_valid: NPArray,  # [L, N]
    rules: list[tuple[int, int]],
) -> NPArray:
    """Per-rule satisfaction [n_rules, P, N] for ONE anchor column: does
    node n share the anchor's include-level ancestor and NOT its
    exclude-level ancestor?  Absent anchors satisfy everything.  Validity
    gates on the anchor side only, exactly like the device _hier_penalty."""
    p = anchor.shape[0]
    n = gids.shape[1]
    aa = np.clip(anchor, 0, n - 1)
    present = (anchor >= 0)[:, None]
    out = np.ones((len(rules), p, n), bool)
    for idx, (inc, exc) in enumerate(rules):
        inc_same = (gids[inc][aa][:, None] == gids[inc][None, :]) & \
            gid_valid[inc][aa][:, None]
        exc_same = (gids[exc][aa][:, None] == gids[exc][None, :]) & \
            gid_valid[exc][aa][:, None]
        out[idx] = np.where(present, inc_same & ~exc_same, True)
    return out


# Partition-block size for the matrix-path hierarchy audit: bounds its
# peak numpy temporaries to [n_rules, _HIER_CHUNK, N] regardless of P.
_HIER_CHUNK = 4096


def _audit_rules_nest(problem: DenseProblem) -> bool:
    """True when every rule's exclude level is strictly finer than its
    include level — the tree shape under which an exclude group lies
    inside exactly one include group, so attainability reduces to group
    counting (the same precondition _hier_floor_counts relies on in the
    solver)."""
    return all(exc < inc
               for si in range(problem.S)
               for (inc, exc) in (problem.rules.get(si) or []))


def _count_hier_misses_fast(
    problem: DenseProblem, assign: NPArray
) -> int:
    """Group-counting hierarchy audit: O(P·S·R·rules + N·L) host math.

    Semantically identical to the matrix path (_count_hier_misses_block)
    when every rule nests (_audit_rules_nest) — pinned by
    tests/test_tensor.py's parity fuzz.  Instead of materializing
    per-anchor satisfaction over all N candidates, the attainable tier
    comes from counting: with the exclude level strictly finer than the
    include level, the number of rule-satisfying open candidates is

        count(valid nodes in the anchors' shared include group)
        - sum over DISTINCT anchor exclude groups of count(valid in e)
        - count(already-used nodes in the include group but in none of
          those exclude groups)

    — [N]-bincounts (one per hierarchy level, shared across rules) plus
    [P] gathers.  The achieved tier is a point evaluation at the judged
    node.  This is what makes the audit affordable at the north-star
    scale, so validation defaults ON at every size (maybe_validate);
    the reference's equivalent property surfaces as warnings
    (plan.go:231-235).
    """
    P, S, R = assign.shape
    N = problem.N
    gids, gid_valid = problem.gids, problem.gid_valid
    valid = problem.valid_node
    if not any(problem.rules.get(si) for si in range(S)):
        return 0

    # Valid-node histogram per hierarchy level.  Ancestor PRESENCE is
    # gid_valid, not the gid's sign: encode interns orphans into a shared
    # ""-group with a real dense id and gid_valid=False (encode.py:
    # level_group_ids + find_ancestor), while synthetic/test problems may
    # spell absence as gid -1 — gate on gid_valid and drop negatives so
    # both representations count identically.
    cnt = np.zeros((gids.shape[0], N), np.int64)
    for lv in range(gids.shape[0]):
        g = gids[lv][valid & gid_valid[lv]]
        g = g[g >= 0]
        cnt[lv] = np.bincount(g, minlength=N)

    # Joint histograms per rule: nodes of an exclude group that also hold
    # a PRESENT include-level ancestor.  A node can sit in a real exclude
    # group while its coarser ancestor is missing (e.g. a rack with no
    # zone parent): such a node is never in the shared include group, so
    # subtracting the full exclude-group count would over-subtract it.
    # Present ancestors are tree-consistent (same exclude group + present
    # include ancestor => same include group), so this joint count is
    # exactly |e ∩ g| for every e counted under g.
    cnt_pair: dict[tuple[int, int], NPArray] = {}
    for si in range(S):
        for (inc, exc) in (problem.rules.get(si) or []):
            if (inc, exc) in cnt_pair:
                continue
            sel = valid & gid_valid[exc] & gid_valid[inc] & \
                (gids[exc] >= 0) & (gids[inc] >= 0)
            cnt_pair[(inc, exc)] = np.bincount(
                gids[exc][sel], minlength=N)

    top_anchor = problem.prev[:, 0, 0]
    misses = 0
    used_ids: list[NPArray] = []  # [P] global node ids, -1 = none

    def point_sat(anchors, node, inc, exc):
        """[P] bool: does ``node`` satisfy (inc, exc) for every present
        anchor?  Validity gates on the anchor side only, exactly like
        _anchor_sat_np / the device _anchor_rule_sat."""
        nd = np.clip(node, 0, N - 1)
        out = np.ones(P, bool)
        for a in anchors:
            aa = np.clip(a, 0, N - 1)
            inc_same = (gids[inc][aa] == gids[inc][nd]) & gid_valid[inc][aa]
            exc_same = (gids[exc][aa] == gids[exc][nd]) & gid_valid[exc][aa]
            out &= np.where(a >= 0, inc_same & ~exc_same, True)
        return out

    def attainable_count(anchors, inc, exc):
        """[P] count of rule-satisfying candidates among valid & unused
        nodes, by group counting (see docstring)."""
        # Shared include group across present anchors (else unsatisfiable).
        g = np.full(P, -1, np.int64)
        ok = np.ones(P, bool)
        for a in anchors:
            aa = np.clip(a, 0, N - 1)
            a_g = np.where(gid_valid[inc][aa], gids[inc][aa], -2)
            present = a >= 0
            ok &= np.where(present & (g >= 0), a_g == g, True)
            ok &= np.where(present & (g < 0), a_g >= 0, True)
            g = np.where(present & (g < 0), a_g, g)
        gc = np.clip(g, 0, N - 1)
        count = cnt[inc][gc].astype(np.int64)

        # Subtract distinct anchor exclude groups (each nested inside the
        # shared include group, so each subtracts its full valid count).
        e_seen: list[NPArray] = []
        for a in anchors:
            aa = np.clip(a, 0, N - 1)
            e = np.where((a >= 0) & gid_valid[exc][aa], gids[exc][aa], -1)
            dup = np.zeros(P, bool)
            for prev_e in e_seen:
                dup |= (e == prev_e) & (e >= 0)
            count -= np.where((e >= 0) & ~dup,
                              cnt_pair[(inc, exc)][np.clip(e, 0, N - 1)], 0)
            e_seen.append(e)

        # Subtract already-used nodes still standing in the include group:
        # used nodes inside a counted exclude group are subtracted above
        # already, so only those OUTSIDE every counted group go here.
        for u in used_ids:
            uu = np.clip(u, 0, N - 1)
            in_g = (u >= 0) & valid[uu] & (gids[inc][uu] == g)
            in_excl = np.zeros(P, bool)
            for e in e_seen:
                in_excl |= (e >= 0) & (gids[exc][uu] == e)
            count -= (in_g & ~in_excl).astype(np.int64)
        return np.where(ok & (g >= 0), count, 0)

    for si in range(S):
        rules_si = problem.rules.get(si) or []
        big = len(rules_si)
        if rules_si:
            base = top_anchor if si == 0 else np.where(
                assign[:, 0, 0] >= 0, assign[:, 0, 0], top_anchor)
            anchors: list[NPArray] = [base]
            any_anchor = base >= 0
        for j in range(R):
            node_j = assign[:, si, j]
            has = node_j >= 0
            if rules_si and has.any():
                achieved = np.full(P, big, np.int64)
                attainable = np.full(P, big, np.int64)
                for idx in reversed(range(big)):
                    inc, exc = rules_si[idx]
                    achieved = np.where(
                        point_sat(anchors, node_j, inc, exc), idx, achieved)
                    attainable = np.where(
                        attainable_count(anchors, inc, exc) > 0,
                        idx, attainable)
                misses += int((has & any_anchor
                               & (achieved > attainable)).sum())
            if rules_si:
                anchors.append(node_j)
                any_anchor = any_anchor | has
            # Cross-state exclusivity: every pick occupies its node for
            # the whole partition.  Deduplicate (a malformed assignment
            # can repeat a node; the matrix path's bool [P, N] ``used``
            # dedups structurally, and duplicates are already counted by
            # check_assignment separately).
            dup = np.zeros(P, bool)
            for u in used_ids:
                dup |= (node_j == u) & has
            used_ids.append(np.where(has & ~dup, node_j, -1))
    return misses


def _count_hier_misses(problem: DenseProblem, assign: NPArray) -> int:
    """Feasible-tier hierarchy misses: a copy counts when it sits at a
    WORSE rule tier than some still-open valid node could have achieved
    given the same anchors (the solver's prefix anchoring, reference
    plan.go:185-191): state 0 anchors on the PREVIOUS primary (the
    solver's top_anchor — never on the node being judged), later states
    on the assigned primary plus the state's earlier picks.
    Unsatisfiable rules never count: when no candidate reaches a better
    tier, the flat fallback is correct behavior (plan.go:214-220).

    Two implementations, same contract: the group-counting fast path
    (O(P + N·L), _count_hier_misses_fast) whenever every rule's exclude
    level is strictly finer than its include level — the common tree
    shape — and the exhaustive [P, N] matrix path otherwise, run in
    P-blocks of _HIER_CHUNK so peak memory stays flat in P (at the
    north-star 100k x 10k that is ~40 MB of bool temporaries per rule,
    not ~1 GB)."""
    if _audit_rules_nest(problem):
        return _count_hier_misses_fast(problem, assign)
    P = assign.shape[0]
    total = 0
    for lo in range(0, P, _HIER_CHUNK):
        hi = min(lo + _HIER_CHUNK, P)
        total += _count_hier_misses_block(
            problem, assign[lo:hi], problem.prev[lo:hi])
    return total


def _count_hier_misses_block(
    problem: DenseProblem, assign: NPArray, prev: NPArray
) -> int:
    """One partition block of _count_hier_misses; per-anchor rule
    satisfaction folds in incrementally — each rule-bearing state costs
    one [n_rules, B, N] table plus one AND per ordinal."""
    P, S, R = assign.shape
    N = problem.N
    if not any(problem.rules.get(si) for si in range(S)):
        return 0
    rows = np.arange(P)
    top_anchor = prev[:, 0, 0]
    misses = 0
    used = np.zeros((P, N), bool)  # nodes this partition already occupies
    for si in range(S):
        rules_si = problem.rules.get(si) or []
        if rules_si:
            big = len(rules_si)
            base = top_anchor if si == 0 else np.where(
                assign[:, 0, 0] >= 0, assign[:, 0, 0], top_anchor)
            sat = _anchor_sat_np(base, problem.gids, problem.gid_valid,
                                 rules_si)
            any_anchor = base >= 0
        for j in range(R):
            node_j = assign[:, si, j]
            has = node_j >= 0
            if rules_si and has.any():
                tier = np.full((P, N), big, np.int32)
                for idx in reversed(range(len(rules_si))):
                    tier = np.where(sat[idx], idx, tier)
                cand_ok = problem.valid_node[None, :] & ~used
                attainable = np.min(np.where(cand_ok, tier, big), axis=1)
                achieved = tier[rows, np.clip(node_j, 0, N - 1)]
                misses += int((has & any_anchor
                               & (achieved > attainable)).sum())
            if rules_si:
                # This pick anchors the state's later ordinals.
                sat &= _anchor_sat_np(node_j, problem.gids,
                                      problem.gid_valid, rules_si)
                any_anchor = any_anchor | has
            used[rows, np.clip(node_j, 0, N - 1)] |= has
    return misses


def check_assignment(
    problem: DenseProblem, assign: NPArray
) -> dict[str, int]:
    """Constraint checker — the '0 violations' gate for the TPU backend.

    Counts (a) slot shortfalls beyond what an honest solver could fill,
    (b) same-partition node duplicates across states/slots, (c) assignments
    to removed nodes, (d) feasible-tier hierarchy-rule misses — copies
    placed at a worse rule tier than an open valid node could achieve
    (unmeetable rules degrade softly to the flat fallback and do NOT
    count, like the reference's warnings, plan.go:214-235).

    Pure numpy.  With nesting rules (every exclude level strictly finer
    than its include level — the common tree shape) the hierarchy audit
    runs by group counting in O(P + N·L), noise next to the solve at any
    size, so maybe_validate defaults it ON at every scale.  Exotic
    non-nesting rules fall back to the exhaustive [P, N] matrix audit
    (streamed in P-blocks: bounded memory, but O(P*N) time — tens of
    seconds at 100k x 10k), which stays behind the auto-validation
    ceiling unless explicitly requested.  See the
    ``validate_assignment`` wiring in plan_next_map_tpu /
    PlannerSession.replan."""
    assign = np.asarray(assign)
    P, S, R = assign.shape
    n_valid = int(problem.valid_node.sum())
    if P == 0:
        return {"duplicates": 0, "on_removed_nodes": 0,
                "unfilled_feasible_slots": 0, "hierarchy_misses": 0}

    def row_dups(rows: NPArray) -> NPArray:
        """Per row: count of valid entries equal to an earlier entry."""
        srt = np.sort(rows, axis=1)
        return ((srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)).sum(axis=1)

    flat = assign.reshape(P, S * R)
    dup = int(row_dups(flat).sum())
    held = flat[flat >= 0]
    removed = int((~problem.valid_node[held]).sum())

    # Shortfall per (partition, state): want vs got, capped by what an
    # honest solver could still fill given the distinct nodes the
    # partition already occupies through this state (prefix-distinct).
    shortfall = 0
    got_ps = (assign >= 0).sum(axis=2)  # [P, S]
    for si in range(S):
        want = int(problem.constraints[si])
        if want <= 0:
            continue
        pre = assign[:, :si + 1, :].reshape(P, -1)
        distinct = (pre >= 0).sum(axis=1) - row_dups(pre)
        got = got_ps[:, si]
        achievable = np.minimum(want, np.maximum(n_valid - distinct + got, 0))
        shortfall += int(np.maximum(achievable - got, 0).sum())
    return {"duplicates": dup, "on_removed_nodes": removed,
            "unfilled_feasible_slots": shortfall,
            "hierarchy_misses": _count_hier_misses(problem, assign)}


# Auto-validation ceiling for the EXOTIC-rules path only: the exhaustive
# matrix audit is O(P*N) time, so above this many cells it needs an
# explicit opt-in.  Nesting rules (the common case) audit in O(P + N·L)
# and validate by default at every scale.
_VALIDATE_AUTO_CELLS = 1 << 22


def maybe_validate(
    problem: DenseProblem, assign: NPArray, validate: Optional[bool],
    context: str, timer=None,
) -> Optional[dict[str, int]]:
    """Run check_assignment per the ``validate_assignment`` policy and
    surface violations as a UserWarning (reference analogue: constraint
    problems degrade to warnings, plan.go:231-235).  Returns the counts
    when the check ran, else None.  The check runs in the
    ``plan.validate`` phase span."""
    import warnings as _warnings

    if validate is None:
        validate = _audit_rules_nest(problem) or \
            problem.P * problem.N <= _VALIDATE_AUTO_CELLS
    if not validate:
        return None
    with phase_span("plan.validate", timer=timer):
        counts = check_assignment(problem, assign)
    if any(counts.values()):
        _warnings.warn(
            f"blance_tpu {context}: solver produced a constraint-violating "
            f"assignment: {counts}", UserWarning, stacklevel=3)
    return counts


def _tpu_supported(opts: PlanOptions) -> bool:
    """Can the batched solver honor these options' placement policy?

    The device score bakes in the default scoring formula plus the cbgt
    booster shape max(-weight, stickiness); an arbitrary Python
    ``node_scorer``/``node_sorter`` or a non-cbgt ``node_score_booster``
    cannot run inside the jitted computation (reference contract:
    plan.go:566-580,693-697).
    Negative node weights WITHOUT a booster are also unsupported: the
    reference ignores them entirely (plan.go:675-684 boosts only when the
    booster is set), while the device score would pin them."""
    if opts.node_scorer is not None or opts.node_sorter is not None:
        return False
    booster = opts.node_score_booster
    if booster is not None and \
            getattr(booster, "__blance_native__", None) != "cbgt":
        return False
    if booster is None and opts.node_weights and \
            any(w < 0 for w in opts.node_weights.values()):
        return False
    return True


def plan_next_map_tpu(
    prev_map: PartitionMap,
    partitions_to_assign: PartitionMap,
    nodes_all: list[str],
    nodes_to_remove: Optional[list[str]],
    nodes_to_add: Optional[list[str]],
    model: PartitionModel,
    opts: Optional[PlanOptions] = None,
    timer=None,
) -> tuple[PartitionMap, dict[str, list[str]]]:
    """TPU-backed equivalent of plan_next_map_greedy: one global batched
    solve instead of a sequential pass, through :func:`plan_pipeline`
    (its move lists are not materialized).  Same inputs/outputs.
    ``timer`` (utils.trace.PhaseTimer) attributes wall-clock to the
    pipeline's phases when provided."""
    next_map, warnings, _ = plan_pipeline(
        prev_map, partitions_to_assign, nodes_all, nodes_to_remove,
        nodes_to_add, model, opts, timer=timer, want_moves=False)
    return next_map, warnings
