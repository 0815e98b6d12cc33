"""Dense encoding: PartitionMap <-> int32/float32 arrays.

The reference's data model is maps of strings (reference api.go:24-36); the
TPU planner needs dense tensors.  This module interns node/partition/state
names to ids and packs the planning problem into arrays:

- assign[P, S, R] : int32 node ids, -1 = empty slot (R = max slots seen).
- constraints[S]  : per-state target copy counts, priority-ordered.
- weights         : float32 partition/node weights.
- hierarchy       : per-level group ids per node (see
  core.hierarchy.level_group_ids) so include/exclude rules are integer
  compares, never N x N masks (SURVEY.md §7 hard part 2).

Partitions are ordered by the same zero-padded-numeric-else-raw name key the
planner sorts by, so dense ids match the greedy planner's deterministic
iteration order.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from . import marshal as _marshal
from .hierarchy import find_ancestor, level_group_ids
from .setops import strings_remove
from .types import (
    Partition,
    PartitionMap,
    PartitionModel,
    PlanOptions,
)

__all__ = ["DenseProblem", "encode_problem", "decode_assignment",
           "bucket_size", "pad_to", "pad_problem_arrays",
           "stack_problem_arrays", "pack_assignment_core",
           "pack_assignment", "prev_from_entries_core",
           "prev_from_entries", "pack_slot_rows", "strip_prev_rows"]

# Host-side array annotation shorthand.  numpy's ndarray is generic
# under the stubs, and every module under the mypy
# disallow_any_generics ratchet must parameterize it at each spelling.
# Dtype precision is not what that gate buys (the dense encoding is
# int32/float32 by construction, asserted here at encode time) —
# structural parameterization is.
NPArray = np.ndarray[Any, np.dtype[Any]]

# Shape-bucket granularity: buckets per power-of-two octave.  8 keeps the
# worst-case padding overhead at 1/8 = 12.5% of the axis while collapsing
# the jit-cache key space to ~8 entries per octave — the GSPMD insight
# (arXiv:2105.04663) that repeated invocation is cheap exactly when the
# compiled program's static shapes are reused.
_BUCKET_GRANULARITY = 8


def bucket_size(x: int, granularity: int = _BUCKET_GRANULARITY) -> int:
    """Round ``x`` up to the next static-shape bucket.

    Buckets are multiples of 2**floor(log2(x)) / granularity, i.e. the
    octave [2^k, 2^(k+1)) is split into ``granularity`` evenly spaced
    sizes.  A cluster drifting 1000 -> 1007 -> 998 nodes maps to one
    bucket (1024), so every replan hits the jit cache instead of
    recompiling; the pad rows/columns are inert by construction (weight-0
    partitions, invalid nodes — the same trick parallel/sharded.py uses
    for mesh divisibility)."""
    if x <= granularity:
        return max(x, 0)
    step = max(1, (1 << (x.bit_length() - 1)) // granularity)
    return -(-x // step) * step


def pad_to(arr: np.ndarray, axis: int, target: int,
           fill: float | int | bool) -> np.ndarray:
    """Pad ``arr`` along ``axis`` up to ``target`` entries with ``fill``;
    no-op when already that long.  The one padding spelling shared by
    shape bucketing here and mesh-divisibility padding in
    parallel/sharded.py."""
    cur = arr.shape[axis]
    if cur >= target:
        return arr
    pad_shape = list(arr.shape)
    pad_shape[axis] = target - cur
    return np.concatenate(
        [arr, np.full(pad_shape, fill, arr.dtype)], axis=axis)


def pad_problem_arrays(
    prev: np.ndarray,
    partition_weights: np.ndarray,
    node_weights: np.ndarray,
    valid_node: np.ndarray,
    stickiness: np.ndarray,
    gids: np.ndarray,
    gid_valid: np.ndarray,
    p_target: int,
    n_target: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray,
           np.ndarray, np.ndarray]:
    """Pad one problem's solver arrays to (p_target, n_target), inertly.

    THE bit-neutral padding recipe, shared by plan_pipeline's
    shape-bucketed path and the fleet batch stacker (plan/fleet.py):
    pad partitions are weight-0 bidders (their assignments are sliced
    off by the caller) and pad nodes invalid (valid=False => zero
    capacity, +INF score, gid_valid=False), the same inert-padding
    contract parallel/sharded.py relies on, so the real rows solve
    identically to the unpadded problem.  Parameters and the returned
    tuple both follow the solver's positional order (prev, pweights,
    nweights, valid, stickiness, gids, gid_valid) so the call sites
    splat straight into solve_dense and friends."""
    prev = pad_to(prev, 0, p_target, -1)
    partition_weights = pad_to(partition_weights, 0, p_target, 0.0)
    stickiness = pad_to(stickiness, 0, p_target, 0.0)
    node_weights = pad_to(node_weights, 0, n_target, 1.0)
    valid_node = pad_to(valid_node, 0, n_target, False)
    gids = pad_to(gids, 1, n_target, -1)
    gid_valid = pad_to(gid_valid, 1, n_target, False)
    return (prev, partition_weights, node_weights, valid_node,
            stickiness, gids, gid_valid)


def stack_problem_arrays(
    padded: "list[tuple[np.ndarray, ...]]",
) -> tuple[np.ndarray, ...]:
    """Stack B same-shape padded array tuples into [B, ...] batch
    tensors (one np.stack per operand, solver positional order
    preserved).  The batch analog of pad_problem_arrays: pad first so
    every element of a bucket class shares its static shape, then
    stack — the [B, P, S, N] problem tensor the fleet solver vmaps
    over."""
    if not padded:
        raise ValueError("stack_problem_arrays: empty batch")
    width = len(padded[0])
    return tuple(
        np.stack([np.asarray(arrs[i]) for arrs in padded])
        for i in range(width))


# --- device integer cores ---------------------------------------------------
#
# The string<->id interning at the map edges is inherently host work, but
# the INTEGER cores of encode (filling prev[P, S, R] from interned
# entries) and decode (packing each state row's non-empty slots left and
# counting them) are pure array programs.  They live here as traceable
# jnp functions so the fused plan pipeline (plan/tensor.plan_pipeline)
# can run them INSIDE its single jitted dispatch — decode's host share
# shrinks to one id->name gather, and nothing round-trips between solve
# and diff.  jax imports stay function-local: this module is also the
# greedy/native path's encoder, which must import without touching jax.


def pack_assignment_core(assign):  # type: ignore[no-untyped-def]
    """Decode's integer core, traceable: pack every (partition, state)
    row's non-empty slots left (stable, preserving slot order) and count
    them.  [P, S, R] int32 -> (packed [P, S, R] int32, counts [P, S]
    int32).  Bit-equivalent to the numpy pack in decode_assignment
    (pinned by tests), so device-packed rows feed the same host
    materializer."""
    import jax.numpy as jnp

    mask = assign >= 0
    order = jnp.argsort(~mask, axis=2, stable=True)
    packed = jnp.take_along_axis(assign, order, axis=2)
    counts = jnp.sum(mask, axis=2, dtype=jnp.int32)
    return packed, counts


_pack_assignment_jit = None


def pack_assignment(assign):  # type: ignore[no-untyped-def]
    """Host-facing jitted spelling of :func:`pack_assignment_core`."""
    global _pack_assignment_jit
    if _pack_assignment_jit is None:
        import jax

        _pack_assignment_jit = jax.jit(pack_assignment_core)
    return _pack_assignment_jit(assign)


def prev_from_entries_core(pi, si, ri, node, p: int, s: int, r: int):  # type: ignore[no-untyped-def]
    """Encode's integer core, traceable: scatter interned (partition,
    state, slot, node) entry columns into a dense prev[P, S, R] (-1
    empties).  Out-of-range entries drop (mode="drop"), so callers can
    pad entry lists with -1 rows.  Equivalent to encode_problem's host
    fill loop for already-interned entries (pinned by tests) — the
    spelling a device-resident caller uses to apply map deltas without
    re-marshalling strings."""
    import jax.numpy as jnp

    flat = pi * (s * r) + si * r + ri
    flat = jnp.where((pi >= 0) & (si >= 0) & (ri >= 0), flat, p * s * r)
    return jnp.full((p * s * r,), -1, jnp.int32).at[flat].set(
        node.astype(jnp.int32), mode="drop").reshape(p, s, r)


_prev_from_entries_jit = None


def prev_from_entries(pi, si, ri, node, p: int, s: int, r: int):  # type: ignore[no-untyped-def]
    """Jitted spelling of :func:`prev_from_entries_core` (static dims)."""
    global _prev_from_entries_jit
    if _prev_from_entries_jit is None:
        import jax
        from functools import partial as _partial

        _prev_from_entries_jit = _partial(
            jax.jit, static_argnames=("p", "s", "r"))(prev_from_entries_core)
    return _prev_from_entries_jit(pi, si, ri, node, p=p, s=s, r=r)


def pack_slot_rows(rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host pack of ``[..., S, R]`` assignment rows: non-empty slots
    left (stable, preserving slot order) + per-(row, state) counts.

    THE numpy spelling of decode_assignment's per-state pack (argsort
    on the empty mask, ``kind="stable"``) lifted to whole rows, and the
    host twin of the traceable :func:`pack_assignment_core` — shared by
    the encode-residency layer (plan/resident.py) so its delta-patched
    ``prev`` is bit-equal to what a fresh ``encode_problem`` of the
    decoded map would scatter."""
    mask = rows >= 0
    counts = mask.sum(axis=-1).astype(np.int64)
    if rows.shape[-1] <= 1:
        return rows.copy(), counts  # one slot: already packed
    order = np.argsort(~mask, axis=-1, kind="stable")
    packed = np.take_along_axis(rows, order, axis=-1)
    return packed, counts


def strip_prev_rows(prev: np.ndarray,
                    node_ids: np.ndarray) -> tuple[np.ndarray,
                                                   np.ndarray]:
    """Remove every placement on ``node_ids`` from ``prev`` [P, S, R]
    and re-pack the touched rows left; returns ``(patched prev — a new
    array, dirty row mask [P])``.

    The array twin of ``rebalance._strip_nodes`` + re-encode: a fresh
    ``encode_problem`` of the stripped map fills each touched row with
    the surviving entries in their original order, packed left — which
    is exactly mask-to-(-1) + :func:`pack_slot_rows` on those rows.
    Untouched rows are returned byte-identical (same values, new array
    object: callers memoize on array identity, so an in-place patch
    could serve stale memo hits)."""
    hit = np.isin(prev, node_ids)
    dirty = hit.any(axis=(1, 2))
    out = prev.copy()
    if dirty.any():
        sub = out[dirty]
        sub[hit[dirty]] = -1
        packed, _counts = pack_slot_rows(sub)
        out[dirty] = packed
    return out, dirty


@dataclass
class DenseProblem:
    """A fully interned planning problem, ready for the tensor planner."""

    nodes: list[str]  # id -> name, in nodes_all order (ties break by this)
    partitions: list[str]  # id -> name, in planner sort order
    states: list[str]  # priority-ordered (sort_state_names)

    constraints: np.ndarray  # [S] int32
    prev: np.ndarray  # [P, S, R] int32 node ids, -1 empty
    partition_weights: np.ndarray  # [P] float32
    node_weights: np.ndarray  # [N] float32 (raw; may be negative)
    valid_node: np.ndarray  # [N] bool — False for nodes_to_remove
    stickiness: np.ndarray  # [P, S] float32

    # Hierarchy: group ids per level per node; level 0 = the node itself.
    # gids[l, n] == gids[l, m] iff nodes n, m share their level-l ancestor.
    gids: np.ndarray  # [L, N] int32
    gid_valid: np.ndarray  # [L, N] bool — ancestor exists at that level
    # Per state, list of (include_level, exclude_level) rules.
    rules: dict[int, list[tuple[int, int]]]

    @property
    def P(self) -> int:
        return len(self.partitions)

    @property
    def N(self) -> int:
        return len(self.nodes)

    @property
    def S(self) -> int:
        return len(self.states)

    @property
    def R(self) -> int:
        return self.prev.shape[2] if self.prev.size else 0


def encode_problem(
    prev_map: PartitionMap,
    partitions_to_assign: PartitionMap,
    nodes_all: list[str],
    nodes_to_remove: Optional[list[str]],
    model: PartitionModel,
    opts: PlanOptions,
) -> DenseProblem:
    """Intern and pack a planning problem into dense arrays."""
    # Deferred to avoid a core <-> plan import cycle at package init; the
    # greedy key function is the single source of truth so dense ids match
    # the greedy planner's deterministic iteration order exactly.
    from ..plan.greedy import sort_state_names, sorted_by_partition_name

    nodes = list(nodes_all)
    node_index = {n: i for i, n in enumerate(nodes)}

    partitions = sorted_by_partition_name(partitions_to_assign.keys())
    states = sort_state_names(model)
    state_index = {s: i for i, s in enumerate(states)}

    constraints = np.zeros(len(states), dtype=np.int32)
    for s, st in model.items():
        c = st.constraints
        if opts.model_state_constraints is not None:
            c = opts.model_state_constraints.get(s, c)
        constraints[state_index[s]] = c

    # Slot depth: enough for the widest constraint and the widest prev row.
    # The R scan and the [P, S, R] fill each touch every cell once; at 100k
    # partitions that dict/list traversal dominates end-to-end wall-clock,
    # so both run in the native marshalling layer when it's available
    # (native/marshal.c), with this pure-Python path as the fallback.
    # The C fast path is stricter about shapes (real dicts, real lists);
    # any structural surprise raises TypeError there and we fall back to
    # this loop, which tolerates arbitrary Mappings/Sequences.
    native = _marshal.get()
    r_max = int(constraints.max()) if len(constraints) else 0
    filled = None
    if native is not None:
        try:
            r_max = max(r_max, native.max_slots(
                partitions, prev_map, partitions_to_assign, state_index))
            r_max = max(r_max, 1)
            P, S = len(partitions), len(states)
            filled = np.empty((P, S, r_max), dtype=np.int32)
            native.fill_prev(filled, P, S, r_max, partitions, prev_map,
                             partitions_to_assign, state_index, node_index)
        except (TypeError, AttributeError):
            # AttributeError: a None/falsy entry in prev_map reaches
            # .nodes_by_state in C; the Python loop below tolerates it
            # via the `or partitions_to_assign[...]` fallthrough.
            filled = None
            r_max = int(constraints.max()) if len(constraints) else 0
    if filled is None:
        for pname in partitions:
            src = prev_map.get(pname) or partitions_to_assign[pname]
            for s, ns in src.nodes_by_state.items():
                if s in state_index:
                    r_max = max(r_max, len(ns))
        r_max = max(r_max, 1)

    P, S, N = len(partitions), len(states), len(nodes)
    if filled is not None:
        prev = filled
    else:
        prev = np.full((P, S, r_max), -1, dtype=np.int32)
        for pi, pname in enumerate(partitions):
            src = prev_map.get(pname) or partitions_to_assign.get(pname)
            if src is None:
                continue
            for s, ns in src.nodes_by_state.items():
                si = state_index.get(s)
                if si is None:
                    continue
                for ri, node in enumerate(ns[:r_max]):
                    prev[pi, si, ri] = node_index.get(node, -1)

    pweights = np.ones(P, dtype=np.float32)
    if opts.partition_weights:
        for pi, pname in enumerate(partitions):
            pweights[pi] = opts.partition_weights.get(pname, 1)

    nweights = np.ones(N, dtype=np.float32)
    if opts.node_weights:
        for ni, n in enumerate(nodes):
            nweights[ni] = opts.node_weights.get(n, 1)

    valid = np.ones(N, dtype=bool)
    if nodes_to_remove:
        removed = set(nodes_to_remove)
        for ni, n in enumerate(nodes):
            if n in removed:
                valid[ni] = False

    # Stickiness per (partition, state), with the reference's resolution
    # order (plan.go:104-115): partition weight if present, else state
    # stickiness (gated on partition_weights presence unless the standalone
    # compat switch), else 1.5.
    stickiness = np.full((P, S), 1.5, dtype=np.float32)
    pw = opts.partition_weights
    ss = opts.state_stickiness
    ss_active = ss is not None and (pw is not None or opts.state_stickiness_standalone)
    if pw or ss_active:
        for pi, pname in enumerate(partitions):
            if pw is not None and pname in pw:
                stickiness[pi, :] = pw[pname]
            elif ss_active:
                for si, s in enumerate(states):
                    if s in ss:
                        stickiness[pi, si] = ss[s]

    # Hierarchy group ids.  Levels needed = max level referenced by any rule.
    rules_by_state: dict[int, list[tuple[int, int]]] = {}
    max_level = 0
    if opts.hierarchy_rules:
        for s, rl in opts.hierarchy_rules.items():
            si = state_index.get(s)
            if si is None:
                continue
            rules_by_state[si] = [
                (r.include_level, r.exclude_level) for r in rl
            ]
            for r in rl:
                max_level = max(max_level, r.include_level, r.exclude_level)

    gid_rows = level_group_ids(nodes, opts.node_hierarchy, max_level)
    gids = np.asarray(gid_rows, dtype=np.int32).reshape(max_level + 1, N) \
        if N else np.zeros((max_level + 1, 0), np.int32)
    gid_valid = np.ones((max_level + 1, N), dtype=bool)
    for level in range(max_level + 1):
        for ni, n in enumerate(nodes):
            gid_valid[level, ni] = find_ancestor(n, opts.node_hierarchy, level) != ""

    return DenseProblem(
        nodes=nodes,
        partitions=partitions,
        states=states,
        constraints=constraints,
        prev=prev,
        partition_weights=pweights,
        node_weights=nweights,
        valid_node=valid,
        stickiness=stickiness,
        gids=gids,
        gid_valid=gid_valid,
        rules=rules_by_state,
    )


def decode_assignment(
    problem: DenseProblem,
    assign: np.ndarray,  # [P, S, R] int32 node ids, -1 empty
    partitions_to_assign: PartitionMap,
    nodes_to_remove: Optional[list[str]] = None,
    *,
    packed: Optional[np.ndarray] = None,  # [P, S, R] device-packed rows
    counts: Optional[np.ndarray] = None,  # [P, S] per-row filled counts
) -> tuple[PartitionMap, dict[str, list[str]]]:
    """Dense assignment -> PartitionMap + constraint-shortfall warnings.

    States absent from the model keep their (removed-node-stripped) previous
    assignment, matching the greedy planner's pass-through of unmodeled
    states.  Vectorized over P: the id->name gather, empty-slot packing and
    shortfall detection run as whole-array numpy ops so decode stays off the
    end-to-end critical path at 100k partitions (BASELINE.md).

    ``packed``/``counts`` (both or neither) short-circuit the host pack:
    the fused plan pipeline computes them on device inside its single
    dispatch (:func:`pack_assignment_core`), leaving only the id->name
    gather and list building here.
    """
    assign = np.asarray(assign)
    warnings: dict[str, list[str]] = {}
    P = problem.P
    if (packed is None) != (counts is None):
        raise ValueError("decode_assignment: packed and counts must be "
                         "passed together")

    # Per modeled state with constraints > 0: pack non-empty slots left
    # (stable, preserving slot order), gather names in one shot, and convert
    # to nested Python lists at C speed.
    names_arr = np.asarray(problem.nodes, dtype=object) \
        if problem.nodes else np.zeros(0, dtype=object)
    per_state_rows: dict[int, list[list[str]]] = {}
    per_state_counts: dict[int, np.ndarray] = {}
    for si, sname in enumerate(problem.states):
        want = int(problem.constraints[si])
        if want <= 0:
            continue
        if P == 0 or not problem.nodes:
            # Degenerate: nothing assignable; every slot is a shortfall.
            per_state_rows[si] = [[] for _ in range(P)]
            per_state_counts[si] = np.zeros(P, dtype=np.int64)
            continue
        if packed is not None and counts is not None:
            row_ids = np.asarray(packed)[:, si, :]
            row_counts = np.asarray(counts)[:, si].astype(np.int64)
        else:
            ids = assign[:, si, :]
            mask = ids >= 0
            row_counts = mask.sum(axis=1)
            order = np.argsort(~mask, axis=1, kind="stable")
            row_ids = np.take_along_axis(ids, order, axis=1)
        names = names_arr[np.maximum(row_ids, 0)]
        nested = names.tolist()
        if row_counts.min() == row_ids.shape[1]:  # all slots filled
            per_state_rows[si] = nested
        else:
            per_state_rows[si] = [
                row[:c] for row, c in zip(nested, row_counts.tolist())]
        per_state_counts[si] = row_counts

    # Partitions needing the slow path: source has unmodeled or
    # zero-constraint states to pass through (rare in practice).
    constraints = problem.constraints
    modeled = [
        (si, s) for si, s in enumerate(problem.states)
        if int(constraints[si]) > 0
    ]
    solved_states = {s for _, s in modeled}
    mod_names = [s for _, s in modeled]
    rows_per_state = [per_state_rows[si] for si, _ in modeled]
    removed = nodes_to_remove or []
    native = _marshal.get()
    next_map = None
    if native is not None:
        try:
            next_map = native.build_map(
                Partition, problem.partitions, mod_names, rows_per_state,
                partitions_to_assign, solved_states, set(removed))
        except (TypeError, AttributeError):
            next_map = None  # structural surprise: pure-Python fallback
    if next_map is None:
        next_map = {}
        rows_iter = zip(*rows_per_state) if rows_per_state \
            else (() for _ in range(P))
        get_src = partitions_to_assign.get
        for pname, vals in zip(problem.partitions, rows_iter):
            src = get_src(pname)
            # keys() <= set is a C-level check; the passthrough branch
            # (source carries unmodeled / zero-constraint states) is rare
            # in practice.
            if src is None or src.nodes_by_state.keys() <= solved_states:
                nbs = dict(zip(mod_names, vals))
            else:
                nbs = {}
                for s, ns in src.nodes_by_state.items():
                    if s not in solved_states:
                        nbs[s] = strings_remove(ns, removed)
                for s, v in zip(mod_names, vals):
                    nbs[s] = v
            next_map[pname] = Partition(pname, nbs)

    for si, sname in modeled:
        want = int(constraints[si])
        short = np.nonzero(per_state_counts[si] < want)[0]
        for pi in short:
            pname = problem.partitions[pi]
            warnings.setdefault(pname, []).append(
                "could not meet constraints: %d, stateName: %s,"
                " partitionName: %s" % (want, sname, pname)
            )

    return next_map, warnings
