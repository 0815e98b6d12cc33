"""Public planning entry point with backend selection.

``plan_next_map`` is the equivalent of the reference's PlanNextMapEx
(reference: /root/reference/api.go:147-157).  Backends:

- "greedy": the exact sequential planner (semantics oracle; plan/greedy.py).
- "native": the same exact algorithm with the hot loop in C++ (plan/native.py
            + native/planner.cpp) — bit-identical results, ~100x throughput;
            falls back to "greedy" when unsupported hooks are in play.
- "tpu":    the batched cost-tensor planner (plan/tensor.py) — whole-problem
            scoring on device, constraint repair, sharded over partitions;
            one fused dispatch (plan_pipeline) per plan.
- "auto":   "tpu" for large problems, "native" (or "greedy") otherwise.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Optional

from ..core.types import (
    HierarchyRules,
    PartitionMap,
    PartitionModel,
    PlanOptions,
)
from ..obs import get_recorder
from .greedy import plan_next_map_greedy

if TYPE_CHECKING:  # annotation-only
    from ..utils.trace import PhaseTimer

__all__ = ["plan_next_map", "plan_next_map_legacy"]

# Below this many (partitions x nodes), the exact greedy is faster than a
# device round-trip; above it, the batched solver wins.  The library
# default for backend="auto"; override per deployment with
# PlanOptions.auto_tpu_threshold (the calibration behind this constant is
# one host class — crossovers move with interconnect and host CPU).
_AUTO_TPU_THRESHOLD = 256 * 1024


def plan_next_map(
    prev_map: PartitionMap,
    partitions_to_assign: PartitionMap,
    nodes_all: list[str],
    nodes_to_remove: Optional[list[str]] = None,
    nodes_to_add: Optional[list[str]] = None,
    model: Optional[PartitionModel] = None,
    opts: Optional[PlanOptions] = None,
    backend: str = "greedy",
    timer: Optional["PhaseTimer"] = None,
) -> tuple[PartitionMap, dict[str, list[str]]]:
    """Compute the next balanced partition map.

    Returns (next_map, warnings) where warnings is keyed by partition name
    (constraint shortfalls degrade to warnings, never errors — reference
    plan.go:231-235).  ``timer`` (utils.trace.PhaseTimer) attributes
    wall-clock to the pipeline phases (encode, dispatch, validate,
    decode) on the tpu backend.
    """
    if model is None:
        raise ValueError("model is required")
    opts = opts or PlanOptions()

    requested = backend
    if backend == "auto":
        size = len(partitions_to_assign) * len(nodes_all)
        threshold = (_AUTO_TPU_THRESHOLD
                     if opts.auto_tpu_threshold is None
                     else int(opts.auto_tpu_threshold))
        backend = "tpu" if size >= threshold else "native"

    with get_recorder().span(
            "plan.plan_next_map", backend=backend, requested=requested,
            partitions=len(partitions_to_assign), nodes=len(nodes_all)):
        if backend == "greedy":
            return plan_next_map_greedy(
                prev_map, partitions_to_assign, nodes_all,
                nodes_to_remove, nodes_to_add, model, opts)
        if backend == "native":
            from .native import plan_next_map_native  # deferred: may compile

            return plan_next_map_native(
                prev_map, partitions_to_assign, nodes_all,
                nodes_to_remove, nodes_to_add, model, opts)
        if backend == "tpu":
            from .tensor import plan_next_map_tpu  # deferred: imports jax

            return plan_next_map_tpu(
                prev_map, partitions_to_assign, nodes_all,
                nodes_to_remove, nodes_to_add, model, opts, timer=timer)
        raise ValueError(f"unknown backend: {backend!r}")


def plan_next_map_legacy(
    prev_map: PartitionMap,
    partitions_to_assign: PartitionMap,
    nodes_all: list[str],
    nodes_to_remove: Optional[list[str]],
    nodes_to_add: Optional[list[str]],
    model: PartitionModel,
    model_state_constraints: Optional[dict[str, int]] = None,
    partition_weights: Optional[dict[str, int]] = None,
    state_stickiness: Optional[dict[str, int]] = None,
    node_weights: Optional[dict[str, int]] = None,
    node_hierarchy: Optional[dict[str, str]] = None,
    hierarchy_rules: Optional["HierarchyRules"] = None,
    backend: str = "greedy",
) -> tuple[PartitionMap, dict[str, list[str]]]:
    """Positional-options compatibility shim mirroring the reference's
    deprecated PlanNextMap signature (api.go:109-132); prefer plan_next_map
    with PlanOptions."""
    return plan_next_map(
        prev_map, partitions_to_assign, nodes_all,
        nodes_to_remove, nodes_to_add, model,
        PlanOptions(
            model_state_constraints=model_state_constraints,
            partition_weights=partition_weights,
            state_stickiness=state_stickiness,
            node_weights=node_weights,
            node_hierarchy=node_hierarchy,
            hierarchy_rules=hierarchy_rules,
        ),
        backend=backend,
    )
