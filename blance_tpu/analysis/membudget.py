"""HBM budgets: the device-memory contract as a declarative CI table.

The dense-memory guard (``plan/tensor.check_dense_memory``) rejects a
solve whose projected score matrix cannot fit the device — but it only
models the ONE dominant [P, S, N] allocation, and nothing bounds what a
program actually allocates end to end (temps, the fused pipeline's
diff/pack stages, the fleet's stacked [B, ...] batches).  GSPMD's
memory-driven contracts (arXiv:2105.04663) argue for budgeting programs,
not formulas.  This module promotes the per-entry HBM ceilings from
DESIGN.md §4b prose into ``HBM_BUDGETS``: for every solver dispatch
entry (the ``obs/device.entry`` labels the retrace budgets already pin),
the maximum peak allocation the AOT-compiled program may report at each
declared bucket-shape class.  The check rides the PR-8 cost-analysis
path — ``jax.jit(...).lower(...).compile()`` on ``ShapeDtypeStruct``
operands, then ``memory_analysis()`` via ``obs/device._extract_cost`` —
so ZERO solver FLOPs execute and no concrete arrays are materialized.

Rules (all fold through analysis/baseline.toml like every other pass):

- MEM001 — an entry's compiled peak allocation exceeds its budget.
- MEM002 — table drift: a measured entry with no budget row, a budget
  row with no measurable builder, or a budget row for a mesh-exempt
  entry.
- MEM003 — a budget row the dense-memory guard would already have
  rejected at that class's (P, N): the runtime guard refuses such a
  solve before dispatch, so the row is dead — and letting it exist
  would let the two ceilings drift apart.

Shape classes: the ``smoke`` class runs in every ``--ci`` / CI static
tier; the ``north`` class (the BASELINE.json 100k x 10k north-star, for
the sparse-engine entries the dense guard permits there) is opt-in via
``BLANCE_MEMBUDGET_NORTH=1`` because its AOT compiles cost minutes of
CPU, not seconds.

Budgets are ceilings calibrated on the pinned jax (0.4.37) CPU backend
with ~25% headroom over the measured peak (argument + output + temp
bytes — the backend-independent allocation model XLA's
``memory_analysis()`` reports).  Recalibrate after an intentional
change with ``BLANCE_MEMBUDGET_CALIBRATE=1 python -m
blance_tpu.analysis --membudget``, which prints the measured-vs-budget
table, then update the row — the same workflow as the retrace budgets'
``BLANCE_RECOMPILE_CALIBRATE=1``.

The sharded entries (``sharded.*``, ``sparse.sharded.*``) are
deliberately exempt (``MESH_EXEMPT``): their per-device peak scales with
the mesh actually constructed, so a number measured on CI's 8 virtual
CPU devices would pin the wrong artifact for every real TPU topology.
Their memory story is the per-shard slice of the same budgeted bodies.
"""

from __future__ import annotations

import os
from typing import TYPE_CHECKING, Callable, Optional

from .shape_audit import Dims

if TYPE_CHECKING:  # annotation-only
    from . import Finding

__all__ = [
    "HBM_BUDGETS",
    "SHAPE_CLASSES",
    "MESH_EXEMPT",
    "run_membudget_check",
    "measure_budget_table",
]

_PATH = "blance_tpu/analysis/membudget.py"

# -- shape classes -----------------------------------------------------------

# "smoke": a production-shaped but CPU-cheap class (every --ci run);
# "north": the BASELINE.json north-star 100k x 10k, sparse entries only
# (the dense guard rejects a 100k x 10k score matrix, and MEM003
# enforces that no dense row claims otherwise) — opt-in, see module
# docstring.
SHAPE_CLASSES: dict[str, Dims] = {
    "smoke": Dims(P=512, S=2, N=64, R=2, L=2),
    "north": Dims(P=100_000, S=2, N=10_000, R=2, L=2),
}

_NORTH_ENV = "BLANCE_MEMBUDGET_NORTH"
_CALIBRATE_ENV = "BLANCE_MEMBUDGET_CALIBRATE"


def _classes_to_run() -> list[str]:
    out = ["smoke"]
    if os.environ.get(_NORTH_ENV):
        out.append("north")
    return out


# -- builders ----------------------------------------------------------------

# entry label -> abstract-operand builder, reusing the shape-audit
# builders so the measured program IS the audited contract.  Keys are
# the live ``obs/device.entry`` labels (reality-guarded by
# tests/test_analysis.py against the dispatch sites' string literals).
_Builder = Callable[[Dims], "tuple[object, tuple, dict]"]


def _build_dense_bucketed(d: Dims):
    import numpy as np

    from . import shape_audit as sa

    db = sa._bucketed_dims(d)
    fn, args, kwargs = sa._build_converged(db)
    kwargs["p_real"] = sa._sds((), np.float32)
    return fn, args, kwargs


def _build_sparse_pipeline(d: Dims):
    from ..plan.tensor import _pipeline_sparse_cold_impl
    from . import shape_audit as sa

    return _pipeline_sparse_cold_impl, sa._solver_args(d, None), {
        "constraints": d.constraints, "rules": d.rules,
        "max_iterations": 4, "shortlist_k": sa._sparse_k(d),
        "sparse_impl": "xla", "favor_min_nodes": False}


def _builders() -> dict[str, _Builder]:
    # Imported lazily (pulls jax transitively) so the editor-loop lints
    # never pay for it; the shape-audit builders do the same internally.
    from . import shape_audit as sa

    return {
        "solve_dense.cold": lambda d: sa._build_converged(d),
        "solve_dense.carry": lambda d: sa._build_converged(d, carry=True),
        "solve_dense.bucketed": _build_dense_bucketed,
        "solve_dense.warm": lambda d: sa._build_warm(d),
        "sparse.cold": lambda d: sa._build_sparse_cold(d),
        "sparse.carry": lambda d: sa._build_sparse_cold(d, carry=True),
        "sparse.warm": lambda d: sa._build_sparse_warm(d),
        "sparse.pipeline": _build_sparse_pipeline,
        "pipeline.cold": lambda d: sa._build_pipeline_cold(d),
        "pipeline.warm": lambda d: sa._build_pipeline_warm(d),
        "fleet.cold": lambda d: sa._build_fleet_cold(d),
        "fleet.warm": lambda d: sa._build_fleet_warm(d),
        "sched.ranks": lambda d: sa._build_sched_ranks(d),
    }


# Entries whose peak allocation scales with the constructed mesh: a
# budget measured on CI's 8 virtual CPU devices would pin the wrong
# number for every real topology, so they are exempt BY NAME (a budget
# row for one of these is MEM002 table drift).  Their bodies are the
# same budgeted impls above, sliced per shard.
MESH_EXEMPT: frozenset[str] = frozenset({
    "sharded.cold",
    "sharded.warm",
    "sharded.pipeline",
    "sparse.sharded.cold",
    "sparse.sharded.warm",
})

# Entries whose program traces the dense [P, S, N] score matrix: MEM003
# cross-checks their budget rows against the runtime dense-memory
# guard's projection so the static table can never admit a class the
# guard rejects at dispatch.
_DENSE_ENTRIES: frozenset[str] = frozenset({
    "solve_dense.cold",
    "solve_dense.carry",
    "solve_dense.bucketed",
    "solve_dense.warm",
    "pipeline.cold",
    "pipeline.warm",
    "fleet.cold",
    "fleet.warm",
})

# The dense guard's reference ceiling for MEM003: the v5e 16 GiB HBM at
# plan/tensor._HBM_BUDGET_FRACTION, FIXED here rather than read from
# _device_hbm_bytes() so the static verdict cannot vary with the CI
# host (the runtime guard keeps its live device query).
_DENSE_GUARD_REF_BYTES = int(0.6 * 16 * 2**30)

# -- the table ---------------------------------------------------------------

# entry -> class -> peak-allocation ceiling in bytes.  Calibrated
# standalone (see module docstring); measured peaks on jax 0.4.37 CPU
# are noted inline so the next recalibration can see the drift.
HBM_BUDGETS: dict[str, dict[str, int]] = {
    # Dense converged fixpoint at smoke: ~355 KB measured (the
    # [P, S, N] f32 score matrix + operands + assign outputs).
    "solve_dense.cold": {"smoke": 450_000},
    "solve_dense.carry": {"smoke": 450_000},  # ~355 KB measured
    # The bucketed program pads (P, N) to bucket boundaries and adds the
    # traced p_real scalar: same peak as cold at this class (~355 KB).
    "solve_dense.bucketed": {"smoke": 450_000},
    # One-sweep repair: carry_used operand + masked sweep temps
    # (~344 KB measured).
    "solve_dense.warm": {"smoke": 430_000},
    # Sparse shortlist fixpoint: no dense matrix; [P, K] shortlist
    # gathers dominate (~142 KB measured at smoke).  North-star rows
    # are the point of the sparse engine — the only entries the dense
    # guard admits at 100k x 10k (~24.6 MB measured: linear in P, not
    # P*N).
    "sparse.cold": {"smoke": 180_000, "north": 31_000_000},
    "sparse.carry": {"smoke": 180_000, "north": 31_000_000},
    "sparse.warm": {"smoke": 165_000, "north": 30_000_000},
    # Fused sparse pipeline (shortlist -> solve -> diff -> pack in one
    # program): the diff op-list [P, 2*S*R] i32 triple rides on top
    # (~30.4 MB north measured; smoke ~173 KB on jax 0.4.37, ~227 KB on
    # the installed 0.9.0 — recalibrated for it in PR 21).
    "sparse.pipeline": {"smoke": 285_000, "north": 38_000_000},
    # Fused dense pipeline: dense matrix + diff/pack stages (~396 KB /
    # ~387 KB measured).
    "pipeline.cold": {"smoke": 500_000},
    "pipeline.warm": {"smoke": 490_000},
    # Fleet batch programs: B=4 stacked bucket-class operands, vmapped
    # over the same converged/warm bodies (~3.09 MB / ~1.41 MB
    # measured).
    "fleet.cold": {"smoke": 3_900_000},
    "fleet.warm": {"smoke": 1_800_000},
    # Critical-path rank sweep: [P, 4] in / [P, 4] out (~33 KB
    # measured — XLA's CPU scan temps, not the 16 KB operand pair).
    "sched.ranks": {"smoke": 42_000},
}


# -- measurement -------------------------------------------------------------


def _measure_entry(entry: str, d: Dims, builder: _Builder) -> float:
    """AOT-compile one entry at one class and return the peak
    allocation ``memory_analysis()`` reports.  Zero FLOPs: operands are
    ShapeDtypeStructs end to end."""
    from functools import partial

    import jax

    from ..obs.device import _extract_cost

    fn, args, kwargs = builder(d)
    # Static (non-array) kwargs ride a partial closure, exactly like the
    # shape audit's eval_shape runner: a tuple/str static must stay a
    # concrete Python value at trace time.
    statics = {k: v for k, v in kwargs.items()
               if not isinstance(v, jax.ShapeDtypeStruct)}
    arrays = {k: v for k, v in kwargs.items()
              if isinstance(v, jax.ShapeDtypeStruct)}
    compiled = jax.jit(partial(fn, **statics)).lower(
        *args, **arrays).compile()
    cost = _extract_cost(compiled)
    return float(cost["peak_alloc_bytes"])


def measure_budget_table(
        classes: Optional[list[str]] = None) -> list[dict[str, object]]:
    """Measure every budgeted (entry, class) row; returns dicts with
    entry/class/measured/budget/ok — what the calibration workflow
    prints.  Rows whose AOT compile raises carry ``error`` instead of
    ``measured``."""
    builders = _builders()
    rows: list[dict[str, object]] = []
    for ent in sorted(HBM_BUDGETS):
        for klass in sorted(HBM_BUDGETS[ent]):
            if classes is not None and klass not in classes:
                continue
            builder = builders.get(ent)
            dims = SHAPE_CLASSES.get(klass)
            if builder is None or dims is None:
                continue  # run_membudget_check reports these as MEM002
            budget = HBM_BUDGETS[ent][klass]
            row: dict[str, object] = {"entry": ent, "class": klass,
                                      "budget": budget}
            try:
                measured = _measure_entry(ent, dims, builder)
            except Exception as e:
                first = (str(e).splitlines() or [""])[0][:200]
                row["error"] = f"{type(e).__name__}: {first}"
                row["ok"] = False
            else:
                row["measured"] = measured
                row["ok"] = measured <= budget
            rows.append(row)
    return rows


def run_membudget_check() -> tuple[list["Finding"], int]:
    """The --membudget / --ci pass: structural table checks (MEM002 /
    MEM003, host-only) plus AOT measurement of every budgeted row at
    the classes in play (MEM001).  Returns (findings, rows measured)."""
    from . import Finding

    findings: list[Finding] = []
    builders = _builders()
    classes = _classes_to_run()

    # MEM002: table drift, both directions, plus exemption violations.
    for ent in sorted(builders):
        if ent not in HBM_BUDGETS:
            findings.append(Finding(
                rule="MEM002", path=_PATH, line=1, symbol=ent,
                message=f"dispatch entry {ent!r} has a measurable "
                        f"builder but no row in HBM_BUDGETS — every "
                        f"solver entry carries an HBM ceiling (docs/"
                        f"STATIC_ANALYSIS.md, 'HBM budgets')"))
    for ent in sorted(HBM_BUDGETS):
        if ent in MESH_EXEMPT:
            findings.append(Finding(
                rule="MEM002", path=_PATH, line=1, symbol=ent,
                message=f"budget row for mesh-exempt entry {ent!r}: "
                        f"its peak scales with the constructed mesh, "
                        f"so a fixed ceiling pins the wrong artifact "
                        f"— remove the row (MESH_EXEMPT)"))
        elif ent not in builders:
            findings.append(Finding(
                rule="MEM002", path=_PATH, line=1, symbol=ent,
                message=f"budget row {ent!r} matches no measurable "
                        f"builder — a renamed/removed dispatch entry "
                        f"leaves a dead ceiling; update the row"))
        for klass in sorted(HBM_BUDGETS[ent]):
            if klass not in SHAPE_CLASSES:
                findings.append(Finding(
                    rule="MEM002", path=_PATH, line=1,
                    symbol=f"{ent}@{klass}",
                    message=f"budget row {ent!r} names unknown shape "
                            f"class {klass!r} (declared: "
                            f"{sorted(SHAPE_CLASSES)})"))

    # MEM003: a dense-engine row at a class the runtime dense-memory
    # guard would reject before dispatch — the row is dead and lets the
    # two ceilings drift.
    from ..plan.tensor import projected_score_bytes

    for ent in sorted(HBM_BUDGETS):
        if ent not in _DENSE_ENTRIES:
            continue
        for klass in sorted(HBM_BUDGETS[ent]):
            dims = SHAPE_CLASSES.get(klass)
            if dims is None:
                continue
            projected = projected_score_bytes(dims.P, dims.N)
            if projected > _DENSE_GUARD_REF_BYTES:
                findings.append(Finding(
                    rule="MEM003", path=_PATH, line=1,
                    symbol=f"{ent}@{klass}",
                    message=f"budget row {ent!r} at class {klass!r} "
                            f"({dims.P}x{dims.N}): check_dense_memory "
                            f"projects {projected} score-matrix bytes, "
                            f"over the {_DENSE_GUARD_REF_BYTES}-byte "
                            f"reference ceiling — the runtime guard "
                            f"rejects this solve before dispatch, so "
                            f"the row is dead; use the sparse engine "
                            f"entries at this class"))

    # MEM001: measure what the table budgets, at the classes in play.
    rows = measure_budget_table(classes)
    if os.environ.get(_CALIBRATE_ENV):
        print("membudget calibration (peak_alloc_bytes):")
        for row in rows:
            got = row.get("measured", row.get("error"))
            print(f"  {row['entry']:<24} {row['class']:<6} "
                  f"measured={got} budget={row['budget']} "
                  f"ok={row['ok']}")
    for row in rows:
        ent = str(row["entry"])
        klass = str(row["class"])
        if "error" in row:
            findings.append(Finding(
                rule="MEM001", path=_PATH, line=1,
                symbol=f"{ent}@{klass}",
                message=f"AOT compile for {ent!r} at class {klass!r} "
                        f"failed, so its budget is unverifiable: "
                        f"{row['error']}"))
        elif not row["ok"]:
            findings.append(Finding(
                rule="MEM001", path=_PATH, line=1,
                symbol=f"{ent}@{klass}",
                message=f"entry {ent!r} at class {klass!r} peaks at "
                        f"{row['measured']:.0f} bytes, over its "
                        f"{row['budget']}-byte HBM budget — recalibrate "
                        f"deliberately (BLANCE_MEMBUDGET_CALIBRATE=1) "
                        f"or shrink the program"))
    return findings, len(rows)
