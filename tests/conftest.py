"""Test configuration.

Forces JAX onto the host CPU platform with 8 virtual devices BEFORE jax is
imported anywhere, so multi-chip sharding tests (jax.sharding.Mesh over 8
devices) run on machines with no TPU attached.  Real-TPU benchmarking happens
in benchmark/ (BENCHMARK.json), not in the test suite.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import logging  # noqa: E402

import pytest  # noqa: E402

# The fast-CI tier (pytest -m smoke): every data-model / moves / planner
# golden module plus the cheap orchestrator goldens — the suites most
# likely to catch a regression per second of runtime.  The heavy tiers
# (fuzz parametrizations, 8-device sharding, orchestrator stress, Pallas
# interpret runs) stay full-suite-only.  Module-level so the list is one
# place, applied at collection time.
SMOKE_MODULES = {
    "test_setops",
    "test_hierarchy",
    "test_moves",
    "test_moves_batch",
    "test_marshal",
    "test_plan_helpers",
    "test_plan",
    "test_control",
    "test_rebalance",
    "test_orchestrate",
    "test_plan_vis",
    "test_plan_hierarchy",
    "test_session",
    "test_native",
    "test_ops_reduce2",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        module = item.nodeid.split("::", 1)[0].rsplit("/", 1)[-1]
        if module.removesuffix(".py") in SMOKE_MODULES:
            item.add_marker(pytest.mark.smoke)


_EXIT_STATUS = [0]


@pytest.hookimpl(trylast=True)
def pytest_sessionfinish(session, exitstatus):
    _EXIT_STATUS[0] = int(exitstatus)


@pytest.hookimpl(trylast=True)
def pytest_unconfigure(config):
    """Skip interpreter teardown after the summary is written.

    A full tier-1 process accumulates thousands of compiled XLA
    executables; their destructor cascade (plus the final GC of the
    multi-GB object graph) burns tens of seconds AFTER the last test —
    wall-clock the CI/driver timeout still charges to the suite, with
    zero verification value.  Once pytest has printed its terminal
    summary (unconfigure runs after the sessionfinish wrapper's tail),
    hard-exit with pytest's own status.  Set BLANCE_FAST_EXIT=0 to
    keep normal teardown (e.g. when profiling shutdown or running
    under coverage tools that finalize at exit)."""
    if os.environ.get("BLANCE_FAST_EXIT", "1") == "0":
        return
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(_EXIT_STATUS[0])


# -- static-contract fixtures (docs/STATIC_ANALYSIS.md) ---------------------

# Transfer-guard allowlist contract: the pure solver paths convert at the
# boundaries EXPLICITLY (jnp.asarray in / np.asarray-device_get out), so
# under jax.transfer_guard("disallow") — which blocks only IMPLICIT
# transfers — an accidental host sync inside solve_dense /
# solve_dense_warm (a raw numpy operand reaching a jit call, a silent
# device round-trip between dispatches) fails the test instead of
# silently eating a sync.  The known host prechecks (the O(N) carry
# routing check in PlannerSession._capacity_shrank, the tier-band guard)
# already read through explicit np.asarray, which the guard permits.
_TRANSFER_GUARD_MODULES = {"test_warm_replan", "test_pipeline"}


@pytest.fixture(autouse=True)
def _solver_transfer_guard(request):
    """Autouse for the pure-solver suites: any implicit host<->device
    transfer inside the solve is a test failure.  Opt in elsewhere with
    the named ``no_implicit_transfers`` fixture."""
    module = request.node.nodeid.split("::", 1)[0] \
        .rsplit("/", 1)[-1].removesuffix(".py")
    if module not in _TRANSFER_GUARD_MODULES:
        yield
        return
    with jax.transfer_guard("disallow"):
        yield


@pytest.fixture
def no_implicit_transfers():
    """Opt-in: run one test under jax.transfer_guard("disallow")."""
    with jax.transfer_guard("disallow"):
        yield


# Recompile-count regression budgets (the PR-2 shape-bucketing
# guarantee): per module, the maximum number of XLA compilations the
# suite may trigger when run standalone (a shared-process run prewarms
# caches and compiles strictly less).  Counted from jax's own
# log_compiles stream, so shard_map-level compiles are included.
# Calibrated standalone values, with ~30% headroom for jax-internal
# helper jits; a solver entry point growing a new retrace per call site
# blows well past these.  Recalibrate with
# BLANCE_RECOMPILE_CALIBRATE=1 pytest tests/<module>.py.
# Standalone calibration (jax 0.4.37 / CPU, 8 virtual devices):
#   test_warm_replan  total=166 (impl 9, warm 6, carry 3; the '<unnamed'
#                     bulk is eager-op + shard_map programs, inflated by
#                     transfer-guard state flips busting the eager cache)
#   test_sharded      total=190 (shard_map bodies log as '<unnamed')
#   test_sharded_2d   total=171 (shared-process; standalone runs higher)
#   test_fleet        total=35  (impl 10, fleet_cold 9, fleet_warm 4 —
#                     the fleet/service suites ride the same bucketed
#                     batch programs, so the budget is tight by design)
# The per-ENTRY-POINT companion to these per-module budgets is the
# declarative RETRACE_BUDGETS table in blance_tpu/analysis/retrace.py,
# checked by `python -m blance_tpu.analysis --ci` with compiles
# attributed to their owning dispatch site (obs/device.py).
_RECOMPILE_BUDGETS = {
    "test_warm_replan": 220,
    "test_sharded": 260,
    "test_sharded_2d": 260,
    "test_fleet": 50,
    #   test_encode_resident total=20 standalone (fleet_cold 11,
    #                     fleet_warm 9 — the residency layer adds ZERO
    #                     new device programs by design: every cycle
    #                     rides the existing bucketed fleet batch
    #                     entries, so the budget pins exactly that)
    "test_encode_resident": 28,
    #   test_pipeline     total=360 standalone (impl 8+7, solve 7, diff 7,
    #                     '<unnamed' bulk = eager ops + the memoized
    #                     sharded-pipeline programs across 5 meshes)
    "test_pipeline": 470,
}


class _CompileCounter(logging.Handler):
    def __init__(self) -> None:
        super().__init__()
        self.by_name: dict = {}

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if not msg.startswith("Compiling "):
            return
        name = msg.split(" ", 2)[1]
        self.by_name[name] = self.by_name.get(name, 0) + 1

    @property
    def total(self) -> int:
        return sum(self.by_name.values())


@pytest.fixture(scope="module", autouse=True)
def _recompile_budget(request):
    """Module-scoped retrace budget for the solver suites: snapshots XLA
    compile events across the module and fails teardown when the count
    exceeds the pinned budget — so a change that breaks the jit-cache
    contract (new dynamic shape, a traced value becoming static, a
    static becoming traced) cannot land silently."""
    module = request.node.nodeid.split("::", 1)[0] \
        .rsplit("/", 1)[-1].removesuffix(".py")
    budget = _RECOMPILE_BUDGETS.get(module)
    calibrate = bool(os.environ.get("BLANCE_RECOMPILE_CALIBRATE"))
    if budget is None and not calibrate:
        yield
        return
    counter = _CompileCounter()
    logger = logging.getLogger("jax._src.interpreters.pxla")
    prev_log_compiles = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    logger.addHandler(counter)
    try:
        yield
    finally:
        logger.removeHandler(counter)
        jax.config.update("jax_log_compiles", prev_log_compiles)
    if calibrate:
        print(f"\n[recompile-calibrate] {module}: total={counter.total} "
              f"by_name={dict(sorted(counter.by_name.items()))}")
        return
    assert counter.total <= budget, (
        f"{module} triggered {counter.total} XLA compilations, over its "
        f"pinned budget of {budget}: a solver entry point is retracing "
        f"more than the shape-bucketing/static-args contract allows "
        f"(per function: {dict(sorted(counter.by_name.items()))}); if "
        f"the extra compiles are intended, recalibrate with "
        f"BLANCE_RECOMPILE_CALIBRATE=1 and raise the budget")


def planner_backends():
    """Parametrize golden suites over every planner backend: the Python
    greedy oracle and the native C++ core run the goldens bit-for-bit
    (native.py's stated contract); the batched "tpu" backend runs the
    same corpus in CONTRACT mode (testing/vis.py assert_contract: zero
    audit violations, weighted balance within the golden oracle + 1,
    warnings-count equality) — it solves globally and is deliberately
    not bit-identical."""
    from blance_tpu.plan.native import native_available

    return [
        "greedy",
        pytest.param("native", marks=pytest.mark.skipif(
            not native_available(),
            reason="native toolchain unavailable")),
        "tpu",
    ]
