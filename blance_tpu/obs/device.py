"""Device-side performance observatory: compile accounting + XLA costs.

The telemetry plane (PR 6) covers the orchestration side; this module is
the SOLVER/DEVICE side — the place the 304 ms north-star solve actually
spends its time.  Three instruments, all fed through the same Recorder
as everything else:

- **Compile & retrace accounting** (:class:`CompileMonitor`).  JAX
  announces every XLA compilation on its own loggers when
  ``jax_log_compiles`` is on; the monitor taps that stream (the same
  one tests/conftest.py's recompile-budget fixture counts) and
  attributes each compile to the OWNING ENTRY POINT — ``solve_dense``
  cold/warm/bucketed, the fleet batch classes, the sharded dispatch —
  via the :func:`entry` contextvar the dispatch sites set.  Counts land
  as ``device.compiles{entry=...}`` counters and compile durations as
  ``device.compile_s{entry=...}`` histograms.  The per-entry retrace
  BUDGETS live in ``analysis/retrace.py`` (a declarative table checked
  by ``python -m blance_tpu.analysis --ci``), the promotion of the
  test-fixture budgets into a CI contract.
- **Static cost & memory gauges** (:func:`maybe_publish_cost`).  At the
  first dispatch per (entry, bucket-shape) — memoized, so steady state
  pays nothing — the entry point's jitted callable is lowered and
  AOT-compiled once more and XLA's own ``cost_analysis()`` /
  ``memory_analysis()`` are published as ``device.flops`` /
  ``device.hbm_bytes`` / ``device.peak_alloc_bytes`` gauges labeled
  ``{entry=,klass=}``: the Prometheus endpoint then shows exactly
  what each bucket class costs on device, per the GSPMD argument
  (arXiv:2105.04663) that bucketed compilation is only a win if
  retraces and per-class costs are actually measured.
- **Sweep-level convergence traces** (:func:`record_sweep_trace`).  The
  converged solve's fixpoint loop is fused into one device program, so
  per-sweep host spans cannot exist; instead the solver (with
  ``trace_sweeps``) accumulates each sweep's accepted-bid fraction
  in-graph and this module emits them as a ``device.sweep_accept_frac``
  Chrome counter track, with samples interpolated across the solve's
  host span so the track sits under the ``device_profile`` slices it
  belongs to.

Everything is OFF by default: attribution contextvars are always set
(they cost a token swap), but no logging handler is installed, no AOT
compile runs, and no extra solver outputs exist until :func:`enable` —
so the tier-1 recompile budgets and the timed benchmark loops see
byte-for-byte identical behavior unless a caller opted in (the CI
``device-obs`` step and the device-obs tests do).

CLI (the CI step)::

    python -m blance_tpu.obs.device --check [--trace-out PATH]

runs the retrace-budget workload + a cost-analysis smoke on CPU and
exits nonzero when a budget is blown or the gauges fail to publish;
``--trace-out`` captures the run as a Chrome trace for the artifact
upload.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import re
import threading
from typing import Any, Callable, Iterator, Optional

from .recorder import Recorder, escape_label_value as _lbl, get_recorder

__all__ = [
    "entry",
    "current_entry",
    "CompileMonitor",
    "enable",
    "disable",
    "enabled",
    "cost_enabled",
    "sweep_trace_enabled",
    "maybe_publish_cost",
    "cost_summaries",
    "reset_cost_cache",
    "record_sweep_trace",
    "main",
]


# -- entry-point attribution --------------------------------------------------

_entry_var: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("blance_device_entry", default=None)

# Fallback classification for compiles that fire outside any entry
# scope (jax-internal eager helper jits, test-local functions).
_DEFAULT_ENTRY = "other"


@contextlib.contextmanager
def entry(label: str) -> Iterator[None]:
    """Attribute every XLA compile inside the body to ``label``.

    FIRST WINS: a nested entry (solve_dense_converged tracing inside the
    sharded dispatch) does not re-label the outer scope — the outermost
    dispatch site owns the compile.  Always active (a contextvar swap),
    whether or not a monitor is installed."""
    if _entry_var.get() is not None:
        yield
        return
    token = _entry_var.set(label)
    try:
        yield
    finally:
        _entry_var.reset(token)


def current_entry() -> str:
    """The owning entry label for a compile happening right now."""
    return _entry_var.get() or _DEFAULT_ENTRY


def ambient_entry() -> Optional[str]:
    """The enclosing entry scope, or None outside any — for inner
    layers whose OWN label must yield to an outer dispatch site's (the
    bucketed plan path labels solve_dense_converged's cost gauges)."""
    return _entry_var.get()


# -- the jit-cache monitor ----------------------------------------------------

# jax announces compiles on two loggers (verified against the pinned
# jax 0.4.37; the conftest fixture parses the same stream):
#   jax._src.interpreters.pxla:  "Compiling <name> with global shapes..."
#                                "Compiling <name> (<id>) for <n> devices..."
#   jax._src.dispatch:           "Finished XLA compilation of jit(<name>)
#                                 in <secs> sec"
_PXLA_LOGGER = "jax._src.interpreters.pxla"
_DISPATCH_LOGGER = "jax._src.dispatch"
_FINISHED_RE = re.compile(
    r"Finished XLA compilation of (?:jit\()?([^)\s]+)\)? "
    r"in ([0-9.eE+-]+) sec")


class _Tap(logging.Handler):
    """Routes matching log records into the owning monitor."""

    def __init__(self, monitor: "CompileMonitor") -> None:
        super().__init__()
        self._monitor = monitor

    def emit(self, record: logging.LogRecord) -> None:
        # Runs on the COMPILING thread, so current_entry() sees the
        # dispatch site's attribution contextvar.
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self._monitor._on_compile(msg.split(" ", 2)[1])
            return
        m = _FINISHED_RE.match(msg)
        if m:
            try:
                secs = float(m.group(2))
            except ValueError:
                return
            self._monitor._on_compile_done(m.group(1), secs)


class CompileMonitor:
    """Process-wide XLA compile counter with entry attribution.

    Use as a context manager around a stage (``chip_smoke.py``'s phases
    do) or install the process-global one via :func:`enable`.
    ``emit=True`` additionally publishes every event to the CURRENT
    recorder
    (``device.compiles{entry=}`` counter, ``device.compile_s{entry=}``
    histogram) — stage-local monitors keep ``emit=False`` so a stage
    nested inside the global observatory never double-counts.

    Counts are exact per attribution scope; thread-safe (compiles can
    happen on executor threads — the fleet service's solve path)."""

    def __init__(self, emit: bool = False) -> None:
        self.emit = emit
        self.by_entry: dict[str, int] = {}
        self.by_fn: dict[str, int] = {}
        self.compile_s_by_entry: dict[str, float] = {}
        self._lock = threading.Lock()
        self._tap: Optional[_Tap] = None
        self._prev_levels: dict[str, int] = {}
        self._prev_propagate: dict[str, bool] = {}

    # -- event fan-in (called from the logging tap) --------------------------

    def _on_compile(self, fn_name: str) -> None:
        ent = current_entry()
        with self._lock:
            self.by_entry[ent] = self.by_entry.get(ent, 0) + 1
            self.by_fn[fn_name] = self.by_fn.get(fn_name, 0) + 1
        if self.emit:
            get_recorder().count(
                f'device.compiles{{entry="{_lbl(ent)}"}}')

    def _on_compile_done(self, fn_name: str, secs: float) -> None:
        ent = current_entry()
        with self._lock:
            self.compile_s_by_entry[ent] = \
                self.compile_s_by_entry.get(ent, 0.0) + secs
        if self.emit:
            get_recorder().observe(
                f'device.compile_s{{entry="{_lbl(ent)}"}}', secs)

    # -- lifecycle ------------------------------------------------------------

    def install(self) -> "CompileMonitor":
        """Attach the tap.  Deliberately does NOT flip
        ``jax_log_compiles``: jax logs the same records at DEBUG when
        the flag is off, so dropping the two loggers to DEBUG level
        feeds the tap while the root handler (WARNING by default) keeps
        stderr quiet — no spam for the observatory's whole lifetime."""
        if self._tap is not None:
            return self
        self._tap = _Tap(self)
        for name in (_PXLA_LOGGER, _DISPATCH_LOGGER):
            logger = logging.getLogger(name)
            self._prev_levels[name] = logger.level
            self._prev_propagate[name] = logger.propagate
            logger.setLevel(logging.DEBUG)
            # The tap is the only intended consumer of the DEBUG-level
            # stream; without this, jax's own console handler (attached
            # to the parent "jax" logger) would echo every record.
            logger.propagate = False
            logger.addHandler(self._tap)
        return self

    def uninstall(self) -> None:
        if self._tap is None:
            return
        for name in (_PXLA_LOGGER, _DISPATCH_LOGGER):
            logger = logging.getLogger(name)
            logger.removeHandler(self._tap)
            logger.setLevel(self._prev_levels.get(name, logging.NOTSET))
            logger.propagate = self._prev_propagate.get(name, True)
        self._prev_levels.clear()
        self._prev_propagate.clear()
        self._tap = None

    def __enter__(self) -> "CompileMonitor":
        return self.install()

    def __exit__(self, *exc: object) -> None:
        self.uninstall()

    # -- summaries ------------------------------------------------------------

    @property
    def total(self) -> int:
        with self._lock:
            return sum(self.by_entry.values())

    def summary(self) -> dict:
        """JSON-ready stage summary."""
        with self._lock:
            return {
                "total": sum(self.by_entry.values()),
                "by_entry": dict(sorted(self.by_entry.items())),
                "compile_s_by_entry": {
                    k: round(v, 4)
                    for k, v in sorted(self.compile_s_by_entry.items())},
            }


# -- the process-global observatory ------------------------------------------

_state: dict[str, Any] = {
    "monitor": None,  # the emit=True process monitor, when enabled
    "cost": False,
    "sweep_trace": False,
}
_state_lock = threading.Lock()


def enable(cost_analysis: bool = True, sweep_trace: bool = True) -> None:
    """Switch the observatory ON process-wide: install the emitting
    compile monitor and (optionally) arm AOT cost analysis + in-graph
    sweep tracing.  Idempotent."""
    with _state_lock:
        if _state["monitor"] is None:
            _state["monitor"] = CompileMonitor(emit=True).install()
        _state["cost"] = bool(cost_analysis)
        _state["sweep_trace"] = bool(sweep_trace)


def disable() -> None:
    """Switch the observatory OFF and restore jax_log_compiles."""
    with _state_lock:
        mon = _state["monitor"]
        if mon is not None:
            mon.uninstall()
        _state["monitor"] = None
        _state["cost"] = False
        _state["sweep_trace"] = False


def enabled() -> bool:
    return _state["monitor"] is not None


def cost_enabled() -> bool:
    return bool(_state["cost"])


def sweep_trace_enabled() -> bool:
    return bool(_state["sweep_trace"])


def monitor() -> Optional[CompileMonitor]:
    """The process-global monitor (None while disabled)."""
    mon: Optional[CompileMonitor] = _state["monitor"]
    return mon


# -- static cost & memory gauges ----------------------------------------------

# (entry, klass) -> summary dict (or None when analysis failed): the
# first-dispatch memo.  Bounded by the entry x bucket-class product,
# which bucketing keeps small by design.
_COST_CACHE: dict[tuple[str, str], Optional[dict]] = {}
_COST_LOCK = threading.Lock()


def reset_cost_cache() -> None:
    with _COST_LOCK:
        _COST_CACHE.clear()


def cost_summaries() -> dict:
    """{entry: {klass: summary}} for everything published so far."""
    out: dict[str, dict[str, dict]] = {}
    with _COST_LOCK:
        items = list(_COST_CACHE.items())
    for (ent, klass), summary in sorted(items):
        if summary is not None:
            out.setdefault(ent, {})[klass] = summary
    return out


def _extract_cost(compiled: Any) -> dict:
    """Pull flops / traffic / peak-alloc numbers off an AOT-compiled
    executable, tolerant of per-backend shape differences
    (cost_analysis returns a list of dicts on CPU, a dict on some
    backends; memory_analysis can be absent)."""
    flops = hbm = 0.0
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # per-backend API gaps (absent/NotImplemented)
        logging.getLogger(__name__).debug(
            "device-obs: cost_analysis unavailable: %s: %s",
            type(e).__name__, e)
        ca = None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if isinstance(ca, dict):
        flops = float(ca.get("flops", 0.0) or 0.0)
        hbm = float(ca.get("bytes accessed", 0.0) or 0.0)
    peak = 0.0
    mem: dict[str, float] = {}
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # same per-backend API gap class as above
        logging.getLogger(__name__).debug(
            "device-obs: memory_analysis unavailable: %s: %s",
            type(e).__name__, e)
        ma = None
    if ma is not None:
        for fieldname in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = float(getattr(ma, fieldname, 0) or 0)
            mem[fieldname] = v
            if fieldname != "generated_code_size_in_bytes":
                peak += v
    return {"flops": flops, "hbm_bytes": hbm,
            "peak_alloc_bytes": peak, "memory": mem}


def maybe_publish_cost(ent: str, klass: str, fn: Any,
                       *args: Any, **kwargs: Any) -> Optional[dict]:
    """AOT cost/memory analysis for one (entry, bucket-shape), once.

    ``fn`` must be a jitted callable (``.lower`` supported); ``args`` /
    ``kwargs`` are exactly what the live dispatch passes.  No-op unless
    :func:`enable` armed cost analysis — the extra AOT compile this
    costs (one per memo key) is an explicit opt-in, so the tier-1
    recompile budgets never see it.  Publishes ``device.flops`` /
    ``device.hbm_bytes`` / ``device.peak_alloc_bytes`` gauges labeled
    ``{entry=,klass=}`` and bumps ``device.cost_analyses``; returns the
    summary dict (None on analysis failure, which is recorded so the
    failure isn't retried per dispatch)."""
    if not cost_enabled():
        return None
    key = (ent, klass)
    with _COST_LOCK:
        if key in _COST_CACHE:
            return _COST_CACHE[key]
    try:
        # The AOT lower+compile is real work owned by the entry point,
        # but it is observation overhead, not a retrace: label it
        # "<entry>+aot" so operators can see it while the retrace-budget
        # check (analysis/retrace.py) excludes it from the live counts.
        # Escape any ambient scope first — entry() is first-wins.
        tok = _entry_var.set(None)
        try:
            with entry(f"{ent}+aot"):
                compiled = fn.lower(*args, **kwargs).compile()
        finally:
            _entry_var.reset(tok)
        summary = _extract_cost(compiled)
    except Exception as e:  # analysis is best-effort observability:
        # an unlowerable shape must never fail the solve it observes.
        summary = None
        logging.getLogger(__name__).warning(
            "device-obs: cost analysis failed for %s/%s: %s: %s",
            ent, klass, type(e).__name__, e)
    with _COST_LOCK:
        _COST_CACHE[key] = summary
    if summary is not None:
        rec = get_recorder()
        labels = f'{{entry="{_lbl(ent)}",klass="{_lbl(klass)}"}}'
        rec.set_gauge(f"device.flops{labels}", summary["flops"])
        rec.set_gauge(f"device.hbm_bytes{labels}", summary["hbm_bytes"])
        rec.set_gauge(f"device.peak_alloc_bytes{labels}",
                      summary["peak_alloc_bytes"])
        rec.count("device.cost_analyses")
    return summary


# -- sweep-level convergence traces -------------------------------------------


def record_sweep_trace(rec: Recorder, t0: float, t1: float,
                       sweeps: int, fracs: Any) -> None:
    """Emit one solve's per-sweep accepted-bid fractions as a Chrome
    counter track (``device.sweep_accept_frac``).

    The fixpoint loop is one fused device program, so per-sweep host
    timestamps do not exist; samples are INTERPOLATED evenly across the
    solve's host interval [t0, t1] — the track then sits under the
    solve's span (and its device_profile slices) with the right number
    of steps, which is the alignment that matters for reading
    convergence shape in Perfetto."""
    n = int(sweeps)
    if n <= 0:
        return
    span = max(t1 - t0, 0.0)
    for i in range(n):
        t = t0 + span * (i + 1) / n
        rec.sample("device.sweep_accept_frac", float(fracs[i]), t=t)


# -- CLI: the CI device-obs gate ----------------------------------------------


def main(argv: Optional[list[str]] = None) -> int:
    """``python -m blance_tpu.obs.device --check``: the retrace-budget
    table check + a cost-analysis smoke, on CPU, with an optional Chrome
    trace artifact for upload on failure."""
    import argparse
    import os
    import sys

    ap = argparse.ArgumentParser(
        prog="python -m blance_tpu.obs.device",
        description="device-side observatory checks "
                    "(docs/OBSERVABILITY.md)")
    ap.add_argument("--check", action="store_true",
                    help="run the retrace-budget workload + a smoke "
                         "cost-analysis pass; exit nonzero on failure")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the run's spans + counter tracks as a "
                         "Chrome trace (the CI failure artifact)")
    args = ap.parse_args(argv)
    if not args.check:
        ap.print_help()
        return 2

    # CPU + virtual devices BEFORE jax initializes, like every other
    # host-side gate (tests/conftest.py, analysis --ci).
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    from ..analysis.retrace import run_retrace_check
    from .chrome import trace
    from .recorder import use_recorder

    rec = Recorder()
    failures: list[str] = []
    with use_recorder(rec):
        enable(cost_analysis=True, sweep_trace=True)
        ctx = trace(args.trace_out, recorder=rec) if args.trace_out \
            else contextlib.nullcontext()
        try:
            with ctx:
                findings, entries = run_retrace_check()
                for f in findings:
                    failures.append(f.render())
                    print(f.render(), file=sys.stderr)
                # Cost-analysis smoke: the workload above dispatched the
                # solver entry points with cost analysis armed, so the
                # gauges and compile counters must be live.
                flops = [v for k, v in rec.gauges.items()
                         if k.startswith("device.flops{")]
                if not flops or not any(v > 0 for v in flops):
                    failures.append(
                        "cost-analysis smoke: no nonzero device.flops "
                        "gauge published")
                compiles = [v for k, v in rec.counters.items()
                            if k.startswith("device.compiles{")]
                if not compiles:
                    failures.append(
                        "compile accounting: no device.compiles counter "
                        "moved during the workload")
        finally:
            disable()
    print(f"device-obs: {entries} budget entries, "
          f"{len(failures)} failure(s)"
          + (" — FAIL" if failures else " — OK"), file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    import sys

    # Under ``python -m`` runpy executes this file as a SECOND module
    # instance ("__main__") distinct from the already-imported
    # ``blance_tpu.obs.device`` the solver entry points call into —
    # enabling the observatory on the copy would arm the wrong _state.
    # Delegate to the canonical instance.
    from blance_tpu.obs.device import main as _canonical_main

    sys.exit(_canonical_main())
