"""Lightweight tracing/profiling for planner and orchestrator phases.

The reference has no tracing (SURVEY.md §5); its observability surface is
the orchestrator progress stream.  Here, in addition to that stream, the
framework exposes:

- ``PhaseTimer``: wall-clock phase timing with a queryable report — kept
  as a thin compatibility shim over ``blance_tpu.obs``: every phase is
  also recorded as a Recorder span (and annotations land on the current
  span), so legacy PhaseTimer callers feed the unified trace for free
  while ``report()`` output stays byte-identical to the pre-obs shape.
- ``device_profile``: context manager around jax.profiler.trace for real
  TPU traces (viewable in TensorBoard / Perfetto), no-op if profiling is
  unavailable.
"""

from __future__ import annotations

import contextlib
from contextlib import AbstractContextManager
from dataclasses import dataclass, field
from typing import Iterator, Optional

from ..obs import Span, get_recorder, phase_span

__all__ = ["PhaseTimer", "device_profile"]


@dataclass
class PhaseTimer:
    """Accumulates wall-clock per named phase; phases may repeat.

    ``annotations`` carries non-timing facts a caller wants surfaced with
    the timing report — e.g. which score engine the solve actually ran
    after auto-selection/fallback (tensor._with_engine_fallback)."""

    totals: dict[str, float] = field(default_factory=dict)
    counts: dict[str, int] = field(default_factory=dict)
    annotations: dict[str, str] = field(default_factory=dict)

    def phase(self, name: str) -> AbstractContextManager[Span]:
        """Time one region as the Recorder span ``name``."""
        return phase_span(name, timer=self, phase=name)

    def _accumulate(self, name: str, elapsed: float) -> None:
        """Fold one elapsed interval into the report totals; obs.phase_span
        adds each span's duration here."""
        self.totals[name] = self.totals.get(name, 0.0) + elapsed
        self.counts[name] = self.counts.get(name, 0) + 1

    def annotate(self, key: str, value: str) -> None:
        self.annotations[key] = value
        get_recorder().set_attr(key, value)

    def report(self) -> dict[str, dict]:
        out: dict = {
            name: {"total_s": self.totals[name], "count": self.counts[name]}
            for name in self.totals
        }
        if self.annotations:
            out["annotations"] = dict(self.annotations)
        return out

    def __str__(self) -> str:
        parts = [
            f"{name}: {self.totals[name]*1000:.1f}ms x{self.counts[name]}"
            for name in sorted(self.totals, key=self.totals.get, reverse=True)
        ]
        parts += [f"{k}={v}" for k, v in sorted(self.annotations.items())]
        return "; ".join(parts)


@contextlib.contextmanager
def device_profile(log_dir: Optional[str]) -> Iterator[None]:
    """jax.profiler.trace wrapper; inert when log_dir is None or the
    profiler can't start (e.g. no device)."""
    if not log_dir:
        yield
        return
    # Guard only profiler startup — exceptions raised by the caller's body
    # must propagate unchanged (a second yield after throw() would mask
    # them with RuntimeError).
    trace_cm = None
    try:
        import jax

        trace_cm = jax.profiler.trace(log_dir)
        trace_cm.__enter__()
    except (ImportError, RuntimeError, OSError):
        # The documented no-op cases: jax absent, profiler unavailable /
        # already active, log dir unwritable.  Profiling stays
        # best-effort for these.
        trace_cm = None
    except Exception as e:
        # Anything else is unexpected — still best-effort (a profiler
        # bug must not kill the profiled run), but say so instead of
        # silently dropping the trace.
        trace_cm = None
        import warnings

        warnings.warn(
            f"device_profile: unexpected profiler failure "
            f"({type(e).__name__}: {e}); continuing without a device "
            f"trace", RuntimeWarning, stacklevel=3)
    try:
        yield
    finally:
        if trace_cm is not None:
            trace_cm.__exit__(None, None, None)
