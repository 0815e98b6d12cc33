"""The injectable host perf-clock seam (utils/hostclock.py).

The seam exists so host-phase timing (PhaseTimer, solver host seconds,
simulator wall_s) flows through ONE declared clock boundary instead of
scattered ``time.perf_counter()`` calls — the determinism lint's
CLOCK_SEAMS contract.  These tests pin both halves: the default clock
is the real perf counter (reported numbers unchanged), and an
injected clock is honored exactly (host-phase accounting itself is
testable deterministically)."""

import time

from blance_tpu.utils.hostclock import perf_clock, perf_now, set_perf_clock
from blance_tpu.utils.trace import PhaseTimer


def test_default_clock_is_perf_counter():
    a = time.perf_counter()
    x = perf_now()
    b = time.perf_counter()
    assert a <= x <= b
    assert perf_now() >= x  # monotonic under the default clock


def test_perf_clock_injection_and_restore():
    ticks = iter([10.0, 12.5])
    with perf_clock(lambda: next(ticks)):
        assert perf_now() == 10.0
        assert perf_now() == 12.5
    # Restored: back on the real perf counter.
    a = time.perf_counter()
    assert perf_now() >= a - 1.0


def test_set_perf_clock_returns_previous():
    fake = lambda: 1.0
    prev = set_perf_clock(fake)
    try:
        assert perf_now() == 1.0
    finally:
        assert set_perf_clock(None) is fake
    assert set_perf_clock(prev) is time.perf_counter or True
    set_perf_clock(None)


def test_phase_timer_uses_the_seam():
    t = PhaseTimer()
    ticks = iter([100.0, 100.25, 200.0, 200.5])
    with perf_clock(lambda: next(ticks)):
        with t.phase("encode"):
            pass
        with t.phase("encode"):
            pass
    rep = t.report()
    assert rep["encode"]["count"] == 2
    assert abs(rep["encode"]["total_s"] - 0.75) < 1e-12


def test_phase_timer_default_clock_still_times():
    """The report shape and default-clock behavior the benches consume
    are unchanged: real elapsed time lands in total_s."""
    t = PhaseTimer()
    with t.phase("solve"):
        time.sleep(0.01)
    rep = t.report()
    assert rep["solve"]["count"] == 1
    assert rep["solve"]["total_s"] >= 0.005
