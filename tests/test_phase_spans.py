"""plan_pipeline's host time inside named phase spans, and those spans in
the profiler's trace.

Every host step of one dense ``plan_pipeline`` call falls inside exactly
one phase span (``plan.encode``, ``plan.pipeline.prepare``,
``plan.pipeline.dispatch`` with ``plan.pipeline.launch`` inside it,
``plan.pipeline.fetch``, ``plan.validate``, ``plan.decode``,
``plan.pipeline.materialize``), all under ``plan.pipeline``; each phase
span is also a host annotation of the same name while a profiler session
is active, and never builds one otherwise.  The call reads the device's
outputs to the host in one wait (``plan.pipeline.d2h_waits``), before
``plan.pipeline.fetch`` opens.  The problem is shaped like a
Couchbase bucket: one active and two replicas, server groups as the
hierarchy, replicas outside the active's group."""

import glob
import os

import jax
import numpy as np
import pytest

from blance_tpu import HierarchyRule, Partition, PlanOptions, model
from blance_tpu.obs import Recorder, get_recorder, phase_span, use_recorder
from blance_tpu.obs.sinks import InMemorySink
from blance_tpu.plan import tensor
from blance_tpu.plan.tensor import plan_pipeline
from blance_tpu.utils.trace import PhaseTimer

P, N, GROUP = 64, 8, 2
NODES = [f"n{i}" for i in range(N)]
MODEL = model(primary=(0, 1), replica=(1, 2))

# Span name -> PhaseTimer key, for every phase of the dense path.
PHASES = {
    "plan.pipeline": "pipeline",
    "plan.encode": "encode",
    "plan.pipeline.prepare": "prepare",
    "plan.pipeline.dispatch": "dispatch",
    "plan.pipeline.launch": "launch",
    "plan.pipeline.fetch": "fetch",
    "plan.validate": "validate",
    "plan.decode": "decode",
    "plan.pipeline.materialize": "materialize",
}


def _opts(**kw):
    parents = {n: f"g{i // GROUP}" for i, n in enumerate(NODES)}
    parents.update({f"g{i}": "c0" for i in range(N // GROUP)})
    return PlanOptions(node_hierarchy=parents,
                       hierarchy_rules={"replica": [HierarchyRule(2, 1)]},
                       **kw)


def _bucket(seed=7):
    rng = np.random.default_rng(seed)
    out = {}
    for p in range(P):
        ids = rng.choice(N, 3, replace=False)
        out[str(p)] = Partition(str(p), {
            "primary": [NODES[ids[0]]],
            "replica": [NODES[i] for i in ids[1:]]})
    return out


def _plan(prev, removed, timer=None):
    return plan_pipeline(prev, prev, NODES, [removed], [], MODEL, _opts(),
                         timer=timer)


@pytest.fixture(scope="module")
def prev():
    """A bucket map, planned once so the pipeline program is compiled
    before any test times or traces it."""
    start = _bucket()
    nxt, _w, _m = _plan(start, NODES[0])
    return nxt


def test_host_steps_land_in_their_spans(prev, monkeypatch):
    seen = {}

    def spy(fn):
        def wrapped(*a, **kw):
            seen[fn.__name__] = get_recorder().current_span().name
            return fn(*a, **kw)
        return wrapped

    for name in ("_check_tier_band_scale", "check_assignment",
                 "_record_sweeps"):
        monkeypatch.setattr(tensor, name, spy(getattr(tensor, name)))
    rec = Recorder()
    sink = InMemorySink()
    rec.add_sink(sink)
    timer = PhaseTimer()
    with use_recorder(rec):
        _plan(prev, NODES[1], timer)
    assert seen == {"_check_tier_band_scale": "plan.pipeline.prepare",
                    "check_assignment": "plan.validate",
                    "_record_sweeps": "plan.pipeline.fetch"}
    spans = {sp.name: sp for sp in sink.spans}
    assert set(spans) == set(PHASES)
    assert len(sink.spans) == len(PHASES)  # each phase once per call
    top = spans["plan.pipeline"]
    assert top.attrs == {"partitions": P, "nodes": N, "engine": "matrix"}
    launch, dispatch = spans["plan.pipeline.launch"], \
        spans["plan.pipeline.dispatch"]
    assert launch.parent_id == dispatch.span_id
    assert dispatch.attrs["engine"] in ("on", "off")
    for name, sp in spans.items():
        if name not in ("plan.pipeline", "plan.pipeline.launch"):
            assert sp.parent_id == top.span_id, name
    # One interval each: the timer holds the spans' own durations.
    assert timer.totals == {PHASES[n]: sp.duration_s
                            for n, sp in spans.items()}


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_outputs_reach_the_host_in_one_wait(prev, monkeypatch, sparse):
    """One plan_pipeline call waits on the device once: every output the
    host needs is numpy before ``plan.pipeline.fetch`` opens."""
    real, at_fetch = tensor._record_sweeps, []

    def spy(sweeps):
        rec = get_recorder()
        at_fetch.append((rec.current_span().name, type(sweeps),
                         rec.counters.get("plan.pipeline.d2h_waits")))
        return real(sweeps)

    monkeypatch.setattr(tensor, "_record_sweeps", spy)
    name = "_dispatch_pipeline_sparse" if sparse else \
        "_dispatch_pipeline_cold"
    dispatch, outs = getattr(tensor, name), []

    def keep(*a, **kw):
        outs.append(dispatch(*a, **kw))
        return outs[-1]

    monkeypatch.setattr(tensor, name, keep)
    rec = Recorder()
    with use_recorder(rec):
        plan_pipeline(prev, prev, NODES, [NODES[1]], [], MODEL,
                      _opts(sparse=sparse))
    assert rec.counters["plan.pipeline.d2h_waits"] == 1
    assert rec.counters.get("plan.sparse.dense_fallback_rows", 0) == 0
    assert at_fetch == [("plan.pipeline.fetch", np.ndarray, 1)]
    (assign, sweeps, carry, darrs, packs), = outs
    for a in (assign, sweeps, *darrs, *packs):
        assert type(a) is np.ndarray
    # The carry keeps its device arrays for the next warm start.
    for a in carry:
        assert isinstance(a, jax.Array)


def _trace_events(log_dir, names=tuple(PHASES)):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns * 1e-9))
    return {k: [d for _, d in sorted(v)] for k, v in out.items()}


def test_phases_reach_the_profiler_trace(prev, tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    timers = [PhaseTimer(), PhaseTimer()]
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        nxt, _w, _m = _plan(prev, NODES[1], timers[0])
        _plan(nxt, NODES[2], timers[1])
    events = _trace_events(str(tmp_path))
    assert set(events) == set(PHASES)
    for name, key in PHASES.items():
        assert len(events[name]) == len(timers), name  # once per call
        for got, timer in zip(events[name], timers):
            want = timer.totals[key]
            assert abs(got - want) <= 0.05 * want + 2e-4, (name, got, want)


def test_no_profiler_no_annotation(prev, monkeypatch):
    real = jax.profiler.TraceAnnotation
    assert not real.is_enabled()

    class Refused:
        is_enabled = staticmethod(real.is_enabled)

        def __init__(self, *a, **kw):
            raise AssertionError("TraceAnnotation built with no profiler")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
    timer = PhaseTimer()
    with phase_span("plan.encode", timer=timer):
        pass
    with timer.phase("solve"):
        pass
    _plan(prev, NODES[3], timer)
    assert set(timer.totals) == set(PHASES.values()) | {"solve"}


def test_phase_span_times_once():
    """phase_span reads the clock only for its span, and the timer and
    the span hold the same interval; PhaseTimer.phase is phase_span."""
    ticks = iter([0.0, 10.0, 10.25, 20.0, 20.5])
    rec = Recorder(clock=lambda: next(ticks))
    sink = InMemorySink()
    rec.add_sink(sink)
    timer = PhaseTimer()
    with use_recorder(rec):
        with phase_span("plan.encode", timer=timer, phase="enc") as sp:
            pass
        with timer.phase("solve"):
            pass
    assert sp.duration_s == timer.totals["enc"] == 0.25
    assert timer.totals["solve"] == 0.5
    assert [s.name for s in sink.spans] == ["plan.encode", "solve"]
    assert rec.span_totals == {"plan.encode": 0.25, "solve": 0.5}


# -- the fleet tier: ServicePlanner over one PlanService -------------------


FLEET_SPANS = ("fleet.encode", "fleet.validate", "fleet.dispatch",
               "fleet.launch", "fleet.fetch", "fleet.decode")


def _fleet_event(rec, sizes, removed):
    """One coalesced fleet event: one ServicePlanner per index (one
    index of each partition count in ``sizes``), every plan_cycle
    gathered on one loop over one PlanService; returns the sink."""
    import asyncio

    from blance_tpu.fleetloop import ServicePlanner
    from blance_tpu.plan.native import cbgt_node_score_booster
    from blance_tpu.plan.service import PlanService

    opts = _opts(node_score_booster=cbgt_node_score_booster)
    maps = [{k: v for k, v in list(_bucket(seed).items())[:size]}
            for seed, size in enumerate(sizes)]
    sink = InMemorySink()
    rec.add_sink(sink)

    async def event():
        svc = PlanService(recorder=rec, batch_floor=16)
        await svc.start()
        planners = [ServicePlanner(f"t{i}", svc, recorder=rec)
                    for i in range(len(sizes))]
        await asyncio.gather(*[
            p.plan_cycle(m, NODES, [removed], MODEL, opts)
            for p, m in zip(planners, maps)])
        await svc.stop()

    asyncio.run(event())
    rec.remove_sink(sink)
    return sink


def test_fleet_event_opens_its_phase_spans():
    """One coalesced event over three indexes of each of two shape
    classes: fleet.encode and fleet.decode once per index, one
    fleet.validate for the batch (the tier-band pass over both
    classes), one fleet.dispatch per class (with its engine) holding
    one fleet.launch and one fleet.fetch, and three blocking reads per
    class."""
    rec = Recorder()
    sink = _fleet_event(rec, [16, 16, 16, 40, 40, 40], NODES[1])
    spans = [sp for sp in sink.spans if sp.name in FLEET_SPANS]
    count = {n: sum(sp.name == n for sp in spans) for n in FLEET_SPANS}
    assert count == {"fleet.encode": 6, "fleet.decode": 6,
                     "fleet.validate": 1, "fleet.dispatch": 2,
                     "fleet.launch": 2, "fleet.fetch": 2}
    (validate,) = [sp for sp in spans if sp.name == "fleet.validate"]
    assert validate.attrs == {"tenants": 6, "classes": 2, "refused": 0}
    dispatch = {sp.span_id: sp for sp in spans
                if sp.name == "fleet.dispatch"}
    for sp in dispatch.values():
        assert sp.attrs["engine"] in ("on", "off")
        assert not sp.attrs["warm"]
    for sp in spans:
        if sp.name in ("fleet.launch", "fleet.fetch"):
            assert sp.parent_id in dispatch
            outer = dispatch[sp.parent_id]
            assert outer.t_start <= sp.t_start <= sp.t_end <= outer.t_end
    assert rec.counters["fleet.batches"] == 2
    assert rec.counters["fleet.d2h_waits"] == 3 * 2


def test_fleet_phases_reach_the_profiler_trace(tmp_path):
    """The six fleet spans are host annotations in the profiler's trace
    too, as many as the recorder opened."""
    _fleet_event(Recorder(), [16, 40], NODES[2])  # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    rec = Recorder()
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        sink = _fleet_event(rec, [16, 40], NODES[3])
    events = _trace_events(str(tmp_path), FLEET_SPANS)
    want = {n: len(sink.by_name(n)) for n in FLEET_SPANS}
    assert {n: len(v) for n, v in events.items()} == want
    assert want["fleet.encode"] == 2 and want["fleet.fetch"] == 2
    assert want["fleet.validate"] == 1
