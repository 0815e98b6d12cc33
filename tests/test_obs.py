"""The obs layer: Recorder spans/counters/histograms, sinks, Chrome
export, the PhaseTimer shim, and the pipeline instrumentation contract
(plan + moves + orchestrate all reporting into one recorder)."""

import asyncio
import json

import pytest

from blance_tpu.obs import (
    ChromeTraceSink,
    InMemorySink,
    JsonlSink,
    Recorder,
    get_recorder,
    percentile,
    phase_span,
    use_recorder,
    write_chrome_trace,
)
from blance_tpu.utils.trace import PhaseTimer


# ---------------------------------------------------------------------------
# Recorder core
# ---------------------------------------------------------------------------


def test_span_nesting_and_aggregates_sync():
    rec = Recorder()
    sink = InMemorySink()
    rec.add_sink(sink)
    with rec.span("outer", kind="test") as outer:
        with rec.span("inner") as inner:
            assert inner.parent_id == outer.span_id
        with rec.span("inner"):
            pass
    assert outer.parent_id is None
    assert rec.span_counts == {"outer": 1, "inner": 2}
    assert rec.span_totals["outer"] >= rec.span_totals["inner"] > 0
    names = [sp.name for sp in sink.spans]
    # Children finish before their parent.
    assert names == ["inner", "inner", "outer"]
    assert sink.spans[-1].attrs == {"kind": "test"}


def test_span_nesting_under_asyncio_concurrency():
    """Sibling tasks run concurrently, each with its own span stack: every
    child parents on the span that was current when the task was created,
    and concurrent siblings never corrupt each other's parenthood."""
    rec = Recorder()
    sink = InMemorySink()
    rec.add_sink(sink)

    async def child(i):
        with rec.span(f"child{i}") as sp:
            await asyncio.sleep(0.01 * (i % 3))
            with rec.span("grand", idx=i) as g:
                await asyncio.sleep(0.005)
                assert g.parent_id == sp.span_id
        return sp

    async def main():
        with rec.span("root") as root:
            spans = await asyncio.gather(*(child(i) for i in range(8)))
        return root, spans

    root, children = asyncio.run(main())
    assert all(sp.parent_id == root.span_id for sp in children)
    grands = sink.by_name("grand")
    assert len(grands) == 8
    child_ids = {sp.span_id: sp for sp in children}
    for g in grands:
        parent = child_ids[g.parent_id]
        assert parent.name == f"child{g.attrs['idx']}"
    # Totals accumulated for every name despite interleaving.
    assert rec.span_counts["root"] == 1
    assert sum(rec.span_counts[f"child{i}"] for i in range(8)) == 8


def test_record_span_backdated_and_counters():
    rec = Recorder()
    sink = InMemorySink()
    rec.add_sink(sink)
    with rec.span("parent") as p:
        sp = rec.record_span("waited", 1.0, 3.5, task="lane-x", node="n1")
    assert sp.parent_id == p.span_id
    assert sp.duration_s == pytest.approx(2.5)
    assert sp.task == "lane-x"
    rec.count("hits")
    rec.count("hits", 4)
    assert rec.counters == {"hits": 5}


def test_set_attr_outside_span_is_noop():
    rec = Recorder()
    rec.set_attr("k", "v")  # must not raise
    with rec.span("s") as sp:
        rec.set_attr("k", "v")
    assert sp.attrs == {"k": "v"}


# ---------------------------------------------------------------------------
# Histogram percentile math
# ---------------------------------------------------------------------------


def test_percentile_nearest_rank():
    vals = list(range(1, 101))  # 1..100
    assert percentile(vals, 50) == 50
    assert percentile(vals, 95) == 95
    assert percentile(vals, 0) == 1
    assert percentile(vals, 100) == 100
    assert percentile([7.0], 50) == 7.0
    assert percentile([3, 1], 50) == 1  # rank ceil(1.0)=1 -> min
    assert percentile([3, 1], 51) == 3
    with pytest.raises(ValueError):
        percentile([], 50)


def test_histogram_sample_bounded_with_exact_stats():
    """Past the cap the percentile sample is decimated + subsampled, but
    count/sum/min/max stay exact and percentiles stay representative."""
    from blance_tpu.obs.recorder import _HIST_CAP

    rec = Recorder()
    n = _HIST_CAP * 4
    for v in range(n):
        rec.observe("lat", v)
    assert len(rec.histograms["lat"]) <= _HIST_CAP
    s = rec.histogram_summary("lat")
    assert s["count"] == n
    assert s["sum"] == n * (n - 1) / 2
    assert s["min"] == 0 and s["max"] == n - 1
    # Systematic subsample of a monotone series: percentiles within a few
    # strides of the exact values.
    assert abs(s["p50"] - n / 2) <= n * 0.02
    assert abs(s["p95"] - n * 0.95) <= n * 0.02


def test_histogram_summary():
    rec = Recorder()
    for v in (5, 1, 9, 3, 7):
        rec.observe("lat", v)
    s = rec.histogram_summary("lat")
    assert s == {"count": 5, "sum": 25.0, "min": 1.0, "max": 9.0,
                 "p50": 5.0, "p95": 9.0}
    assert rec.histogram_summary("missing") is None
    full = rec.summary()
    assert full["histograms"]["lat"]["p95"] == 9.0
    assert full["spans"] == {} and full["counters"] == {}


# ---------------------------------------------------------------------------
# Sinks & Chrome export
# ---------------------------------------------------------------------------


def test_jsonl_sink(tmp_path):
    rec = Recorder()
    path = tmp_path / "spans.jsonl"
    sink = JsonlSink(str(path), t0=rec.t0)
    rec.add_sink(sink)
    with rec.span("a", answer=42):
        with rec.span("b"):
            pass
    sink.close()
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [entry["name"] for entry in lines] == ["b", "a"]
    assert lines[1]["attrs"] == {"answer": 42}
    assert lines[0]["parent_id"] == lines[1]["span_id"]
    assert all(entry["duration_s"] >= 0 for entry in lines)


def test_chrome_trace_schema_valid(tmp_path):
    """The exported file must be loadable trace-event JSON: an object with
    a traceEvents list of 'X' complete events (µs ts/dur) for live spans,
    nestable async 'b'/'e' pairs for overlappable (backdated) spans, 'M'
    lane metadata, and 'C' counter events — the subset chrome://tracing
    and Perfetto both accept."""
    rec = Recorder()
    sink = ChromeTraceSink(rec)
    rec.add_sink(sink)
    with rec.span("plan.encode"):
        with rec.span("plan.solve", engine="matrix"):
            pass
    # A manufactured span (queue wait): may overlap live slices on its
    # lane, so it must ship as an async pair, not an "X" slice.
    rec.record_span("orchestrate.move.wait", rec.t0, rec.t0 + 0.25,
                    task="mover:n1", node="n1")
    rec.count("orchestrate.tot_mover_loop", 3)

    path = tmp_path / "trace.json"
    write_chrome_trace(str(path), sink, rec)

    doc = json.loads(path.read_text())
    assert isinstance(doc, dict)
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    phases = {}
    for ev in events:
        assert isinstance(ev["name"], str)
        assert ev["ph"] in ("X", "M", "C", "b", "e")
        assert isinstance(ev["pid"], int)
        phases.setdefault(ev["ph"], []).append(ev)
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            assert isinstance(ev["tid"], int)
            assert isinstance(ev["args"], dict)
            assert "span_id" in ev["args"]
        if ev["ph"] in ("b", "e"):
            # Nestable async events require cat + id for pairing.
            assert ev["cat"] and isinstance(ev["id"], str)
            assert ev["ts"] >= 0
    x_names = {ev["name"] for ev in phases["X"]}
    assert {"plan.encode", "plan.solve"} <= x_names
    # Nesting survives: solve's ts window sits inside encode's.
    enc = next(ev for ev in phases["X"] if ev["name"] == "plan.encode")
    sol = next(ev for ev in phases["X"] if ev["name"] == "plan.solve")
    assert enc["ts"] <= sol["ts"]
    assert sol["ts"] + sol["dur"] <= enc["ts"] + enc["dur"] + 1e-3
    # The backdated wait is a matched b/e pair, 0.25s apart.
    (b,) = phases["b"]
    (e,) = phases["e"]
    assert b["name"] == e["name"] == "orchestrate.move.wait"
    assert (b["id"], b["cat"]) == (e["id"], e["cat"])
    assert e["ts"] - b["ts"] == pytest.approx(0.25e6, rel=1e-6)
    # Lane metadata names the mover lane; counters carried as C events.
    lanes = {ev["args"]["name"] for ev in phases["M"]}
    assert "mover:n1" in lanes
    counters = {ev["name"]: ev["args"]["value"] for ev in phases["C"]}
    assert counters["orchestrate.tot_mover_loop"] == 3


# ---------------------------------------------------------------------------
# PhaseTimer compatibility shim
# ---------------------------------------------------------------------------


def test_phase_timer_report_unchanged_through_shim():
    """PhaseTimer.report() keeps the exact pre-obs shape: per-phase
    {total_s, count} plus an optional 'annotations' block — while every
    phase is ALSO recorded as a span on the process recorder."""
    rec = Recorder()
    with use_recorder(rec):
        t = PhaseTimer()
        with t.phase("encode"):
            pass
        with t.phase("solve"):
            with t.phase("encode"):
                pass
        t.annotate("engine", "matrix")

    report = t.report()
    assert set(report) == {"encode", "solve", "annotations"}
    assert report["encode"]["count"] == 2
    assert report["solve"]["count"] == 1
    assert isinstance(report["encode"]["total_s"], float)
    assert report["encode"]["total_s"] > 0
    assert report["annotations"] == {"engine": "matrix"}
    # No annotations -> no annotations key (legacy shape).
    assert "annotations" not in PhaseTimer().report()
    # str() still renders.
    assert "engine=matrix" in str(t)
    # The shim recorded the same phases as spans.
    assert rec.span_counts == {"encode": 2, "solve": 1}


def test_phase_timer_annotate_lands_on_current_span():
    rec = Recorder()
    with use_recorder(rec):
        sink = InMemorySink()
        rec.add_sink(sink)
        t = PhaseTimer()
        with t.phase("solve"):
            t.annotate("engine", "fused")
    (sp,) = sink.by_name("solve")
    assert sp.attrs["engine"] == "fused"
    assert t.annotations == {"engine": "fused"}


def test_phase_span_dual_view():
    """phase_span times once, publishing a hierarchical span name AND the
    short phase key into the timer (no double-recorded span)."""
    rec = Recorder()
    with use_recorder(rec):
        t = PhaseTimer()
        with phase_span("plan.encode", timer=t):
            pass
        with phase_span("plan.solve", timer=t, phase="the-solve"):
            pass
    assert set(t.totals) == {"encode", "the-solve"}
    assert set(rec.span_counts) == {"plan.encode", "plan.solve"}


# ---------------------------------------------------------------------------
# Pipeline instrumentation contracts
# ---------------------------------------------------------------------------


def _mini_model():
    from blance_tpu import model

    return model(primary=(0, 1), replica=(1, 1))


def _mini_maps(p=24, n=6):
    from blance_tpu import Partition

    nodes = [f"n{i}" for i in range(n)]
    prev = {
        str(i): Partition(str(i), {"primary": [nodes[i % n]],
                                   "replica": [nodes[(i + 1) % n]]})
        for i in range(p)
    }
    return prev, nodes


def test_plan_tpu_emits_phase_spans_and_sweeps():
    from blance_tpu.plan.api import plan_next_map

    rec = Recorder()
    with use_recorder(rec):
        prev, nodes = _mini_maps()
        plan_next_map(prev, prev, nodes, [nodes[0]], [], _mini_model(),
                      None, backend="tpu")
    for name in ("plan.plan_next_map", "plan.encode",
                 "plan.pipeline.dispatch", "plan.decode"):
        assert rec.span_counts.get(name, 0) >= 1, (name, rec.span_counts)
    assert rec.counters["plan.solve.calls"] >= 1
    assert rec.counters["plan.solve.sweeps"] >= 1
    assert rec.histograms["plan.solve.sweeps"]


def test_plan_greedy_emits_candidate_histogram():
    from blance_tpu.plan.api import plan_next_map

    rec = Recorder()
    with use_recorder(rec):
        prev, nodes = _mini_maps()
        plan_next_map(prev, prev, nodes, [], [], _mini_model(),
                      None, backend="greedy")
    assert rec.span_counts["plan.greedy"] == 1
    h = rec.histogram_summary("plan.greedy.candidates")
    assert h is not None and h["count"] > 0 and h["max"] <= 6


def test_node_sorter_output_validated():
    """A node_sorter hook that drops, duplicates, or invents nodes is
    rejected with a ValueError naming the hook (ADVICE round 5)."""
    from blance_tpu import PlanOptions
    from blance_tpu.plan.api import plan_next_map

    prev, nodes = _mini_maps()

    def dropping_sorter(ctx, candidates):
        return candidates[:-1]  # loses one node

    def duplicating_sorter(ctx, candidates):
        return [candidates[0]] * len(candidates)

    for bad in (dropping_sorter, duplicating_sorter):
        with pytest.raises(ValueError, match="node_sorter"):
            plan_next_map(prev, prev, nodes, [], [], _mini_model(),
                          PlanOptions(node_sorter=bad), backend="greedy")

    # A well-behaved sorter (any permutation) still works.
    def reversing_sorter(ctx, candidates):
        return list(reversed(candidates))

    out, _ = plan_next_map(prev, prev, nodes, [], [], _mini_model(),
                           PlanOptions(node_sorter=reversing_sorter),
                           backend="greedy")
    assert len(out) == len(prev)


def test_moves_batch_spans_and_counters():
    from blance_tpu.moves.batch import calc_all_moves

    rec = Recorder()
    with use_recorder(rec):
        prev, nodes = _mini_maps()
        end, _ = _mini_maps()
        # Shift every primary by one node to force ops.
        from blance_tpu import Partition

        end = {
            name: Partition(name, {
                "primary": [nodes[(int(name) + 2) % len(nodes)]],
                "replica": p.nodes_by_state["replica"],
            })
            for name, p in prev.items()
        }
        moves = calc_all_moves(prev, end, _mini_model())
    assert any(moves.values())
    for name in ("moves.calc_all_moves", "moves.encode",
                 "moves.device_diff", "moves.materialize"):
        assert rec.span_counts.get(name, 0) == 1, (name, rec.span_counts)
    assert rec.counters["moves.total_ops"] > 0
    assert rec.counters["moves.diff_partitions"] == len(prev)


def test_orchestrator_mirrors_progress_and_records_move_lifecycle():
    """One sink sees everything: progress counters mirrored 1:1 into the
    recorder, and each fed batch becomes an orchestrate.move span whose
    wait/exec children split queue time from callback time."""
    from blance_tpu.orchestrate.orchestrator import (
        OrchestratorOptions, orchestrate_moves)
    from blance_tpu.plan.api import plan_next_map

    rec = Recorder()
    sink = InMemorySink()
    rec.add_sink(sink)
    with use_recorder(rec):
        prev, nodes = _mini_maps()
        end, _ = plan_next_map(prev, prev, nodes, [nodes[0]], [],
                               _mini_model(), None, backend="greedy")

        async def assign(stop_ch, node, partitions, states, ops):
            await asyncio.sleep(0.001)

        async def run():
            o = orchestrate_moves(
                _mini_model(),
                OrchestratorOptions(max_concurrent_partition_moves_per_node=4),
                nodes, prev, end, assign)
            final = None
            async for p in o.progress_ch():
                final = p
            o.stop()
            return final

        final = asyncio.run(run())

    assert final.tot_mover_assign_partition_ok > 0
    for field in ("tot_run_mover", "tot_mover_loop",
                  "tot_mover_assign_partition",
                  "tot_mover_assign_partition_ok",
                  "tot_run_supply_moves_loop", "tot_progress_close"):
        assert rec.counters["orchestrate." + field] == \
            getattr(final, field), field

    assert rec.span_counts["orchestrate.plan_moves"] == 1
    moves = sink.by_name("orchestrate.move")
    assert len(moves) == final.tot_mover_assign_partition
    for mv in moves:
        kids = [sp for sp in sink.spans if sp.parent_id == mv.span_id]
        assert {"orchestrate.move.wait", "orchestrate.move.exec"} == \
            {sp.name for sp in kids}
        assert mv.attrs["wait_s"] >= 0 and mv.attrs["exec_s"] > 0
        assert mv.task == f"mover:{mv.attrs['node']}"
        # Lifecycle covers wait + exec.
        assert mv.duration_s + 1e-6 >= \
            mv.attrs["wait_s"] + mv.attrs["exec_s"] - 1e-4

    lat = rec.histogram_summary("orchestrate.move_latency_s")
    assert lat is not None
    assert lat["p95"] >= lat["p50"] > 0
    # One observation per partition move, the batch's exec time amortized
    # across its moves: count is total moves fed and the sum is real
    # callback wall-clock, not batch-size-weighted batch latency.
    assert lat["count"] == sum(mv.attrs["moves"] for mv in moves)
    assert lat["count"] >= final.tot_mover_assign_partition_ok
    assert lat["sum"] == pytest.approx(
        sum(mv.attrs["exec_s"] for mv in moves), rel=1e-9)


def test_recorder_without_sink_retains_no_spans():
    """Aggregate-only by default: a long-running service with no sink
    attached must not accumulate span objects."""
    rec = Recorder()
    with rec.span("s"):
        pass
    assert rec.span_counts["s"] == 1
    assert not hasattr(rec, "spans")
    assert rec.sinks == []


# ---------------------------------------------------------------------------
# Injectable clock, gauges, exact histogram buckets (the live-telemetry
# plane's Recorder extensions)
# ---------------------------------------------------------------------------


def test_recorder_injectable_clock_drives_all_timestamps():
    """Every timestamp — t0, span endpoints, now() — comes from the
    injected clock, so virtual-time tests control telemetry time."""
    t = [100.0]
    rec = Recorder(clock=lambda: t[0])
    assert rec.t0 == 100.0
    assert rec.now() == 100.0
    with rec.span("s") as sp:
        t[0] = 101.5
    assert sp.t_start == 100.0 and sp.t_end == 101.5
    assert sp.duration_s == pytest.approx(1.5)
    assert rec.span_totals["s"] == pytest.approx(1.5)
    t[0] = 103.25
    assert rec.now() == 103.25


def test_recorder_gauges_last_value_wins_and_summary():
    rec = Recorder()
    rec.set_gauge("slo.partition_availability", 0.5)
    rec.set_gauge("slo.partition_availability", 0.75)
    rec.set_gauge('slo.quarantine_exposure_s{node="n1"}', 2.5)
    assert rec.gauges["slo.partition_availability"] == 0.75
    s = rec.summary()
    assert s["gauges"]["slo.partition_availability"] == 0.75
    assert s["gauges"]['slo.quarantine_exposure_s{node="n1"}'] == 2.5


def test_histogram_bucket_counts_exact_le_semantics():
    """Bucket counts are exact with `le` (<=) boundary semantics: a
    value equal to a bound lands in that bound's bucket; the implicit
    final slot is +Inf."""
    rec = Recorder()
    rec.set_hist_bounds("lat", (0.01, 0.1, 1.0))
    for v in (0.01, 0.05, 0.5, 5.0):
        rec.observe("lat", v)
    bounds, cum, count, total = rec.histogram_buckets("lat")
    assert bounds == (0.01, 0.1, 1.0)
    assert cum == [1, 2, 3, 4]  # cumulative; 0.01 counted at le=0.01
    assert count == 4
    assert total == pytest.approx(5.56)
    # Re-binning after data exists is refused (counts are exact, not
    # reconstructible).
    with pytest.raises(ValueError, match="before the first observe"):
        rec.set_hist_bounds("lat", (1.0,))
    assert rec.histogram_buckets("never") is None


def test_histogram_default_buckets_cover_outliers():
    from blance_tpu.obs.recorder import DEFAULT_BUCKETS

    rec = Recorder()
    rec.observe("big", 1e9)  # beyond every bound: +Inf bucket only
    bounds, cum, count, total = rec.histogram_buckets("big")
    assert bounds == DEFAULT_BUCKETS
    assert cum[-2] == 0 and cum[-1] == 1 and count == 1


def test_histogram_buckets_consistent_with_exact_stats_at_scale():
    """Bucket count stays exact (== stats count) even past the
    percentile sample's decimation cap."""
    from blance_tpu.obs.recorder import _HIST_CAP

    rec = Recorder()
    n = _HIST_CAP * 3
    for v in range(n):
        rec.observe("lat", v / 1000.0)
    _bounds, cum, count, _total = rec.histogram_buckets("lat")
    assert count == n == cum[-1]
    assert len(rec.histograms["lat"]) <= _HIST_CAP


# ---------------------------------------------------------------------------
# JsonlSink rotation (size-capped, keep-N)
# ---------------------------------------------------------------------------


def _spin_spans(rec, n, name="s"):
    for _ in range(n):
        with rec.span(name, pad="x" * 64):
            pass


def test_jsonl_sink_rotation_boundary(tmp_path):
    """Crossing max_bytes rotates AFTER the triggering line: no record
    is ever split across files, every file is valid JSONL, the cap is
    overshot by at most one record, and only `keep` rotated files
    survive."""
    rec = Recorder()
    path = tmp_path / "spans.jsonl"
    sink = JsonlSink(str(path), t0=rec.t0, max_bytes=512, keep=2)
    rec.add_sink(sink)
    _spin_spans(rec, 40)
    sink.close()

    rotated = sorted(p.name for p in tmp_path.iterdir())
    assert "spans.jsonl.1" in rotated and "spans.jsonl.2" in rotated
    assert "spans.jsonl.3" not in rotated  # keep=2 drops older files
    line_len = None
    for p in tmp_path.iterdir():
        text = p.read_text()
        lines = text.splitlines()
        for line in lines:  # every record whole and parseable
            entry = json.loads(line)
            assert entry["name"] == "s"
            line_len = len(line) + 1
        if p.name != "spans.jsonl":
            # A rotated file crossed the cap by at most one record.
            assert 512 <= len(text) < 512 + line_len
    # The live file was reopened fresh (below the cap).
    assert len(path.read_text()) < 512


def test_jsonl_sink_rotation_boundary_exact_hit(tmp_path):
    """A write landing exactly ON the cap rotates too (>= semantics)."""
    rec = Recorder()
    path = tmp_path / "s.jsonl"
    sink = JsonlSink(str(path), t0=rec.t0, max_bytes=1, keep=1)
    rec.add_sink(sink)
    _spin_spans(rec, 3)
    sink.close()
    # Every span rotated its file: the live file is empty, .1 has the
    # last record whole.
    assert path.read_text() == ""
    assert len((tmp_path / "s.jsonl.1").read_text().splitlines()) == 1


def test_jsonl_sink_rotation_rejects_file_objects(tmp_path):
    import io

    with pytest.raises(ValueError, match="path-owned"):
        JsonlSink(io.StringIO(), max_bytes=100)


# ---------------------------------------------------------------------------
# Chrome counter tracks (live "C" samples)
# ---------------------------------------------------------------------------


def test_chrome_counter_track_time_series():
    """Each count() becomes a time-stamped "C" sample carrying the
    cumulative value, so Perfetto renders an evolving counter track on
    the span timeline (plus the final-value sample at the trace end)."""
    from blance_tpu.obs import ChromeTraceSink

    t = [10.0]
    rec = Recorder(clock=lambda: t[0])
    sink = ChromeTraceSink(rec)
    rec.add_sink(sink)
    rec.count("orchestrate.retries")
    t[0] = 11.0
    rec.count("orchestrate.retries", 2)
    t[0] = 12.0
    with rec.span("work"):
        pass
    events = sink.events(counters=dict(rec.counters))
    c_events = [ev for ev in events if ev["ph"] == "C"]
    live = [ev for ev in c_events if ev["name"] == "orchestrate.retries"]
    # Two live samples (cumulative 1 then 3) + the final-value sample.
    assert [ev["args"]["value"] for ev in live] == [1, 3, 3]
    assert live[0]["ts"] == pytest.approx(0.0)
    assert live[1]["ts"] == pytest.approx(1e6)  # 1 virtual second later
    assert live[0]["ts"] <= live[1]["ts"] <= live[2]["ts"]
