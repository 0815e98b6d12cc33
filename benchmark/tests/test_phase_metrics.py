"""The per-layer metrics that read plan_pipeline's phase spans, from a
traced run of the cell on the CPU."""

import io
import json

from benchmark import run

CELL = "cb-bucket.swap1"
SEED = 2**31 + 4242
NEW = {"prepare_ms", "launch_ms", "fetch_ms", "validate_ms", "unspanned_ms"}
OLD = {"encode_ms", "dispatch_ms", "decode_ms", "materialize_ms"}


def test_traced_run_reports_the_phase_metrics():
    totals = {}
    base = run.load_module("entries", "plan_pipeline").Entry

    class Entry(base):
        def phase_totals(self):
            totals.update(super().phase_totals())
            return totals

    out = io.StringIO()
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                   "1.5", "--trace", "1"],
                  require_tpu=False, make_entry=Entry, out=out)
    assert rc == 0
    line = json.loads(out.getvalue().splitlines()[-1])
    assert line["correct"] is True
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert NEW | OLD <= set(metrics)
    assert all(metrics[k] > 0 for k in NEW - {"unspanned_ms"} | OLD)
    pipeline_ms = totals["pipeline"] / line["attempted"] * 1e3
    assert 0 <= metrics["unspanned_ms"] < 0.25 * pipeline_ms
    assert metrics["launch_ms"] < metrics["dispatch_ms"]
