"""Tests for the static-analysis suite itself (blance_tpu/analysis).

Three layers, mirroring docs/STATIC_ANALYSIS.md:

- rule fixtures: a snippet that MUST trip each rule, and a clean twin
  that must NOT (the false-positive guard — a lint nobody trusts is a
  lint nobody runs);
- baseline semantics: matching (symbol/line pinning), stale-entry
  detection, and the parse errors that keep the allowlist honest;
- end-to-end: the real package carries zero non-baselined findings, an
  injected violation fails the CLI, and the eval_shape contract table
  passes against the live solver.
"""

import textwrap

import pytest

from blance_tpu.analysis import Finding, run_all, run_lints
from blance_tpu.analysis.asyncio_lint import lint_source
from blance_tpu.analysis.baseline import (
    Baseline,
    BaselineEntry,
    parse_toml_findings,
)
from blance_tpu.analysis.jit_purity import JitPurityPass


def _jit_findings(tmp_path, source, name="mod.py"):
    f = tmp_path / name
    f.write_text(textwrap.dedent(source))
    return JitPurityPass([str(f)], repo_root=str(tmp_path)).run()


def _rules(findings):
    return sorted({f.rule for f in findings})


# -- jit purity: each rule trips, and its clean twin does not ---------------


def test_jit001_host_nondeterminism_trips(tmp_path):
    fs = _jit_findings(tmp_path, """
        import time
        import jax

        @jax.jit
        def f(x):
            t = time.perf_counter()
            return x + t
    """)
    assert _rules(fs) == ["JIT001"]
    assert fs[0].symbol == "f"


def test_jit001_numpy_random_trips(tmp_path):
    fs = _jit_findings(tmp_path, """
        import numpy as np
        import jax

        @jax.jit
        def f(x):
            return x + np.random.rand()
    """)
    assert _rules(fs) == ["JIT001"]


def test_jit001_reached_helper_trips(tmp_path):
    # Impurity in a helper REACHED from a jit root is still a finding.
    fs = _jit_findings(tmp_path, """
        import random
        import jax

        def helper(x):
            return x * random.random()

        @jax.jit
        def f(x):
            return helper(x)
    """)
    assert _rules(fs) == ["JIT001"]
    assert fs[0].symbol == "helper"


def test_jit001_unreached_host_code_is_clean(tmp_path):
    # The same impurity OUTSIDE the traced call graph is fine.
    fs = _jit_findings(tmp_path, """
        import time
        import jax

        def host_wrapper(x):
            t0 = time.perf_counter()
            out = f(x)
            return out, time.perf_counter() - t0

        @jax.jit
        def f(x):
            return x + 1
    """)
    assert fs == []


def test_jit002_traced_branch_trips(tmp_path):
    fs = _jit_findings(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """)
    assert _rules(fs) == ["JIT002"]


def test_jit002_static_and_is_none_branches_are_clean(tmp_path):
    fs = _jit_findings(tmp_path, """
        from functools import partial
        import jax

        @partial(jax.jit, static_argnames=("mode",))
        def f(x, mode, y=None):
            if mode == "fast":
                x = x * 2
            if y is not None:
                x = x + y
            if x.shape[0] > 4:
                x = x[:4]
            return x
    """)
    assert fs == []


def test_jit003_coercion_trips_and_shape_is_clean(tmp_path):
    fs = _jit_findings(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return float(x)

        @jax.jit
        def g(x):
            n = int(x.shape[0])
            return x * n
    """)
    assert _rules(fs) == ["JIT003"]
    assert all(f.symbol == "f" for f in fs)


def test_jit004_captured_mutation_trips_local_is_clean(tmp_path):
    fs = _jit_findings(tmp_path, """
        import jax

        _CACHE = {}
        _SEEN = []

        @jax.jit
        def f(x):
            _SEEN.append(1)
            return x

        @jax.jit
        def g(x):
            local = []
            local.append(1)
            return x

        @jax.jit
        def h(x):
            global _MODE
            _MODE = "hot"
            return x
    """)
    assert _rules(fs) == ["JIT004"]
    assert sorted(f.symbol for f in fs) == ["f", "h"]


def test_jit004_subscript_write_does_not_hide_capture(tmp_path):
    # d[k] = v must NOT make ``d`` look locally bound.
    fs = _jit_findings(tmp_path, """
        import jax

        _MEMO = {}

        @jax.jit
        def f(x):
            _MEMO["k"] = 1
            _MEMO.clear()
            return x
    """)
    assert _rules(fs) == ["JIT004"]


def test_jit005_bogus_static_argname_trips(tmp_path):
    fs = _jit_findings(tmp_path, """
        from functools import partial
        import jax

        @partial(jax.jit, static_argnames=("mode", "modes"))
        def f(x, mode):
            return x
    """)
    assert _rules(fs) == ["JIT005"]
    assert "modes" in fs[0].message


def test_jit_roots_via_call_and_partial_forms(tmp_path):
    # name = jax.jit(f, ...) and partial(jax.jit, ...)(f) both root f.
    fs = _jit_findings(tmp_path, """
        from functools import partial
        import time
        import jax

        def f(x):
            return x + time.time()

        def g(x):
            return x * time.time()

        f_jit = jax.jit(f)
        g_jit = partial(jax.jit, static_argnames=())(g)
    """)
    assert _rules(fs) == ["JIT001"]
    assert sorted(x.symbol for x in fs) == ["f", "g"]


def test_jit001_reached_through_package_reexport(tmp_path):
    """Impurity must stay visible through the `from .impl import helper`
    + `from . import helper` package re-export idiom the codebase uses
    for its public surfaces."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "impl.py").write_text(textwrap.dedent("""
        import time

        def helper(x):
            return x + time.time()
    """))
    (pkg / "__init__.py").write_text("from .impl import helper\n")
    (pkg / "use.py").write_text(textwrap.dedent("""
        import jax
        from . import helper

        @jax.jit
        def f(x):
            return helper(x)
    """))
    files = [str(pkg / n) for n in ("__init__.py", "impl.py", "use.py")]
    fs = JitPurityPass(files, repo_root=str(tmp_path)).run()
    assert _rules(fs) == ["JIT001"]
    assert fs[0].symbol == "helper" and fs[0].path == "pkg/impl.py"


def test_jit_root_via_shard_map_wrapper(tmp_path):
    fs = _jit_findings(tmp_path, """
        from functools import partial
        import time
        import jax

        def body(x):
            return x + time.time()

        def build(mesh, spec):
            fn = jax.shard_map(partial(body), mesh=mesh,
                               in_specs=spec, out_specs=spec)
            return fn
    """)
    assert _rules(fs) == ["JIT001"]
    assert fs[0].symbol == "body"


# -- asyncio lint -----------------------------------------------------------


def _asy(source):
    return lint_source(textwrap.dedent(source), "/r/mod.py", "/r")


def test_asy101_fire_and_forget_trips_stored_is_clean():
    fs = _asy("""
        import asyncio

        async def bad(coro):
            asyncio.ensure_future(coro)

        async def good(coro, tasks):
            t = asyncio.ensure_future(coro)
            tasks.append(t)
            await t
    """)
    assert _rules(fs) == ["ASY101"]
    assert fs[0].symbol == "bad"


def test_asy102_blocking_call_trips_async_sleep_is_clean():
    fs = _asy("""
        import asyncio
        import time

        async def bad():
            time.sleep(1.0)

        async def good():
            await asyncio.sleep(1.0)

        def sync_ok():
            time.sleep(0.1)
    """)
    assert _rules(fs) == ["ASY102"]
    assert fs[0].symbol == "bad"


def test_asy103_silent_swallow_trips():
    fs = _asy("""
        def bad():
            try:
                work()
            except Exception:
                return False
            return True
    """)
    assert _rules(fs) == ["ASY103"]


def test_asy103_using_or_raising_handler_is_clean():
    fs = _asy("""
        import logging

        def uses_exc():
            try:
                work()
            except Exception as e:
                logging.warning("failed: %s", e)
                return False
            return True

        def reraises():
            try:
                work()
            except Exception:
                cleanup()
                raise

        def narrow():
            try:
                work()
            except ValueError:
                return False
    """)
    assert fs == []


def test_asy104_undeadlined_callback_await_trips():
    fs = _asy("""
        class O:
            async def run(self, node):
                result = self._assign_partitions(node)
                return await result
    """)
    assert _rules(fs) == ["ASY104"]


def test_asy104_wait_for_wrapped_is_clean():
    fs = _asy("""
        import asyncio

        class O:
            async def run(self, node):
                result = self._assign_partitions(node)
                return await asyncio.wait_for(result, 5.0)
    """)
    assert fs == []


# -- race lint ---------------------------------------------------------------


def _race(source, shared=None):
    from blance_tpu.analysis.race_lint import lint_source

    return lint_source(textwrap.dedent(source), "/r/mod.py", "/r",
                       shared_state=shared)


_TOY_SHARED = {"Orchestrator": frozenset({"_flag", "_count", "_items"})}


def test_race001_rmw_across_await_trips():
    fs = _race("""
        class Orchestrator:
            async def bump(self):
                tmp = self._count
                await self._notify()
                self._count = tmp + 1
    """, shared=_TOY_SHARED)
    assert _rules(fs) == ["RACE001"]
    assert fs[0].symbol == "Orchestrator.bump"


def test_race001_augassign_with_awaiting_rhs_trips():
    # self.x += await f(): CPython reads self.x BEFORE the await and
    # writes after — the torn RMW in a single statement.
    fs = _race("""
        class Orchestrator:
            async def bump(self):
                self._count += await self._notify()
    """, shared=_TOY_SHARED)
    assert _rules(fs) == ["RACE001"]
    assert "pre-await" in fs[0].message


def test_race001_augassign_without_await_is_clean():
    fs = _race("""
        class Orchestrator:
            async def bump(self):
                self._count += 1
                await self._notify()
    """, shared=_TOY_SHARED)
    assert fs == []


def test_race001_atomic_rmw_is_clean():
    # Same RMW with no intervening await: atomic in asyncio, clean.
    fs = _race("""
        class Orchestrator:
            async def bump(self):
                tmp = self._count
                self._count = tmp + 1
                await self._notify()
    """, shared=_TOY_SHARED)
    assert fs == []


def test_race002_stale_guard_trips():
    fs = _race("""
        class Orchestrator:
            async def run(self):
                flag = self._flag
                await self._notify()
                if flag is not None:
                    await flag.get()
    """, shared=_TOY_SHARED)
    assert _rules(fs) == ["RACE002"]
    assert "revalidat" in fs[0].message or "re-read" in fs[0].message


def test_race002_revalidation_loop_is_clean():
    # The fixed supplier shape: re-bind from the attribute after every
    # wake, use before any further await.
    fs = _race("""
        class Orchestrator:
            async def run(self):
                await self._notify()
                while True:
                    flag = self._flag
                    if flag is None:
                        break
                    await flag.get()
    """, shared=_TOY_SHARED)
    assert fs == []


def test_race002_use_before_await_is_clean():
    fs = _race("""
        class Orchestrator:
            async def run(self):
                flag = self._flag
                if flag is not None:
                    await flag.get()
    """, shared=_TOY_SHARED)
    assert fs == []


def test_race002_untracked_attr_is_clean():
    # Locals from attributes OUTSIDE the shared-state model never trip.
    fs = _race("""
        class Orchestrator:
            async def run(self):
                opts = self.options
                await self._notify()
                return opts.timeout
    """, shared=_TOY_SHARED)
    assert fs == []


def test_race003_multi_root_mutation_trips():
    fs = _race("""
        import asyncio

        class Orchestrator:
            def start(self):
                self._spawn(self._worker_a())
                self._spawn(self._worker_b())

            def _spawn(self, coro):
                return asyncio.ensure_future(coro)

            async def _worker_a(self):
                self._items.append(1)
                await self._notify()

            async def _worker_b(self):
                self._items.append(2)
                await self._notify()
    """, shared=_TOY_SHARED)
    assert _rules(fs) == ["RACE003"]
    assert "_items" in fs[0].message
    assert "_worker_a" in fs[0].message and "_worker_b" in fs[0].message


def test_race003_subscript_writes_count_as_mutations():
    # self._items[k] = v / del self._items[k] mutate the shared
    # container just as surely as .append does.
    fs = _race("""
        import asyncio

        class Orchestrator:
            def start(self):
                self._spawn(self._worker_a())
                self._spawn(self._worker_b())

            def _spawn(self, coro):
                return asyncio.ensure_future(coro)

            async def _worker_a(self):
                self._items["a"] = 1
                await self._notify()

            async def _worker_b(self):
                del self._items["b"]
                await self._notify()
    """, shared=_TOY_SHARED)
    assert _rules(fs) == ["RACE003"]
    assert "_items" in fs[0].message


def test_race003_single_root_is_clean():
    fs = _race("""
        import asyncio

        class Orchestrator:
            def start(self):
                self._spawn(self._worker())

            def _spawn(self, coro):
                return asyncio.ensure_future(coro)

            async def _worker(self):
                self._items.append(1)
                await self._notify()
                self._helper()

            def _helper(self):
                self._items.append(2)
    """, shared=_TOY_SHARED)
    assert fs == []


def test_race003_needs_a_task_owning_class():
    # A passive shared structure (no spawns) is RACE001/002 territory;
    # RACE003 stays quiet.
    fs = _race("""
        class Orchestrator:
            def a(self):
                self._items.append(1)

            def b(self):
                self._items.append(2)
    """, shared=_TOY_SHARED)
    assert fs == []


def test_race_lint_ignores_unmodeled_classes():
    fs = _race("""
        class Whatever:
            async def run(self):
                flag = self._flag
                await self._notify()
                return flag
    """, shared=_TOY_SHARED)
    assert fs == []


def test_race_lint_real_package_model_matches_reality():
    """The shared-state table must keep naming real attributes of the
    real classes — a renamed attribute would silently blind the lint."""
    import blance_tpu.obs.costmodel as costmodel
    import blance_tpu.obs.slo as slo
    import blance_tpu.orchestrate.csp as csp
    import blance_tpu.orchestrate.health as health
    import blance_tpu.orchestrate.orchestrator as orch
    import importlib

    import blance_tpu.orchestrate.sched.policy as schedpolicy
    import blance_tpu.plan.carry as plancarry
    import blance_tpu.plan.service as planservice
    from blance_tpu.analysis.race_lint import SHARED_STATE

    import blance_tpu.control as control
    import blance_tpu.durability.epoch as depoch
    import blance_tpu.durability.journal as djournal
    import blance_tpu.fleetloop as fleetloop

    # `import blance_tpu.rebalance as ...` would resolve to the
    # same-named FUNCTION the package re-exports, not the module.
    rebalance = importlib.import_module("blance_tpu.rebalance")

    import inspect

    sources = {
        "CycleEngine": inspect.getsource(control.CycleEngine),
        "FleetController": inspect.getsource(fleetloop.FleetController),
        "FleetSloRollup": inspect.getsource(slo.FleetSloRollup),
        "Orchestrator": inspect.getsource(orch.Orchestrator),
        "OrchestratorProgress": inspect.getsource(
            orch.OrchestratorProgress),
        "HealthTracker": inspect.getsource(health.HealthTracker),
        "NodeHealth": inspect.getsource(health.NodeHealth),
        "Chan": inspect.getsource(csp.Chan),
        "NextMoves": inspect.getsource(orch.NextMoves),
        "SloTracker": inspect.getsource(slo.SloTracker),
        "CostModel": inspect.getsource(costmodel.CostModel),
        "PlanService": inspect.getsource(planservice.PlanService),
        "CarryCache": inspect.getsource(plancarry.CarryCache),
        "EncodeCache": inspect.getsource(plancarry.EncodeCache),
        "RebalanceController": inspect.getsource(
            rebalance.RebalanceController),
        "_CriticalPathBound": inspect.getsource(
            schedpolicy._CriticalPathBound),
        "Journal": inspect.getsource(djournal.Journal),
        "EpochFence": inspect.getsource(depoch.EpochFence),
    }
    for cls, attrs in SHARED_STATE.items():
        src = sources[cls]
        for attr in attrs:
            leaf = attr.split(".")[0]
            assert leaf in src, \
                f"SHARED_STATE[{cls!r}] names {leaf!r} which no longer " \
                f"appears in the class source — update the model"


# -- baseline semantics -----------------------------------------------------


def _finding(rule="ASY103", path="pkg/m.py", line=10, symbol="f"):
    return Finding(rule=rule, path=path, line=line, symbol=symbol,
                   message="msg")


def test_baseline_matches_on_rule_path_symbol():
    b = Baseline([BaselineEntry(rule="ASY103", path="pkg/m.py",
                                symbol="f", reason="why")])
    new, accepted = b.split([_finding(), _finding(symbol="g")])
    assert [f.symbol for f in new] == ["g"]
    assert [(f.symbol, r) for f, r in accepted] == [("f", "why")]
    assert b.unused() == []


def test_baseline_line_pin_and_stale_entries():
    entries = [
        BaselineEntry(rule="ASY103", path="pkg/m.py", line=10,
                      reason="pinned"),
        BaselineEntry(rule="JIT001", path="pkg/other.py",
                      reason="stale"),
    ]
    b = Baseline(entries)
    new, accepted = b.split([_finding(line=10), _finding(line=11)])
    assert [f.line for f in new] == [11]
    assert len(accepted) == 1
    assert [e.reason for e in b.unused()] == ["stale"]


def test_baseline_toml_roundtrip_and_errors():
    entries = parse_toml_findings(textwrap.dedent("""
        # comment
        [[finding]]
        rule = "ASY103"
        path = "pkg/m.py"  # trailing comment
        symbol = "f"
        line = 12
        reason = "a \\"quoted\\" reason"
    """))
    assert len(entries) == 1
    e = entries[0]
    assert (e.rule, e.path, e.symbol, e.line) == \
        ("ASY103", "pkg/m.py", "f", 12)
    assert e.reason == 'a "quoted" reason'

    # Shared validation: identical on the tomllib and subset paths.
    with pytest.raises(ValueError, match="missing required key"):
        parse_toml_findings('[[finding]]\nrule = "X"\npath = "p"\n')
    with pytest.raises(ValueError, match="unknown keys"):
        parse_toml_findings(
            '[[finding]]\nrule = "X"\npath = "p"\nreason = "r"\n'
            'bogus = "v"\n')
    # A stray top-level key is an error on either path (the messages
    # differ: tomllib flags the unknown table, the subset the bare key).
    with pytest.raises(ValueError):
        parse_toml_findings('rule = "X"\n')


def test_baseline_subset_parser_errors():
    """The 3.10 fallback parser's own strictness (exercised explicitly
    so the tomllib path on newer Pythons doesn't mask it)."""
    from blance_tpu.analysis.baseline import _parse_subset

    with pytest.raises(ValueError, match="unsupported"):
        _parse_subset('[[finding]]\nrule = [1]\n', "<t>")
    with pytest.raises(ValueError, match="outside"):
        _parse_subset('rule = "X"\n', "<t>")
    with pytest.raises(ValueError, match="expected key"):
        _parse_subset('[[finding]]\njunk\n', "<t>")
    entries = _parse_subset(
        '[[finding]]\nrule = "R"\npath = "p"\nreason = "r"\nline = 3\n',
        "<t>")
    assert entries[0].line == 3


# -- end-to-end -------------------------------------------------------------


def test_package_has_zero_nonbaselined_findings():
    """The gate the static CI tier enforces, minus the shape audit
    (covered separately below so this stays sub-second)."""
    result = run_all(shape_audit=False)
    assert result.errors == []
    rendered = "\n".join(f.render() for f in result.new)
    assert result.new == [], f"non-baselined findings:\n{rendered}"
    # The allowlist carries no dead weight.
    stale = [e.render() for e in result.unused_baseline]
    assert stale == [], f"stale baseline entries: {stale}"


def test_lints_cover_expected_file_count():
    _, nfiles = run_lints()
    # The package's module count only grows; a collapse here means the
    # walker lost a directory.
    assert nfiles >= 30


def test_cli_fails_on_injected_violation(tmp_path, capsys):
    from blance_tpu.analysis.__main__ import main

    bad = tmp_path / "violation.py"
    bad.write_text(textwrap.dedent("""
        import time
        import jax

        @jax.jit
        def f(x):
            return x + time.time()
    """))
    rc = main([str(bad)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "JIT001" in out and "FAIL" in out

    clean = tmp_path / "clean.py"
    clean.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return x\n")
    assert main([str(clean)]) == 0


def test_cli_stale_baseline_warns_by_default_fails_under_ci(tmp_path,
                                                            capsys,
                                                            monkeypatch):
    """A baseline entry matching nothing is a warning in the editor
    loop but a hard error under --ci (a fixed finding must delete its
    suppression in the same change)."""
    from blance_tpu.analysis import retrace
    from blance_tpu.analysis.__main__ import main

    # --ci also runs the device retrace-budget workload (real solver
    # compiles); stub it here — this test pins the stale-baseline
    # semantics, and the real workload is covered by
    # tests/test_device_obs.py plus the CI device-obs step.
    monkeypatch.setattr(retrace, "_workload", lambda: None)

    clean = tmp_path / "clean.py"
    clean.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return x\n")
    stale = tmp_path / "baseline.toml"
    stale.write_text(
        '[[finding]]\nrule = "JIT001"\npath = "gone.py"\n'
        'reason = "fixed long ago"\n')

    rc = main([str(clean), "--baseline", str(stale)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "warning: stale baseline entry" in out

    # --ci implies the shape audit; pointing the run at the tmp file
    # keeps the lint scope identical while the audit runs for real.
    rc = main([str(clean), "--baseline", str(stale), "--ci"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "ERROR: stale baseline entry" in out and "FAIL" in out
    # The same run measured every HBM budget row of the smoke class:
    # each AOT peak sits under its ceiling.
    assert "MEM001" not in out


def test_cli_json_output(tmp_path, capsys):
    import json

    from blance_tpu.analysis.__main__ import main

    bad = tmp_path / "violation.py"
    bad.write_text(
        "import asyncio\n\nasync def f(c):\n"
        "    asyncio.ensure_future(c)\n")
    rc = main(["--json", str(bad)])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["pass"] is False
    assert [f["rule"] for f in payload["new"]] == ["ASY101"]


def test_shape_audit_passes_against_live_solver():
    """Every declared contract holds on the real entry points; the full
    matrix (cold/carry/bucketed/sharded + encode/decode + bucketing
    algebra) runs in seconds with zero FLOPs."""
    from blance_tpu.analysis.shape_audit import CONTRACTS, run_shape_audit

    findings, entries = run_shape_audit()
    rendered = "\n".join(f.render() for f in findings)
    assert findings == [], f"shape contract violations:\n{rendered}"
    assert entries == len(CONTRACTS) + 4  # + encode-residency check
    # Acceptance coverage: warm, sharded and bucketed variants all audit.
    entry_names = {c.entry for c in CONTRACTS}
    assert {"solve_dense", "solve_dense_converged", "solve_dense_warm",
            "solve_dense_sharded", "carry_from_assignment"} <= entry_names
    assert any("bucketed" in c.variant for c in CONTRACTS)


def test_shape_audit_catches_drift(monkeypatch):
    """Break a contract deliberately: the audit must report SHP001."""
    from blance_tpu.analysis import shape_audit as sa

    broken = sa.ShapeContract(
        entry="solve_dense", variant="drifted",
        build=lambda: sa._build_solve_dense(sa.Dims(P=8, S=1, N=5, R=1)),
        expect=lambda: ((8, 1, 2), "int32"))  # wrong R
    monkeypatch.setattr(sa, "CONTRACTS", (broken,))
    findings, _ = sa.run_shape_audit()
    assert any(f.rule == "SHP001" for f in findings)


# -- determinism lint: each rule trips, and its clean twin does not ----------


def _det_findings(tmp_path, source, name="mod.py", *, roots=None,
                  clock_seams=None, serialized_sinks=None,
                  config_knobs=None):
    from blance_tpu.analysis.determinism import DeterminismPass

    f = tmp_path / name
    f.write_text(textwrap.dedent(source))
    return DeterminismPass(
        [str(f)], repo_root=str(tmp_path),
        replay_roots={"mod": "fixture"} if roots is None else roots,
        clock_seams=clock_seams or {},
        serialized_sinks=serialized_sinks,
        config_knobs=config_knobs or {},
    ).run()


def test_det001_wall_clock_trips(tmp_path):
    fs = _det_findings(tmp_path, """
        import time

        def f():
            return time.monotonic()
    """)
    assert _rules(fs) == ["DET001"]
    assert fs[0].symbol == "f"


def test_det001_raw_loop_time_trips(tmp_path):
    fs = _det_findings(tmp_path, """
        def f(loop):
            return loop.time() + 1.0
    """)
    assert _rules(fs) == ["DET001"]


def test_det001_clean_inside_declared_seam(tmp_path):
    fs = _det_findings(tmp_path, """
        import time

        def f():
            return time.perf_counter()
    """, clock_seams={"mod.f": "the declared boundary"})
    assert fs == []


def test_det001_clean_injected_clock_default(tmp_path):
    # A default-parameter REFERENCE to the clock is the injectable-seam
    # idiom (Recorder, HealthTracker) — only CALLS trip the rule.
    fs = _det_findings(tmp_path, """
        import time

        def f(clock=time.monotonic):
            return clock()
    """)
    assert fs == []


def test_det002_unseeded_randomness_trips(tmp_path):
    fs = _det_findings(tmp_path, """
        import random
        import uuid

        def f():
            return random.random(), uuid.uuid4(), random.Random()
    """)
    assert _rules(fs) == ["DET002"]
    assert len(fs) == 3


def test_det002_numpy_global_prng_trips(tmp_path):
    fs = _det_findings(tmp_path, """
        import numpy as np

        def f(n):
            return np.random.rand(n)
    """)
    assert _rules(fs) == ["DET002"]


def test_det002_clean_seeded_random(tmp_path):
    fs = _det_findings(tmp_path, """
        import random

        def f(seed):
            rng = random.Random(seed)
            return rng.random()
    """)
    assert fs == []


def test_det003_set_into_sink_trips(tmp_path):
    fs = _det_findings(tmp_path, """
        def canonical_log_text(events):
            return str(events)

        def f(xs):
            pending = set(xs)
            return canonical_log_text(pending)
    """)
    assert "DET003" in _rules(fs)


def test_det003_propagates_through_list(tmp_path):
    fs = _det_findings(tmp_path, """
        def canonical_log_text(events):
            return str(events)

        def f(xs):
            pending = set(xs)
            items = list(pending)
            return canonical_log_text(items)
    """)
    assert "DET003" in _rules(fs)


def test_det003_clean_with_sorted_on_path(tmp_path):
    fs = _det_findings(tmp_path, """
        def canonical_log_text(events):
            return str(events)

        def f(xs):
            pending = set(xs)
            return canonical_log_text(sorted(pending))
    """)
    assert fs == []


def test_det004_json_dumps_without_sort_keys_trips(tmp_path):
    fs = _det_findings(tmp_path, """
        import json

        def f(d):
            return json.dumps(d)
    """)
    assert _rules(fs) == ["DET004"]
    assert fs[0].symbol == "f"


def test_det004_clean_sort_keys_and_passthrough(tmp_path):
    fs = _det_findings(tmp_path, """
        import json

        def f(d):
            return json.dumps(d, sort_keys=True)

        def g(d, sort_keys):
            return json.dumps(d, sort_keys=sort_keys)
    """)
    assert fs == []


def test_det005_hash_ordering_trips(tmp_path):
    fs = _det_findings(tmp_path, """
        def f(xs):
            xs.sort(key=lambda x: hash(x))
            return sorted(xs, key=lambda x: (id(x), x))
    """)
    assert _rules(fs) == ["DET005"]
    assert len(fs) == 2


def test_det005_clean_field_key_and_identity_id(tmp_path):
    fs = _det_findings(tmp_path, """
        def f(xs, h):
            keep = id(xs)  # identity use outside ordering is fine
            h[keep] = True
            return sorted(xs, key=lambda x: x.name)
    """)
    assert fs == []


def test_det006_env_read_trips(tmp_path):
    fs = _det_findings(tmp_path, """
        import os

        def f():
            return os.environ.get("KNOB", "1"), os.environ["OTHER"]

        def g():
            return os.getenv("THIRD")
    """)
    assert _rules(fs) == ["DET006"]
    assert len(fs) == 3


def test_det006_clean_declared_knob(tmp_path):
    fs = _det_findings(tmp_path, """
        import os

        def f():
            return os.environ.get("KNOB", "1")
    """, config_knobs={"mod.f": "KNOB: fixture"})
    assert fs == []


def test_det_rules_only_fire_on_replay_reachable_code(tmp_path):
    # Same wall-clock call, but the module is not under any replay root
    # and nothing reaches it: DET001 stays quiet (DET004 is the one
    # package-wide rule).
    fs = _det_findings(tmp_path, """
        import time

        def f():
            return time.monotonic()
    """, roots={"other_module": "not this one"})
    assert fs == []


def _resolve_fq(fq):
    import importlib

    parts = fq.split(".")
    for cut in range(len(parts), 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:cut]))
        except ImportError:
            continue
        try:
            for attr in parts[cut:]:
                obj = getattr(obj, attr)
        except AttributeError:
            return None
        return obj
    return None


def test_determinism_tables_match_reality():
    """Every REPLAY_ROOTS / CLOCK_SEAMS / CONFIG_KNOBS entry must name a
    real module/class/function — a renamed symbol would silently blind
    the lint (same guard pattern as the race lint's SHARED_STATE)."""
    from blance_tpu.analysis.determinism import (
        CLOCK_SEAMS,
        CONFIG_KNOBS,
        REPLAY_ROOTS,
    )

    for table_name, table in [("REPLAY_ROOTS", REPLAY_ROOTS),
                              ("CLOCK_SEAMS", CLOCK_SEAMS),
                              ("CONFIG_KNOBS", CONFIG_KNOBS)]:
        for fq, reason in table.items():
            assert reason.strip(), f"{table_name}[{fq!r}] has no reason"
            assert _resolve_fq(fq) is not None, (
                f"{table_name} entry {fq!r} does not resolve to a real "
                f"symbol — update the table")


def test_determinism_sinks_match_reality():
    """Each SERIALIZED_SINKS suffix must have a real representative
    symbol, so a renamed renderer can't silently un-cover its artifact."""
    from blance_tpu.analysis.determinism import SERIALIZED_SINKS

    representatives = {
        "journal.append": "blance_tpu.durability.journal.Journal.append",
        "canonical_log_text":
            "blance_tpu.testing.simulate.canonical_log_text",
        "canonical_fleet_log_text":
            "blance_tpu.testing.fleetsim.canonical_fleet_log_text",
        "crash_log_text": "blance_tpu.testing.crashsim.crash_log_text",
        "render_prometheus": "blance_tpu.obs.expo.render_prometheus",
        "atomic_write_json": "blance_tpu.utils.atomicio.atomic_write_json",
        "atomic_write_text": "blance_tpu.utils.atomicio.atomic_write_text",
    }
    assert set(representatives) == set(SERIALIZED_SINKS), \
        "new sink entries need a representative symbol here"
    for sink, fq in representatives.items():
        assert _resolve_fq(fq) is not None, (
            f"SERIALIZED_SINKS representative for {sink!r} ({fq}) does "
            f"not resolve — update the table or this map")


def test_determinism_real_package_is_clean():
    """The real package carries ZERO determinism findings, baselined or
    not — the triage (hostclock seam, sort_keys fixes, declared knobs)
    left nothing to allowlist."""
    from blance_tpu.analysis import PACKAGE_ROOT, REPO_ROOT, _iter_py_files
    from blance_tpu.analysis.determinism import DeterminismPass

    findings = DeterminismPass(
        _iter_py_files([PACKAGE_ROOT]), REPO_ROOT).run()
    assert findings == [], "\n".join(f.render() for f in findings)


def test_cli_determinism_only_mode(capsys):
    from blance_tpu.analysis.__main__ import main

    rc = main(["--determinism"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 new finding(s)" in out
    # JIT/ASY/RACE baseline pins must NOT be reported stale in this mode.
    assert "stale baseline entry" not in out


# -- donation lint: each rule trips, and its clean twin does not -------------

# A self-contained donating dispatch family, the same wrapper shapes the
# real package uses (_warm_repair_donating & co): jit-with-donate
# module-level bindings over a shared impl.
_DON_PRELUDE = """
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np


    def _impl(prev, pweights, carry_used, constraints):
        return prev, carry_used


    _impl_jit = partial(jax.jit, static_argnames=("constraints",))(_impl)
    _impl_donating = jax.jit(
        _impl, static_argnames=("constraints",),
        donate_argnames=("prev", "carry_used"))
    _impl_nums = jax.jit(_impl, static_argnums=(3,), donate_argnums=(0,))
"""


def _don_findings(tmp_path, source, name="fix.py"):
    from blance_tpu.analysis.donation import DonationPass

    f = tmp_path / name
    f.write_text(textwrap.dedent(_DON_PRELUDE)
                 + textwrap.dedent(source))
    return DonationPass([str(f)], repo_root=str(tmp_path)).run()


def test_don001_pr11_shape_trips(tmp_path):
    # The PR-11 bug verbatim: the engine-exhaustion fallback re-reads
    # prev after the donating dispatch instead of a pre-dispatch
    # snapshot.
    fs = _don_findings(tmp_path, """
        def warm(prev, pweights, carry, fallback, donate=True):
            impl = _impl_donating if donate else _impl_jit
            out, used = impl(jnp.asarray(prev), jnp.asarray(pweights),
                             jnp.asarray(carry.used), constraints=(2,))
            return fallback(np.asarray(out), prev, pweights)
    """)
    assert _rules(fs) == ["DON001"]
    assert fs[0].symbol == "warm"
    assert "prev" in fs[0].message


def test_don001_clean_snapshot_twin(tmp_path):
    # The sanctioned fix: snapshot host-side BEFORE the dispatch
    # (np.asarray dominates the donation), read the snapshot after.
    fs = _don_findings(tmp_path, """
        def warm(prev, pweights, carry, fallback, donate=True):
            impl = _impl_donating if donate else _impl_jit
            prev_fb = np.asarray(prev) if donate else prev
            out, used = impl(jnp.asarray(prev), jnp.asarray(pweights),
                             jnp.asarray(carry.used), constraints=(2,))
            return fallback(np.asarray(out), prev_fb, pweights)
    """)
    assert fs == []


def test_don001_packed_tuple_and_splat_dispatch_trips(tmp_path):
    # The solve_dense_warm idiom: operands packed into dev_args and
    # splatted into the dispatch — a post-dispatch read of the packed
    # tuple's element is still a read of the donated buffer.
    fs = _don_findings(tmp_path, """
        def warm(prev, pweights, carry):
            dev_args = (jnp.asarray(prev), jnp.asarray(pweights),
                        jnp.asarray(carry.used))
            out, used = _impl_donating(*dev_args, constraints=(2,))
            return dev_args[0] + out
    """)
    assert _rules(fs) == ["DON001"]


def test_don001_attribute_root_trips(tmp_path):
    # Donating straight off self.current, then returning it: the
    # session-state shape of the same bug.
    fs = _don_findings(tmp_path, """
        class Session:
            def warm(self, pweights, carry):
                out, used = _impl_donating(
                    jnp.asarray(self.current), pweights,
                    jnp.asarray(carry.used), constraints=(2,))
                return self.current
    """)
    assert _rules(fs) == ["DON001"]


def test_don001_returning_donated_operand_trips(tmp_path):
    # Returning the donated operand hands the invalidated buffer to the
    # caller — a read-at-a-distance.
    fs = _don_findings(tmp_path, """
        def warm(prev, pweights, carry):
            out, used = _impl_donating(jnp.asarray(prev), pweights,
                                       jnp.asarray(carry.used),
                                       constraints=(2,))
            return prev
    """)
    assert _rules(fs) == ["DON001"]


def test_don001_metadata_reads_are_clean(tmp_path):
    # .shape/.dtype survive donation (the aval outlives the buffer).
    fs = _don_findings(tmp_path, """
        def warm(prev, pweights, carry):
            out, used = _impl_donating(jnp.asarray(prev), pweights,
                                       jnp.asarray(carry.used),
                                       constraints=(2,))
            return out.reshape(prev.shape), prev.dtype
    """)
    assert fs == []


def test_don001_donate_argnums_positional_mapping_trips(tmp_path):
    # donate_argnums resolve through the wrapped signature to the same
    # parameter names donate_argnames would use.
    fs = _don_findings(tmp_path, """
        def warm(prev, pweights, carry):
            out, used = _impl_nums(jnp.asarray(prev), pweights,
                                   jnp.asarray(carry.used), (2,))
            return prev
    """)
    assert _rules(fs) == ["DON001"]


def test_don002_escape_before_dispatch_trips(tmp_path):
    # Stashing the operand on self before donating it: another window
    # can observe the invalidated buffer (the CarryCache risk surface).
    fs = _don_findings(tmp_path, """
        class Session:
            def warm(self, prev, pweights, carry):
                self._stash = prev
                out, used = _impl_donating(jnp.asarray(prev), pweights,
                                           jnp.asarray(carry.used),
                                           constraints=(2,))
                return out
    """)
    assert _rules(fs) == ["DON002"]


def test_don002_store_method_escape_trips(tmp_path):
    fs = _don_findings(tmp_path, """
        class Session:
            def warm(self, prev, pweights, carry):
                self.cache.store("k", prev)
                out, used = _impl_donating(jnp.asarray(prev), pweights,
                                           jnp.asarray(carry.used),
                                           constraints=(2,))
                return out
    """)
    assert _rules(fs) == ["DON002"]


def test_don002_storing_the_output_is_clean(tmp_path):
    # Escaping the dispatch OUTPUT is the normal result path, not a
    # donated-operand escape.
    fs = _don_findings(tmp_path, """
        class Session:
            def warm(self, prev, pweights, carry):
                out, used = _impl_donating(jnp.asarray(prev), pweights,
                                           jnp.asarray(carry.used),
                                           constraints=(2,))
                self._stash = np.asarray(out)
                return out
    """)
    assert fs == []


def test_don003_double_dispatch_trips(tmp_path):
    fs = _don_findings(tmp_path, """
        def warm(prev, pweights, carry):
            out, used = _impl_donating(jnp.asarray(prev), pweights,
                                       jnp.asarray(carry.used),
                                       constraints=(2,))
            out2, used2 = _impl_donating(jnp.asarray(prev), pweights,
                                         used, constraints=(2,))
            return out2
    """)
    assert _rules(fs) == ["DON003"]


def test_don003_rebound_redispatch_is_clean(tmp_path):
    fs = _don_findings(tmp_path, """
        def warm(prev, pweights, carry):
            out, used = _impl_donating(jnp.asarray(prev), pweights,
                                       jnp.asarray(carry.used),
                                       constraints=(2,))
            prev = np.asarray(out)
            out2, used2 = _impl_donating(jnp.asarray(prev), pweights,
                                         used, constraints=(2,))
            return out2
    """)
    assert fs == []


def test_don004_post_dispatch_snapshot_trips(tmp_path):
    # Snapshotting AFTER the dispatch reads the invalidated buffer; the
    # same call BEFORE the dispatch is the fix recipe and stays clean
    # (test_don001_clean_snapshot_twin).
    fs = _don_findings(tmp_path, """
        def warm(prev, pweights, carry):
            out, used = _impl_donating(jnp.asarray(prev), pweights,
                                       jnp.asarray(carry.used),
                                       constraints=(2,))
            keep = np.asarray(prev)
            return out, keep
    """)
    assert _rules(fs) == ["DON004"]


def test_donation_real_package_is_clean():
    """The real package carries ZERO donation findings, baselined or
    not — the PR-11 snapshot fixes cover every donating dispatch."""
    from blance_tpu.analysis import PACKAGE_ROOT, REPO_ROOT, _iter_py_files
    from blance_tpu.analysis.donation import DonationPass

    findings = DonationPass(
        _iter_py_files([PACKAGE_ROOT]), REPO_ROOT).run()
    assert findings == [], "\n".join(f.render() for f in findings)


def test_donation_registry_sees_real_donating_wrappers():
    """The wrapper registry must resolve every jit-with-donate binding
    the package actually declares — a parse regression here would turn
    the whole pass into a silent no-op."""
    from blance_tpu.analysis import PACKAGE_ROOT, REPO_ROOT, _iter_py_files
    from blance_tpu.analysis.donation import DonationPass

    p = DonationPass(_iter_py_files([PACKAGE_ROOT]), REPO_ROOT)
    p.run()
    by_name = {fq.rsplit(".", 1)[-1]: dc
               for fq, dc in p.registry.items()}
    assert by_name["_warm_repair_donating"].donated == (
        "prev", "carry_used")
    assert by_name["_warm_repair_sparse_donating"].donated == (
        "prev", "carry_used")
    assert by_name["_pipeline_cold_donating"].donated == ("prev",)
    assert by_name["_pipeline_warm_donating"].donated == (
        "prev", "carry_used")
    assert by_name["_pipeline_sparse_donating"].donated == ("prev",)


def test_cli_donation_only_mode(capsys):
    from blance_tpu.analysis.__main__ import main

    rc = main(["--donation"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 new finding(s)" in out
    # Other passes' baseline pins must NOT be reported stale in this mode.
    assert "stale baseline entry" not in out


def test_cli_donation_catches_seeded_pr11_regression(tmp_path, capsys):
    # The acceptance fixture: re-introduce the PR-11 sparse-warm read
    # and the CLI must fail with DON001.
    bad = tmp_path / "fix.py"
    bad.write_text(textwrap.dedent(_DON_PRELUDE) + textwrap.dedent("""
        def warm(prev, pweights, carry, fallback):
            out, used = _impl_donating(jnp.asarray(prev), pweights,
                                       jnp.asarray(carry.used),
                                       constraints=(2,))
            return fallback(np.asarray(out), prev, pweights)
    """))
    from blance_tpu.analysis.__main__ import main

    rc = main(["--donation", str(bad)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "DON001" in out


# -- JIT005: donate_argnums validation (the PR-20 gap-fill) ------------------


def test_jit005_donate_argnums_out_of_range_trips(tmp_path):
    fs = _jit_findings(tmp_path, """
        import jax

        def f(x, y, mode):
            return x

        g = jax.jit(f, static_argnames=("mode",), donate_argnums=(5,))
    """)
    assert _rules(fs) == ["JIT005"]
    assert "outside" in fs[0].message


def test_jit005_donate_argnums_static_overlap_trips(tmp_path):
    fs = _jit_findings(tmp_path, """
        import jax

        def f(x, y, mode):
            return x

        g = jax.jit(f, static_argnames=("mode",), donate_argnums=(2,))
    """)
    assert _rules(fs) == ["JIT005"]
    assert "static_argnames" in fs[0].message


def test_jit005_donate_argnums_clean_twin(tmp_path):
    fs = _jit_findings(tmp_path, """
        import jax

        def f(x, y, mode):
            return x

        g = jax.jit(f, static_argnames=("mode",), donate_argnums=(0, 1))
    """)
    assert fs == []


# -- membudget: the declarative HBM-ceiling table ----------------------------


def _mb_patched(monkeypatch, budgets, entries=None):
    """Shrink the membudget pass to a controlled (budgets, builders)
    pair; measurement stays real (AOT on abstract operands — cheap at
    the entries these tests keep)."""
    from blance_tpu.analysis import membudget as mb

    orig = mb._builders()
    keep = {e: orig[e] for e in (entries or []) if e in orig}
    monkeypatch.setattr(mb, "HBM_BUDGETS", budgets)
    monkeypatch.setattr(mb, "_builders", lambda: keep)
    return mb


def test_mem001_over_budget_trips(monkeypatch):
    mb = _mb_patched(monkeypatch, {"sched.ranks": {"smoke": 1}},
                     entries=["sched.ranks"])
    findings, n = mb.run_membudget_check()
    assert _rules(findings) == ["MEM001"]
    assert n == 1
    assert findings[0].symbol == "sched.ranks@smoke"


def test_mem001_within_budget_is_clean(monkeypatch):
    mb = _mb_patched(monkeypatch, {"sched.ranks": {"smoke": 100_000}},
                     entries=["sched.ranks"])
    findings, n = mb.run_membudget_check()
    assert findings == []
    assert n == 1


def test_mem002_table_drift_trips(monkeypatch):
    # All three drift shapes at once: a budget row with no builder, a
    # builder with no row, and a row for a mesh-exempt entry.
    mb = _mb_patched(monkeypatch,
                     {"ghost.entry": {"smoke": 5},
                      "sharded.cold": {"smoke": 5}},
                     entries=["sched.ranks"])
    findings, _ = mb.run_membudget_check()
    assert _rules(findings) == ["MEM002"]
    symbols = sorted(f.symbol for f in findings)
    assert symbols == ["ghost.entry", "sched.ranks", "sharded.cold"]


def test_mem002_unknown_class_trips(monkeypatch):
    mb = _mb_patched(monkeypatch, {"sched.ranks": {"bogus": 5}},
                     entries=["sched.ranks"])
    findings, _ = mb.run_membudget_check()
    assert _rules(findings) == ["MEM002"]
    assert findings[0].symbol == "sched.ranks@bogus"


def test_mem003_dense_row_past_guard_trips(monkeypatch):
    # A dense-engine budget at the north-star class: check_dense_memory
    # rejects a 100k x 10k score matrix at dispatch, so the row is dead
    # and MEM003 must say so (structurally — the gated class is never
    # AOT-compiled).
    mb = _mb_patched(monkeypatch,
                     {"solve_dense.cold": {"north": 10}},
                     entries=["solve_dense.cold"])
    findings, _ = mb.run_membudget_check()
    assert _rules(findings) == ["MEM003"]
    assert findings[0].symbol == "solve_dense.cold@north"


def test_membudget_real_table_is_structurally_sound():
    """MEM002/MEM003 over the REAL table without any measurement:
    every builder budgeted, no dead/exempt/unknown rows."""
    from blance_tpu.analysis import membudget as mb

    assert set(mb._builders()) == set(mb.HBM_BUDGETS)
    assert not (set(mb.HBM_BUDGETS) & mb.MESH_EXEMPT)
    for ent, rows in mb.HBM_BUDGETS.items():
        assert set(rows) <= set(mb.SHAPE_CLASSES), (ent, rows)
        for klass, budget in rows.items():
            assert budget > 0
        if ent in mb._DENSE_ENTRIES:
            for klass in rows:
                d = mb.SHAPE_CLASSES[klass]
                from blance_tpu.plan.tensor import projected_score_bytes

                assert projected_score_bytes(d.P, d.N) <= \
                    mb._DENSE_GUARD_REF_BYTES, (ent, klass)


def test_membudget_entries_match_live_dispatch_labels():
    """Reality guard: every budgeted/exempted entry label must appear
    as a string literal in the dispatch modules — a renamed
    obs/device.entry label would otherwise leave a dead ceiling that
    MEM002 can't see (the builder registry renames with the code, the
    label string does not)."""
    import ast
    import os

    from blance_tpu.analysis import PACKAGE_ROOT
    from blance_tpu.analysis import membudget as mb

    dispatch_modules = [
        os.path.join(PACKAGE_ROOT, "plan", "tensor.py"),
        os.path.join(PACKAGE_ROOT, "plan", "session.py"),
        os.path.join(PACKAGE_ROOT, "plan", "fleet.py"),
        os.path.join(PACKAGE_ROOT, "parallel", "sharded.py"),
        os.path.join(PACKAGE_ROOT, "orchestrate", "sched", "ranks.py"),
    ]
    literals = set()
    for path in dispatch_modules:
        with open(path) as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                literals.add(node.value)
    for label in sorted(set(mb.HBM_BUDGETS) | mb.MESH_EXEMPT):
        assert label in literals, (
            f"membudget entry {label!r} does not appear in any dispatch "
            f"module — the live entry label moved; update HBM_BUDGETS/"
            f"MESH_EXEMPT")
