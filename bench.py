"""Benchmark: batched TPU planner vs the sequential CPU greedy planner.

Measures TWO configs, both primary + 1 replica with rack rules and a warm
previous map with 5% of nodes removed (the realistic delta-rebalance
shape):

  - 100k partitions x  1k nodes  (continuity with earlier rounds)
  - 100k partitions x 10k nodes  (the BASELINE.json north-star shape)

The headline metric is the ON-DEVICE CONVERGED SOLVE of the north-star
config (jit-compiled, post-warmup, forced host sync) — encode/decode are
reported separately as phases of one end-to-end plan_next_map_tpu call,
so the artifact never conflates the two.  The CPU baseline is this repo's
own NATIVE C++ exact greedy planner (the strongest CPU implementation
available — the reference publishes no numbers, BASELINE.md); its
provenance, including any P-scaling, is recorded per config in the JSON.

The compiled Pallas min2/argmin kernel (the auction's hot op) is verified
against the XLA reference spelling on a real device batch before timing;
the result ships in the JSON as pallas/pallas_verified.

Observability (blance_tpu.obs): the run ends with a small end-to-end
plan -> moves -> orchestrate pipeline stage, and the emitted JSON carries
an "obs" block — per-phase span totals, counters (solver sweeps, engine
fallbacks), and histogram p50/p95 summaries including per-move latency.
``--trace-out PATH`` additionally captures every span into a Chrome
trace-event file (open in chrome://tracing or https://ui.perfetto.dev);
``--device-trace-dir DIR`` wraps the run in jax.profiler's device trace
over the same interval so host spans and TPU traces line up.

Prints ONE JSON line:
  {"metric", "value", "unit", "vs_baseline", "detail": {...}}
plus human-readable detail on stderr.
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

# (P, N, headline?) — both rack rules + 5% node removal.  The HEADLINE
# config runs FIRST, so a run cut short still holds the number it is
# judged on (every completed stage also persists to PROGRESS_PATH).
CONFIGS = [
    (100_000, 10_000, True),  # north star (BASELINE.json)
    (100_000, 1_000, False),
]
RUNS = 4  # timed runs per config (min + median reported)
PY_GREEDY_P = 4_000  # python-greedy fallback measured here, scaled in P
CPU_TIMEOUT_S = 540  # budget for one full-size CPU baseline measurement


def _progress_path():
    """Anchored to this file, not the cwd — the driver may launch the
    bench from anywhere, and persistence landing in a scratch dir would
    defeat its purpose."""
    import os

    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "docs", "BENCH_progress.json")


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def _ensure_virtual_devices(count=8):
    """Force a multi-device CPU host (the tests/conftest.py trick) so
    the CPU modes exercise the mesh-sharded code paths (the fleet
    stage's batch axis).  Must run before jax first imports; a no-op
    when the flag is already set."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={count}"
        ).strip()


def first_line(e):
    """First line of an exception message, '' when the message is empty
    (a bare RuntimeError() must not crash the degradation path)."""
    return (str(e).splitlines() or [""])[0][:200]


def index_pct(xs, q):
    """Nearest-rank percentile of a lag list (index formula shared by
    the simulate and sched stages), rounded to ms; None when empty."""
    xs = sorted(xs)
    if not xs:
        return None
    return round(xs[min(int(q * len(xs)), len(xs) - 1)], 3)


def save_progress(detail, stage):
    """Persist everything measured so far.  Only the final stdout JSON
    line is the artifact; a run killed between stages would otherwise
    lose every number already in hand, so each completed stage
    overwrites this file with the full detail tree (stage-stamped)."""
    import os

    path = _progress_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump({"stage": stage, "time": time.strftime(
                "%Y-%m-%dT%H:%M:%S"), "detail": detail}, f, indent=1)
    except OSError as e:  # persistence is best-effort, never fatal
        log(f"save_progress failed: {e}")


def build_dense(P, N, seed=0):
    """Dense arrays for the rack-rule delta-rebalance shape."""
    rng = np.random.default_rng(seed)
    S, R = 2, 1
    prev = np.full((P, S, R), -1, np.int32)
    prev[:, 0, 0] = rng.integers(0, N, P)
    prev[:, 1, 0] = (prev[:, 0, 0] + 1 + rng.integers(0, N - 1, P)) % N
    pweights = np.ones(P, np.float32)
    nweights = np.ones(N, np.float32)
    valid = np.ones(N, bool)
    valid[rng.choice(N, N // 20, replace=False)] = False  # 5% nodes leave
    stickiness = np.full((P, S), 1.5, np.float32)
    gids = np.stack([np.arange(N, dtype=np.int32),
                     np.arange(N, dtype=np.int32) // 25,  # racks of 25
                     np.zeros(N, np.int32)])
    gid_valid = np.ones((3, N), bool)
    constraints = (1, 1)
    rules = ((), ((2, 1),))  # replica on another rack
    return (prev, pweights, nweights, valid, stickiness, gids, gid_valid,
            constraints, rules)


def audit(assign, valid, gids):
    """Violation counts straight off the solved assignment (the '0
    violations' evidence the artifact carries)."""
    a = np.asarray(assign)
    prim, repl = a[:, 0, 0], a[:, 1, 0]
    held = a[a >= 0]
    rack = gids[1]
    co_racked = int(((rack[np.clip(prim, 0, None)] ==
                      rack[np.clip(repl, 0, None)])
                     & (prim >= 0) & (repl >= 0)).sum())
    return {
        "unassigned_slots": int((a < 0).sum()),
        "on_removed_nodes": int((~valid[held]).sum()),
        "duplicates": int(((prim == repl) & (prim >= 0)).sum()),
        "co_racked_replicas": co_racked,
    }


def verify_pallas(N, seed=7):
    """Run the COMPILED Pallas kernel against the XLA oracle on a real
    device batch (ties included); returns (available, verified)."""
    import jax
    import jax.numpy as jnp
    from blance_tpu.ops.reduce2 import (
        min2_argmin_reference, pallas_available, priced_min2_argmin)

    if not pallas_available():
        return False, False
    rng = np.random.default_rng(seed)
    # Quantized scores force duplicate minima so tie-breaks are exercised.
    score = jnp.asarray(
        rng.integers(0, 50, (4096, N)).astype(np.float32) * 0.125)
    price = jnp.asarray(rng.integers(0, 8, N).astype(np.float32) * 0.25)
    b1, c1, s1 = (np.asarray(x) for x in priced_min2_argmin(score, price))
    b2, c2, s2 = (np.asarray(x) for x in
                  min2_argmin_reference(score + price[None, :]))
    ok = (np.array_equal(b1, b2) and np.array_equal(c1, c2)
          and np.array_equal(s1, s2))
    log(f"pallas kernel vs XLA oracle on device (4096x{N}): "
        f"{'bit-identical' if ok else 'MISMATCH'}")
    return True, bool(ok)


def _device_block(mon):
    """One stage's device-observatory summary for the artifact:
    per-entry compile counts from the stage-local monitor plus whatever
    (entry, bucket-shape) cost analyses have been published so far."""
    from blance_tpu.obs import device as obs_device

    return {"compiles": mon.summary(),
            "cost": obs_device.cost_summaries()}


def bench_tpu(P, N, fused=False):
    """On-device converged solve: compile + RUNS timed runs + audit."""
    import jax.numpy as jnp
    from blance_tpu.obs import device as obs_device
    from blance_tpu.plan.tensor import solve_dense_converged

    (prev, pweights, nweights, valid, stickiness, gids, gid_valid,
     constraints, rules) = build_dense(P, N)
    dev_args = [jnp.asarray(a) for a in
                (prev, pweights, nweights, valid, stickiness, gids, gid_valid)]
    mode = "on" if fused else "off"
    tag = f"[{P}x{N}{' fused' if fused else ''}]"

    # record=False in the timed loop: the obs sweeps read is one extra
    # scalar D2H round-trip inside the timed region.  The compile call
    # records once, so the counter still moves.
    def run(record=False):
        return solve_dense_converged(
            *dev_args, constraints, rules, fused_score=mode,
            record=record).block_until_ready()

    with obs_device.CompileMonitor() as mon:
        t0 = time.perf_counter()
        out = run(record=True)
        compile_s = time.perf_counter() - t0
        log(f"{tag} compile+first-run: {compile_s:.2f}s")

        times = []
        for _ in range(RUNS):
            t0 = time.perf_counter()
            out = run()
            times.append(time.perf_counter() - t0)
    log(f"{tag} on-device solve: min {min(times)*1000:.1f}ms  runs: "
        f"{[f'{t*1000:.1f}' for t in times]}")

    counts = audit(out, valid, gids)
    log(f"{tag} audit: {counts}")
    assert counts["unassigned_slots"] == 0
    assert counts["on_removed_nodes"] == 0
    return {
        "compile_s": round(compile_s, 2),
        "solve_ms_min": round(min(times) * 1000, 2),
        "solve_ms_median": round(statistics.median(times) * 1000, 2),
        "solve_ms_runs": [round(t * 1000, 2) for t in times],
        "violations": counts,
        "device": _device_block(mon),
    }


def verify_fused_engine():
    """Contract-check the COMPILED fused score engine against the matrix
    engine on device at small scale: both audits clean, per-node load
    spread within +2.  Gates whether fused timed runs happen at all."""
    import jax.numpy as jnp
    from blance_tpu.ops.reduce2 import pallas_available
    from blance_tpu.plan.tensor import solve_dense_converged

    if not pallas_available():
        return False
    P, N = 4096, 512
    (prev, pweights, nweights, valid, stickiness, gids, gid_valid,
     constraints, rules) = build_dense(P, N, seed=3)
    dev = [jnp.asarray(a) for a in
           (prev, pweights, nweights, valid, stickiness, gids, gid_valid)]
    outs = {}
    for mode in ("off", "on"):
        try:
            a = np.asarray(solve_dense_converged(
                *dev, constraints, rules, fused_score=mode))
        except Exception as e:  # a kernel that won't lower must not
            log(f"fused-engine verify: mode={mode} failed to "  # kill the
                f"compile/run: {type(e).__name__}: "            # bench
                f"{first_line(e)}")
            return False
        counts = audit(a, valid, gids)
        if any(counts.values()):
            log(f"fused-engine verify: mode={mode} violations {counts}")
            return False
        outs[mode] = a
    spreads = {}
    for mode, a in outs.items():
        ids = a[a >= 0]
        loads = np.bincount(ids, minlength=N)[valid]
        spreads[mode] = int(loads.max() - loads.min())
    ok = spreads["on"] <= spreads["off"] + 2
    log(f"fused-engine verify @ {P}x{N}: clean audits, spreads "
        f"matrix={spreads['off']} fused={spreads['on']} -> "
        f"{'OK' if ok else 'REJECTED'}")
    return ok


def _make_map(P, N, seed=0):
    """PartitionMap + node list mirroring build_dense's shape."""
    from blance_tpu import Partition

    rng = np.random.default_rng(seed)
    nodes = [f"n{i:05d}" for i in range(N)]
    removed = [nodes[i] for i in
               rng.choice(N, N // 20, replace=False)]
    p_ids = rng.integers(0, N, P)
    r_ids = (p_ids + 1 + rng.integers(0, N - 1, P)) % N
    prev = {str(i): Partition(str(i), {"primary": [nodes[p_ids[i]]],
                                       "replica": [nodes[r_ids[i]]]})
            for i in range(P)}
    return prev, nodes, removed


def _rack_opts(nodes):
    from blance_tpu import HierarchyRule, PlanOptions

    hier = {n: f"r{i // 25}" for i, n in enumerate(nodes)}
    hier.update({f"r{i}": "z0" for i in range((len(nodes) + 24) // 25)})
    return PlanOptions(node_hierarchy=hier,
                       hierarchy_rules={"replica": [HierarchyRule(2, 1)]})


def bench_phases(P, N):
    """One end-to-end plan_next_map_tpu call with PhaseTimer: attributes
    wall-clock to encode / solve / decode (compile already warm from
    bench_tpu, same static shapes)."""
    from blance_tpu import model
    from blance_tpu.plan.tensor import plan_next_map_tpu
    from blance_tpu.utils.trace import PhaseTimer

    prev, nodes, removed = _make_map(P, N)
    m = model(primary=(0, 1), replica=(1, 1))
    # The map-derived encode can produce different static shapes (hierarchy
    # levels) than build_dense, so warm its compile separately; the timed
    # call below is the steady-state end-to-end cost.
    plan_next_map_tpu(prev, prev, nodes, removed, [], m, _rack_opts(nodes))
    timer = PhaseTimer()
    t0 = time.perf_counter()
    plan_next_map_tpu(prev, prev, nodes, removed, [], m,
                      _rack_opts(nodes), timer=timer)
    total = time.perf_counter() - t0
    phases = {name: round(timer.totals[name] * 1000, 1)
              for name in ("encode", "solve", "decode")
              if name in timer.totals}
    phases["total"] = round(total * 1000, 1)
    log(f"[{P}x{N}] end-to-end phases (ms): {phases}")
    return phases


def bench_pipeline(P=256, N=32):
    """End-to-end plan -> moves -> orchestrate at a small fixed size.

    This is the stage that exercises the moves and orchestrate layers, so
    a --trace-out trace carries spans from the whole pipeline (plan
    encode/solve/decode already come from bench_phases at bench scale)
    and the obs histograms gain per-move latency (orchestrate.move.exec
    with a no-op data plane: pure scheduling cost)."""
    import asyncio

    from blance_tpu import model
    from blance_tpu.orchestrate.orchestrator import (
        OrchestratorOptions, orchestrate_moves)
    from blance_tpu.plan.api import plan_next_map

    prev, nodes, removed = _make_map(P, N, seed=11)
    m = model(primary=(0, 1), replica=(1, 1))
    t0 = time.perf_counter()
    next_map, _warn = plan_next_map(
        prev, prev, nodes, removed, [], m, _rack_opts(nodes),
        backend="greedy")

    async def assign(stop_ch, node, partitions, states, ops):
        await asyncio.sleep(0)  # no data plane: scheduling cost only

    async def run():
        o = orchestrate_moves(
            m,
            OrchestratorOptions(device_diff=True,
                                interrupt_on_first_feed=False,
                                max_concurrent_partition_moves_per_node=4),
            nodes, prev, next_map, assign)
        events = 0
        final = None
        async for p in o.progress_ch():
            events += 1
            final = p
        o.stop()
        return events, final

    events, final = asyncio.run(run())
    total_ms = (time.perf_counter() - t0) * 1000
    log(f"[pipeline {P}x{N}] plan+diff+orchestrate: {total_ms:.0f}ms, "
        f"{final.tot_mover_assign_partition_ok} batches ok, "
        f"{events} progress events")
    return {"P": P, "N": N, "total_ms": round(total_ms, 1),
            "batches_ok": final.tot_mover_assign_partition_ok,
            "errors": len(final.errors),
            "progress_events": events}


def bench_chaos(P=96, N=12, seed=7, fail_rate=0.3):
    """Chaos stage: transition completion under a fixed injected fault
    rate (ISSUE 3).  A seeded FaultPlan makes one node dead and two
    flaky at ``fail_rate``; the fault-tolerant rebalance (deadlines +
    retries + quarantine + bounded recovery replans) must still land a
    complete map on the surviving nodes.  Reports wall-clock, retry/
    timeout/quarantine counters, recovery rounds, and whether the final
    reconstructed map is whole — the robustness headline."""
    from blance_tpu import Partition, model
    from blance_tpu.obs import Recorder, use_recorder
    from blance_tpu.orchestrate import FaultPlan, NodeFaults
    from blance_tpu.orchestrate.orchestrator import OrchestratorOptions
    from blance_tpu.rebalance import rebalance

    nodes = [f"n{i:03d}" for i in range(N)]
    live = nodes[:-1]
    dead = nodes[-1]
    m = model(primary=(0, 1), replica=(1, 1))
    beg = {
        f"{i:04d}": Partition(f"{i:04d}", {
            "primary": [live[i % len(live)]],
            "replica": [live[(i + 1) % len(live)]]})
        for i in range(P)
    }
    plan = FaultPlan(seed=seed, nodes={
        dead: NodeFaults(dead=True),
        nodes[0]: NodeFaults(fail_rate=fail_rate),
        nodes[1]: NodeFaults(fail_rate=fail_rate),
    })

    async def assign(stop_ch, node, partitions, states, ops):
        import asyncio

        await asyncio.sleep(0)

    rec = Recorder()
    t0 = time.perf_counter()
    with use_recorder(rec):
        result = rebalance(
            m, beg, nodes, [], [dead], plan.wrap(assign),
            orchestrator_options=OrchestratorOptions(
                move_timeout_s=0.25, max_retries=4, backoff_base_s=0.002,
                quarantine_after=3, probe_after_s=60.0),
            max_recovery_rounds=3, backend="greedy")
    total_ms = (time.perf_counter() - t0) * 1000

    complete = all(
        len(p.nodes_by_state.get("primary", [])) == 1
        and len(p.nodes_by_state.get("replica", [])) == 1
        for p in result.achieved_map.values())
    slo = result.slo
    out = {
        "P": P, "N": N, "seed": seed, "fail_rate": fail_rate,
        "total_ms": round(total_ms, 1),
        "complete": complete,
        "failures": len(result.failures),
        "recovery_rounds": len(result.rounds) - 1,
        "quarantined": result.quarantined_nodes,
        "injected": dict(plan.injected),
        "retries": rec.counters.get("orchestrate.retries", 0),
        "timeouts": rec.counters.get("orchestrate.timeouts", 0),
        "quarantine_trips": rec.counters.get(
            "orchestrate.quarantine_trips", 0),
        # Online SLO accounting (obs/slo.py): the live gauges' final
        # reading — availability/churn/lag plus per-node quarantine
        # exposure — as streamed on the exposition endpoint mid-run.
        "slo": {
            "availability": round(slo.availability, 6),
            "churn_ratio": round(slo.churn_ratio, 4),
            "convergence_lag_ms": round(slo.convergence_lag_s * 1000, 2),
            "moves_executed": slo.moves_executed,
            "moves_failed": slo.moves_failed,
            "min_moves": slo.min_moves,
            "quarantine_exposure_s": {
                n: round(v, 4)
                for n, v in sorted(slo.quarantine_exposure_s.items())},
        },
    }
    log(f"[chaos {P}x{N}] complete={complete} failures={out['failures']} "
        f"retries={out['retries']:.0f} trips={out['quarantine_trips']:.0f} "
        f"recovery_rounds={out['recovery_rounds']} "
        f"avail={out['slo']['availability']} "
        f"churn={out['slo']['churn_ratio']} in {total_ms:.0f}ms")
    return out


def bench_simulate(seed=7, days=1.0):
    """Continuous-rebalance simulator stage (docs/SIMULATOR.md): one
    seeded mixed-fault scenario (daily churn, spot preemptions, a zone
    flap, hot-tenant drift, overlapping deltas) replayed under the
    DeterministicLoop virtual clock.  Reports the horizon SLO account —
    time-weighted availability, churn vs the offline-optimal single
    plan, p50/p95 per-incident convergence lag — plus the simulator's
    own throughput headline: virtual sim-seconds per wall-second."""
    from blance_tpu.testing.scenarios import mixed_week
    from blance_tpu.testing.simulate import run_scenario

    scn = mixed_week(seed, days=days)
    r = run_scenario(scn)
    lags = r.convergence_lags

    def pct(q):
        return index_pct(lags, q)

    s = r.summary
    out = {
        "scenario": r.scenario, "seed": seed, "days": days,
        "deltas": r.deltas, "rebalances": r.rebalances,
        "superseded": r.superseded, "degraded": r.degraded,
        "unconverged": r.unconverged,
        "complete": r.complete,
        "availability": round(s.availability, 6),
        "time_weighted_availability": round(
            s.time_weighted_availability, 6),
        "violation_s": round(s.violation_s, 3),
        "moves_executed": s.moves_executed,
        "offline_min_moves": r.offline_min_moves,
        "churn_vs_offline": (round(r.churn_vs_offline, 3)
                             if r.churn_vs_offline is not None else None),
        "convergence_lag_s": {"p50": pct(0.50), "p95": pct(0.95),
                              "n": len(lags)},
        "unscripted_drops": len(r.unscripted_drops),
        "loop_steps": r.steps,
        "wall_s": round(r.wall_s, 3),
        "sim_s_per_wall_s": round(r.horizon_s / max(r.wall_s, 1e-9)),
    }
    log(f"[simulate {r.scenario} seed={seed} {days:g}d] "
        f"complete={out['complete']} "
        f"tw_avail={out['time_weighted_availability']} "
        f"churn={out['churn_vs_offline']} "
        f"lag p50/p95={out['convergence_lag_s']['p50']}/"
        f"{out['convergence_lag_s']['p95']}s "
        f"superseded={out['superseded']} "
        f"{out['sim_s_per_wall_s']}x sim-s/wall-s")
    return out


def bench_costmodel(P=128, N=10, seed=5, fail_rate=0.25):
    """Cost-model stage: calibrate per-(node, op) EWMA move costs from
    the move-lifecycle spans of a chaos rebalance with a heterogeneous
    data plane, and score the model's predicted-vs-actual relative
    error online (each update falsifies the prediction that preceded
    it).  Also round-trips the model through its JSON persistence —
    the exact artifact ROADMAP item 2's critical-path scheduler loads."""
    import asyncio
    import tempfile

    from blance_tpu import Partition, model
    from blance_tpu.obs import CostModel, Recorder, use_recorder
    from blance_tpu.orchestrate import FaultPlan, NodeFaults
    from blance_tpu.orchestrate.orchestrator import OrchestratorOptions
    from blance_tpu.rebalance import rebalance

    nodes = [f"n{i:03d}" for i in range(N)]
    m = model(primary=(0, 1), replica=(1, 1))
    beg = {
        f"{i:04d}": Partition(f"{i:04d}", {
            "primary": [nodes[i % (N - 1)]],
            "replica": [nodes[(i + 1) % (N - 1)]]})
        for i in range(P)
    }
    plan = FaultPlan(seed=seed, nodes={
        nodes[0]: NodeFaults(fail_rate=fail_rate),
    })

    # Heterogeneous per-(node, op) latency: node index sets the tier,
    # op kind scales it — the structure the EWMA table must learn.
    async def assign(stop_ch, node, partitions, states, ops):
        tier = 1 + int(node[1:]) % 3
        per_op = {"promote": 0.5, "demote": 0.5, "add": 1.0, "del": 0.25}
        await asyncio.sleep(
            0.002 * tier * max(per_op.get(op, 1.0) for op in ops))

    rec = Recorder()
    cm = CostModel(alpha=0.3, recorder=rec)
    rec.add_sink(cm)
    t0 = time.perf_counter()
    with use_recorder(rec):
        rebalance(
            m, beg, nodes, [nodes[1]], [], plan.wrap(assign),
            orchestrator_options=OrchestratorOptions(
                move_timeout_s=1.0, max_retries=3, backoff_base_s=0.001,
                quarantine_after=0),
            backend="greedy")
    total_ms = (time.perf_counter() - t0) * 1000

    cal = cm.calibration()
    # Persistence round trip: the scheduler-facing contract is that a
    # reloaded model predicts exactly what the live one does.
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        path = f.name
    cm.save(path)
    reloaded = CostModel.load(path)
    probes = list(cm.estimates())[:16] + [("never-seen", "add")]
    roundtrip_ok = all(
        cm.predict(n, op) == reloaded.predict(n, op) for n, op in probes)
    os.unlink(path)

    out = {
        "P": P, "N": N, "seed": seed, "total_ms": round(total_ms, 1),
        "observations": cal["observations"],
        "scored": cal["scored"],
        "estimates": cal["estimates"],
        "p50_rel_err": round(cal.get("p50_rel_err", float("nan")), 4),
        "p95_rel_err": round(cal.get("p95_rel_err", float("nan")), 4),
        "roundtrip_ok": roundtrip_ok,
    }
    log(f"[costmodel {P}x{N}] {out['observations']} obs, "
        f"{out['estimates']} (node,op) estimates, p50 rel err "
        f"{out['p50_rel_err']}, p95 {out['p95_rel_err']}, "
        f"roundtrip_ok={roundtrip_ok} in {total_ms:.0f}ms")
    return out


def bench_sched(seed=41):
    """Sched stage (ISSUE 12): the critical-path scheduled move order vs
    the legacy app-weight order at EXACTLY equal churn, scored on the
    two scenario families the scheduler was built for — ``hetero_drain``
    (one slow node, heterogeneous mover latencies: the showcase) and the
    ``mixed_week`` soak.  Both runs replay under the DeterministicLoop
    virtual clock, so every number here is exact and replayable, and the
    committed ``hetero_drain`` trace is regenerated byte-for-byte as the
    drift gate.

    The identity half of the contract — same final map, same move set,
    only the clock changes — is asserted, not just reported; ``gates``
    collects every pass/fail the perf-smoke tier checks."""
    import dataclasses

    from blance_tpu.testing.scenarios import hetero_drain, mixed_week
    from blance_tpu.testing.simulate import run_scenario

    def p95(lags):
        return index_pct(lags, 0.95)

    def compare(scn, skip_incidents=0):
        """Run one scenario legacy vs critical-path; the first
        ``skip_incidents`` incidents are the cost model's calibration
        pass (identical either way) and leave the makespan score."""
        t0 = time.perf_counter()
        leg = run_scenario(scn)
        crit = run_scenario(
            dataclasses.replace(scn, scheduler="critical_path"))
        wall = time.perf_counter() - t0
        leg_map = {k: v.nodes_by_state for k, v in leg.final_map.items()}
        crit_map = {k: v.nodes_by_state
                    for k, v in crit.final_map.items()}
        leg_lags = leg.summary.first_converged_lags[skip_incidents:]
        crit_lags = crit.summary.first_converged_lags[skip_incidents:]
        return {
            "scenario": scn.name, "seed": scn.seed,
            "deltas": leg.deltas,
            "identical_final_map": leg_map == crit_map,
            "equal_churn": (leg.summary.moves_executed
                            == crit.summary.moves_executed),
            "moves_executed": leg.summary.moves_executed,
            "moves_executed_scheduled": crit.summary.moves_executed,
            "legacy": {
                "makespan_p95_s": p95(leg_lags),
                "makespan_total_s": round(sum(leg_lags), 3),
                "convergence_lag_p95_s": p95(leg.convergence_lags),
            },
            "critical_path": {
                "makespan_p95_s": p95(crit_lags),
                "makespan_total_s": round(sum(crit_lags), 3),
                "convergence_lag_p95_s": p95(crit.convergence_lags),
            },
            "wall_s": round(wall, 3),
        }, crit

    hetero, hetero_crit = compare(hetero_drain(seed), skip_incidents=1)
    week, _ = compare(mixed_week(7))

    # The committed replay trace is the CRITICAL-PATH account of the
    # hetero_drain family: any drift in scheduler arithmetic (ranks,
    # lane assignment, reschedule timing) shows up as a byte diff.
    trace_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "tests", "traces",
                              f"sim_hetero_drain_s{seed}.json")
    try:
        with open(trace_path) as f:
            replay_ok = f.read() == hetero_crit.log_text()
    except OSError:
        replay_ok = False

    gates = {
        "hetero_identical_final_map": hetero["identical_final_map"],
        "hetero_equal_churn": hetero["equal_churn"],
        # The headline: scheduled order must strictly beat app-weight
        # order on the heterogeneous family's post-warmup makespan p95.
        "hetero_makespan_win": (
            hetero["critical_path"]["makespan_p95_s"]
            < hetero["legacy"]["makespan_p95_s"]),
        "hetero_trace_replay": replay_ok,
        # The soak is fault-HEAVY: move order changes which moves land
        # inside flaky windows, so the fault draws (and thus the exact
        # retry churn) legitimately differ — the exact-equality identity
        # claim lives on the fault-free hetero family and in the chaos
        # tests with deterministic (dead-node) faults.  Here the gates
        # are one-sided: scheduling must never BUY makespan with extra
        # churn (at most 2% more moves) nor LENGTHEN the week's tail.
        "week_no_extra_churn": (
            week["moves_executed_scheduled"]
            <= 1.02 * week["moves_executed"]),
        "week_no_regression": (
            week["critical_path"]["makespan_p95_s"]
            <= week["legacy"]["makespan_p95_s"]),
    }
    out = {"hetero_drain": hetero, "mixed_week": week, "gates": gates,
           "pass": all(gates.values())}
    log(f"[sched hetero_drain seed={seed}] makespan p95 "
        f"{hetero['legacy']['makespan_p95_s']}s legacy -> "
        f"{hetero['critical_path']['makespan_p95_s']}s scheduled, "
        f"equal_churn={hetero['equal_churn']} "
        f"identical_map={hetero['identical_final_map']} "
        f"replay_ok={replay_ok}; mixed_week p95 "
        f"{week['legacy']['makespan_p95_s']}s -> "
        f"{week['critical_path']['makespan_p95_s']}s "
        f"pass={out['pass']}")
    return out


def bench_fleet(B=64):
    """Fleet stage: batched multi-tenant bucket-class solves vs the
    sequential single-problem loop (ISSUE 7).

    B small tenant indexes with mixed sizes across two shape-bucket
    classes solve three ways: per tenant through the existing single-
    problem path (solve_converged_resilient on the same padded arrays —
    the loop a fleet replan runs today), as fleet batches (one vmapped
    device dispatch per bucket class, batch axis sharded over the
    mesh), and through the asyncio plan service (request coalescing,
    per-tenant carry cache).  Reports solves/sec both ways, the
    speedup, per-tenant bit-identity (the fleet contract), and the
    service's p50/p99 admission-to-result latency."""
    from blance_tpu.plan.fleet import TenantProblem, batch_class_of

    def tenant(i):
        # Mixed sizes spanning two bucket classes: the [16, 32) octave
        # buckets in steps of 2, so P 17/18 -> class 18 and 19/20 ->
        # class 20 (cbgt/FTS-style small per-index plans).
        P = 17 + (i % 4)
        N = 8
        rng = np.random.default_rng(1000 + i)
        S, R = 2, 1
        prev = np.full((P, S, R), -1, np.int32)
        prev[:, 0, 0] = rng.integers(0, N, P)
        prev[:, 1, 0] = (prev[:, 0, 0] + 1 + rng.integers(0, N - 1, P)) % N
        return TenantProblem(
            key=f"tenant-{i:03d}", prev=prev,
            partition_weights=np.ones(P, np.float32),
            node_weights=np.ones(N, np.float32),
            valid_node=np.ones(N, bool),
            stickiness=np.full((P, S), 1.5, np.float32),
            gids=np.stack([np.arange(N, dtype=np.int32),
                           np.arange(N, dtype=np.int32) // 4,
                           np.zeros(N, np.int32)]),
            gid_valid=np.ones((3, N), bool),
            constraints=(1, 1), rules=((), ((2, 1),)))

    from blance_tpu.obs import device as obs_device

    tenants = [tenant(i) for i in range(B)]
    classes = sorted({(k.p, k.n) for k in map(batch_class_of, tenants)})
    # Same leak discipline as bench_delta_replan: the stage may fail and
    # be survived by _run_benchmarks, so the tap must come down with it.
    mon = obs_device.CompileMonitor().install()
    try:
        return _bench_fleet_measured(B, tenants, classes, mon)
    finally:
        mon.uninstall()


def _bench_fleet_measured(B, tenants, classes, mon):
    import asyncio

    import jax
    import jax.numpy as jnp
    from blance_tpu.core.encode import pad_problem_arrays
    from blance_tpu.parallel.sharded import make_mesh
    from blance_tpu.plan.fleet import batch_class_of, solve_fleet
    from blance_tpu.plan.service import PlanService
    from blance_tpu.plan.tensor import (
        resolve_default_fused_score, solve_converged_resilient)

    def solve_seq(t):
        # The existing single-problem path on the SAME padded arrays +
        # real-P fill denominator, so identity is checkable and the
        # comparison is one-dispatch-per-tenant vs one-per-class.
        k = batch_class_of(t)
        arrs = pad_problem_arrays(
            t.prev, t.partition_weights, t.node_weights, t.valid_node,
            t.stickiness, t.gids, t.gid_valid, k.p, k.n)
        out, _eng = solve_converged_resilient(
            *[jnp.asarray(a) for a in arrs], t.constraints, t.rules,
            max_iterations=10,
            mode=resolve_default_fused_score(k.p, k.n),
            allow_fallback=False, context="bench.fleet.sequential",
            p_real=jax.device_put(np.float32(t.prev.shape[0])))
        return np.asarray(out)[:t.prev.shape[0]]

    # Batch-axis mesh: all devices on an accelerator; on a cpu host the
    # virtual devices share the physical cores, so cap the shard count
    # at the core count (8 virtual shards on 2 cores just context-
    # switch — measured slower than 2).
    n_dev = len(jax.devices())
    if jax.default_backend() == "cpu":
        n_dev = min(n_dev, os.cpu_count() or 1)
    mesh = make_mesh(n_dev) if n_dev > 1 else None

    # Warm both paths' compiles, and pin the contract: batched results
    # must be bit-identical to the per-tenant sequential solves.
    seq_outs = [solve_seq(t) for t in tenants]
    fleet_res = solve_fleet(tenants, mesh=mesh)
    identical = all(np.array_equal(a, r.assign)
                    for a, r in zip(seq_outs, fleet_res))
    assert identical, "fleet batch diverged from sequential solves"

    reps = 3
    seq_s = min(_timed(lambda: [solve_seq(t) for t in tenants])
                for _ in range(reps))
    fleet_s = min(_timed(lambda: solve_fleet(tenants, mesh=mesh))
                  for _ in range(reps))

    # The asyncio front door: submit all B concurrently, coalesced into
    # per-class batches within the admission window.
    async def drive():
        svc = PlanService(admission_window_s=0.005, mesh=mesh,
                          max_pending=max(B, 64))
        await svc.start()
        lat = []

        async def one(t):
            t0 = time.perf_counter()
            r = await svc.submit(t)
            lat.append(time.perf_counter() - t0)
            return r

        t0 = time.perf_counter()
        results = await asyncio.gather(*[one(t) for t in tenants])
        total = time.perf_counter() - t0
        await svc.stop()
        ok = all(np.array_equal(a, r.assign)
                 for a, r in zip(seq_outs, results))
        return total, sorted(lat), ok

    service_s, lat, service_identical = asyncio.run(drive())
    mon.uninstall()

    def pct(q):
        return lat[min(int(q * (len(lat) - 1)), len(lat) - 1)]

    out = {
        "tenants": B,
        "device": _device_block(mon),
        "classes": [f"{p}x{n}" for p, n in classes],
        "mesh_devices": 1 if mesh is None
        else int(np.prod(mesh.devices.shape)),
        "seq_ms": round(seq_s * 1000, 1),
        "fleet_ms": round(fleet_s * 1000, 1),
        "speedup": round(seq_s / fleet_s, 2),
        "solves_per_s_seq": round(B / seq_s, 1),
        "solves_per_s_fleet": round(B / fleet_s, 1),
        "identical": identical,
        "service_ms": round(service_s * 1000, 1),
        "service_identical": service_identical,
        "admission_p50_ms": round(pct(0.50) * 1000, 2),
        "admission_p99_ms": round(pct(0.99) * 1000, 2),
    }
    log(f"[fleet {B} tenants, classes {out['classes']}] "
        f"seq {out['seq_ms']}ms ({out['solves_per_s_seq']}/s) vs fleet "
        f"{out['fleet_ms']}ms ({out['solves_per_s_fleet']}/s) = "
        f"{out['speedup']}x, identical={identical}; service "
        f"{out['service_ms']}ms p50/p99 admission "
        f"{out['admission_p50_ms']}/{out['admission_p99_ms']}ms")
    return out


def _bench_encode_host_costs(P=4096, n_nodes=16, cycles=30, reps=3):
    """Direct planner-layer A/B at a scale where O(cluster) host encode
    matters: one resident and one full-re-encode ServicePlanner drive
    identical warm converge cycles (weight drift + node fail/strip +
    re-add) against private inline services on the DeterministicLoop,
    and the planners' own host_phase clocks time exactly the
    encode/decode halves the residency layer changed — no simulator or
    data-plane wall-clock in the measurement.  Partition weights are
    set for every partition, so the baseline pays encode_problem's
    O(P) Python weight/stickiness loops per cycle while the resident
    path dict-diffs the 4 drifted rows.  Returns the phase totals +
    the bit-identity verdict."""
    import asyncio

    from blance_tpu.core.types import Partition, model
    from blance_tpu.fleetloop import ServicePlanner
    from blance_tpu.obs import Recorder, use_recorder
    from blance_tpu.plan.service import PlanService
    from blance_tpu.rebalance import _strip_nodes
    from blance_tpu.testing.sched import DeterministicLoop, FifoPolicy

    import gc

    mdl = model(primary=(0, 1), replica=(1, 1))
    nodes = [f"n{i:02d}" for i in range(n_nodes)]
    pmap = {}
    for i in range(P):
        p = f"p{i:05d}"
        pmap[p] = Partition(p, {"primary": [nodes[i % n_nodes]],
                                "replica": [nodes[(i + 1) % n_nodes]]})
    base_weights = {f"p{i:05d}": 2 for i in range(P)}

    def one_rep():
        loop = DeterministicLoop(FifoPolicy(), max_steps=20_000_000)
        rec = Recorder(clock=loop.time)
        weights = dict(base_weights)

        async def drive():
            from blance_tpu.core.types import PlanOptions

            svc_r = PlanService(admission_window_s=0.0,
                                inline_solve=True, recorder=rec,
                                batch_floor=1)
            svc_b = PlanService(admission_window_s=0.0,
                                inline_solve=True, recorder=rec,
                                batch_floor=1)
            await svc_r.start()
            await svc_b.start()
            pr = ServicePlanner("t", svc_r, recorder=rec)
            pb = ServicePlanner("t", svc_b, recorder=rec,
                                encode_residency=False)
            cur_r = pmap
            cur_b = {k: p.copy() for k, p in pmap.items()}
            removes: list = []
            identical = True
            for c in range(cycles):
                # The steady-state mix the ISSUE's claim is about:
                # one abrupt fail + strip episode (with its re-add),
                # periodic small weight drift, and mostly converged
                # repeat cycles — per-cycle cost should track the
                # DELTA size, and the one big re-place episode is a
                # genuinely big delta on both sides.
                if c == 3:
                    dark = nodes[0]
                    removes = [dark]
                    before_r, before_b = cur_r, cur_b
                    cur_r = _strip_nodes(cur_r, {dark})
                    cur_b = _strip_nodes(cur_b, {dark})
                    pr.notify_strip({dark}, before_r, cur_r)
                elif c == 6:
                    removes = []
                elif c % 5 == 1:
                    for j in range(4):
                        weights[f"p{(c * 97 + j * 31) % P:05d}"] = \
                            2 + (c + j) % 7
                opts = PlanOptions(partition_weights=dict(weights))
                mr, _w = await pr.plan_cycle(cur_r, nodes, removes,
                                             mdl, opts)
                mb, _w = await pb.plan_cycle(cur_b, nodes, removes,
                                             mdl, opts)
                identical = identical and mr.keys() == mb.keys() \
                    and all(mr[k].nodes_by_state == mb[k].nodes_by_state
                            for k in mr)
                cur_r, cur_b = mr, mb
            await svc_r.stop()
            await svc_b.stop()
            return identical, pr.host_phase, pb.host_phase

        with use_recorder(rec):
            return loop.run_until_complete(drive())

    # Min-of-reps per side, GC parked during the timed window: the two
    # planners allocate millions of short-lived map objects per rep, so
    # collector pauses otherwise land stochastically inside the phase
    # clocks (observed 10x swings on identical deterministic work) and
    # the ratio — not just the absolute — gets distorted.
    identical = True
    best_r: dict = {}
    best_b: dict = {}
    for _ in range(max(int(reps), 1)):
        gc.collect()
        gc.disable()
        try:
            ok, ph_r, ph_b = one_rep()
        finally:
            gc.enable()
        identical = identical and ok
        if not best_r or sum(ph_r.values()) < sum(best_r.values()):
            best_r = dict(ph_r)
        if not best_b or sum(ph_b.values()) < sum(best_b.values()):
            best_b = dict(ph_b)
    res_ms = sum(best_r.values()) * 1000
    base_ms = sum(best_b.values()) * 1000
    return {
        "P": P, "nodes": n_nodes, "cycles": cycles, "reps": reps,
        "identical": bool(identical),
        "resident_host_ms": round(res_ms, 2),
        "full_reencode_host_ms": round(base_ms, 2),
        "resident_encode_ms": round(best_r["encode"] * 1000, 2),
        "full_reencode_encode_ms": round(best_b["encode"] * 1000, 2),
        "resident_decode_ms": round(best_r["decode"] * 1000, 2),
        "full_reencode_decode_ms": round(best_b["decode"] * 1000, 2),
        "host_speedup": round(base_ms / max(res_ms, 1e-9), 2),
    }


def bench_fleet_loop(tenants=8, seed=5):
    """Fleet-of-loops stage (ISSUE 13 + ISSUE 14, docs/FLEET.md): N
    tenants' CONTINUOUS rebalance loops — debounce, converge cycles,
    warm carries — multiplexed over one shared plan service, coalesced
    converge cycles vs the sequential loop-per-tenant baseline (same
    code path, zero admission window, max_batch=1) on the same seeded
    multi-tenant scenario under the DeterministicLoop virtual clock.

    The gate: identical per-tenant final maps, equal executed moves
    (churn) and equal availability across the two modes, strictly fewer
    device dispatches coalesced, and higher converge-cycles/sec
    wall-clock throughput.  Both modes are warmed first so throughput
    compares steady-state cycle cost, not XLA compile time.

    Encode-residency A/B (ISSUE 14): the same coalesced scenario runs
    with residency OFF (full re-encode per cycle) on BIGGER tenants so
    the host-encode share is visible; the stage reports the per-cycle
    phase split (encode / decode / device / orchestrate+other host
    work, plus the virtual admission latency) for both, and gates:
    byte-identical event logs (residency is a pure perf change), zero
    unattributed full re-encodes on warm cycles (``encode_cold ==
    tenants + demotions + evictions`` — the steady-state flat-line),
    warm patch bytes bounded by the patched-row count + scalar slack,
    and a smaller encode share + at least as many converge-cycles/sec
    with residency on."""
    from blance_tpu.testing.fleetsim import run_fleet_scenario
    from blance_tpu.testing.scenarios import fleet_zone_outage

    scn = fleet_zone_outage(seed=seed, tenants=tenants)
    run_fleet_scenario(scn)  # warm the coalesced-mode programs
    run_fleet_scenario(scn, coalesce=False)  # and the B=1 classes
    # Min-of-3 wall-clock per mode (each run is deterministic in every
    # VIRTUAL quantity; only wall_s is host-dependent and CI-noisy).
    co_runs = [run_fleet_scenario(scn) for _ in range(3)]
    seq_runs = [run_fleet_scenario(scn, coalesce=False)
                for _ in range(3)]
    co, seq = co_runs[0], seq_runs[0]
    co.wall_s = min(r.wall_s for r in co_runs)
    seq.wall_s = min(r.wall_s for r in seq_runs)

    def nbs(maps):
        return {t: {k: {s: list(ns)
                        for s, ns in p.nodes_by_state.items()}
                    for k, p in m.items()}
                for t, m in maps.items()}

    identical = nbs(co.final_maps) == nbs(seq.final_maps)
    equal_churn = co.fleet.moves_executed == seq.fleet.moves_executed
    equal_slo = (
        co.fleet.availability_min == seq.fleet.availability_min and
        {k: s.availability for k, s in co.summaries.items()} ==
        {k: s.availability for k, s in seq.summaries.items()})
    co_cps = co.cycles / max(co.wall_s, 1e-9)
    seq_cps = seq.cycles / max(seq.wall_s, 1e-9)
    # -- encode-residency A/B (ISSUE 14): bigger tenants, resident vs
    # full-re-encode baseline on the SAME coalesced scenario.
    big = fleet_zone_outage(seed=seed, tenants=tenants,
                            partitions=(48, 64))
    run_fleet_scenario(big)  # warm the bigger bucket classes
    run_fleet_scenario(big, encode_residency=False)
    res_runs = [run_fleet_scenario(big) for _ in range(3)]
    base_runs = [run_fleet_scenario(big, encode_residency=False)
                 for _ in range(3)]
    res = min(res_runs, key=lambda r: r.wall_s)
    base = min(base_runs, key=lambda r: r.wall_s)

    def phases(r):
        other = max(r.wall_s - sum(r.phase_wall.values()), 0.0)
        out = {k: round(v * 1000, 2) for k, v in r.phase_wall.items()}
        out["orchestrate_other"] = round(other * 1000, 2)
        out["encode_share"] = round(
            r.phase_wall.get("encode", 0.0) / max(r.wall_s, 1e-9), 4)
        return out

    res_cps = res.cycles / max(res.wall_s, 1e-9)
    base_cps = base.cycles / max(base.wall_s, 1e-9)
    # Patch bytes bounded by the patched-row count + scalar slack: the
    # per-row ceiling is a strip/adopt row's prev scatter + counts row
    # (S*R*4 + S*8) + a weight row (4 + 4*S); node-add columns and
    # dark-set flips ride the per-warm-cycle scalar slack.  S/R derive
    # from the scenario's own tenant model so a replica-count change
    # moves the bound with it.
    from blance_tpu.testing.fleetsim import tenant_model

    mdl = tenant_model(big.tenants[0])
    s_dim = len(mdl)
    r_dim = max(st.constraints for st in mdl.values())
    row_cap = s_dim * r_dim * 4 + s_dim * 8 + 4 + 4 * s_dim
    bytes_bounded = res.encode_patch_bytes <= (
        res.encode_patch_rows * row_cap + 256 * max(res.encode_warm, 1))
    # Two-sided attribution bound: every counted cold (re)established
    # resident state, so cold >= one per tenant, and every cold beyond
    # that was preceded by a counted demotion/eviction (a demotion on a
    # tenant's FINAL cycle has no rebuilding cold, hence <=).
    attributable = res.tenants + sum(res.encode_demotions.values()) + \
        sum(res.encode_evictions.values())
    cold_attributed = res.tenants <= res.encode_cold <= attributable
    residency = {
        "tenants": tenants, "partitions": [48, 64],
        "log_identical": res.log_text() == base.log_text(),
        "encode_cold": res.encode_cold,
        "encode_warm": res.encode_warm,
        "encode_demotions": res.encode_demotions,
        "encode_evictions": res.encode_evictions,
        "cold_attributed": cold_attributed,
        "decode_full": res.decode_full,
        "decode_patch": res.decode_patch,
        "encode_patch_rows": res.encode_patch_rows,
        "encode_patch_bytes": res.encode_patch_bytes,
        "patch_bytes_bounded": bytes_bounded,
        "wall_s_resident": round(res.wall_s, 3),
        "wall_s_full_reencode": round(base.wall_s, 3),
        "cycles_per_s_resident": round(res_cps, 1),
        "cycles_per_s_full_reencode": round(base_cps, 1),
        "phases_resident": phases(res),
        "phases_full_reencode": phases(base),
    }
    # The perf half of the gate is the DIRECT planner-layer A/B at a
    # scale where the O(cluster) host encode matters (P=4096, every
    # partition weighted): the planners' own phase clocks time exactly
    # what residency changed, immune to simulator wall-clock noise.
    # The 2x margin is conservative — the baseline re-runs O(P) Python
    # weight/stickiness loops + a full decode per cycle, the resident
    # path dict-diffs a handful of rows.
    micro = _bench_encode_host_costs()
    residency["host_micro"] = micro
    residency_ok = bool(
        residency["log_identical"] and cold_attributed and bytes_bounded
        and res.encode_warm > 0
        and micro["identical"]
        and micro["resident_host_ms"] * 2
        <= micro["full_reencode_host_ms"])
    residency["pass"] = residency_ok

    out = {
        "scenario": scn.name, "seed": seed, "tenants": tenants,
        "identical_final_maps": identical,
        "equal_churn": equal_churn,
        "equal_slo": equal_slo,
        "complete": co.complete and seq.complete,
        "moves_executed": co.fleet.moves_executed,
        "plan_requests": co.plan_requests,
        "dispatches_coalesced": co.dispatches,
        "dispatches_sequential": seq.dispatches,
        "dispatch_reduction": round(
            seq.dispatches / max(co.dispatches, 1), 2),
        "carry_hits_coalesced": co.carry_hits,
        "converge_cycles": co.cycles,
        "wall_s_coalesced": round(co.wall_s, 3),
        "wall_s_sequential": round(seq.wall_s, 3),
        "cycles_per_s_coalesced": round(co_cps, 1),
        "cycles_per_s_sequential": round(seq_cps, 1),
        "admission_p50_ms": round(co.admission_p50_s * 1000, 2),
        "admission_p99_ms": round(co.admission_p99_s * 1000, 2),
        "starved_admissions": co.starved_admissions,
        "residency": residency,
    }
    out["pass"] = bool(
        identical and equal_churn and equal_slo and out["complete"]
        and co.dispatches < seq.dispatches and co_cps > seq_cps
        and residency_ok)
    log(f"[fleet_loop {tenants} tenants seed={seed}] "
        f"dispatches {seq.dispatches}->{co.dispatches} "
        f"({out['dispatch_reduction']}x fewer), cycles/s "
        f"{out['cycles_per_s_sequential']}->"
        f"{out['cycles_per_s_coalesced']}, identical={identical} "
        f"equal_churn={equal_churn} equal_slo={equal_slo} "
        f"admission p50/p99 {out['admission_p50_ms']}/"
        f"{out['admission_p99_ms']}ms (virtual)")
    log(f"[fleet_loop residency A/B 48-64p] encode "
        f"{residency['phases_full_reencode']['encode']}ms->"
        f"{residency['phases_resident']['encode']}ms "
        f"(share {residency['phases_full_reencode']['encode_share']}->"
        f"{residency['phases_resident']['encode_share']}), warm "
        f"{res.encode_warm}/{res.encode_warm + res.encode_cold} "
        f"cycles, patch {res.encode_patch_bytes}B/"
        f"{res.encode_patch_rows} rows, log_identical="
        f"{residency['log_identical']} attributed={cold_attributed}; "
        f"host micro P={micro['P']}: "
        f"{micro['full_reencode_host_ms']}ms->"
        f"{micro['resident_host_ms']}ms "
        f"({micro['host_speedup']}x, encode "
        f"{micro['full_reencode_encode_ms']}->"
        f"{micro['resident_encode_ms']}ms, decode "
        f"{micro['full_reencode_decode_ms']}->"
        f"{micro['resident_decode_ms']}ms)")
    return out


def bench_durability(seed=19):
    """Durability stage (ISSUE 18): crash-recovery cost and correctness
    on the ``crash_storm`` chain (docs/DURABILITY.md).

    Runs the crash-free reference, then the scripted three-crash chain
    — every restart recovers from the WAL into a fresh virtual loop and
    must converge to the reference's final map bit-identically, with
    recovery cold solves bounded by the counted attribution identity
    (one per resumed tenant per recovery).  Then measures the recovery
    path itself: wall-clock ``recover()`` over the completed journal
    (records replayed per ms is the headline recovery rate) and the
    epoch fence — a zombie pre-crash journal handle must have its
    append REJECTED and counted, never applied.

    ``gates`` collects every pass/fail the perf-smoke tier checks."""
    import shutil
    import tempfile

    from blance_tpu.durability import Journal, recover, reset_fences
    from blance_tpu.obs import Recorder, use_recorder
    from blance_tpu.testing.crashsim import (
        maps_identical, run_crash_scenario)
    from blance_tpu.testing.scenarios import crash_storm

    os.environ.setdefault("BLANCE_WAL_FSYNC", "0")
    cs = crash_storm(seed)
    base = tempfile.mkdtemp(prefix="blance-durability-")
    try:
        reset_fences()
        ref = run_crash_scenario(cs.base, os.path.join(base, "ref"))
        storm = run_crash_scenario(
            cs.base, os.path.join(base, "storm"), crashes=cs.crashes,
            snapshot_every=cs.snapshot_every,
            rotate_records=cs.rotate_records)
        identical = maps_identical(storm.final_map, ref.final_map)
        recoveries = int(storm.counters.get("durability.recoveries", 0))
        cold = int(storm.counters.get(
            "durability.recovery_cold_solves", 0))
        # One tenant per life in this scenario: the attribution bound
        # is exactly one counted cold solve per recovery.
        cold_bounded = cold <= recoveries

        # Recovery-time measurement over the storm run's full journal
        # (its final epoch's history), plus the fence check: a journal
        # handle opened BEFORE the recovery is a zombie afterwards.
        rec = Recorder()
        with use_recorder(rec):
            reset_fences()
            storm_dir = os.path.join(base, "storm")
            zombie = Journal(storm_dir)
            t0 = time.perf_counter()
            state = recover(storm_dir)
            recover_ms = (time.perf_counter() - t0) * 1e3
            state.journal.close()
            zombie_applied = zombie.append("delta", {"zombie": True})
            zombie.close()
        stale_counted = rec.counters.get(
            "durability.stale_epoch_rejections", 0) >= 1
        gates = {
            "final_map_identical": bool(identical),
            "chain_completed": storm.lives == len(cs.crashes) + 1,
            "cold_solves_bounded": bool(cold_bounded),
            "zombie_append_rejected": (not zombie_applied)
            and stale_counted,
        }
        out = {
            "scenario": cs.name,
            "seed": seed,
            "crashes": list(cs.crashes),
            "lives": storm.lives,
            "recoveries": recoveries,
            "recovery_cold_solves": cold,
            "records_replayed": state.records_replayed,
            "recover_ms": round(recover_ms, 3),
            "records_per_ms": round(
                state.records_replayed / recover_ms, 2)
            if recover_ms > 0 else None,
            "torn_segments": state.torn_segments,
            "stale_dropped": state.stale_dropped,
            "journal_records": int(storm.counters.get(
                "durability.journal_records", 0)),
            "journal_bytes": int(storm.counters.get(
                "durability.journal_bytes", 0)),
            "snapshots": int(storm.counters.get(
                "durability.snapshots", 0)),
            "gates": gates,
            "pass": all(gates.values()),
        }
    finally:
        reset_fences()
        shutil.rmtree(base, ignore_errors=True)
    log(f"[durability {cs.name} s{seed}] lives={out['lives']} "
        f"recoveries={out['recoveries']} cold={cold} "
        f"recover {out['recover_ms']}ms for "
        f"{out['records_replayed']} records "
        f"({out['records_per_ms']}/ms), identical={identical} "
        f"zombie_rejected={gates['zombie_append_rejected']}")
    return out


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def bench_plan_pipeline(P, N, reps=3):
    """Fused single-dispatch pipeline vs the staged path (ROADMAP 3).

    The staged path is what production ran before: plan_next_map_tpu
    (host encode -> device solve -> host decode) plus a separate
    calc_all_moves device diff.  The fused path is plan_pipeline: one
    jitted, buffer-donated dispatch chaining solve -> diff -> decode
    pack, with only the id->name materialization left on host.  Asserts
    the bit-identity contract (same map AND same move lists) and
    reports per-phase wall-clock for BOTH paths so the host-phase win
    is visible in every artifact."""
    from blance_tpu import model
    from blance_tpu.moves.batch import calc_all_moves
    from blance_tpu.obs import device as obs_device
    from blance_tpu.plan.tensor import plan_next_map_tpu, plan_pipeline
    from blance_tpu.utils.trace import PhaseTimer

    prev, nodes, removed = _make_map(P, N, seed=23)
    m = model(primary=(0, 1), replica=(1, 1))
    opts = _rack_opts(nodes)

    mon = obs_device.CompileMonitor().install()
    try:
        # Warm both compiles + pin the identity contract.
        staged_map, staged_warn = plan_next_map_tpu(
            prev, prev, nodes, removed, [], m, opts)
        staged_moves = calc_all_moves(prev, staged_map, m)
        fused_map, fused_warn, fused_moves = plan_pipeline(
            prev, prev, nodes, removed, [], m, opts)
        identical_map = (
            {k: v.nodes_by_state for k, v in staged_map.items()} ==
            {k: v.nodes_by_state for k, v in fused_map.items()})
        identical_moves = staged_moves == fused_moves
        assert identical_map, "pipeline map diverged from staged path"
        assert identical_moves, "pipeline moves diverged from staged path"
        assert staged_warn == fused_warn, "pipeline warnings diverged"

        def staged_once():
            timer = PhaseTimer()
            t0 = time.perf_counter()
            smap, _ = plan_next_map_tpu(prev, prev, nodes, removed, [],
                                        m, opts, timer=timer)
            t1 = time.perf_counter()
            calc_all_moves(prev, smap, m)
            total = time.perf_counter() - t0
            phases = {k: round(timer.totals[k] * 1000, 1)
                      for k in ("encode", "solve", "decode")
                      if k in timer.totals}
            phases["diff"] = round((time.perf_counter() - t1) * 1000, 1)
            phases["total"] = round(total * 1000, 1)
            return total, phases

        def fused_once():
            timer = PhaseTimer()
            t0 = time.perf_counter()
            plan_pipeline(prev, prev, nodes, removed, [], m, opts,
                          timer=timer)
            total = time.perf_counter() - t0
            phases = {k: round(timer.totals[k] * 1000, 1)
                      for k in ("encode", "dispatch", "decode",
                                "materialize")
                      if k in timer.totals}
            phases["total"] = round(total * 1000, 1)
            return total, phases

        staged = min((staged_once() for _ in range(reps)),
                     key=lambda r: r[0])
        fused = min((fused_once() for _ in range(reps)),
                    key=lambda r: r[0])
    finally:
        mon.uninstall()

    out = {
        "P": P, "N": N,
        "identical_map": identical_map,
        "identical_moves": identical_moves,
        # phases_ms for BOTH paths — the per-artifact host-phase
        # attribution the ISSUE 9 acceptance requires.
        "phases_ms": {"staged": staged[1], "fused": fused[1]},
        "staged_ms": round(staged[0] * 1000, 1),
        "fused_ms": round(fused[0] * 1000, 1),
        "speedup": round(staged[0] / max(fused[0], 1e-9), 2),
        "device": _device_block(mon),
    }
    log(f"[plan-pipeline {P}x{N}] staged {out['staged_ms']}ms "
        f"{staged[1]} vs fused {out['fused_ms']}ms {fused[1]} = "
        f"{out['speedup']}x, identical map={identical_map} "
        f"moves={identical_moves}")
    return out


def bench_warm_pipeline(P, N):
    """Warm delta-replan end-to-end through the fused session fast path:
    one node removed, one donated device dispatch returning the new map
    AND the move arrays — the sub-100 ms delta-replan target's
    measurement (ISSUE 9 acceptance)."""
    from blance_tpu import model
    from blance_tpu.plan.session import PlannerSession

    nodes = [f"n{i:05d}" for i in range(N)]
    parts = [str(i) for i in range(P)]
    m = model(primary=(0, 1), replica=(1, 1))
    s = PlannerSession(m, nodes, parts, opts=_rack_opts(nodes))
    s.replan_with_moves()
    s.apply()
    # Warm-up delta cycle compiles the warm pipeline program; the timed
    # cycle below is the steady-state delta replan.
    s.remove_nodes([nodes[0]])
    s.replan_with_moves()
    s.apply()
    victim = nodes[N // 3]
    s.remove_nodes([victim])
    from blance_tpu.obs import get_recorder

    # Delta, not cumulative: the warm-up cycle above already scored a
    # pipeline.warm, and this field must report the TIMED replan's
    # outcome (same discipline as bench_delta_replan's carry_hit).
    w0 = get_recorder().counters.get("plan.pipeline.warm", 0)
    t0 = time.perf_counter()
    _assign, (d_nodes, _ds, _do) = s.replan_with_moves()
    warm_ms = (time.perf_counter() - t0) * 1000
    s.apply()
    hit = get_recorder().counters.get("plan.pipeline.warm", 0) - w0 > 0
    out = {"P": P, "N": N, "warm_e2e_ms": round(warm_ms, 1),
           "warm_hit": bool(hit),
           "moves_rows": int((d_nodes >= 0).any(axis=1).sum())}
    log(f"[warm-pipeline {P}x{N}] delta replan end-to-end "
        f"{out['warm_e2e_ms']}ms (hit={out['warm_hit']}, "
        f"{out['moves_rows']} partitions moving)")
    return out


def bench_delta_replan(P, N):
    """Cold vs warm delta replan through PlannerSession: the
    incremental-replanning headline (ISSUE 2).

    Protocol: one session solves and applies a map (building the warm
    carry), then removes one node and replans WARM; a second session
    loads the identical pre-delta map (which invalidates any carry),
    applies the same delta and replans COLD.  Reports sweeps (from the
    obs plan.solve.sweeps counter), wall-clock for both paths, and
    whether the maps are bit-identical — the warm path's contract."""
    from blance_tpu import model
    from blance_tpu.obs import device as obs_device
    from blance_tpu.obs import get_recorder

    nodes = [f"n{i:05d}" for i in range(N)]
    parts = [str(i) for i in range(P)]
    m = model(primary=(0, 1), replica=(1, 1))
    opts = _rack_opts(nodes)
    rec = get_recorder()

    def sweeps():
        return rec.counters.get("plan.solve.sweeps", 0)

    # try/finally: _run_benchmarks survives a failed stage by design,
    # and an abandoned monitor would keep its logging tap (and the
    # suppressed propagation) for the rest of the process.
    mon = obs_device.CompileMonitor().install()
    try:
        return _bench_delta_replan_body(P, N, m, nodes, parts, opts,
                                        rec, sweeps, mon)
    finally:
        mon.uninstall()


def _bench_delta_replan_body(P, N, m, nodes, parts, opts, rec, sweeps,
                             mon):
    from blance_tpu.plan.session import PlannerSession

    s = PlannerSession(m, nodes, parts, opts=opts)
    s.replan()
    s.apply()  # promotes the carry: the next replan is warm
    # Warm-up delta cycle: compiles the warm-repair program (the cold
    # program compiled during the first replan), so the timed cycle
    # below measures steady-state wall-clock on both paths.
    s.remove_nodes([nodes[0]])
    s.replan()
    s.apply()
    pre_map, _ = s.to_map()
    victim = nodes[N // 3]

    s.remove_nodes([victim])
    c0 = sweeps()
    h0 = rec.counters.get("plan.solve.carry_hit", 0)
    t0 = time.perf_counter()
    warm = s.replan().copy()
    warm_ms = (time.perf_counter() - t0) * 1000
    warm_sweeps = sweeps() - c0
    # Delta, not cumulative: the warm-up cycle above already scored a
    # hit, and this field must report the TIMED replan's outcome.
    warm_hit = rec.counters.get("plan.solve.carry_hit", 0) - h0 > 0

    s2 = PlannerSession(m, nodes, parts, opts=opts)
    s2.load_map(pre_map)  # same state, no carry
    s2.remove_nodes(sorted(s.removed_nodes))  # same node set incl. victim
    c1 = sweeps()
    t0 = time.perf_counter()
    cold = s2.replan()
    cold_ms = (time.perf_counter() - t0) * 1000
    cold_sweeps = sweeps() - c1

    out = {
        "P": P, "N": N,
        "cold_sweeps": int(cold_sweeps), "warm_sweeps": int(warm_sweeps),
        "cold_ms": round(cold_ms, 1), "warm_ms": round(warm_ms, 1),
        "warm_carry_hit": bool(warm_hit),
        "identical": bool(np.array_equal(warm, cold)),
        "device": _device_block(mon),
    }
    log(f"[delta-replan {P}x{N}] cold: {cold_sweeps} sweeps "
        f"{cold_ms:.0f}ms / warm: {warm_sweeps} sweeps {warm_ms:.0f}ms "
        f"(hit={warm_hit}, identical={out['identical']})")
    return out


def bench_sparse(P, N, k=None, identity_shape=(512, 64)):
    """Sparse shortlist solve vs the dense engines (ISSUE 11).

    Three parts, all reported in one block:

    - **saturating-K bit-identity** at a small dense-feasible shape:
      solve_sparse with K = N must equal the dense converged solve
      bit-for-bit (the contract that keeps the engines from drifting);
    - **the big config** (1M partitions x 1k nodes on device hosts,
      smoke sizes on CPU): shortlist build + converged sparse solve
      timed end-to-end with the full audit, WITHOUT materializing any
      dense [P, S, N] score tensor;
    - **peak-bytes evidence**: the AOT memory analysis of the compiled
      sparse program vs the dense matrix engine's projected [P, N]
      working set (plan.tensor.projected_score_bytes) — the number the
      dense-memory guard refuses past budget.
    """
    import jax
    import jax.numpy as jnp
    from blance_tpu.obs import device as obs_device
    from blance_tpu.obs import get_recorder
    from blance_tpu.core.shortlist import auto_shortlist_k, build_shortlist
    from blance_tpu.ops.sparse2 import (
        sparse_min2_reference, sparse_priced_min2)
    from blance_tpu.plan.tensor import (
        _solve_sparse_converged_impl, projected_score_bytes,
        resolve_sparse_impl, solve_dense_converged, solve_sparse)

    rec = get_recorder()
    out = {"P": P, "N": N}

    # Kernel verification (compiled on TPU, interpret elsewhere): the
    # fused sparse min2 must match its XLA oracle bit-for-bit before any
    # timed run uses it.
    rng = np.random.default_rng(11)
    score = jnp.asarray(
        rng.integers(0, 50, (2048, 64)).astype(np.float32) * 0.125)
    price = jnp.asarray(
        rng.integers(0, 8, (2048, 64)).astype(np.float32) * 0.25)
    impl = resolve_sparse_impl(None)
    kb, kk_, ks, kr = sparse_priced_min2(
        score, price, interpret=(impl != "pallas"))
    rb, rk, rs, rr = sparse_min2_reference(score, price)
    out["kernel_verified"] = bool(
        np.array_equal(np.asarray(kb), np.asarray(rb))
        and np.array_equal(np.asarray(kk_), np.asarray(rk))
        and np.array_equal(np.asarray(ks), np.asarray(rs))
        and np.array_equal(np.asarray(kr), np.asarray(rr)))
    log(f"[sparse] min2 kernel ({impl}) vs oracle: "
        f"{'bit-identical' if out['kernel_verified'] else 'MISMATCH'}")

    # Saturating-K bit-identity at a dense-feasible shape.
    ip, inn = identity_shape
    (prev, pweights, nweights, valid, stickiness, gids, gid_valid,
     constraints, rules) = build_dense(ip, inn, seed=13)
    dev = [jnp.asarray(a) for a in
           (prev, pweights, nweights, valid, stickiness, gids, gid_valid)]
    dense_small = np.asarray(solve_dense_converged(
        *dev, constraints, rules, record=False))
    sparse_small = solve_sparse(prev, pweights, nweights, valid,
                                stickiness, gids, gid_valid, constraints,
                                rules, k=inn, record=False)
    out["saturating_identity"] = bool(
        np.array_equal(dense_small, sparse_small))
    log(f"[sparse] saturating K={inn} identity @ {ip}x{inn}: "
        f"{out['saturating_identity']}")

    # The big config: never materializes a dense [P, S, N] score.
    (prev, pweights, nweights, valid, stickiness, gids, gid_valid,
     constraints, rules) = build_dense(P, N, seed=7)
    kk = int(k) if k is not None else auto_shortlist_k(
        N, constraints, rules)
    out["k"] = kk

    t0 = time.perf_counter()
    shortlist = build_shortlist(prev, pweights, nweights, valid, gids,
                                gid_valid, constraints, rules, kk)
    jax.block_until_ready(shortlist)
    out["shortlist_build_s"] = round(time.perf_counter() - t0, 3)

    dev = [jnp.asarray(a) for a in
           (prev, pweights, nweights, valid, stickiness, gids, gid_valid)]
    impl_big = resolve_sparse_impl(None)

    def run():
        a, sweeps, exh = _solve_sparse_converged_impl(
            *dev, shortlist, constraints=constraints, rules=rules,
            sparse_impl=impl_big)
        return jax.block_until_ready((a, exh))

    with obs_device.CompileMonitor() as mon:
        t0 = time.perf_counter()
        assign, exh = run()
        out["compile_s"] = round(time.perf_counter() - t0, 2)
        times = []
        for _ in range(RUNS):
            t0 = time.perf_counter()
            assign, exh = run()
            times.append(time.perf_counter() - t0)
    out["solve_ms_min"] = round(min(times) * 1000, 2)
    out["solve_ms_runs"] = [round(t * 1000, 2) for t in times]
    out["exhausted_rows"] = int(np.asarray(exh).sum())
    counts = audit(np.asarray(assign), valid, gids)
    # Exhausted rows are -1 by design until the host fallback fills
    # them; audit the FINAL (fallback-patched) assignment.
    if out["exhausted_rows"]:
        from blance_tpu.plan.tensor import _apply_sparse_fallback

        patched, _ = _apply_sparse_fallback(
            np.asarray(assign), np.asarray(exh), prev, pweights,
            nweights, valid, stickiness, gids, gid_valid, constraints,
            rules)
        counts = audit(patched, valid, gids)
    out["violations"] = counts
    out["device"] = _device_block(mon)

    # Peak-bytes evidence: AOT memory analysis of the compiled sparse
    # program vs the dense matrix estimate.
    out["dense_score_bytes_est"] = projected_score_bytes(P, N)
    try:
        lowered = _solve_sparse_converged_impl.lower(
            *dev, shortlist, constraints=constraints, rules=rules,
            sparse_impl=impl_big)
        peak = obs_device._extract_cost(lowered.compile())[
            "peak_alloc_bytes"]
        out["sparse_peak_bytes"] = int(peak)
        if peak:
            out["sparse_vs_dense_bytes"] = round(
                peak / max(out["dense_score_bytes_est"], 1), 4)
    except Exception as e:
        out["sparse_peak_bytes_error"] = first_line(e)
    log(f"[sparse {P}x{N}] K={kk} build {out['shortlist_build_s']}s "
        f"solve min {out['solve_ms_min']}ms exhausted "
        f"{out['exhausted_rows']} audit {counts} peak "
        f"{out.get('sparse_peak_bytes')}B vs dense est "
        f"{out['dense_score_bytes_est']}B")
    assert counts["unassigned_slots"] == 0
    assert counts["on_removed_nodes"] == 0
    return out


def obs_summary():
    """The Recorder's aggregates, floats rounded for the JSON artifact:
    per-span-name totals (phase attribution), counters (solver sweeps,
    fallbacks, orchestrator progress mirror), gauges (SLO), histogram
    p50/p95."""
    from blance_tpu.obs import get_recorder

    def r(x):
        return round(x, 6) if isinstance(x, float) else x

    s = get_recorder().summary()
    return {
        "spans": {k: {kk: r(vv) for kk, vv in v.items()}
                  for k, v in s["spans"].items()},
        "counters": {k: r(v) for k, v in s["counters"].items()},
        "gauges": {k: r(v) for k, v in s["gauges"].items()},
        "histograms": {k: {kk: r(vv) for kk, vv in v.items()}
                       for k, v in s["histograms"].items() if v},
    }


# Child program for one CPU baseline measurement.  Runs in a subprocess so
# the parent can enforce CPU_TIMEOUT_S (the native call is one C++ planner
# invocation — uninterruptible in-process); the parent starts it with
# JAX_PLATFORMS=cpu, so it never asks for the chip the parent holds.
_CPU_CHILD = r"""
import json, sys, time
sys.path.insert(0, {repo!r})
import bench
P, N, backend = {P}, {N}, {backend!r}
from blance_tpu import PlanOptions, model, plan_next_map
prev, nodes, removed = bench._make_map(P, N)
m = model(primary=(0, 1), replica=(1, 1))
opts = bench._rack_opts(nodes)
opts.max_iterations = 1  # single pass, same work as one solve
# Epoch marker AFTER imports + problem construction: a timed-out parent
# bounds PLANNER time from here, not from process start.
print("SETUP_DONE", time.time(), flush=True)
t0 = time.perf_counter()
plan_next_map(prev, prev, nodes, removed, [], m, opts, backend=backend)
print(json.dumps({{"cpu_s": time.perf_counter() - t0}}))
"""


def bench_cpu(P, N):
    """CPU baseline, MEASURED at the full problem size (no P-scaling): the
    native C++ exact planner when built, else the Python greedy scaled
    from PY_GREEDY_P (toolchain-less hosts only).  Runs under a hard
    timeout; on expiry the elapsed budget is reported as an explicit
    LOWER BOUND on the CPU time (so the derived speedup is a lower bound
    too), never an extrapolation."""
    import os
    import subprocess

    from blance_tpu.plan.native import native_available

    use_native = native_available()
    cpu_p = P if use_native else min(P, PY_GREEDY_P)
    backend = "native" if use_native else "greedy"
    child = _CPU_CHILD.format(
        repo=os.path.dirname(os.path.abspath(__file__)),
        P=cpu_p, N=N, backend=backend)
    log(f"[{P}x{N}] cpu {backend} @ {cpu_p}x{N} (full-size measurement, "
        f"timeout {CPU_TIMEOUT_S}s)...")
    try:
        r = subprocess.run([sys.executable, "-c", child],
                           env=dict(os.environ, JAX_PLATFORMS="cpu"),
                           timeout=CPU_TIMEOUT_S, capture_output=True,
                           text=True, check=True)
        cpu_s = json.loads(r.stdout.strip().splitlines()[-1])["cpu_s"]
        bound = False
    except subprocess.TimeoutExpired as e:
        # Lower-bound the PLANNER time only: the child stamps wall time
        # after imports + problem construction, so the bound excludes
        # startup.  No marker captured (killed during setup) = no claim.
        out = e.stdout or ""
        if isinstance(out, bytes):  # text= capture varies across versions
            out = out.decode(errors="replace")
        marker = None
        for line in out.splitlines():
            if line.startswith("SETUP_DONE"):
                marker = float(line.split()[1])
        if marker is None:
            log(f"[{P}x{N}] cpu baseline timed out during setup; "
                f"no measurement")
            return {"cpu_s": None, "baseline": f"{backend}-timeout"}
        cpu_s = time.time() - marker
        bound = True
    except (subprocess.CalledProcessError, ValueError, KeyError,
            IndexError) as e:
        err = getattr(e, "stderr", "") or str(e)
        log(f"[{P}x{N}] cpu baseline child failed: {err[-400:]}")
        return {"cpu_s": None, "baseline": f"{backend}-failed"}
    # A timed-out partial run may only be reported UNSCALED: scaling a
    # lower bound linearly in P would be exactly the extrapolation this
    # function exists to avoid (it can only overstate the bound's claim).
    scale = 1.0 if bound else P / cpu_p
    scaled = cpu_s * scale
    provenance = ("native-c++" if use_native else "python-greedy") + \
        ("" if scale == 1 else f"-scaled-x{scale:g}-in-P") + \
        ("-timeout-lower-bound" if bound else "")
    log(f"[{P}x{N}] cpu {backend}: "
        + (f">= {cpu_s:.0f}s (timed out; lower bound)" if bound
           else f"{cpu_s:.2f}s")
        + ("" if scale == 1 else f" -> scaled to P={P}: {scaled:.1f}s"))
    return {"cpu_s": round(scaled, 2), "baseline": provenance,
            "cpu_is_lower_bound": bound}


# Child program for one tile-sweep measurement: a fresh subprocess per
# tile combination (the tiles are jit-static, read once at import — see
# ops/_tiles.py), timing the fused converged solve AND a fused warm
# one-sweep repair so the sweep's tile choice covers the delta-replan
# kernels too.  The child reports the platform it found: on a TPU it
# runs the compiled kernels at the requested shape, elsewhere the Pallas
# interpreter at smoke size.
_TILE_CHILD = r"""
import json, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
import jax
import jax.numpy as jnp
import bench
from blance_tpu.plan.tensor import (carry_from_assignment,
                                    solve_dense_converged,
                                    solve_dense_warm)
from blance_tpu.ops import reduce2, score_fused
platform = jax.devices()[0].platform
if platform == "tpu":
    P, N, mode, runs = {P} or 100_000, {N} or 10_000, "on", 4
else:
    P, N, mode, runs = {P} or 256, {N} or 32, "interpret", 1
args = bench.build_dense(P, N)
(prev, pweights, nweights, valid, stickiness, gids, gid_valid,
 constraints, rules) = args
dev = [jnp.asarray(a) for a in
       (prev, pweights, nweights, valid, stickiness, gids, gid_valid)]
def run():
    return solve_dense_converged(*dev, constraints, rules, fused_score=mode,
                                 record=False).block_until_ready()
t0 = time.perf_counter(); out = run(); compile_s = time.perf_counter() - t0
times = []
for _ in range(runs):
    t0 = time.perf_counter(); run(); times.append(time.perf_counter() - t0)
# Warm one-sweep repair through the same kernels (tile choice feeds the
# Pallas warm-repair path too).
out_np = np.asarray(out)
dirty = np.zeros(P, bool); dirty[: max(P // 64, 1)] = True
warm_times = []
for _ in range(max(runs - 1, 1)):
    carry = carry_from_assignment(jnp.asarray(out_np), dev[1], dev[2])
    t0 = time.perf_counter()
    solve_dense_warm(out_np, *dev[1:7], constraints, rules, dirty=dirty,
                     carry=carry, fused_score=mode, record=False)
    warm_times.append(time.perf_counter() - t0)
print(json.dumps({{
    "platform": platform, "P": P, "N": N, "mode": mode,
    "tile_p": score_fused._TILE_P, "tile_n": score_fused._TILE_N,
    "reduce2_tile_p": reduce2._TILE_P, "reduce2_tile_n": reduce2._TILE_N,
    "compile_s": round(compile_s, 1),
    "solve_ms_min": round(min(times) * 1000, 2),
    "solve_ms_runs": [round(t * 1000, 2) for t in times],
    "warm_ms_min": round(min(warm_times) * 1000, 2)}}))
"""

# Tile grids per platform.  Both start with (256, 2048): the first child
# runs that combination and reports the platform, which picks the rest —
# the parent never touches JAX, so each child can hold the chip.
_TILE_GRIDS = {
    "tpu": [(tp, tn) for tp in (256, 128, 512) for tn in (2048, 1024, 4096)],
    "cpu": [(256, 2048), (512, 2048)],
}


def _tile_child(tile_p, tile_n, P, N, timeout):
    """Run one tile combination in a fresh child; its JSON result or an
    ``error`` record."""
    import subprocess

    env = dict(os.environ,
               BLANCE_FUSED_TILE_P=str(tile_p),
               BLANCE_FUSED_TILE_N=str(tile_n),
               BLANCE_REDUCE2_TILE_P=str(tile_p),
               BLANCE_REDUCE2_TILE_N=str(tile_n))
    child = _TILE_CHILD.format(
        repo=os.path.dirname(os.path.abspath(__file__)), P=P, N=N)
    t0 = time.time()
    try:
        r = subprocess.run([sys.executable, "-c", child], env=env,
                           timeout=timeout, capture_output=True,
                           text=True, check=True)
        lines = r.stdout.strip().splitlines()
        res = json.loads(lines[-1]) if lines else {"error": "no output"}
    except subprocess.TimeoutExpired:
        res = {"error": "timeout", "elapsed_s": round(time.time() - t0)}
    except (subprocess.CalledProcessError, ValueError) as e:
        err = (getattr(e, "stderr", "") or str(e)).strip()
        res = {"error": err.splitlines()[-1][-200:] if err else "failed"}
    # Keep the CHILD-reported tiles (the values actually compiled in) —
    # overwriting them would destroy the only evidence the env override
    # applied; flag a propagation break instead.
    res.setdefault("tile_p", tile_p)
    res.setdefault("tile_n", tile_n)
    if "solve_ms_min" in res and (res["tile_p"] != tile_p
                                  or res["tile_n"] != tile_n):
        res["error"] = (f"env override did not apply: child "
                        f"compiled {res['tile_p']}x{res['tile_n']}")
        res.pop("solve_ms_min", None)
    log(f"tile-sweep {tile_p}x{tile_n}: "
        + (f"{res['solve_ms_min']}ms solve / {res.get('warm_ms_min')}ms "
           f"warm" if "solve_ms_min" in res else res.get("error", "?")))
    return res


def run_tile_sweep(P=None, N=None):
    """bench.py --tile-sweep: sweep BLANCE_FUSED_TILE_P/N and
    BLANCE_REDUCE2_TILE_P/N together over aligned candidates, one
    subprocess per combination, and print ONE artifact line naming the
    winning tile — the value to export before latency-critical runs.
    On a TPU the compiled kernels run at the north-star shape (default);
    elsewhere the interpreter runs at smoke size."""
    first = _tile_child(*_TILE_GRIDS["cpu"][0], P, N, timeout=900)
    if "platform" not in first:
        log(f"tile-sweep: first child failed: {first.get('error')}")
        sys.exit(1)
    platform = "tpu" if first["platform"] == "tpu" else "cpu"
    timeout = 600 if platform == "tpu" else 900
    results = [first] + [_tile_child(tp, tn, P, N, timeout)
                         for tp, tn in _TILE_GRIDS[platform][1:]]
    done = [r for r in results if "solve_ms_min" in r]
    best = min(done, key=lambda r: r["solve_ms_min"]) if done else None
    P, N, mode = first["P"], first["N"], first["mode"]
    print(json.dumps({
        "metric": f"fused-kernel tile sweep @ {P}x{N} ({mode})",
        "value": best["solve_ms_min"] if best else None,
        "unit": "ms",
        "vs_baseline": None,
        "detail": {"P": P, "N": N, "mode": mode,
                   "platform": first["platform"], "results": results,
                   "best": best,
                   "env": (None if best is None else {
                       "BLANCE_FUSED_TILE_P": best["tile_p"],
                       "BLANCE_FUSED_TILE_N": best["tile_n"],
                       "BLANCE_REDUCE2_TILE_P": best["tile_p"],
                       "BLANCE_REDUCE2_TILE_N": best["tile_n"]})},
        "pass": best is not None,
    }))
    if best is None:
        sys.exit(1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CPU mode: tiny sizes, every stage (code-path "
                         "test; no device numbers)")
    ap.add_argument("--tile-sweep", action="store_true",
                    help="sweep the Pallas kernel tile sizes (one "
                         "subprocess per combination) and emit a JSON "
                         "artifact naming the winner; interpret-mode "
                         "smoke on cpu hosts")
    ap.add_argument("--tile-sweep-shape", default=None, metavar="PxN",
                    help="override the tile sweep problem shape, e.g. "
                         "100000x10000")
    ap.add_argument("--perf-smoke", action="store_true",
                    help="CI guard, CPU mode: run ONLY the delta-replan "
                         "stage at smoke size and fail (exit 1) if the "
                         "warm path does not beat the cold path's sweep "
                         "count or diverges from it")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of every obs "
                         "span (open in chrome://tracing / Perfetto)")
    ap.add_argument("--device-trace-dir", default=None, metavar="DIR",
                    help="also capture a jax.profiler device trace over "
                         "the same interval (TensorBoard/Perfetto)")
    args = ap.parse_args()

    if args.tile_sweep:
        # The parent stays off JAX: every child needs the chip.
        tp = tn = None
        if args.tile_sweep_shape:
            tp, tn = (int(x) for x in args.tile_sweep_shape.split("x"))
        run_tile_sweep(tp, tn)
        return

    smoke = args.smoke
    cpu_mode = smoke or args.perf_smoke
    if cpu_mode:
        # The explicit CPU modes: 8 virtual devices, so the fleet stage's
        # batch-axis mesh runs the sharded code path.  Must precede the
        # first jax import.
        _ensure_virtual_devices()
    import jax

    if cpu_mode:
        jax.config.update("jax_platforms", "cpu")
    from blance_tpu.utils.compile_cache import enable_compile_cache

    log(f"persistent XLA compilation cache: {enable_compile_cache()}")

    if args.perf_smoke:
        _run_perf_smoke()
        return

    log(f"devices: {jax.devices()}")
    if not smoke and jax.devices()[0].platform != "tpu":
        sys.exit(f"bench.py: no TPU (JAX platform "
                 f"{jax.devices()[0].platform!r}); device numbers need "
                 f"the chip — pass --smoke or --perf-smoke for the CPU "
                 f"modes")

    global CONFIGS, RUNS
    if smoke:
        CONFIGS = [(512, 128, True), (512, 64, False)]  # headline first,
        RUNS = 3                                        # like the real list

    if args.trace_out:
        from blance_tpu.obs import trace

        log(f"obs: capturing spans -> {args.trace_out}")
        try:
            # trace() validates the path up front and writes the file
            # even when the run raises — a crashed run's trace is
            # exactly the one worth reading.
            with trace(args.trace_out,
                       device_log_dir=args.device_trace_dir):
                _run_benchmarks(smoke)
        finally:
            if os.path.exists(args.trace_out):
                log(f"obs: chrome trace written to {args.trace_out}")
    else:
        from blance_tpu.utils.trace import device_profile

        with device_profile(args.device_trace_dir):
            _run_benchmarks(smoke)


def _bench_membudget():
    """Measure every budgeted solver entry's AOT peak allocation
    against HBM_BUDGETS (zero FLOPs — abstract operands end to end) and
    log the table.  Returns the measured-vs-budget rows for
    detail.membudget."""
    from blance_tpu.analysis.membudget import measure_budget_table

    rows = measure_budget_table(["smoke"])
    for r in rows:
        got = r.get("measured", r.get("error"))
        log(f"[membudget] {r['entry']:<24} {r['class']:<6} "
            f"peak={got} budget={r['budget']} "
            f"{'OK' if r.get('ok') else 'OVER'}")
    return rows


def _run_perf_smoke():
    """The CI perf gate (bench.py --perf-smoke): delta-replan at smoke
    size on CPU; exit 1 when warm sweeps fail to beat cold sweeps or the
    warm map diverges — so the warm path cannot silently regress to (or
    past) a cold solve.

    Runs the static shape-contract audit FIRST (blance_tpu.analysis
    shape_audit, eval_shape only — milliseconds per entry): a drifted
    solver signature would otherwise surface here as an opaque stack
    trace mid-benchmark.  A broken static pass emits a PARSEABLE JSON
    artifact with ``"pass": false`` and exits 1, same shape as the perf
    result, so the driver always gets data."""
    import jax

    from blance_tpu.obs import device as obs_device

    log(f"perf-smoke on {jax.default_backend()}")
    # Device observatory ON for the gate: the artifact's
    # detail.device block must carry nonzero per-entry compile counts
    # AND cost-analysis FLOPs/bytes for the solve stage.
    obs_device.enable(cost_analysis=True, sweep_trace=False)
    try:
        from blance_tpu.analysis.shape_audit import run_shape_audit

        shape_findings, shape_entries = run_shape_audit()
    except Exception as e:
        shape_findings, shape_entries = [
            f"shape audit crashed: {type(e).__name__}: {first_line(e)}"
        ], 0
    if shape_findings:
        rendered = [f if isinstance(f, str) else f.render()
                    for f in shape_findings]
        print(json.dumps({
            "metric": "delta-replan perf smoke (warm vs cold sweeps)",
            "value": None,
            "unit": "sweeps",
            "vs_baseline": None,
            "detail": {"static_audit": {"entries": shape_entries,
                                        "findings": rendered}},
            "pass": False,
        }))
        log(f"PERF-SMOKE FAILED: static shape audit broken "
            f"({len(rendered)} finding(s)); fix the contracts before "
            f"benchmarking")
        sys.exit(1)
    log(f"static shape audit OK ({shape_entries} contracts)")
    res = bench_delta_replan(512, 64)
    ok = (res["identical"] and res["warm_carry_hit"]
          and res["warm_sweeps"] * 2 <= res["cold_sweeps"])

    # Pipeline gate (ISSUE 9): the fused single-dispatch pipeline must
    # stay bit-identical to the staged path (map AND move lists) and
    # beat it end-to-end at smoke sizes — the dispatch-count win must
    # not silently erode back into staged-path territory.  The timing
    # half is inherently wall-clock (unlike the sweep-count gate above):
    # min-of-5 on both sides damps CI-runner noise, and the structural
    # margin (one dispatch + no host decode pack/diff re-encode vs
    # four boundaries) is ~40% at this shape, not knife-edge.
    try:
        pipe = bench_plan_pipeline(512, 64, reps=5)
        pipe_ok = (pipe["identical_map"] and pipe["identical_moves"]
                   and pipe["fused_ms"] < pipe["staged_ms"])
    except AssertionError as e:
        pipe = {"error": first_line(e)}
        pipe_ok = False
    ok = ok and pipe_ok

    # Sparse gate (ISSUE 11): saturating-K bit-identity must hold, the
    # sparse min2 kernel must match its oracle, the audit at the large
    # smoke config must be clean, and the compiled sparse program's AOT
    # peak bytes must sit below the dense matrix engine's projected
    # [P, N] working set (the memory the dense guard refuses) — so the
    # "breaks the dense wall" claim is CI-checked, not aspirational.
    try:
        sparse = bench_sparse(4096, 256)
        sparse_ok = (sparse["saturating_identity"]
                     and sparse["kernel_verified"]
                     and not any(sparse["violations"].values()))
        peak = sparse.get("sparse_peak_bytes")
        if peak:
            sparse_ok = sparse_ok and \
                peak < sparse["dense_score_bytes_est"]
    except AssertionError as e:
        sparse = {"error": first_line(e)}
        sparse_ok = False
    ok = ok and sparse_ok

    # Sched gate (ISSUE 12): the critical-path order must produce the
    # identical final map and move count as the legacy order AND beat
    # its makespan p95 on the heterogeneous family (no-regression on
    # the mixed_week soak), with the committed hetero_drain trace
    # regenerating byte-for-byte — all under the virtual clock, so the
    # gate is exact, not wall-clock-noisy.
    try:
        sched = bench_sched()
        sched_ok = sched["pass"]
    except Exception as e:  # any stage crash must fail THIS gate, not
        sched = {"error": first_line(e)}  # eat the results above it
        sched_ok = False
    ok = ok and sched_ok

    # Fleet-loop gate (ISSUE 13): coalesced converge cycles must land
    # on the IDENTICAL per-tenant final maps as the sequential
    # loop-per-tenant baseline at equal churn and equal SLO, with
    # measurably fewer device dispatches and higher converge-cycles/sec
    # — the fleet tier's dispatch-economics win must not silently erode.
    try:
        floop = bench_fleet_loop()
        floop_ok = floop["pass"]
    except Exception as e:  # any stage crash must fail THIS gate
        floop = {"error": first_line(e)}
        floop_ok = False
    ok = ok and floop_ok

    # Durability gate (ISSUE 18): the crash_storm recovery chain must
    # converge to the crash-free reference's final map bit-identically,
    # with recovery cold solves inside the counted attribution bound
    # and a zombie (pre-recovery) journal handle's append rejected and
    # counted — plus the recovery-time numbers the round reports.
    try:
        durability = bench_durability()
        durability_ok = durability["pass"]
    except Exception as e:  # any stage crash must fail THIS gate
        durability = {"error": first_line(e)}
        durability_ok = False
    ok = ok and durability_ok

    # Membudget gate (ISSUE 20): every solver entry's AOT peak bytes
    # must sit under its declarative HBM ceiling
    # (blance_tpu.analysis.membudget.HBM_BUDGETS) — the same table the
    # --ci static tier enforces, re-measured here so the perf artifact
    # embeds the measured-vs-budget evidence (detail.membudget) next to
    # the numbers it explains.
    try:
        mb_rows = _bench_membudget()
        mb_ok = bool(mb_rows) and all(r.get("ok") for r in mb_rows)
    except Exception as e:  # any stage crash must fail THIS gate
        mb_rows = [{"error": first_line(e)}]
        mb_ok = False
    ok = ok and mb_ok

    print(json.dumps({
        "metric": "delta-replan perf smoke (warm vs cold sweeps)",
        "value": res["warm_sweeps"],
        "unit": "sweeps",
        "vs_baseline": res["cold_sweeps"],
        "detail": {**res, "pipeline": pipe, "sparse": sparse,
                   "sched": sched, "fleet_loop": floop,
                   "durability": durability, "membudget": mb_rows},
        "pass": ok,
    }))
    if not ok:
        log(f"PERF-SMOKE FAILED: warm={res['warm_sweeps']} sweeps vs "
            f"cold={res['cold_sweeps']} (hit={res['warm_carry_hit']}, "
            f"identical={res['identical']}); pipeline "
            f"{'OK' if pipe_ok else f'FAILED: {pipe}'}; sparse "
            f"{'OK' if sparse_ok else f'FAILED: {sparse}'}; fleet_loop "
            f"{'OK' if floop_ok else f'FAILED: {floop}'}; durability "
            f"{'OK' if durability_ok else f'FAILED: {durability}'}; "
            f"membudget {'OK' if mb_ok else f'FAILED: {mb_rows}'}")
        sys.exit(1)


def _run_benchmarks(smoke):
    import jax

    from blance_tpu.obs import device as obs_device

    # Device observatory: compile accounting always (per-stage counts in
    # detail.<stage>.device); the AOT cost analyses only at smoke sizes
    # — on a real device the extra AOT compile per bucket shape would
    # double the north-star compile cost for numbers XLA reports
    # identically at smoke scale.
    obs_device.enable(cost_analysis=smoke, sweep_trace=False)

    # Verify at the LARGEST node count benched (the headline shape),
    # regardless of config order.
    pallas, pallas_ok = verify_pallas(max(c[1] for c in CONFIGS))

    fused_ok = not smoke and verify_fused_engine()

    detail = {"configs": [], "pallas": pallas, "pallas_verified": pallas_ok,
              "fused_engine_verified": fused_ok,
              "device": str(jax.devices()[0]), "jax": jax.__version__,
              "backend": jax.default_backend(),
              "runs_per_config": RUNS}
    save_progress(detail, "verified")

    # Pass 1 — ALL device work, headline config first: a run cut short
    # still holds (persisted after every config) the headline number.
    headline = None
    for P, N, is_headline in CONFIGS:
        entry = {"P": P, "N": N}
        detail["configs"].append(entry)
        try:
            entry.update(bench_tpu(P, N))
            entry["engine"] = "matrix"
        except AssertionError:
            # An audit failure is a correctness regression, not a
            # capacity limit — the bench must fail loudly, not degrade.
            raise
        except Exception as e:
            # Expected at the north-star shape: the matrix engine's
            # [P, N] working set (~4 GB x several live copies at
            # 100k x 10k) exceeds one chip's HBM.  The fused engine
            # below, whose per-round traffic is O(P + N), is the
            # production path at that scale.
            log(f"[{P}x{N}] matrix engine failed ({type(e).__name__}: "
                f"{first_line(e)})")
            entry["matrix_error"] = first_line(e)
        if fused_ok:
            # The verify gate ran at 4096x512; this is a different static
            # shape — a lowering failure here must degrade to the matrix
            # result, not abort the bench.
            try:
                fused_res = bench_tpu(P, N, fused=True)
            except AssertionError:
                # Same contract as the matrix path: a failed audit is a
                # correctness regression and must abort loudly, not
                # silently degrade to the matrix headline.
                raise
            except Exception as e:
                log(f"[{P}x{N}] fused timed run failed "
                    f"({type(e).__name__}: {first_line(e)})")
                fused_res = None
            if fused_res is not None:
                entry["fused"] = fused_res
            if fused_res is not None and \
                    not any(fused_res["violations"].values()) and (
                    "solve_ms_min" not in entry
                    or fused_res["solve_ms_min"] < entry["solve_ms_min"]):
                # Both engines are production-selectable
                # (set_fused_score_default); report the better one as the
                # headline and name it.
                entry.update({k: fused_res[k] for k in
                              ("compile_s", "solve_ms_min",
                               "solve_ms_median", "solve_ms_runs",
                               "violations")})
                entry["engine"] = "fused"
        if "solve_ms_min" not in entry:
            # The engine tag must be present and truthful even when no
            # engine produced a number, so top-level and per-config
            # reporting agree.
            entry["engine"] = "none-failed"
            log(f"[{P}x{N}] no engine produced a result; config recorded "
                f"as failed")
            save_progress(detail, f"solve {P}x{N} failed")
            continue
        # End-to-end phases through the same engine as the headline solve.
        from blance_tpu.plan.tensor import set_fused_score_default

        set_fused_score_default("on" if entry["engine"] == "fused" else "off")
        try:
            entry["phases_ms"] = bench_phases(P, N)
        except Exception as e:  # phases are attribution detail — a
            log(f"[{P}x{N}] phase attribution failed "  # failure must not
                f"({type(e).__name__}: {first_line(e)})")  # eat the solve
            entry["phases_error"] = first_line(e)
        finally:
            set_fused_score_default("auto")
        save_progress(detail, f"solve {P}x{N} done")
        if is_headline:
            headline = entry

    # Pass 2 — CPU baselines (no device involvement: the measurement runs
    # in a subprocess started with JAX_PLATFORMS=cpu).
    for entry in detail["configs"]:
        if "solve_ms_min" not in entry:
            continue
        entry.update(bench_cpu(entry["P"], entry["N"]))
        if entry.get("cpu_s") is not None:
            entry["vs_baseline"] = round(
                entry["cpu_s"] * 1000 / entry["solve_ms_min"], 1)
        else:
            # Baseline failed (tagged in "baseline" above): an explicit
            # null, never a 0.0 sentinel a dashboard could mistake for a
            # measured "no speedup".
            entry["vs_baseline"] = None
        save_progress(detail, f"cpu {entry['P']}x{entry['N']} done")

    # Pipeline + metrics stage: exercise moves + orchestrate so the trace
    # and the "obs" block cover every layer, then embed the recorder's
    # aggregates (span totals, counters, histogram p50/p95 — including
    # orchestrate.move_latency_s) into the artifact.
    try:
        detail["pipeline"] = bench_pipeline()
    except Exception as e:  # attribution detail — must not eat the solve
        log(f"pipeline stage failed ({type(e).__name__}: {first_line(e)})")
        detail["pipeline_error"] = first_line(e)
    save_progress(detail, "pipeline done")

    # Chaos stage: transition completion under a fixed injected fault
    # rate — retries + quarantine + recovery replans end-to-end.  The
    # stage's `slo` block is the online SLO accounting's final reading.
    try:
        detail["chaos"] = bench_chaos()
    except Exception as e:  # must not eat the solve numbers
        log(f"chaos stage failed ({type(e).__name__}: {first_line(e)})")
        detail["chaos_error"] = first_line(e)
    save_progress(detail, "chaos done")

    # Simulator stage: a virtual day of closed-loop cluster life under
    # mixed faults — the horizon SLO account (time-weighted
    # availability, churn vs offline-optimal, convergence-lag
    # percentiles) plus sim-seconds-per-wall-second.
    try:
        detail["simulate"] = bench_simulate()
    except Exception as e:  # must not eat the solve numbers
        log(f"simulate stage failed ({type(e).__name__}: {first_line(e)})")
        detail["simulate_error"] = first_line(e)
    save_progress(detail, "simulate done")

    # Fleet-loop stage: N tenants' coalesced converge cycles vs the
    # sequential loop-per-tenant baseline (identical final maps, equal
    # churn, fewer device dispatches — ISSUE 13, docs/FLEET.md).
    try:
        detail["fleet_loop"] = bench_fleet_loop()
    except Exception as e:  # must not eat the solve numbers
        log(f"fleet-loop stage failed "
            f"({type(e).__name__}: {first_line(e)})")
        detail["fleet_loop_error"] = first_line(e)
    save_progress(detail, "fleet-loop done")

    # Cost-model stage: EWMA (node, op) move costs calibrated from the
    # chaos run's move-lifecycle spans, scored predicted-vs-actual.
    try:
        detail["costmodel"] = bench_costmodel()
    except Exception as e:  # must not eat the solve numbers
        log(f"costmodel stage failed ({type(e).__name__}: {first_line(e)})")
        detail["costmodel_error"] = first_line(e)
    save_progress(detail, "costmodel done")

    # Sched stage: critical-path scheduled move order vs the legacy
    # app-weight order at equal churn on hetero_drain + mixed_week —
    # makespan / convergence-lag p95 both ways, identity + committed-
    # trace-replay gates (ISSUE 12, docs/SCHEDULER.md).
    try:
        detail["sched"] = bench_sched()
    except Exception as e:  # must not eat the solve numbers
        log(f"sched stage failed ({type(e).__name__}: {first_line(e)})")
        detail["sched_error"] = first_line(e)
    save_progress(detail, "sched done")

    # Delta-replan stage: the incremental (warm-carry) replan against a
    # cold solve of the identical delta — cold vs warm sweeps and
    # wall-clock, plus the bit-identity contract.
    try:
        dp, dn = (512, 64) if smoke else (100_000, 1_000)
        detail["delta_replan"] = bench_delta_replan(dp, dn)
    except Exception as e:  # must not eat the solve numbers
        log(f"delta-replan stage failed "
            f"({type(e).__name__}: {first_line(e)})")
        detail["delta_replan_error"] = first_line(e)
    save_progress(detail, "delta-replan done")

    # Plan-pipeline stage: the fused single-dispatch encode→solve→diff
    # →decode-pack program vs the staged path — bit-identity asserted,
    # phases_ms reported for BOTH paths (the host-phase win), plus the
    # warm delta-replan end-to-end through the session fast path.
    try:
        pp, pn = (512, 64) if smoke else (100_000, 1_000)
        detail["plan_pipeline"] = bench_plan_pipeline(pp, pn)
        detail["plan_pipeline"]["warm"] = bench_warm_pipeline(pp, pn)
    except AssertionError:
        raise  # identity divergence is a correctness regression
    except Exception as e:  # must not eat the solve numbers
        log(f"plan-pipeline stage failed "
            f"({type(e).__name__}: {first_line(e)})")
        detail["plan_pipeline_error"] = first_line(e)
    save_progress(detail, "plan-pipeline done")

    # Sparse stage: the shortlist engine at the million-partition config
    # (ISSUE 11) — saturating-K bit-identity, the 1M x 1k solve with no
    # dense [P, S, N] score tensor, and AOT peak-bytes vs the dense
    # estimate.  Smoke sizes on cpu hosts.
    try:
        sp, sn = (4096, 128) if smoke else (1_000_000, 1_000)
        detail["sparse"] = bench_sparse(sp, sn)
    except AssertionError:
        raise  # a failed sparse audit is a correctness regression
    except Exception as e:  # must not eat the solve numbers
        log(f"sparse stage failed ({type(e).__name__}: {first_line(e)})")
        detail["sparse_error"] = first_line(e)
    save_progress(detail, "sparse done")

    # Fleet stage: 64 small tenant indexes solved per-tenant (the loop a
    # fleet replan runs today) vs batched by bucket class through the
    # vmapped fleet solver and the coalescing plan service — throughput
    # both ways plus p50/p99 admission-to-result latency.
    try:
        detail["fleet"] = bench_fleet()
    except Exception as e:  # must not eat the solve numbers
        log(f"fleet stage failed ({type(e).__name__}: {first_line(e)})")
        detail["fleet_error"] = first_line(e)
    detail["obs"] = obs_summary()
    save_progress(detail, "fleet done")

    if headline is None:
        # The headline config failed outright on every engine; fall back
        # to the largest config that did produce a number so the driver
        # artifact still carries a measured result (plus the failure
        # record above).
        done = [e for e in detail["configs"] if "solve_ms_min" in e]
        if not done:
            log("FATAL: no config produced a result")
            sys.exit(4)
        headline = max(done, key=lambda e: (e["P"], e["N"]))

    def _k(n):
        return f"{n // 1000}k" if n >= 1000 and n % 1000 == 0 else str(n)

    print(json.dumps({
        "metric": f"on-device converged solve @ {_k(headline['P'])} "
                  f"partitions x {_k(headline['N'])} nodes (primary+"
                  f"replica, rack rules, 5% node removal); phases + the "
                  f"other config in detail",
        "value": headline["solve_ms_min"],
        "unit": "ms",
        "vs_baseline": headline["vs_baseline"],
        "engine": headline.get("engine"),
        "detail": detail,
    }))


if __name__ == "__main__":
    main()
