"""Fleet-scale multi-tenant replanning through the plan service.

A cbgt/FTS-style deployment rebalances ~100 tenant indexes at once —
each a small independent plan.  Solved one at a time, that is ~100
device dispatches; the fleet tier groups the tenants into shape-bucket
batch classes, stacks each class into one [B, P, S, N] problem tensor,
and vmaps the dense solver over the batch (plan/fleet.py), fronted by
an asyncio plan service with request coalescing and a per-tenant
warm-carry cache (plan/service.py).  This script drives two fleet
rounds — a cold fleet-wide replan, then a node-outage delta round that
rides the carry cache warm — printing batch occupancy, admission
latency, and the speedup vs the sequential per-tenant loop.

Run:  python examples/fleet_replan.py   [TENANTS]
(default 100; use JAX_PLATFORMS=cpu off-TPU — multi-device hosts shard
the batch axis over the mesh automatically)
"""

import asyncio
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

from blance_tpu.core.encode import pad_problem_arrays
from blance_tpu.obs import Recorder, use_recorder
from blance_tpu.parallel.sharded import make_mesh
from blance_tpu.plan.fleet import TenantProblem, batch_class_of
from blance_tpu.plan.service import PlanService
from blance_tpu.plan.tensor import (
    resolve_default_fused_score,
    solve_converged_resilient,
)


def make_tenant(i):
    """One tenant index: mixed sizes (17..24 partitions) spread across
    four shape-bucket classes, rack rules on."""
    rng = np.random.default_rng(7_000 + i)
    P = int(rng.integers(17, 25))
    N = 8
    prev = np.full((P, 2, 1), -1, np.int32)
    prev[:, 0, 0] = rng.integers(0, N, P)
    prev[:, 1, 0] = (prev[:, 0, 0] + 1 + rng.integers(0, N - 1, P)) % N
    return TenantProblem(
        key=f"index-{i:03d}", prev=prev,
        partition_weights=np.ones(P, np.float32),
        node_weights=np.ones(N, np.float32),
        valid_node=np.ones(N, bool),
        stickiness=np.full((P, 2), 1.5, np.float32),
        gids=np.stack([np.arange(N, dtype=np.int32),
                       np.arange(N, dtype=np.int32) // 4,
                       np.zeros(N, np.int32)]),
        gid_valid=np.ones((3, N), bool),
        constraints=(1, 1), rules=((), ((2, 1),)))


def solve_sequential(t):
    """The single-problem path a fleet replan runs today: one bucketed
    device dispatch per tenant."""
    k = batch_class_of(t)
    arrs = pad_problem_arrays(
        t.prev, t.partition_weights, t.node_weights, t.valid_node,
        t.stickiness, t.gids, t.gid_valid, k.p, k.n)
    out, _ = solve_converged_resilient(
        *[jnp.asarray(a) for a in arrs], t.constraints, t.rules,
        max_iterations=10, mode=resolve_default_fused_score(k.p, k.n),
        allow_fallback=False, context="fleet_replan.sequential",
        p_real=jax.device_put(np.float32(t.prev.shape[0])))
    return np.asarray(out)[:t.prev.shape[0]]


async def main():
    n_tenants = int(sys.argv[1]) if len(sys.argv) > 1 else 100
    tenants = [make_tenant(i) for i in range(n_tenants)]
    classes = sorted({(k.p, k.n) for k in map(batch_class_of, tenants)})
    print(f"{n_tenants} tenant indexes in {len(classes)} bucket "
          f"classes: {['%dx%d' % c for c in classes]}")

    n_dev = len(jax.devices())
    if jax.default_backend() == "cpu":
        n_dev = min(n_dev, os.cpu_count() or 1)
    mesh = make_mesh(n_dev) if n_dev > 1 else None
    def outage_round(base, results):
        """Delta requests: one held node dies per tenant; each request
        states its delta (dirty mask) so the service's carry cache can
        ride the one-sweep warm repair."""
        reqs = []
        for t, r in zip(base, results):
            victim = int(np.unique(r.assign[r.assign >= 0])[0])
            valid2 = t.valid_node.copy()
            valid2[victim] = False
            reqs.append(TenantProblem(
                key=t.key, prev=r.assign,
                partition_weights=t.partition_weights,
                node_weights=t.node_weights, valid_node=valid2,
                stickiness=t.stickiness, gids=t.gids,
                gid_valid=t.gid_valid, constraints=t.constraints,
                rules=t.rules,
                dirty=(r.assign == victim).any(axis=(1, 2))))
        return reqs

    rec = Recorder()
    with use_recorder(rec):
        svc = PlanService(admission_window_s=0.005, mesh=mesh,
                          max_pending=n_tenants, recorder=rec)
        await svc.start()

        # Warm-up pass: one cold + one warm round compiles each bucket
        # class's batch programs (batch sizes bucket too, so the timed
        # rounds below reuse these compiles), then the timed rounds
        # measure steady-state service throughput.
        t0 = time.perf_counter()
        w1 = await asyncio.gather(*[svc.submit(t) for t in tenants])
        await asyncio.gather(
            *[svc.submit(t) for t in outage_round(tenants, w1)])
        print(f"warm-up (jit compiles, cold + warm programs per class): "
              f"{time.perf_counter() - t0:.1f}s")

        # Round 1 — fleet-wide cold replan: every tenant coalesces into
        # one batch per bucket class.
        t0 = time.perf_counter()
        round1 = await asyncio.gather(*[svc.submit(t) for t in tenants])
        fleet_s = time.perf_counter() - t0

        # Round 2 — a node outage touches every tenant; the requests
        # reuse round 1's cached carries and ride the warm repair.
        t0 = time.perf_counter()
        round2 = await asyncio.gather(
            *[svc.submit(t) for t in outage_round(tenants, round1)])
        delta_s = time.perf_counter() - t0
        await svc.stop()

    # Sequential baseline (one compile warm-up per class, same backend,
    # same padded shapes).
    seen = set()
    for t in tenants:
        if batch_class_of(t) not in seen:
            seen.add(batch_class_of(t))
            solve_sequential(t)
    t0 = time.perf_counter()
    seq_outs = [solve_sequential(t) for t in tenants]
    seq_s = time.perf_counter() - t0

    identical = all(np.array_equal(a, r.assign)
                    for a, r in zip(seq_outs, round1))
    warm = sum(r.warm for r in round2)
    occ = rec.histogram_summary("fleet.batch_tenants")
    lat = rec.histogram_summary("fleet.admission_latency_s")
    print(f"round 1 (cold fleet replan): {fleet_s * 1000:.0f}ms for "
          f"{n_tenants} tenants ({n_tenants / fleet_s:.0f} solves/s), "
          f"bit-identical to the sequential loop: {identical}")
    print(f"round 2 (node-outage delta): {delta_s * 1000:.0f}ms, "
          f"{warm}/{n_tenants} tenants rode the warm carry cache")
    print(f"sequential loop: {seq_s * 1000:.0f}ms "
          f"({n_tenants / seq_s:.0f} solves/s)  ->  fleet speedup "
          f"{seq_s / fleet_s:.1f}x")
    print(f"batch occupancy: mean {occ['sum'] / occ['count']:.1f} "
          f"tenants/dispatch (max {occ['max']:.0f}); admission latency "
          f"p50 {lat['p50'] * 1000:.1f}ms (p95 "
          f"{lat['p95'] * 1000:.1f}ms — includes the warm-up rounds' "
          f"compiles)")


if __name__ == "__main__":
    asyncio.run(main())
