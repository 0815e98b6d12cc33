"""Bring-up smoke of the planner on the chip, at the north-star size.

    python chip_smoke.py               # one chip: the four phases below
    python chip_smoke.py --four-chips  # the sharded solves on 4 chips and
                                       # the one-chip solve they are
                                       # compared with, nothing else

The deployment is BASELINE.json's north star (``_make_map`` /
``_rack_opts``): 100,000 partitions x 10,000 nodes, primary + 1
replica, racks of 25 nodes with the rule "replica on another rack in
the zone", and a warm previous map with 5% of the nodes removed.

One-chip phases, in one process:

1. rebalance — ``bt.rebalance(..., backend="tpu")`` against an in-memory
   data plane: plan (sparse engine), diff, orchestrate every move;
2. pipeline — ``plan_pipeline`` bit-equal to the bare solver of the
   engine it selects (its own dispatch, a host read, the host decode)
   + ``calc_all_moves``;
3. dense — the dense engine (``PlanOptions(sparse=False)``) at the same
   size, which resolves to the in-kernel ``score_fused`` engine;
4. kernels — each compiled Pallas kernel against its XLA reference on a
   batch with ties.

Each phase prints one line (wall and compile seconds, moves, audit,
peak device bytes).  The last line is the JSON contract line, printed
only when every check passed: a platform other than TPU, no compiled
Pallas path, a moved engine-fallback counter or any failed audit or
equality exits nonzero.  This is a smoke run, not a benchmark: its
times include compilation.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import sys
import time

import numpy as np

P_FULL, N_FULL = 100_000, 10_000
CLEAN = {"duplicates": 0, "on_removed_nodes": 0,
         "unfilled_feasible_slots": 0, "hierarchy_misses": 0}
FALLBACK_COUNTERS = ("plan.engine_fallback",)


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass
class Deployment:
    prev: dict
    nodes: list
    removed: list
    model: dict
    opts: object
    problem: object  # the encoded DenseProblem the audits run against

    @property
    def constraints(self) -> tuple:
        return tuple(int(c) for c in self.problem.constraints)

    @property
    def rules(self) -> tuple:
        return tuple(tuple(self.problem.rules.get(i, ()))
                     for i in range(self.problem.S))

    @property
    def k(self) -> int:
        """The shortlist width the sparse engine derives for this plan."""
        from blance_tpu.plan.tensor import _opts_shortlist_k

        return _opts_shortlist_k(self.opts, self.problem.N,
                                 self.constraints, self.rules)


def _make_map(P, N, seed=0):
    """PartitionMap, node list and the 5% of nodes removed, seeded."""
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


def deployment(P: int, N: int) -> Deployment:
    from blance_tpu import model
    from blance_tpu.core.encode import encode_problem

    prev, nodes, removed = _make_map(P, N)
    m = model(primary=(0, 1), replica=(1, 1))
    opts = _rack_opts(nodes)
    problem = encode_problem(prev, prev, nodes, removed, m, opts)
    return Deployment(prev, nodes, removed, m, opts, problem)


def dense_of(dep: Deployment, pmap: dict) -> np.ndarray:
    """``pmap`` as a [P, S, R] id array in the problem's own order; a
    placement on an unknown node or beyond R fails."""
    prob = dep.problem
    idx = {n: i for i, n in enumerate(prob.nodes)}
    out = np.full(prob.prev.shape, -1, np.int32)
    for pi, name in enumerate(prob.partitions):
        nbs = pmap[name].nodes_by_state
        for si, state in enumerate(prob.states):
            held = nbs.get(state, [])
            check(len(held) <= out.shape[2],
                  f"partition {name} holds {len(held)} {state} copies")
            for ri, node in enumerate(held):
                out[pi, si, ri] = idx[node]
    return out


def audit(dep: Deployment, assign) -> dict:
    from blance_tpu.plan.tensor import check_assignment

    counts = check_assignment(dep.problem, np.asarray(assign))
    check(counts == CLEAN, f"audit {counts}")
    return counts


def holders(pmap: dict) -> dict:
    """partition -> {node: state}: what a data plane holds."""
    return {name: {n: s for s, ns in p.nodes_by_state.items() for n in ns}
            for name, p in pmap.items()}


def snapshot(pmap: dict) -> str:
    from blance_tpu import partition_map_to_json

    return json.dumps(partition_map_to_json(pmap), sort_keys=True)


def count_moves(moves: dict) -> int:
    return sum(len(ops) for ops in moves.values())


class Phase:
    """Wall time, compile seconds and fallback counters of one phase."""

    def __init__(self, name: str) -> None:
        self.name = name
        self.fields: dict = {}

    def __enter__(self) -> "Phase":
        from blance_tpu.obs import device as obs_device

        self._mon = obs_device.CompileMonitor().install()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        wall = time.perf_counter() - self._t0
        self._mon.uninstall()
        if exc[0] is not None:
            return
        import jax

        from blance_tpu.obs import get_recorder

        counters = get_recorder().counters
        fallbacks = {c: counters.get(c, 0) for c in FALLBACK_COUNTERS}
        stats = jax.devices()[0].memory_stats() or {}
        line = {"wall_s": wall,
                "compile_s": sum(self._mon.compile_s_by_entry.values()),
                "compiles": self._mon.total, **self.fields,
                "fallbacks": fallbacks,
                "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
        print(f"phase {self.name}: {json.dumps(line)}", flush=True)
        check(not any(fallbacks.values()),
              f"{self.name}: engine fallback counters moved {fallbacks}")


def phase_rebalance(dep: Deployment, require_chip: bool) -> None:
    import blance_tpu as bt
    from blance_tpu.orchestrate import OrchestratorOptions
    from blance_tpu.plan.tensor import _sparse_selected, resolve_sparse_impl

    prob = dep.problem
    sparse = _sparse_selected(dep.opts, prob.P, prob.S, prob.N, dep.rules)
    if require_chip:
        check(sparse and resolve_sparse_impl(None) == "pallas",
              "the plan does not resolve to the sparse engine's kernel")
    before = snapshot(dep.prev)
    plane = holders(dep.prev)
    ops_done: collections.Counter = collections.Counter()

    def assign(stop_ch, node, partitions, states, ops):
        for name, state, op in zip(partitions, states, ops):
            if op == "del":
                check(node in plane[name], f"del of unheld {name}@{node}")
                del plane[name][node]
            else:
                plane[name][node] = state
            ops_done[op] += 1

    with Phase("rebalance") as ph:
        res = bt.rebalance(
            dep.model, dep.prev, dep.nodes, dep.removed, [], assign,
            backend="tpu", plan_options=dep.opts,
            orchestrator_options=OrchestratorOptions(
                max_concurrent_partition_moves_per_node=4,
                interrupt_on_first_feed=False, device_diff=True,
                max_retries=1))
        check(res.converged and not res.failures
              and not res.progress.errors,
              f"rebalance did not converge: {res.residual_failures} "
              f"{res.progress.errors[:3]}")
        plan = holders(res.next_map)
        check(plane == plan, "data plane differs from the plan")
        check(holders(res.achieved_map) == plan,
              "achieved map differs from the plan")
        ph.fields.update(
            engine=(f"sparse/{resolve_sparse_impl(None)} K={dep.k}"
                    if sparse else "dense"),
            moves=sum(ops_done.values()), ops=dict(ops_done),
            audit=audit(dep, dense_of(dep, res.next_map)))
        check(snapshot(dep.prev) == before, "input map was changed")


def phase_pipeline(dep: Deployment) -> None:
    import jax.numpy as jnp

    from blance_tpu.core.encode import decode_assignment
    from blance_tpu.moves.batch import calc_all_moves
    from blance_tpu.plan.tensor import (_sparse_selected, plan_pipeline,
                                        resolve_default_fused_score,
                                        solve_dense_converged,
                                        solve_sparse)

    prob = dep.problem
    arrays = (prob.prev, prob.partition_weights, prob.node_weights,
              prob.valid_node, prob.stickiness, prob.gids, prob.gid_valid)
    with Phase("pipeline") as ph:
        f_map, f_warn, f_moves = plan_pipeline(
            dep.prev, dep.prev, dep.nodes, dep.removed, [], dep.model,
            dep.opts)
        if _sparse_selected(dep.opts, prob.P, prob.S, prob.N, dep.rules):
            assign = solve_sparse(*arrays, dep.constraints, dep.rules,
                                  k=dep.k)
        else:
            assign = solve_dense_converged(
                *(jnp.asarray(a) for a in arrays), dep.constraints,
                dep.rules,
                fused_score=resolve_default_fused_score(prob.P, prob.N))
        s_map, s_warn = decode_assignment(prob, np.asarray(assign),
                                          dep.prev, dep.removed)
        s_moves = calc_all_moves(dep.prev, s_map, dep.model)
        check(f_map == s_map, "pipeline map != the bare solver's map")
        check(f_warn == s_warn, "pipeline warnings differ")
        check(f_moves == s_moves, "pipeline moves != calc_all_moves")
        ph.fields.update(moves=count_moves(f_moves),
                         audit=audit(dep, dense_of(dep, f_map)))


def phase_dense(dep: Deployment, require_chip: bool) -> None:
    import blance_tpu as bt
    from blance_tpu.moves.batch import calc_all_moves
    from blance_tpu.plan.tensor import resolve_default_fused_score

    opts = dataclasses.replace(dep.opts, sparse=False)
    mode = resolve_default_fused_score(len(dep.prev), len(dep.nodes))
    if require_chip:
        check(mode == "on", f"dense engine resolved to {mode!r}, not the "
                            f"in-kernel score_fused engine")
    with Phase("dense") as ph:
        next_map, _warn = bt.plan_next_map(
            dep.prev, dep.prev, dep.nodes, dep.removed, [], dep.model,
            opts, backend="tpu")
        ph.fields.update(
            engine={"on": "score_fused", "off": "matrix"}.get(mode, mode),
            moves=count_moves(calc_all_moves(dep.prev, next_map,
                                             dep.model)),
            audit=audit(dep, dense_of(dep, next_map)))


def _score_inputs(dep: Deployment, rows: int):
    """The replica slot's score inputs for the first ``rows`` partitions
    of the deployment, loads as integer counts (so nodes tie)."""
    import jax.numpy as jnp

    from blance_tpu.ops.score_fused import pack_score_inputs

    prob = dep.problem
    prev = prob.prev[:rows]
    n = prob.N
    held = prob.prev[:, 1, 0]
    total = np.bincount(held[held >= 0], minlength=n).astype(np.float32)
    rules = tuple(prob.rules.get(1, ()))
    return pack_score_inputs(
        total_l=jnp.asarray(total), total_p=jnp.float32(prob.P),
        w_div_l=jnp.asarray(prob.node_weights),
        neg_boost_l=jnp.zeros(n, jnp.float32),
        valid_l=jnp.asarray(prob.valid_node),
        stickiness_si=jnp.asarray(prob.stickiness[:rows, 1]),
        prev_slot=jnp.asarray(prev[:, 1, 0]),
        prev_state=jnp.asarray(prev[:, 1, :]),
        taken_ids=[jnp.asarray(prev[:, 0, 0])],
        anchors=jnp.asarray(prev[:, 0, :]),
        gids_l=jnp.asarray(prob.gids), gid_valid=jnp.asarray(prob.gid_valid),
        gids=jnp.asarray(prob.gids), rules=rules), len(rules)


def phase_kernels(dep: Deployment, interpret: bool, seed: int = 7) -> None:
    """Each Pallas kernel against its XLA reference, bit for bit, on
    quantized inputs (duplicate minima exercise the tie-breaks)."""
    import jax.numpy as jnp

    from blance_tpu.ops.reduce2 import (min2_argmin_reference,
                                        priced_min2_argmin)
    from blance_tpu.ops.score_fused import fused_score_min2
    from blance_tpu.ops.sparse2 import (sparse_min2_reference,
                                        sparse_priced_min2)

    rng = np.random.default_rng(seed)
    P, N = dep.problem.P, dep.problem.N

    def quantized(shape, levels, step):
        return jnp.asarray(rng.integers(0, levels, shape).astype(np.float32)
                           * step)

    def same(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, b))

    results = {}
    with Phase("kernels") as ph:
        n_r = min(N, 1_000)
        score, price = quantized((P, n_r), 50, 0.125), \
            quantized((n_r,), 8, 0.25)
        results[f"reduce2[{P}x{n_r}]"] = same(
            priced_min2_argmin(score, price, interpret=interpret),
            min2_argmin_reference(score + price[None, :]))

        k = dep.k
        score, price = quantized((P, k), 50, 0.125), \
            quantized((P, k), 8, 0.25)
        results[f"sparse2[{P}x{k}]"] = same(
            sparse_priced_min2(score, price, interpret=interpret),
            sparse_min2_reference(score, price))

        rows = min(P, 8_192)
        si, nrules = _score_inputs(dep, rows)
        price = quantized((N,), 8, 0.25)
        zero = jnp.zeros((1, 1), jnp.int32)
        run = lambda interp: fused_score_min2(  # noqa: E731
            price, si, zero, zero, nrules=nrules, jitter_scale=0.0,
            interpret=interp)
        results[f"score_fused[{rows}x{N}]"] = same(run(interpret),
                                                   run(True))
        ph.fields.update(bit_identical=results)
        check(all(results.values()), f"kernel mismatch: {results}")


def single_chip(P: int, N: int, require_chip: bool) -> None:
    dep = deployment(P, N)
    phase_rebalance(dep, require_chip)
    phase_pipeline(dep)
    phase_dense(dep, require_chip)
    phase_kernels(dep, interpret=not require_chip)


def spread(assign: np.ndarray, valid: np.ndarray, si: int) -> int:
    ids = assign[:, si, :].ravel()
    loads = np.bincount(ids[ids >= 0], minlength=valid.shape[0])
    return int(loads[valid].max() - loads[valid].min())


def four_chips(P: int, N: int, devices: list) -> None:
    """The sharded solves over a 4-device mesh against the one-chip
    solve, held to dryrun_multichip's bounds: balance spread within
    single + n_devices * max weight, and a one-chip replan of the
    sharded result churning at most max(2N, P // 16) partitions."""
    from blance_tpu import PlannerSession
    from blance_tpu.parallel.sharded import (make_mesh_auto,
                                             solve_sparse_sharded)
    from blance_tpu.plan.tensor import (resolve_default_fused_score,
                                        solve_sparse)

    dep = deployment(P, N)
    prob = dep.problem
    mesh = make_mesh_auto(P, N, devices=devices)
    mesh_devices = list(mesh.devices.flat)
    check(len(set(mesh_devices)) == len(devices) == 4,
          f"mesh spans {len(set(mesh_devices))} devices, not 4")
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} over "
          f"{[str(d) for d in mesh_devices]}", flush=True)
    constraints, rules = dep.constraints, dep.rules
    arrays = (prob.prev, prob.partition_weights, prob.node_weights,
              prob.valid_node, prob.stickiness, prob.gids, prob.gid_valid)
    valid = prob.valid_node
    max_w = float(np.max(prob.partition_weights))

    def solve_one_chip(prev):
        return np.asarray(solve_sparse(
            prev, *arrays[1:], constraints, rules))

    with Phase("one-chip") as ph:
        single = solve_one_chip(prob.prev)
        ph.fields.update(engine="sparse", audit=audit(dep, single),
                         spread=[spread(single, valid, s)
                                 for s in range(prob.S)])

    def compare(name: str, assign: np.ndarray, fields: dict) -> None:
        spreads = [spread(assign, valid, s) for s in range(prob.S)]
        for s in range(prob.S):
            bound = spread(single, valid, s) + len(devices) * max_w
            check(spreads[s] <= bound,
                  f"{name}: state {s} spread {spreads[s]} > {bound}")
        replan = solve_one_chip(assign)
        churn = int((replan != assign).any(axis=(1, 2)).sum())
        churn_bound = max(2 * N, P // 16)
        check(churn <= churn_bound,
              f"{name}: one-chip replan churned {churn} > {churn_bound}")
        fields.update(audit=audit(dep, assign), spread=spreads,
                      replan_churn=churn, churn_bound=churn_bound)

    with Phase("sharded-sparse") as ph:
        assign = solve_sparse_sharded(mesh, *arrays, constraints, rules)
        compare("sharded-sparse", assign, ph.fields)
        ph.fields.update(engine="solve_sparse_sharded")

    with Phase("sharded-pipeline") as ph:
        session = PlannerSession(
            dep.model, dep.nodes, list(dep.prev),
            dataclasses.replace(dep.opts, sparse=False), mesh=mesh)
        session.load_map(dep.prev)
        session.remove_nodes(dep.removed)
        assign, (d_nodes, _s, _o) = session.replan_with_moves()
        # The session's encoding must be the audited problem's.
        check(session.problem.partitions == prob.partitions
              and session.problem.nodes == prob.nodes,
              "session encodes another problem order")
        compare("sharded-pipeline", np.asarray(assign), ph.fields)
        mode = resolve_default_fused_score(-(-P // len(devices)), N)
        ph.fields.update(engine="solve_pipeline_sharded/" + {
            "on": "score_fused", "off": "matrix"}.get(mode, mode),
                         moves=int((d_nodes >= 0).sum()))

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in mesh_devices]
    print(f"peak_bytes_in_use per mesh device: {peaks}", flush=True)
    if mesh_devices[0].platform == "tpu":
        check(all(p > 0 for p in peaks[1:]) and
              min(peaks) * 8 > max(peaks),
              f"work not spread over the mesh: peaks {peaks}")


def native_paths() -> str:
    import os

    from blance_tpu.core import marshal
    from blance_tpu.plan import native

    lib = native._load_lib()
    mod = marshal.get()
    return (f"native planner: "
            f"{os.path.basename(lib._name) if lib else 'python greedy'}; "
            f"marshal: "
            f"{os.path.basename(mod.__file__) if mod else 'python'}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded solves and the "
                         "one-chip solve they are compared with")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    from blance_tpu.ops.reduce2 import pallas_available
    from blance_tpu.utils.compile_cache import enable_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x "
          f"{len(jax.devices())}, bytes_limit "
          f"{(dev.memory_stats() or {}).get('bytes_limit')}; jax "
          f"{jax.__version__}; compile cache {enable_compile_cache()}",
          flush=True)
    print(native_paths(), flush=True)
    try:
        check(pallas_available(), "no compiled Pallas path")
        if args.four_chips:
            devices = jax.devices()[:4]
            check(len(devices) == 4, f"need 4 chips, have {len(devices)}")
            four_chips(P_FULL, N_FULL, devices)
        else:
            single_chip(P_FULL, N_FULL, require_chip=True)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
