"""Steady-state planning at scale with PlannerSession.

One-shot ``plan_next_map`` re-interns every name on every call; a
long-lived cluster controller should hold a session instead — interning
tables, the compiled solver, and the current dense assignment persist, so
each rebalance is: mutate membership, solve on device, diff on device,
apply.  PartitionMaps materialize only for checkpoints.

Run:  python examples/dense_session_loop.py   [P] [N]
(defaults 20000 x 500; use JAX_PLATFORMS=cpu off-TPU)
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import blance_tpu as bt
from blance_tpu.moves.batch import OP_NAMES
from blance_tpu.plan.tensor import check_assignment


def main():
    P = int(sys.argv[1]) if len(sys.argv) > 1 else 20_000
    N = int(sys.argv[2]) if len(sys.argv) > 2 else 500

    model = bt.model(primary=(0, 1), replica=(1, 1))
    nodes = [f"node-{i:04d}" for i in range(N)]
    partitions = [str(i) for i in range(P)]

    session = bt.PlannerSession(model, nodes, partitions)

    t0 = time.perf_counter()
    session.replan()
    session.apply()
    print(f"initial plan of {P}x{N}: {time.perf_counter() - t0:.2f}s "
          f"(includes jit compile)")

    # A rolling maintenance window: drain 2% of nodes, replan, apply,
    # re-add them, five times — the steady-state controller loop.
    drained = [nodes[i::50][0] for i in range(5)]
    for step, victim in enumerate(drained):
        t0 = time.perf_counter()
        session.remove_nodes([victim])
        session.replan()
        mv_nodes, mv_states, mv_ops = session.moves()
        n_ops = int((mv_ops >= 0).sum())
        session.apply()
        session.add_nodes([victim])  # back in service for the next replan
        dt = time.perf_counter() - t0
        ops = {name: int((mv_ops == i).sum())
               for i, name in enumerate(OP_NAMES) if (mv_ops == i).any()}
        print(f"  drain {victim}: {n_ops} ops {ops} in {dt*1000:.0f}ms")

    # The last victim is back in service but empty — one final replan
    # restores it (only the copies it should carry move back).
    session.replan()
    session.apply()

    report = check_assignment(session.problem, session.current)
    assert report == {"duplicates": 0, "on_removed_nodes": 0,
                      "unfilled_feasible_slots": 0,
                      "hierarchy_misses": 0}, report
    counts = np.bincount(session.current[session.current >= 0], minlength=N)
    print(f"final spread: {counts.max() - counts.min()} "
          f"(ideal per-node load {2 * P // N})")

    # Checkpoint only at the edge.
    final_map, warnings = session.to_map()
    assert not warnings
    bt.save_partition_map(final_map, "/tmp/dense_session_map.json")
    print("checkpointed to /tmp/dense_session_map.json")


if __name__ == "__main__":
    main()
