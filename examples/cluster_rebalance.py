"""End-to-end cluster lifecycle with blance_tpu.

A vbucket-style scenario (1024 partitions, primary + 1 replica, two
racks) driven the way couchbase/cbgt drives the reference library:

  1. fresh cluster  -> plan a balanced, rack-aware map
  2. execute the transition with the orchestrator (fake data plane here)
  3. a node dies    -> replan from the current map, orchestrate the delta
  4. cluster grows  -> replan, watch load migrate onto the new nodes

Run:  python examples/cluster_rebalance.py        (any backend machine;
set JAX_PLATFORMS=cpu to force the CPU platform)
"""

import asyncio
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import blance_tpu as bt
from blance_tpu.orchestrate import OrchestratorOptions, orchestrate_moves


MODEL = bt.model(primary=(0, 1), replica=(1, 1))
P = 1024


def racked(nodes):
    """node -> rack -> zone containment for HierarchyRules."""
    hier = {n: f"rack{i % 2}" for i, n in enumerate(nodes)}
    hier.update({"rack0": "dc", "rack1": "dc"})
    return bt.PlanOptions(
        node_hierarchy=hier,
        # Replicas on a different rack than the primary.
        hierarchy_rules={"replica": [bt.HierarchyRule(2, 1)]},
    )


def load_report(pmap, nodes):
    loads = collections.Counter()
    for p in pmap.values():
        for ns in p.nodes_by_state.values():
            loads.update(ns)
    return {n: loads.get(n, 0) for n in nodes}


async def execute(beg_map, end_map, nodes, label):
    """Drive the orchestrator with an in-memory 'data plane'."""
    ops_done = collections.Counter()

    def assign_partitions(stop_ch, node, partitions, states, ops):
        # Real systems move data here (backfill, promote, ...) and block
        # until durable; raising or returning an Exception fails the move.
        for op in ops:
            ops_done[op] += 1

    o = orchestrate_moves(
        MODEL,
        OrchestratorOptions(
            max_concurrent_partition_moves_per_node=4,
            # Throughput mode: fine for big deltas; flip to True for the
            # reference's freshest-choice scheduling.
            interrupt_on_first_feed=False,
            device_diff=True,  # whole-map diff on device
        ),
        nodes, beg_map, end_map, assign_partitions)

    last = None
    async for progress in o.progress_ch():  # MUST drain until close
        last = progress
    o.stop()
    assert not last.errors, last.errors
    print(f"  {label}: ops {dict(ops_done)}, "
          f"moves ok {last.tot_mover_assign_partition_ok}")
    return last


def main():
    nodes = [f"n{i}" for i in range(8)]
    opts = racked(nodes)
    empty = {str(i): bt.Partition(str(i), {}) for i in range(P)}

    # 1. Fresh, balanced, rack-aware plan (auto -> TPU for big problems).
    m1, warnings = bt.plan_next_map(
        empty, empty, nodes, [], nodes, MODEL, opts, backend="auto")
    assert not warnings
    print("fresh plan loads:", load_report(m1, nodes))

    # 2. Execute the initial build-out.
    asyncio.run(execute(empty, m1, nodes, "build-out"))

    # 3. Node n3 dies. Replan from current map; only displaced copies move.
    m2, _ = bt.plan_next_map(m1, m1, nodes, ["n3"], [], MODEL, opts,
                             backend="auto")
    moves = sum(
        m1[p].nodes_by_state != m2[p].nodes_by_state for p in m1)
    print(f"after losing n3: {moves} partitions touched, loads:",
          load_report(m2, nodes))
    asyncio.run(execute(m1, m2, nodes, "failover rebalance"))

    # 4. Two nodes join; load migrates onto them (and nowhere else than
    #    necessary).
    grown = nodes + ["n8", "n9"]
    m3, _ = bt.plan_next_map(m2, m2, grown, ["n3"], ["n8", "n9"], MODEL,
                             racked(grown), backend="auto")
    print("after growth loads:", load_report(m3, grown))
    asyncio.run(execute(m2, m3, grown, "growth rebalance"))

    # Checkpoint: the map itself is the durable state.
    bt.save_partition_map(m3, "/tmp/cluster_map.json")
    restored = bt.load_partition_map("/tmp/cluster_map.json")
    assert {k: v.nodes_by_state for k, v in restored.items()} == \
        {k: v.nodes_by_state for k, v in m3.items()}
    print("checkpoint round-trip OK")


if __name__ == "__main__":
    main()
