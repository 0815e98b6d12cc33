"""Placement-control hooks: booster, custom scorer, full custom sorter.

The reference exposes three escalating control points as package globals
(NodeScoreBooster, CustomNodeSorter — reference plan.go:566-580,693-697);
here they are per-call PlanOptions fields:

  1. node_score_booster + negative node weights — steer NEW load away
     from nodes being drained/protected, without moving what's there
     (the couchbase/cbgt pattern, control_test.go:19-29).
  2. node_scorer — replace the score formula; the framework keeps the
     deterministic node-position tie-break.
  3. node_sorter — replace the ENTIRE candidate ordering, tie-break
     policy included.

Each hook runs on the exact planner; `backend="auto"`/"tpu" route hooked
plans to the exact path automatically (a Python callable can't run
inside the jitted batch solver) — EXCEPT the cbgt booster, whose shape
is baked into the device score, so boosted plans stay on the fast path.

Run:  python examples/custom_policy.py   (JAX_PLATFORMS=cpu works too)
"""

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import blance_tpu as bt
from blance_tpu.plan.greedy import default_node_score
from blance_tpu.plan.native import cbgt_node_score_booster

MODEL = bt.model(primary=(0, 1), replica=(1, 1))
NODES = ["a", "b", "c", "d"]


def loads(pmap):
    out = collections.Counter()
    for p in pmap.values():
        for ns in p.nodes_by_state.values():
            for n in ns:
                out[n] += 1
    return dict(sorted(out.items()))


def fresh(n=32):
    return {str(i): bt.Partition(str(i), {}) for i in range(n)}


def main():
    parts = fresh()

    # 1. Booster: steer NEW load away from node d (weight -2).  The
    #    boost is a fixed score offset (max(-w, stickiness)), NOT a hard
    #    exclusion — once other nodes carry enough copies the count
    #    pressure overrides it, exactly like the reference — so the
    #    steering demo uses few partitions (the reference's control
    #    tests use 1-3, control_test.go:18-416).
    few = fresh(4)
    drained, _ = bt.plan_next_map(
        few, few, NODES, [], NODES, MODEL,
        bt.PlanOptions(node_weights={"d": -2},
                       node_score_booster=cbgt_node_score_booster),
        backend="auto")
    print("booster (steer new load off d):", loads(drained))
    assert loads(drained).get("d", 0) == 0

    # 2. Custom scorer: bias primaries toward node c by 2 score units
    #    (score ~ held count, so c settles ~2 primaries above the rest);
    #    ties still break by node position, so the plan stays
    #    deterministic.
    def prefer_c(ctx, node):
        r = default_node_score(ctx, node)
        return r - 2.0 if (node == "c" and ctx.state_name == "primary") \
            else r

    biased, _ = bt.plan_next_map(
        parts, parts, NODES, [], NODES, MODEL,
        bt.PlanOptions(node_scorer=prefer_c), backend="auto")
    prim = collections.Counter(
        p.nodes_by_state["primary"][0] for p in biased.values())
    print("scorer (bias primaries toward c):", dict(sorted(prim.items())))
    assert prim["c"] > max(v for k, v in prim.items() if k != "c")

    # 3. Full sorter: reverse the tie-break policy (last node wins ties)
    #    — something node_scorer cannot express.
    def reverse_ties(ctx, nodes):
        return sorted(nodes, key=lambda n: (default_node_score(ctx, n),
                                            -ctx.node_positions.get(n, 0)))

    rev, _ = bt.plan_next_map(
        parts, parts, NODES, [], NODES, MODEL,
        bt.PlanOptions(node_sorter=reverse_ties), backend="auto")
    first = rev["0"].nodes_by_state["primary"]
    print("sorter (reversed ties): partition 0 primary ->", first)
    assert first == ["d"]

    print("OK")


if __name__ == "__main__":
    main()
