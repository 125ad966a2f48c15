"""Summed cpu, memory and disk of the live allocs of a node do not
exceed the node's capacity (exact integers), and every alloc sits on a
node the store holds."""

import numpy as np


def check(snap: dict, expected: dict, config: dict) -> list[str]:
    faults = []
    nodes, allocs = snap["nodes"], snap["allocs"]
    index = {n["id"]: i for i, n in enumerate(nodes)}
    where = np.array([index.get(a["node"], -1) for a in allocs],
                     dtype=np.int64)
    if (where < 0).any():
        faults.append(f"{int((where < 0).sum())} allocs sit on nodes the "
                      "store does not hold")
    on = where >= 0
    for res in ("cpu", "mem", "disk"):
        cap = np.array([n[res] for n in nodes], dtype=np.int64)
        used = np.zeros(len(nodes), dtype=np.int64)
        np.add.at(used, where[on],
                  np.array([a[res] for a in allocs], dtype=np.int64)[on])
        over = np.nonzero(used > cap)[0]
        if over.size:
            i = int(over[0])
            faults.append(f"{over.size} nodes are over their {res}: node "
                          f"{nodes[i]['id']} uses {int(used[i])} of "
                          f"{int(cap[i])}")
    return faults
