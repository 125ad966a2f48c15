"""Packing: the least number of nodes that can hold what was placed.

Every node and every ask of a bulk cell is identical, and a job's
datacenter spread fixes how many of its allocs each datacenter gets; so
the least number of nodes is arithmetic, not a run of the host oracle:

    sum over datacenters of ceil(allocs placed there / allocs per node)

`packing_share` is 100 x that over the nodes the placement touches. It is
NOT clipped: it can pass 100 only if this reference is wrong about what
fits on a node, which `correct` catches (the store check would have to
find a node over capacity, or this count is off).
"""

from __future__ import annotations

from collections import Counter


def allocs_per_node(node: dict, ask: dict) -> int:
    """How many asks fit one empty node, by its tightest resource."""
    fits = [
        node[res] // ask[res]
        for res in ("cpu_mhz", "memory_mb", "disk_mb")
        if ask.get(res, 0) > 0
    ]
    return int(min(fits))


def ideal_nodes(placed_by_dc: dict[str, int], per_node: int) -> int:
    return sum(-(-n // per_node) for n in placed_by_dc.values() if n > 0)


def packing(snap: dict, per_node: int) -> dict:
    """From a `store_check.snapshot`: allocs placed, nodes touched, the
    ideal, and the share."""
    dc_of = {n["id"]: n["datacenter"] for n in snap["nodes"]}
    by_dc: Counter = Counter()
    touched = set()
    for a in snap["allocs"]:
        by_dc[dc_of.get(a["node"], "?")] += 1
        touched.add(a["node"])
    ideal = ideal_nodes(by_dc, per_node)
    return {
        "placed": sum(by_dc.values()),
        "nodes_touched": len(touched),
        "ideal_nodes": ideal,
        "packing_share": 100.0 * ideal / len(touched) if touched else None,
    }
