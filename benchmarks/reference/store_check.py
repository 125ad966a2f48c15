"""The plain reference: is what the store holds a correct placement?

Independent of the solver. The store's nodes and allocations are read
into flat numpy arrays and held to the configuration's guarantees by
the feasibility rules written plainly:

  * every alloc id is unique, and so is every alloc name within a job;
  * summed cpu, memory and disk of the live allocs of a node do not
    exceed the node's capacity (exact integers);
  * every alloc sits on a node in one of its job's datacenters whose
    attributes meet the job's constraints;
  * every alloc asks what the configuration says its job asks;
  * every acked job is held by the store, and holds exactly the allocs
    it asked for once its operation completed.

`snapshot()` reads the store; `check()` is pure and takes what
`snapshot()` returns, so the tests can hand it a broken cluster.
"""

from __future__ import annotations

from collections import Counter

import numpy as np


def snapshot(state, namespace: str = "default") -> dict:
    """Nodes, live allocs and jobs of the store as plain data."""
    nodes = []
    for n in state.nodes():
        cap = n.available_resources()
        nodes.append({
            "id": n.id, "datacenter": n.datacenter,
            "cpu": int(cap.cpu), "mem": int(cap.memory_mb),
            "disk": int(cap.disk_mb),
            "attributes": dict(n.attributes),
        })
    allocs = []
    for a in state.allocs():
        if a.terminal_status():
            continue
        r = a.comparable_resources()
        allocs.append({
            "id": a.id, "name": a.name, "job": a.job_id, "node": a.node_id,
            "cpu": int(r.cpu), "mem": int(r.memory_mb),
            "disk": int(r.disk_mb),
        })
    jobs = {}
    for j in state.jobs():
        jobs[j.id] = {
            "datacenters": list(j.datacenters),
            "constraints": [
                (c.ltarget, c.operand, c.rtarget) for c in j.constraints
            ],
        }
    return {"nodes": nodes, "allocs": allocs, "jobs": jobs}


def _attr_of(node: dict, target: str):
    if target.startswith("${attr.") and target.endswith("}"):
        return node["attributes"].get(target[7:-1])
    if target == "${node.datacenter}":
        return node["datacenter"]
    return None


def check(snap: dict, expected: dict[str, int], ask: dict) -> list[str]:
    """The broken invariants, as sentences; empty when all hold.

    expected — job id -> allocs asked, for every acked job whose
    operation completed; ask — the configuration's ask, which every
    alloc of such a job must carry."""
    faults: list[str] = []
    nodes, allocs, jobs = snap["nodes"], snap["allocs"], snap["jobs"]
    index = {n["id"]: i for i, n in enumerate(nodes)}

    ids = Counter(a["id"] for a in allocs)
    dup = [i for i, c in ids.items() if c > 1]
    if dup:
        faults.append(f"{len(dup)} alloc ids are held twice, e.g. {dup[0]}")
    names = Counter((a["job"], a["name"]) for a in allocs)
    dup = [k for k, c in names.items() if c > 1]
    if dup:
        faults.append(f"{len(dup)} (job, alloc name) pairs are placed twice,"
                      f" e.g. {dup[0]}")

    where = np.array([index.get(a["node"], -1) for a in allocs], dtype=np.int64)
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

    # feasibility, once per (job, node) pair that occurs
    bad_dc = bad_constraint = bad_ask = 0
    seen: dict[tuple[str, int], bool] = {}
    for a, w in zip(allocs, where):
        job = jobs.get(a["job"])
        if job is None or w < 0:
            continue
        key = (a["job"], int(w))
        ok = seen.get(key)
        if ok is None:
            node = nodes[int(w)]
            ok = True
            if node["datacenter"] not in job["datacenters"]:
                bad_dc += 1
                ok = False
            for ltarget, operand, rtarget in job["constraints"]:
                if operand == "=" and _attr_of(node, ltarget) != rtarget:
                    bad_constraint += 1
                    ok = False
            seen[key] = ok
        if a["job"] in expected and (
            a["cpu"] != ask["cpu_mhz"] or a["mem"] != ask["memory_mb"]
        ):
            bad_ask += 1
    if bad_dc:
        faults.append(f"{bad_dc} (job, node) placements are outside the "
                      "job's datacenters")
    if bad_constraint:
        faults.append(f"{bad_constraint} (job, node) placements break a "
                      "constraint of the job")
    if bad_ask:
        faults.append(f"{bad_ask} allocs do not carry the configuration's ask")

    placed = Counter(a["job"] for a in allocs)
    missing = [j for j in expected if j not in jobs]
    if missing:
        faults.append(f"{len(missing)} acked jobs are not in the store, "
                      f"e.g. {missing[0]}")
    wrong = [(j, placed.get(j, 0), n) for j, n in expected.items()
             if placed.get(j, 0) != n]
    if wrong:
        j, got, n = wrong[0]
        faults.append(f"{len(wrong)} completed jobs hold another number of "
                      f"allocs than asked, e.g. {j}: {got} of {n}")
    return faults
