"""The store's contents as plain data, for the reference rules.

The plain reference is independent of the solver: `snapshot()` reads the
store's nodes, jobs and allocations into flat dicts, and every rule the
configuration's guarantees name (`reference/rules/<rule>.py`) is a pure
function of that — `check(snap, expected, config) -> list[str]`, the
broken invariants as sentences — so the tests can hand a rule a broken
cluster. `expected` maps every acked job whose operation completed to
`(allocs asked, the ask it was sent with)`.
"""

from __future__ import annotations


def snapshot(state, observed: dict | None = None) -> dict:
    """Nodes, live allocs, terminal allocs and jobs of the store, and
    what the benchmark's observer saw (`observed`) for the rules that
    judge the watch."""
    nodes = []
    for n in state.nodes():
        cap = n.available_resources()
        nodes.append({
            "id": n.id, "datacenter": n.datacenter, "class": n.node_class,
            "cpu": int(cap.cpu), "mem": int(cap.memory_mb),
            "disk": int(cap.disk_mb),
            "attributes": dict(n.attributes),
            "devices": [{"id": d.id_string(),
                         "instances": [i.id for i in d.instances]}
                        for d in n.resources.devices],
        })
    allocs, terminal = [], []
    for a in state.allocs():
        if a.terminal_status():
            terminal.append({
                "id": a.id, "name": a.name, "job": a.job_id,
                "node": a.node_id, "desired_status": a.desired_status,
                "client_status": a.client_status,
                "preempted_by_allocation": a.preempted_by_allocation,
            })
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
            "priority": int(j.priority), "type": j.type,
        }
    return {"nodes": nodes, "allocs": allocs, "terminal_allocs": terminal,
            "jobs": jobs, "observed": dict(observed or {})}
