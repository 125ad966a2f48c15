"""Preemption on a cell of several bands, unequal asks and evicted work
that is placed again: lowest band first, and no more than it needs — as
far as the end of a run can prove it.

Read off the store alone: an alloc that was preempted is terminal with
`desired_status` evict and names its preemptor in
`preempted_by_allocation`; every job carries its priority; a terminal
alloc's size is its job's ask. `preemption_lowest_first` judges a
cluster that is full of equal asks, where "a lower tier stands on a node
the preemptor admits" is a fault as it reads. Here it is not: a lower
alloc may stand on a machine too small for the preemptor whatever is
evicted, and it may have been placed AFTER the preemption, by the
follow-up eval of an evicted job. So this rule counts, of the lower
bands, only what STOOD ALL THE WHILE — a live alloc whose name no
terminal alloc of its job carries: a follow-up eval places what its job
lost under the names it lost, so such an alloc was placed in set-up and
never since — and only where the sizes prove the room. The faults:

 (a) a victim whose job's priority is not at least `priority_delta`
     (the configuration's `preemption`, upstream's 10) under its
     preemptor's;
 (b) on the node, a victim taken while lower bands that stood all the
     while, preemptible by the same preemptor, would have made its room:
     together they are no smaller than the victim in any resource, or
     together with the preemptor's other victims they hold the
     preemptor's whole ask. Sound: the program (and upstream's
     Preemptor) walks a node's candidates lowest priority first and
     reaches a band only when everything under it does not cover the
     shortage, then drops every pick the rest cover without, highest
     priority first; had the survivors covered the victim, the walk
     would have stopped before it or dropped it;
 (c) cluster-wide, a victim of one band while, on ANY machine its
     preemptor's job admits (datacenters and `=` constraints; a spread
     is a preference and admits all), lower preemptible bands that stood
     all the while hold the preemptor's whole ask by themselves. Sound
     with unequal asks and re-placed work: those allocs were on that
     machine when the victim was taken, the machine was within its
     capacity then, so evicting them alone would have made the room
     there whatever else it held — and the solve opens a band only once
     no machine the group admits can take one more instance with every
     lower band evicted. It says nothing of a machine whose lower bands
     are too small for the ask, which is the point: a gratis `sand` on a
     machine that cannot hold a `boulder` proves nothing. Stricter than
     upstream, whose choice across nodes is a score over a sampled set
     (the configuration says so under `assumed`);
 (d) a preemptor with a victim its others made unnecessary: without it
     they still hold the preemptor's whole ask (what was free on the
     node is not in the store, so a set that merely exceeds the shortage
     by less than that goes unseen);
 (e) an alloc marked evicted whose preemptor is not live.
"""

from collections import defaultdict

import numpy as np

RES = ("cpu", "mem", "disk")


def _attr_of(node: dict, target: str):
    if target.startswith("${attr.") and target.endswith("}"):
        return node["attributes"].get(target[7:-1])
    if target == "${node.datacenter}":
        return node["datacenter"]
    return None


def _admits(job: dict, node: dict) -> bool:
    return node["datacenter"] in job["datacenters"] and all(
        _attr_of(node, lt) == rt
        for lt, op, rt in job["constraints"] if op == "=")


def check(snap: dict, expected: dict, config: dict) -> list[str]:
    delta = int(config.get("preemption", {}).get("priority_delta", 10))
    jobs = snap["jobs"]
    live = {a["id"]: a for a in snap["allocs"]}
    nodes = snap["nodes"]
    index = {n["id"]: i for i, n in enumerate(nodes)}
    evicted = [t for t in snap["terminal_allocs"]
               if t["desired_status"] == "evict"]

    def prio(job_id: str) -> int:
        return int(jobs[job_id]["priority"]) if job_id in jobs else 50

    # what an alloc of a job holds: a terminal alloc's grant is not in
    # the snapshot, so its job's live allocs say it, or — a job that was
    # evicted whole has none — the ask the job was sent with
    ask_of: dict[str, tuple] = {}
    for a in snap["allocs"]:
        ask_of.setdefault(a["job"], tuple(a[r] for r in RES))
    for job_id, (_, ask) in expected.items():
        if ask and job_id not in ask_of:
            ask_of[job_id] = (ask["cpu_mhz"], ask["memory_mb"],
                              ask["disk_mb"])

    orphans = 0
    too_close = []      # (a)
    by_preemptor = defaultdict(list)
    for t in evicted:
        p = live.get(t["preempted_by_allocation"])
        if p is None:
            orphans += 1
            continue
        by_preemptor[p["id"]].append(t)
        if prio(p["job"]) - prio(t["job"]) < delta:
            too_close.append((t, p))

    # what stood all the while, by priority: [nodes, 3] sums
    lost_names = {(t["job"], t["name"]) for t in snap["terminal_allocs"]}
    stood: dict[int, np.ndarray] = {}
    for a in snap["allocs"]:
        i = index.get(a["node"])
        if i is None or (a["job"], a["name"]) in lost_names:
            continue
        rows = stood.get(prio(a["job"]))
        if rows is None:
            rows = stood[prio(a["job"])] = np.zeros(
                (len(nodes), 3), dtype=np.int64)
        rows[i] += (a["cpu"], a["mem"], a["disk"])

    def stood_under(pv: int, pp: int) -> np.ndarray:
        """[nodes, 3]: the bands under `pv` that `pp` may preempt."""
        out = np.zeros((len(nodes), 3), dtype=np.int64)
        for q, rows in stood.items():
            if q < pv and pp - q >= delta:
                out += rows
        return out

    under_memo: dict[tuple, np.ndarray] = {}
    admitted: dict[tuple, np.ndarray] = {}
    elsewhere_memo: dict[tuple, bool] = {}

    def room_elsewhere(job_id: str, pv: int, pp: int, need: tuple) -> bool:
        """(c): some admitted machine's standing lower bands hold `need`."""
        job = jobs.get(job_id)
        if job is None:
            return False
        where = (tuple(job["datacenters"]),
                 tuple(tuple(c) for c in job["constraints"]))
        key = (where, pv, pp, need)
        if key not in elsewhere_memo:
            if where not in admitted:
                admitted[where] = np.array(
                    [_admits(job, n) for n in nodes], dtype=bool)
            if (pv, pp) not in under_memo:
                under_memo[pv, pp] = stood_under(pv, pp)
            holds = (under_memo[pv, pp] >= np.array(need)).all(axis=1)
            elsewhere_memo[key] = bool((holds & admitted[where]).any())
        return elsewhere_memo[key]

    same_node = []      # (b)
    cluster_wide = []   # (c)
    too_many = []       # (d)
    for pid, victims in by_preemptor.items():
        p = live[pid]
        pp = prio(p["job"])
        need = tuple(p[r] for r in RES)
        sizes = [ask_of.get(t["job"]) for t in victims]
        known = all(sz is not None for sz in sizes)
        total = [sum(sz[i] for sz in sizes) for i in range(3)] \
            if known else None
        for t, size in zip(victims, sizes):
            pv = prio(t["job"])
            if (pv, pp) not in under_memo:
                under_memo[pv, pp] = stood_under(pv, pp)
            i = index.get(t["node"])
            if i is not None and size is not None:
                low = under_memo[pv, pp][i]
                if low.any() and (
                        all(low[k] >= size[k] for k in range(3))
                        or known and all(
                            total[k] - size[k] + low[k] >= need[k]
                            for k in range(3))):
                    same_node.append((t, low))
            if room_elsewhere(p["job"], pv, pp, need):
                cluster_wide.append(t)
        if len(victims) > 1 and known and any(
                all(total[k] - sz[k] >= need[k] for k in range(3))
                for sz in sizes):
            too_many.append((p, len(victims)))

    faults = []
    if too_close:
        t, p = too_close[0]
        faults.append(
            f"{len(too_close)} victims are not {delta} priorities under "
            f"their preemptor, e.g. {t['id']} of {t['job']} (priority "
            f"{prio(t['job'])}) by {p['job']} (priority {prio(p['job'])})")
    if same_node:
        t, low = same_node[0]
        faults.append(
            f"{len(same_node)} victims were taken while lower bands that "
            f"stood all the while on the same node would have made the "
            f"room, e.g. {t['id']} of {t['job']} (priority "
            f"{prio(t['job'])}) on {t['node']}, where "
            f"{[int(x) for x in low]} (cpu, mem, disk) of lower bands "
            "still runs")
    if cluster_wide:
        t = cluster_wide[0]
        faults.append(
            f"{len(cluster_wide)} victims were taken above the lowest band "
            f"that could have made the room, e.g. {t['id']} of {t['job']} "
            f"(priority {prio(t['job'])}) while lower bands that stood all "
            "the while hold its preemptor's whole ask on a machine the "
            "preemptor's job admits")
    if too_many:
        p, k = too_many[0]
        faults.append(
            f"{len(too_many)} preemptors took a victim their others made "
            f"unnecessary, e.g. {p['id']} of {p['job']}: {k} victims")
    if orphans:
        faults.append(f"{orphans} allocs are marked evicted and their "
                      "preemptor is not live")
    return faults
