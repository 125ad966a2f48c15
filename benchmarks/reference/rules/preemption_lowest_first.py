"""Preemption takes the lowest priority first, and no more than it needs.

Read off the store alone: an alloc that was preempted is terminal with
`desired_status` evict and names its preemptor in
`preempted_by_allocation`; every job carries its priority. The faults:

 (a) a victim whose job's priority is not at least `priority_delta`
     (the configuration's `preemption`, upstream's 10) under its
     preemptor's;
 (b) on a node, a victim of higher priority than a surviving alloc the
     same preemptor could have taken instead (preemptible by it, and no
     smaller than the victim in any resource);
 (c) cluster-wide, a victim of one priority while at the end of the run
     a live alloc of a LOWER preemptible priority stands on a node the
     preemptor's job admits. Sound because the cluster is full: lower
     tiers only shrink, so what stands at the end stood all the while.
     Stricter than upstream, whose choice across nodes is a score over a
     sampled set of nodes (the configuration says so under `assumed`);
 (d) a preemptor with more victims than its shortage needs: one of them
     could have stayed and the others would still have made room for the
     whole ask (equal asks: more than one victim);
 (e) an alloc marked evicted whose preemptor is not live.
"""

from collections import defaultdict

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
    nodes = {n["id"]: n for n in snap["nodes"]}
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

    # live allocs by node and, for (c), the lowest live priority on any
    # node a job admits: once for each (datacenters, constraints) that
    # occurs among the preemptors' jobs. A preemptor's own job never is
    # that lowest: its victims are `delta` under it.
    on_node = defaultdict(list)
    for a in snap["allocs"]:
        on_node[a["node"]].append(a)
    node_low = {nid: min(prio(a["job"]) for a in allocs)
                for nid, allocs in on_node.items() if nid in nodes}
    lowest_admitted: dict[tuple, int] = {}

    def lowest_live_for(job_id: str) -> int:
        job = jobs.get(job_id)
        if job is None:
            return 1 << 30
        key = (tuple(job["datacenters"]),
               tuple(tuple(c) for c in job["constraints"]))
        if key not in lowest_admitted:
            lowest_admitted[key] = min(
                (low for nid, low in node_low.items()
                 if _admits(job, nodes[nid])), default=1 << 30)
        return lowest_admitted[key]

    same_node = []      # (b)
    cluster_wide = []   # (c)
    too_many = []       # (d)
    for pid, victims in by_preemptor.items():
        p = live[pid]
        pp = prio(p["job"])
        for t in victims:
            pv = prio(t["job"])
            size = ask_of.get(t["job"])
            for s in on_node.get(t["node"], ()):
                if s["job"] != p["job"] and prio(s["job"]) < pv \
                        and pp - prio(s["job"]) >= delta and (
                        size is None
                        or all(s[r] >= size[i] for i, r in enumerate(RES))):
                    same_node.append((t, s))
                    break
            low = lowest_live_for(p["job"])
            if low < pv and pp - low >= delta:
                cluster_wide.append((t, low))
        if len(victims) > 1:
            sizes = [ask_of.get(t["job"]) for t in victims]
            if all(sz is not None for sz in sizes):
                total = [sum(sz[i] for sz in sizes) for i in range(3)]
                need = [p[r] for r in RES]
                if any(all(total[i] - sz[i] >= need[i] for i in range(3))
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
        t, s = same_node[0]
        faults.append(
            f"{len(same_node)} victims were taken while a lower priority "
            f"stood on the same node, e.g. {t['id']} of {t['job']} "
            f"(priority {prio(t['job'])}) on {t['node']}, where {s['id']} "
            f"of {s['job']} (priority {prio(s['job'])}) still runs")
    if cluster_wide:
        t, low = cluster_wide[0]
        faults.append(
            f"{len(cluster_wide)} victims were taken above the lowest tier "
            f"standing, e.g. {t['id']} of {t['job']} (priority "
            f"{prio(t['job'])}) while priority {low} still runs on a node "
            "its preemptor's job admits")
    if too_many:
        p, k = too_many[0]
        faults.append(
            f"{len(too_many)} preemptors took more victims than their "
            f"shortage needs, e.g. {p['id']} of {p['job']}: {k}")
    if orphans:
        faults.append(f"{orphans} allocs are marked evicted and their "
                      "preemptor is not live")
    return faults
