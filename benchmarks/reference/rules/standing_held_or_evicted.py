"""Every acked job is held by the store and holds what it asked, less
only what was preempted: allocs that are terminal with `desired_status`
evict and a LIVE preemptor. A job that nothing may preempt (no job of
the run is `priority_delta` above it: the window's production jobs)
holds exactly what it asked. Takes `acked_jobs_held`'s place where the
configuration's window evicts standing work (`expected`: job -> (allocs
asked, ask)). A job never holds more than it asked, and what it lost
and re-placed it may hold again."""

from collections import Counter


def check(snap: dict, expected: dict, config: dict) -> list[str]:
    delta = int(config.get("preemption", {}).get("priority_delta", 10))
    faults = []
    jobs = snap["jobs"]
    held = Counter(a["job"] for a in snap["allocs"])
    live = {a["id"] for a in snap["allocs"]}
    evicted = Counter(
        t["job"] for t in snap["terminal_allocs"]
        if t["desired_status"] == "evict"
        and t["preempted_by_allocation"] in live)
    top = max((int(j["priority"]) for j in jobs.values()), default=0)
    missing = [j for j in expected if j not in jobs]
    if missing:
        faults.append(f"{len(missing)} acked jobs are not in the store, "
                      f"e.g. {missing[0]}")
    wrong = []
    for job_id, (asked, _) in expected.items():
        if job_id not in jobs:
            continue
        may_lose = top - int(jobs[job_id]["priority"]) >= delta
        gone = evicted.get(job_id, 0) if may_lose else 0
        got = held.get(job_id, 0)
        if got > asked or got + gone < asked:
            wrong.append((job_id, got, gone, asked))
    if wrong:
        job_id, got, gone, asked = wrong[0]
        faults.append(
            f"{len(wrong)} completed jobs hold another number of allocs "
            f"than asked less what was preempted, e.g. {job_id}: {got} "
            f"live and {gone} evicted by a live preemptor, of {asked}")
    return faults
