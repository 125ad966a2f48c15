"""`preempt-fill`: a backlog of production jobs landed at once on a
cluster that is full of lower tiers.

`backlog`'s window — `jobs` jobs of `count` allocs each registered back
to back over `PUT /v1/jobs` from `submitters` threads released on one
barrier, then the window watches the backlog drain — with the jobs of
the configuration's `job_class` at the mix's `priority`. At or above the
worker's lane priority every eval is solved alone, so set-up warms one
eval a solve; every placement evicts an alloc of a lower tier, whose job
gets a follow-up eval that finds the cluster full and ends blocked.
"""

from __future__ import annotations

import threading
import time

from benchmarks.harness import jobs


def shapes(params: dict, config: dict) -> list[dict]:
    """What set-up has to warm: one production eval a solve (the lane
    never batches)."""
    return [{"evals": 1, "count": int(params["count"]),
             "priority": int(params["priority"]),
             "job_class": params["job_class"]}]


def warm_jobs(params: dict) -> list[tuple]:
    """One real production deploy before the window: the preempt path,
    its plan with evictions and the evicted jobs' follow-up evals all run
    once in set-up."""
    return [(int(params["count"]), params["job_class"],
             int(params["priority"]))]


def run(ctx) -> None:
    n, count = int(ctx.params["jobs"]), int(ctx.params["count"])
    subs = int(ctx.params["submitters"])
    job_class = ctx.params["job_class"]
    prepared = []
    for i in range(n):
        job = jobs.make_job(ctx.config, f"prod-{ctx.seed}-{i}", count,
                            int(ctx.params["priority"]), job_class)
        prepared.append((ctx.new_op(job.id, count, "job", job_class),
                         jobs.encode(job)))

    # as `backlog`: every submitter is up and waiting when the window
    # opens, and all are released at once
    gate = threading.Barrier(subs + 1)

    def submit(k: int) -> None:
        try:
            gate.wait()
        except threading.BrokenBarrierError:
            return  # the window never opened
        for op, body in prepared[k::subs]:
            if time.monotonic() >= ctx.t_end:
                return  # never sent: not attempted
            ctx.send(op, body)

    threads = [threading.Thread(target=submit, args=(k,),
                                name=f"bench-submit-{k}")
               for k in range(subs)]
    for t in threads:
        t.start()
    try:
        ctx.open_window()
    except BaseException:
        gate.abort()
        raise
    gate.wait()
    for t in threads:
        t.join()
    for op, _ in prepared:
        if op.acked:
            op.watch.done.wait(max(0.0, ctx.t_end - time.monotonic()))
