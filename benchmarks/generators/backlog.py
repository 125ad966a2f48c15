"""`bulk`: a backlog of identical service jobs landed at once.

`jobs` jobs of `count` allocs each are registered back to back over
`PUT /v1/jobs` from `submitters` threads, bodies encoded beforehand and
all threads released on one barrier at the start of the window;
the window then watches the backlog drain. The work is fixed: every
seed registers the same jobs (under other ids, on a cluster whose node
ids the seed made).
"""

from __future__ import annotations

import threading
import time

from benchmarks.harness import jobs


def shapes(params: dict, config: dict, batch_size: int = 64) -> list[dict]:
    """What set-up has to warm: the worker drains up to `batch_size`
    evals into one solve, and how many it catches follows the arrivals —
    so every batch from one eval to the lesser of the backlog and the
    worker's limit."""
    top = min(int(params["jobs"]), batch_size)
    return [{"evals": k, "count": int(params["count"])}
            for k in range(1, top + 1)]


def warm_jobs(params: dict) -> list[int]:
    return [int(params["count"])]


def run(ctx) -> None:
    n, count = int(ctx.params["jobs"]), int(ctx.params["count"])
    subs = int(ctx.params["submitters"])
    prepared = []
    for i in range(n):
        job = jobs.make_job(ctx.config, f"bulk-{ctx.seed}-{i}", count,
                            int(ctx.params["priority"]))
        prepared.append((ctx.new_op(job.id, count, kind="job"),
                         jobs.encode(job)))

    # every submitter is up and waiting when the window opens, and all
    # are released at once: no thread's start-up is inside the window
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
    # the window watches the backlog drain: to its end, or to the last
    # alloc visible, whichever comes first
    for op, _ in prepared:
        if op.sent and op.status // 100 == 2:
            op.watch.done.wait(max(0.0, ctx.t_end - time.monotonic()))
