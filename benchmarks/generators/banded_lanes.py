"""`prod-lanes`: monitoring deploys on the interactive lane beside a
paced production roll-out.

Two streams over `PUT /v1/jobs`. The background is `prod-backlog`'s
period of production services (`banded_backlog`: [ask, allocs] a job,
every job of band `band`, each period shuffled by the seed), one period
every `period_s` seconds, its jobs dealt over `submitters` threads, from
the window's start to its end: a steady share of what the cell
sustains, not a backlog that drains and leaves a quiet cell. The lane is
ONE sender of monitoring deploys at `lane_per_s` a second over the
window, each `PUT` on a thread of its own: one job of one group of
`lane_counts` allocs (every count of the range equally often) of the
`lane_classes` in turn, every seed the same multiset in another order. Background jobs are op kind
"job"; the lane's are kind "lane", the only non-job operations, so
`e2e_p50_ms` is the lane's alone.

Set-up warms what `banded_backlog` warms for the background — the
preempt solve's programs and the compact solve's for the evicted jobs'
follow-up evals — and one lane solve of each monitoring class at the
largest count; `warm` deploys one real job of each, so the monitoring
band stands before the window.
"""

from __future__ import annotations

import random
import threading
import time
from pathlib import Path

from benchmarks.harness import jobs, spec

banded_backlog = spec.load_module(
    "generators", "banded_backlog", Path(__file__).resolve().parents[1])


def background(seed: int, params: dict, periods: int) -> list[tuple[str, int]]:
    """(job class, allocs) of the background's jobs, period by period."""
    return banded_backlog.deal(seed, {**params, "periods": periods})


def lane(seed: int, params: dict, n: int) -> list[tuple[str, int]]:
    """(job class, allocs) of the first `n` lane deploys."""
    lo, hi = params["lane_counts"]
    classes = params["lane_classes"]
    counts = range(int(lo), int(hi) + 1)
    out = [(classes[i % len(classes)], counts[i % len(counts)])
           for i in range(n)]
    random.Random(seed ^ 0x1A4E).shuffle(out)
    return out


def shapes(params: dict, config: dict) -> list[dict]:
    """`banded_backlog`'s dry batches, and one lane solve of each
    monitoring class at the largest count: a lane eval is solved alone,
    at its own priority (the preempt solve's `gp` 8 past the
    microsolve's bound)."""
    classes = spec.job_classes(config)
    top = int(params["lane_counts"][1])
    return banded_backlog.shapes({**params, "periods": 1}, config) + [
        {"evals": 1, "count": top, "job_class": jc,
         "priority": int(classes[jc]["priority"])}
        for jc in params["lane_classes"]]


def warm_jobs(params: dict) -> list[tuple]:
    """[job class, allocs, priority] of `fill` (a rehearsal's: its tiny
    fleet's bands) and then of `warm`: one real monitoring deploy of
    each class."""
    return [(int(count), jc, int(priority))
            for jc, count, priority in (*params.get("fill", ()),
                                        *params["warm"])]


def run(ctx) -> None:
    p = ctx.params
    subs = int(p["submitters"])
    period_s = float(p["period_s"])
    periods = max(1, int(-(-ctx.seconds // period_s)))
    per = len(p["period"])
    prepared = []
    for i, (job_class, count) in enumerate(
            background(ctx.seed, p, periods)):
        job = jobs.make_job(ctx.config, f"prod-{ctx.seed}-{i}", count,
                            None, job_class)
        prepared.append((ctx.new_op(job.id, count, "job", job_class),
                         jobs.encode(job)))
    rate = float(p["lane_per_s"])
    deploys = []
    for i, (job_class, count) in enumerate(
            lane(ctx.seed, p, max(1, int(ctx.seconds * rate)))):
        job = jobs.make_job(ctx.config, f"monitor-{ctx.seed}-{i}", count,
                            None, job_class)
        deploys.append((ctx.new_op(job.id, count, "lane", job_class),
                        jobs.encode(job)))

    # as `banded_backlog`: every sender is up and waiting when the window
    # opens, and all are released at once; each then keeps to its pace
    # from the window's start
    gate = threading.Barrier(subs + 2)

    def until(t: float) -> bool:
        """Sleep until `t`; False once the window has ended."""
        wait = t - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        return time.monotonic() < ctx.t_end

    def submit(k: int) -> None:
        try:
            gate.wait()
        except threading.BrokenBarrierError:
            return  # the window never opened
        for q in range(periods):
            if not until(ctx.t_open + q * period_s):
                return  # never sent: not attempted
            for op, body in prepared[q * per:(q + 1) * per][k::subs]:
                if time.monotonic() >= ctx.t_end:
                    return
                ctx.send(op, body)

    senders: list[threading.Thread] = []

    def lane_sender() -> None:
        try:
            gate.wait()
        except threading.BrokenBarrierError:
            return
        for i, (op, body) in enumerate(deploys):
            if not until(ctx.t_open + i / rate):
                return
            t = threading.Thread(target=ctx.send, args=(op, body),
                                 name=f"bench-submit-lane-{i}")
            t.start()
            senders.append(t)

    threads = [threading.Thread(target=submit, args=(k,),
                                name=f"bench-submit-{k}")
               for k in range(subs)]
    threads.append(threading.Thread(target=lane_sender,
                                    name="bench-submit-lane"))
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
    for t in senders:
        t.join()
    # the window is the streams' whole span: what is in flight at its
    # end is waited for after it
    until(ctx.t_end)
