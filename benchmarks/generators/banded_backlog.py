"""`prod-backlog`: a backlog of production services landed at once on a
cell that batch and best-effort work has filled.

`mixed_backlog`'s window — every job registered back to back over
`PUT /v1/jobs` from `submitters` threads released on one barrier, bodies
encoded beforehand, then the window watches the backlog drain — with the
jobs of `periods` x `period`, a list of [ask, allocs]. Every job is of
the band `band`: its class is `<band>-<ask>`, and type and priority are
that class's, not the mix's. What the free room does not hold is placed
by evicting the bands underneath, whose jobs get follow-up evals that
ride the same batches.

What set-up has to warm follows from the program. A batch that holds one
eval that may preempt takes the preempt solve, one program for each group
rung (`kernels.preempt_programs()`); a batch of follow-up evals alone,
which may not, takes the compact solve (`kernels.compact_programs()`).
`shapes()` lists one dry batch for every program of either list that
this mix can reach.
"""

from __future__ import annotations

import random
import threading
import time

from benchmarks.harness import jobs, spec

BATCH_EVALS = 64  # the worker drains at most so many evals into a solve
JOBS_OF = 1000  # a standing job's allocs: the most a follow-up eval asks


def _class(params: dict, ask: str) -> str:
    return f"{params['band']}-{ask}"


def deal(seed: int, params: dict) -> list[tuple[str, int]]:
    """(job class, allocs) of every job of the window, in order."""
    rng = random.Random(seed ^ 0xBA2D)
    out = []
    for _ in range(int(params["periods"])):
        period = [(_class(params, ask), int(count))
                  for ask, count in params["period"]]
        rng.shuffle(period)
        out += period
    return out


def _under(rungs, rung: int) -> int:
    return max((r for r in rungs if r < rung), default=0)


def _plain_and_spread(classes: dict, band: str) -> tuple[str, str | None]:
    """Of one band: a class with no spread and one constraint, and the
    spread one (one group a datacenter)."""
    own = {n: c for n, c in classes.items() if c.get("band") == band}
    plain = next(n for n, c in own.items()
                 if not c.get("spread") and len(c["constraints"]) <= 1)
    return plain, next((n for n, c in own.items() if c.get("spread")), None)


def _batch_for(gp: int, g_rungs, count: int, plain: str, spread: str | None,
               dcs: int) -> dict | None:
    """A single-class batch whose groups land on the rung `gp`: as many
    evals as fill it, or past what a batch holds, of the spread class,
    one group a datacenter."""
    under_g = _under(g_rungs, gp)
    if under_g < BATCH_EVALS:
        return {"evals": min(gp, BATCH_EVALS), "count": count,
                "job_class": plain}
    if spread is None or under_g >= BATCH_EVALS * dcs:
        return None  # no batch of this mix holds so many groups
    return {"evals": -(-(under_g + 1) // dcs), "count": dcs * count,
            "job_class": spread}


def shapes(params: dict, config: dict) -> list[dict]:
    """One dry batch for every program the window can reach: of the
    window's band for each group rung of the preempt solve, and of the
    lowest band for each (group rung, readback rung) of the compact
    solve that a batch of follow-up evals — at most `JOBS_OF` allocs
    each — lands on."""
    classes = spec.job_classes(config)
    dcs = len(config["datacenters"])
    lowest = min(classes.values(), key=lambda c: c["priority"])["band"]
    try:
        from nomad_tpu.scheduler.context import SchedulerConfig
        from nomad_tpu.scheduler.tpu.kernels import (
            compact_programs, preempt_programs)
    except ImportError:
        # a program from before the preempt solve's closed set lists
        # none: warm one batch of each class of the mix at its largest
        # count, and the window says what that program does with a mix
        top: dict[str, int] = {}
        for jc, count in deal(0, params):
            top[jc] = max(top.get(jc, 0), count)
        return [{"evals": 1, "count": c, "job_class": jc}
                for jc, c in top.items()]
    out = []
    small = SchedulerConfig().small_batch_threshold
    g_rungs = sorted({gp for gp, _ in preempt_programs()})
    plain, spread = _plain_and_spread(classes, params["band"])
    for gp in g_rungs:
        # a few allocs a job: the program follows from the groups alone;
        # past the small-batch bound all the same, for a program that
        # sends a small batch to the host stack though it may preempt
        batch = _batch_for(gp, g_rungs, -(-(small + 1) // min(
            gp, BATCH_EVALS)), plain, spread, dcs)
        if batch is not None:
            out.append(batch)
    programs = compact_programs()
    g_rungs = sorted({gp for gp, _ in programs})
    c_rungs = sorted({maxc for _, maxc in programs})
    plain, spread = _plain_and_spread(classes, lowest)
    for gp, maxc in programs:
        under_c = _under(c_rungs, maxc)
        if under_c >= JOBS_OF:
            continue  # no follow-up eval asks so many
        # the rung's least count but on the lowest rung, whose least is
        # one alloc: a batch of a few allocs never reaches the kernel
        batch = _batch_for(gp, g_rungs, under_c + 1 if under_c else maxc,
                           plain, spread, dcs)
        if batch is not None:
            out.append(batch)
    return out


def warm_jobs(params: dict) -> list[tuple]:
    """One real deploy of each ask of the window's band before the
    window, evictions and follow-up evals included; before them, `fill`
    (a rehearsal's: [job class, allocs, priority] of the lower bands,
    which the tiny fleet's standing load cannot scale down to)."""
    return [(int(count), jc, int(priority))
            for jc, count, priority in params.get("fill", ())] \
        + [(int(count), _class(params, ask), None)
           for ask, count in params["warm"]]


def run(ctx) -> None:
    subs = int(ctx.params["submitters"])
    prepared = []
    for i, (job_class, count) in enumerate(deal(ctx.seed, ctx.params)):
        job = jobs.make_job(ctx.config, f"prod-{ctx.seed}-{i}", count,
                            None, job_class)
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
    # the window watches the backlog drain: to its end, or to the last
    # alloc visible, whichever comes first
    for op, _ in prepared:
        if op.acked:
            op.watch.done.wait(max(0.0, ctx.t_end - time.monotonic()))
