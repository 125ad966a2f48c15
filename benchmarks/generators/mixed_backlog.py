"""`mixed-backlog`: a backlog of jobs that differ, landed at once.

`backlog`'s window — every job registered back to back over
`PUT /v1/jobs` from `submitters` threads released on one barrier, bodies
encoded beforehand, then the window watches the backlog drain — with the
jobs of `periods` x `period`: a list of [job class, allocs], dealt over
the configuration's job classes. Every seed registers the same multiset;
the seed shuffles each period, so any forty jobs in a row hold one of
everything and a drained batch is some mix of classes, counts, a
constrained job and a spread one.

What set-up has to warm follows from the program, not from the mix: the
compact solve compiles one program for each (group bucket, instance
bucket) of `kernels.compact_programs()`, and a dry solve is ONE class at
ONE count (`harness/warm.py`), so `shapes()` lists, for every program of
that list this mix can reach, one single-class batch that lands in it.
"""

from __future__ import annotations

import random
import threading
import time

from benchmarks.harness import jobs, spec

BATCH_EVALS = 64  # the worker drains at most so many evals into a solve


def deal(seed: int, params: dict) -> list[tuple[str, int]]:
    """(job class, allocs) of every job of the window, in order."""
    rng = random.Random(seed ^ 0xB0C6)
    out = []
    for _ in range(int(params["periods"])):
        period = [(jc, int(count)) for jc, count in params["period"]]
        rng.shuffle(period)
        out += period
    return out


def _reach(params: dict, config: dict) -> tuple[int, int, int]:
    """The most evals and groups one drained batch of this mix can hold,
    and its largest group: a spread job is one group a datacenter."""
    classes = spec.job_classes(config)
    dcs = len(config["datacenters"])
    backlog = deal(0, params)
    evals = min(len(backlog), BATCH_EVALS)
    spread = sum(1 for jc, _ in backlog if classes[jc].get("spread"))
    groups = evals + (dcs - 1) * min(evals, spread)
    largest = max(-(-count // dcs) if classes[jc].get("spread") else count
                  for jc, count in backlog)
    return evals, groups, largest


def shapes(params: dict, config: dict) -> list[dict]:
    """One single-class batch for every program of the compact solve
    that this mix can reach. A program (gp, maxc) is reached when the
    mix can fill more groups than the rung under gp and a group larger
    than the rung under maxc; the batch that lands in it is as many
    evals as fill gp, or all a batch holds (past that, of the spread
    class, four groups an eval: the fewest that pass the rung under
    gp), at the least count past the rung under maxc."""
    classes = spec.job_classes(config)
    plain = next(n for n, c in classes.items()
                 if not c.get("spread") and len(c["constraints"]) <= 1)
    spread = next((n for n, c in classes.items() if c.get("spread")), None)
    dcs = len(config["datacenters"])
    evals, groups, largest = _reach(params, config)
    try:
        from nomad_tpu.scheduler.tpu.kernels import compact_programs
    except ImportError:
        # a program from before the closed set lists none: warm one
        # batch of each class of the mix at its largest count, and the
        # window says what that program does with a mix
        top: dict[str, int] = {}
        for jc, count in deal(0, params):
            top[jc] = max(top.get(jc, 0), count)
        return [{"evals": 1, "count": c, "job_class": jc}
                for jc, c in top.items()]
    programs = compact_programs()
    out = []
    for gp, maxc in programs:
        under_g = max((g for g, _ in programs if g < gp), default=0)
        under_c = max((c for _, c in programs if c < maxc), default=0)
        if groups <= under_g or largest <= under_c:
            continue  # no batch of this mix lands there
        # the rung's least count but on the lowest rung, whose least is
        # one alloc: a batch of a few allocs never reaches the kernel
        count = under_c + 1 if under_c else min(maxc, largest)
        if under_g < evals:
            out.append({"evals": min(gp, evals), "count": count,
                        "job_class": plain})
        else:  # more groups than a batch holds evals: four an eval
            out.append({"evals": -(-(under_g + 1) // dcs),
                        "count": dcs * count, "job_class": spread})
    return out


def warm_jobs(params: dict) -> list[tuple]:
    """One real deploy of each class before the window, the constrained
    and the spread one among them."""
    return [(int(count), jc, int(params["priority"]))
            for jc, count in params["warm"]]


def run(ctx) -> None:
    subs = int(ctx.params["submitters"])
    prepared = []
    for i, (job_class, count) in enumerate(deal(ctx.seed, ctx.params)):
        job = jobs.make_job(ctx.config, f"mixed-{ctx.seed}-{i}", count,
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
    # the window watches the backlog drain: to its end, or to the last
    # alloc visible, whichever comes first
    for op, _ in prepared:
        if op.acked:
            op.watch.done.wait(max(0.0, ctx.t_end - time.monotonic()))
