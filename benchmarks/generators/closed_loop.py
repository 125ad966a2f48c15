"""`deploys`: a closed loop of operators with no think time.

Each operator sends `PUT /v1/jobs` for a new one-group service job at
the configuration's ask, waits until every alloc of it is visible on its
node's watch, and sends the next. With one operator every batch the
worker drains holds one eval, so the solve path a deploy takes follows
from the job's own size and not from arrival jitter.

Sizes are a pure function of the seed. Every seed deals the same
multiset in another order: a super-period of len(small) x len(rollout)
periods holds every small count and every rollout count equally often;
each period of `period` deploys has `rollouts_per_period` rollouts at
positions the seed picks.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Iterator

from benchmarks.harness import jobs


def sizes(seed: int, params: dict) -> Iterator[tuple[int, bool]]:
    """(count, is_rollout) of the i-th deploy, for ever."""
    rng = random.Random(seed)
    period = int(params["period"])
    n_roll = int(params["rollouts_per_period"])
    smalls, rolls = params["small_counts"], params["rollout_counts"]
    periods = len(smalls) * len(rolls)
    while True:
        small = list(smalls) * (periods * (period - n_roll) // len(smalls))
        roll = list(rolls) * (periods * n_roll // len(rolls))
        rng.shuffle(small)
        rng.shuffle(roll)
        for _ in range(periods):
            at = set(rng.sample(range(period), n_roll))
            for pos in range(period):
                if pos in at:
                    yield roll.pop(), True
                else:
                    yield small.pop(), False


def shapes(params: dict, config: dict) -> list[dict]:
    """What set-up has to warm: a drained batch holds at most one eval
    of each operator, at any count."""
    counts = sorted(set(params["small_counts"]) | set(params["rollout_counts"]))
    return [{"evals": k, "count": c}
            for k in range(1, int(params["operators"]) + 1) for c in counts]


def warm_jobs(params: dict) -> list[int]:
    """Real deploys sent through the front door before the window: one of
    each kind, so that nothing is done for the first time inside it."""
    return [params["small_counts"][0], params["rollout_counts"][0]]


def run(ctx) -> None:
    threads = [
        threading.Thread(target=_operator, args=(ctx, k),
                         name=f"bench-operator-{k}")
        for k in range(int(ctx.params["operators"]))
    ]
    ctx.open_window()
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def _operator(ctx, k: int) -> None:
    gen = sizes(ctx.seed + 7919 * k, ctx.params)
    i = 0
    while time.monotonic() < ctx.t_end:
        count, rollout = next(gen)
        job = jobs.make_job(ctx.config, f"deploy-{ctx.seed}-{k}-{i}", count,
                            int(ctx.params["priority"]))
        body = jobs.encode(job)
        op = ctx.new_op(job.id, count, kind="rollout" if rollout else "small")
        ctx.send(op, body)
        if op.status // 100 == 2:
            # a deploy in flight when the window closes is waited for:
            # the deadline is the drain's hang detector, no latency limit
            ctx.await_visible(op)
        i += 1
