"""`deploys-c4`: `deploys`' closed loop with several operators in step.

The window is `closed_loop`'s — each operator sends `PUT /v1/jobs` for a
new one-group service job, waits until every alloc of it is visible on
its node's watch, and sends the next, the sizes a pure function of the
seed — with `operators` of them. Their deploys arrive together, so the
worker's drain coalesces a few into one batch, and a batch is solved
while the one before it is still committing.

What set-up has to warm follows from the program: a drained batch holds
at most one eval of each operator, and a partial commit retries the
count it lost, which may be any count up to the largest. So a batch
lands in any program of `kernels.compact_programs()` whose group rung
the operators' groups reach and whose instance rung the largest deploy
reaches, and `shapes()` lists one dry batch for each, derived as
`mixed_backlog.shapes()` does; and one batch of as many small deploys
as there are operators, which the host paths take.
"""

from __future__ import annotations

from pathlib import Path

from benchmarks.harness import spec

closed_loop = spec.load_module(
    "generators", "closed_loop", Path(__file__).resolve().parents[1])

sizes = closed_loop.sizes
warm_jobs = closed_loop.warm_jobs
run = closed_loop.run


def _reach(params: dict, config: dict) -> tuple[int, int, int]:
    """The groups a job splits into (one a datacenter where it spreads),
    the most groups one drained batch can hold (an eval of each
    operator) and the largest group (a retried partial count is never
    larger)."""
    job = spec.job_class(config)
    per_job = len(config["datacenters"]) if job.get("spread") else 1
    largest = max(int(c) for c in
                  list(params["small_counts"]) + list(params["rollout_counts"]))
    return per_job, int(params["operators"]) * per_job, -(-largest // per_job)


def shapes(params: dict, config: dict) -> list[dict]:
    """One dry batch for every program of the compact solve that a
    batch of the operators' deploys, or a retried partial count, can
    reach: as many evals as pass the rung under its group rung, at the
    least count past the rung under its instance rung. Then one batch of
    a small deploy from every operator."""
    from nomad_tpu.scheduler.tpu.kernels import compact_programs

    per_job, groups, largest = _reach(params, config)
    programs = compact_programs()
    out = []
    for gp, maxc in programs:
        under_g = max((g for g, _ in programs if g < gp), default=0)
        under_c = max((c for _, c in programs if c < maxc), default=0)
        if groups <= under_g or largest <= under_c:
            continue  # no batch of these deploys lands there
        count = under_c + 1 if under_c else min(maxc, largest)
        out.append({"evals": -(-(under_g + 1) // per_job),
                    "count": per_job * count})
    out.append({"evals": int(params["operators"]),
                "count": min(params["small_counts"])})
    return out
