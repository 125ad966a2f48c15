"""Warming the shapes a cell's own traffic can reach, and no others.

Two steps, both counted as set-up.

1. A dry solve for every batch shape the generator lists (`shapes()`:
   how many evals a drained batch can hold, at which count, and where
   the generator sends another than the default job, of which
   `job_class` and `priority`). It runs the
   worker's own solve entry (`solve_eval_batch_begin` with the worker's
   resident state and configuration, then `finish`) on jobs that exist
   only in an overlay over a snapshot, and throws the plans away:
   nothing is committed, the store is untouched. Which kernel signature
   and which eager slices a shape needs is the program's business — the
   warm-up only names batch shapes, so a program with coarser buckets
   warms fewer programs through the same code. A solve chained on the
   one in flight before it (the pipelined worker's case) takes the same
   programs: its usage tensor is a jit's output like the resident one.
2. A few real deploys through the front door (`warm_jobs()`), which stay
   placed: nothing on the served path runs for the first time inside the
   window.

The row scatter of the resident usage tensor compiles once per bucket of
changed rows (solver.py `_pad_scatter_args`: powers of two from 1,024);
`scatter_buckets` runs a no-op scatter (every index out of range, so
dropped) through the program's own `_scatter_rows` for each bucket a
commit of this cell can dirty.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import jobs


class _Overlay:
    """A snapshot plus jobs that exist nowhere else."""

    def __init__(self, snapshot, extra_jobs: dict) -> None:
        self._snap = snapshot
        self._extra = extra_jobs

    def __getattr__(self, name):
        return getattr(self._snap, name)

    def job_by_id(self, namespace: str, job_id: str):
        job = self._extra.get(job_id)
        return job if job is not None else self._snap.job_by_id(
            namespace, job_id)


class _NullPlanner:
    """A dry solve creates no eval and submits no plan."""

    def create_eval(self, ev) -> None:
        pass


def dry_solves(server, config: dict, shapes: list[dict]) -> int:
    """Solve every shape without committing. Returns how many."""
    from nomad_tpu import mock
    from nomad_tpu.scheduler.tpu import solve_eval_batch_begin

    worker = server.tpu_worker
    worker.prepare()
    snapshot = server.state.snapshot()
    done = 0
    serial = 0
    for shape in shapes:
        batch = {}
        for _ in range(int(shape["evals"])):
            serial += 1
            job = jobs.make_job(config, f"warm-dry-{serial}",
                                int(shape["count"]), shape.get("priority"),
                                shape.get("job_class"))
            batch[job.id] = job
        evals = [mock.eval_for_job(j) for j in batch.values()]
        solve_eval_batch_begin(
            _Overlay(snapshot, batch), _NullPlanner(), evals,
            worker.config, resident=worker._resident,
        ).finish()
        done += 1
    return done


def scatter_buckets(server, max_rows: int) -> list[int]:
    """Compile the resident row scatter for every bucket up to the one
    that holds `max_rows` changed rows."""
    from nomad_tpu.scheduler.tpu import solver as solver_mod

    resident = server.tpu_worker._resident
    used = getattr(resident, "_used_dev", None)
    if used is None:
        return []
    buckets, b = [], 1024
    while True:
        buckets.append(b)
        if b >= max_rows:
            break
        b *= 2
    for b in buckets:
        idx = np.full(b, 1 << 30, dtype=np.int32)
        rows = np.zeros((b, 3), dtype=np.int32)
        used = solver_mod._scatter_rows(used, idx, rows)  # donated
        solver_mod._scatter_rows(used, idx, rows, donate=False)
    resident._used_dev = used
    return buckets
