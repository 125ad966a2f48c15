"""TPU-backed schedulers, registered through the standard factory seam.

Reference seam: scheduler/scheduler.go BuiltinSchedulers :23 — the TPU
backend plugs in as an alternate implementation of the same
Scheduler/State/Planner contract, so Raft, plan application, and rejection
semantics stay untouched (BASELINE.json north star).

Two operating modes:
  * TPUGenericScheduler / TPUBatchScheduler — drop-in single-eval
    processing (the worker calls process(eval) exactly like the host
    scheduler); the solver batch is just that one eval's groups.
  * solve_eval_batch() — the high-throughput path: many pending evals
    solved in ONE kernel invocation, emitting one plan per eval. The
    server's TPU worker drives this.
"""

from __future__ import annotations

import logging
from typing import Optional

from ... import solverobs, trace
from ...structs import Evaluation, Plan
from ...structs.structs import (
    DEPLOYMENT_STATUS_FAILED,
    EVAL_STATUS_COMPLETE,
    EVAL_STATUS_FAILED,
)
from ...gctune import paused_gc
from ..context import SchedulerConfig
from ..generic import BLOCKED_EVAL_FAILED_PLACEMENTS, GenericScheduler
from ..reconcile import AllocReconciler
from ..util import (
    SchedulerRetryError,
    retry_max,
    tainted_nodes,
    update_non_terminal_allocs_to_lost,
)
from .solver import BatchSolver, GroupAsk

logger = logging.getLogger("nomad_tpu.scheduler.tpu")


def _mesh_for(config: SchedulerConfig, solve_fn):
    """The configured SolverMesh, or None. Only the default kernel path
    shards (an explicit solve_fn brings its own topology); meshes are
    process-cached so every solver shares the compiled kernels.
    mesh_devices=1 is honored as a real 1-device mesh — the sharded
    bench's scaling baseline runs the SAME kernel at every mesh size.
    A mesh wider than the backend raises (sharding.SolverMesh): the TPU
    worker validates the same configuration when it starts, so a served
    solve never gets here with one."""
    n = getattr(config, "mesh_devices", 0) or 0
    if solve_fn is None and n >= 1:
        from .sharding import solver_mesh

        return solver_mesh(n)
    return None


def _bucket_requests(job, place_requests):
    """Group placement requests into solver asks by (group, job version):
    requests carrying a job_override (canary-state downgrades) lower with
    THAT job's task group so old-version resources/constraints hold.

    Requests arrive in contiguous per-group runs (the reconciler emits
    each group's fill as one block), so grouping walks RUNS, not rows —
    one key computation per run instead of 10^5 dict ops per c2m eval.
    A reconcile-minted PlacementRun element (the shared-proto bulk fill)
    is a run BY CONSTRUCTION: when it is a bucket's only content it
    passes through whole, so the lowered group and the SoA fast-mint
    read its (count, names) without per-row request objects ever
    existing; a bucket mixing a run with plain rows (reschedules of the
    same group) materializes the run's rows, the pre-run shape. Output
    order (first-seen keys, original order within a key) is identical
    to the old per-row setdefault walk."""
    from ..reconcile import PlacementRun

    by_group: dict[tuple, list] = {}
    jobs: dict[tuple, object] = {}
    i, n = 0, len(place_requests)
    while i < n:
        req = place_requests[i]
        if isinstance(req, PlacementRun):
            proto = req.proto
            pjob = proto.job_override if proto.job_override is not None \
                else job
            key = (proto.task_group.name, pjob.version)
            by_group.setdefault(key, []).append(req)
            jobs[key] = pjob
            i += 1
            continue
        pjob = req.job_override if req.job_override is not None else job
        key = (req.task_group.name, pjob.version)
        j = i + 1
        tg0 = req.task_group
        ov0 = req.job_override
        while j < n:
            nxt = place_requests[j]
            # identity continuation: a run shares its TaskGroup and
            # override objects; equal-key runs split here re-merge below
            if (
                isinstance(nxt, PlacementRun)
                or nxt.task_group is not tg0
                or nxt.job_override is not ov0
            ):
                break
            j += 1
        by_group.setdefault(key, []).extend(place_requests[i:j])
        jobs[key] = pjob
        i = j
    out = []
    for key, pieces in by_group.items():
        if len(pieces) == 1 and isinstance(pieces[0], PlacementRun):
            reqs = pieces[0]  # pure run: pass the block through whole
        else:
            reqs = []
            for p in pieces:
                if isinstance(p, PlacementRun):
                    reqs.extend(p)  # mixed bucket: rows materialize
                else:
                    reqs.append(p)
        out.append((jobs[key], key[0], reqs))
    return out


class TPUGenericScheduler(GenericScheduler):
    """GenericScheduler with the placement loop replaced by a batched
    tensor solve. Reconciliation, stops, in-place updates, blocked-eval and
    retry semantics are inherited unchanged."""

    scheduler_type = "service"
    solve_fn = None  # overridable: e.g. a mesh-sharded solver
    solve_preempt_fn = None  # its preemption variant (sharded: make_sharded_solver_preempt)

    def _compute_job_allocs(self, job) -> bool:
        eval_obj = self.eval
        allocs = self.state.allocs_by_job(eval_obj.namespace, eval_obj.job_id)
        tainted = tainted_nodes(self.state, allocs)
        update_non_terminal_allocs_to_lost(self.plan, tainted, allocs)

        deployment = None
        if job is not None:
            deployment = self.state.latest_deployment_by_job(
                eval_obj.namespace, eval_obj.job_id
            )
            if deployment is not None and not deployment.active() and (
                deployment.status != DEPLOYMENT_STATUS_FAILED
            ):
                # failed deployments stay attached: they gate placements
                # and their canaries need cleanup (reconcile.py)
                deployment = None

        reconciler = AllocReconciler(
            job if job is not None else self._tombstone(eval_obj),
            eval_obj.job_id,
            allocs,
            tainted,
            eval_obj,
            deployment=deployment,
            batch=self.batch,
        )
        results = reconciler.compute()
        if eval_obj.annotate_plan:
            self._annotate_plan(results)
        self.followup_evals = results.followup_evals
        if results.deployment is not None:
            self.plan.deployment = results.deployment
        self.plan.deployment_updates = results.deployment_updates

        for alloc, desc, client_status in results.stop:
            self.plan.append_stopped_alloc(alloc, desc, client_status)
        for updated in results.inplace_update:
            self.plan.append_alloc(updated, updated.job)
        for alloc_id, eval_id in results.attr_updates.items():
            existing = self.state.alloc_by_id(alloc_id)
            if existing is not None:
                annotated = existing.copy()
                annotated.followup_eval_id = eval_id
                self.plan.append_alloc(annotated, annotated.job)

        place_requests = []
        for old, req in results.destructive_update:
            self.plan.append_stopped_alloc(
                old, "alloc not needed due to job update", ""
            )
            place_requests.append(req)
        place_requests.extend(results.place)

        if job is None or job.stopped():
            return True

        queued = {
            tg: s.place + s.destructive
            for tg, s in results.desired_tg_updates.items()
        }

        active_deployment = self.state.latest_deployment_by_job(job.namespace, job.id)
        if active_deployment is not None and (
            not active_deployment.active()
            or active_deployment.job_version != job.version
        ):
            active_deployment = None

        # --- the TPU departure: one batched solve instead of the loop ---
        solver = BatchSolver(
            self.state, self.config, solve_fn=self.solve_fn,
            solve_preempt_fn=self.solve_preempt_fn,
            mesh=_mesh_for(self.config, self.solve_fn),
        )
        asks = [
            GroupAsk(eval_obj, pjob, tg_name, reqs, plan=self.plan)
            for pjob, tg_name, reqs in _bucket_requests(job, place_requests)
        ]
        outcome = solver.solve(asks)

        for alloc in outcome.placements.get(eval_obj.id, []):
            tg = job.lookup_task_group(alloc.task_group)
            if self.plan.deployment is not None:
                if tg is not None and tg.update is not None:
                    alloc.deployment_id = self.plan.deployment.id
                    dstate = self.plan.deployment.task_groups.get(alloc.task_group)
                    if dstate is not None:
                        dstate.placed_allocs += 1
            elif job.type == "service" and active_deployment is not None:
                alloc.deployment_id = active_deployment.id
            if alloc.id not in outcome.pre_appended:
                # downgraded placements already carry their (old) job
                self.plan.append_fresh_alloc(alloc, alloc.job or job)
            queued[alloc.task_group] = max(0, queued.get(alloc.task_group, 0) - 1)
        for batch in outcome.batch_placements.get(eval_obj.id, []):
            # SoA placements: deployment stamping and queue accounting
            # are batch-level (one shared deployment_id column, one
            # count decrement) — no per-row objects exist yet
            tg = job.lookup_task_group(batch.task_group)
            if self.plan.deployment is not None:
                if tg is not None and tg.update is not None:
                    batch.deployment_id = self.plan.deployment.id
                    dstate = self.plan.deployment.task_groups.get(
                        batch.task_group
                    )
                    if dstate is not None:
                        dstate.placed_allocs += len(batch)
            elif job.type == "service" and active_deployment is not None:
                batch.deployment_id = active_deployment.id
            self.plan.append_placement_batch(batch)
            queued[batch.task_group] = max(
                0, queued.get(batch.task_group, 0) - len(batch)
            )
        for victim, by_id in outcome.preemptions.get(eval_obj.id, []):
            # a pre-appended preemptOR already carried its victims in
            if by_id not in outcome.pre_appended:
                self.plan.append_preempted_alloc(victim, by_id)

        self.failed_tg_allocs = outcome.failures.get(eval_obj.id, {})
        self.queued_allocs = queued
        self.eval.queued_allocations = queued
        return True

    @staticmethod
    def _tombstone(eval_obj):
        from ...structs import Job

        j = Job(id=eval_obj.job_id, namespace=eval_obj.namespace, stop=True)
        j.task_groups = []
        return j


class TPUBatchScheduler(TPUGenericScheduler):
    scheduler_type = "batch"


def solve_eval_batch(
    state,
    planner,
    evals: list[Evaluation],
    config: Optional[SchedulerConfig] = None,
    solve_fn=None,
    solve_preempt_fn=None,
    resident=None,
) -> dict[str, Plan]:
    """High-throughput path: reconcile every pending eval, solve ALL their
    placements in one kernel invocation, and emit one plan per eval.

    Per-job serialization is the caller's duty (the eval broker already
    guarantees one in-flight eval per job). `resident` — an optional
    ResidentClusterState reused across calls so steady-state solves skip
    the cap/used upload (solver.py)."""
    return solve_eval_batch_begin(
        state, planner, evals, config, solve_fn, solve_preempt_fn, resident
    ).finish()


class PendingEvalBatch:
    """Two-phase solve_eval_batch: begin() has reconciled every eval and
    dispatched the device kernel; finish() blocks on the device,
    materializes Allocations, and assembles the per-eval Plans. The
    pipelined TPU worker hands this across its solve→commit stage
    boundary so the device round-trip and plan materialization of batch
    N overlap batch N+1's reconcile/lower/dispatch."""

    def __init__(self, state, evals, plans, pending, config, solver,
                 asks=None) -> None:
        self.state = state
        self.evals = evals
        self.plans = plans
        self._pending = pending
        self.config = config
        self._solver = solver
        # the reconciled asks, kept for solve_host_fallback: a failed
        # device stage re-solves THESE (reconcile is not re-run, so
        # followup evals created during it are never duplicated)
        self._asks = asks or []
        self._finished = False

    @property
    def chain(self):
        """The UsageChain this batch's solve offers (solver.py): the
        NEXT in-flight batch chains on it to stay conflict-free while
        this one's commit is still pending (solver.py used_chain). Read
        live from the solver, not snapshotted at begin(): the
        spread-relaxation retry in finish() refreshes chain_out with its
        own placements, and a reference swap is atomic so a concurrent
        reader sees either consistent tuple."""
        return self._solver.chain_out

    @property
    def solved_in_begin(self) -> bool:
        """Did the solve complete in begin() (host stack, microsolve,
        a sticky partition, nothing to place)? Such a batch has no
        kernel in flight: finish() has nothing to block on. It offers a
        chain all the same, and the next batch chains on it."""
        return self._pending.solved_in_begin

    @property
    def used_micro(self) -> bool:
        """Did this solve run the host microsolve kernel? (zero device
        round-trip; the worker's lane telemetry reads it)."""
        return self._solver.used_micro

    @property
    def chain_accepted(self) -> bool:
        """Did this solve actually consume the used_chain it was given?
        False when the host path ran, resident tensors won, or the chain
        was rejected on a node-universe/shape mismatch — in those cases
        the solve saw only committed state and a failed parent commit
        does not invalidate it."""
        return self._solver.chain_accepted

    def finish(self) -> dict[str, Plan]:
        # Idempotent at THIS layer too: PendingSolve caches its outcome,
        # but re-running _attach_outcome would append every placement and
        # preemption to the plans a second time.
        if not self._finished:
            outcome = self._pending.finish()
            with paused_gc(), trace.span(
                trace.current(), "plan.assemble", cpu=True
            ):
                _attach_outcome(self.state, self.evals, self.plans, outcome)
            self._finished = True
        return self.plans

    def solve_host_fallback(self) -> dict[str, Plan]:
        """Re-solve this batch's asks entirely on the host oracle after
        a retriable device-stage failure (worker.py device failover).

        Reuses the reconcile output verbatim — the plans' stop/update
        halves and any followup evals already created stay as they are;
        only the placement solve re-runs, with small_batch_threshold
        forced past the batch size so no device dispatch can recur. The
        fresh solver exposes no chain (chain_out None, chain_accepted
        False): the worker marks the batch's chain verdict failed so a
        chained child re-solves against committed state.

        Deliberately degraded semantics, both directions of the chain:
        any used_chain THIS solve consumed is dropped too (the host
        oracle has no device tensor to chain on), so the fallback sees
        only committed state and may double-book nodes the still-
        uncommitted parent batch filled — the plan applier's optimistic
        verification trims those and the evals retry. A custom solve_fn
        is likewise not reused: the fallback's whole point is to avoid
        the failing device path, and the host oracle is the common-
        denominator semantics every kernel is differentially tested
        against."""
        if self._finished:
            return self.plans
        import copy

        cfg = copy.copy(self.config)
        cfg.small_batch_threshold = 1 << 62
        solver = BatchSolver(self.state, cfg)
        with paused_gc():
            outcome = solver.solve(self._asks)
            with trace.span(trace.current(), "plan.assemble", cpu=True):
                _attach_outcome(self.state, self.evals, self.plans, outcome)
        self._solver = solver
        self._finished = True
        return self.plans


def solve_eval_batch_begin(
    state,
    planner,
    evals: list[Evaluation],
    config: Optional[SchedulerConfig] = None,
    solve_fn=None,
    solve_preempt_fn=None,
    resident=None,
    used_chain=None,
    extra_usage=None,
) -> PendingEvalBatch:
    """Phase A of solve_eval_batch: reconcile + lower + async device
    dispatch. Returns a PendingEvalBatch; call finish() for the plans.
    used_chain — the previous (still-uncommitted) batch's
    PendingEvalBatch.chain, so this solve sees its placements.
    extra_usage — per-node (cpu, mem, disk) usage deltas beyond the
    snapshot (the worker's interactive-lane ledger), counted by the
    aggregate fast path so a chained solve stays conflict-free with
    lane placements the chain tensor never saw."""
    config = config or SchedulerConfig()
    with paused_gc():
        with trace.span(trace.current(), "reconcile", cpu=True):
            plans, asks = _reconcile_eval_batch(
                state, planner, evals, config
            )
        # asks-per-batch telemetry: how much work one solver dispatch
        # carries (occupancy's numerator lives solver-side; this is the
        # demand side the broker drained into the batch)
        solverobs.note_asks(
            len(asks), sum(len(a.requests) for a in asks)
        )
        solver = BatchSolver(
            state, config, solve_fn=solve_fn,
            solve_preempt_fn=solve_preempt_fn, resident=resident,
            used_chain=used_chain, mesh=_mesh_for(config, solve_fn),
            extra_usage=extra_usage,
        )
        pending = solver.solve_begin(asks)
    return PendingEvalBatch(
        state, evals, plans, pending, config, solver, asks=asks
    )


def _reconcile_eval_batch(
    state,
    planner,
    evals: list[Evaluation],
    config: SchedulerConfig,
) -> tuple[dict[str, Plan], list[GroupAsk]]:
    plans: dict[str, Plan] = {}
    asks: list[GroupAsk] = []
    deployments: dict[str, object] = {}
    for ev in evals:
        job = state.job_by_id(ev.namespace, ev.job_id)
        plan = ev.make_plan(job)
        plans[ev.id] = plan
        allocs = state.allocs_by_job(ev.namespace, ev.job_id)
        tainted = tainted_nodes(state, allocs)
        update_non_terminal_allocs_to_lost(plan, tainted, allocs)
        if job is None or job.stopped():
            for a in allocs:
                if not a.terminal_status():
                    plan.append_stopped_alloc(a, "alloc not needed", "")
            continue
        deployment = state.latest_deployment_by_job(ev.namespace, ev.job_id)
        if deployment is not None and not deployment.active() and (
            deployment.status != DEPLOYMENT_STATUS_FAILED
        ):
            deployment = None
        reconciler = AllocReconciler(
            job,
            ev.job_id,
            allocs,
            tainted,
            ev,
            deployment=deployment,
            batch=(ev.type == "batch"),
        )
        results = reconciler.compute()
        for fe in results.followup_evals:
            planner.create_eval(fe)
        if results.deployment is not None:
            plan.deployment = results.deployment
            deployments[ev.id] = results.deployment
        plan.deployment_updates = results.deployment_updates
        for alloc, desc, client_status in results.stop:
            plan.append_stopped_alloc(alloc, desc, client_status)
        for updated in results.inplace_update:
            plan.append_alloc(updated, updated.job)
        for alloc_id, follow_id in results.attr_updates.items():
            existing = state.alloc_by_id(alloc_id)
            if existing is not None:
                annotated = existing.copy()
                annotated.followup_eval_id = follow_id
                plan.append_alloc(annotated, annotated.job)
        place_requests = []
        for old, req in results.destructive_update:
            plan.append_stopped_alloc(old, "alloc not needed due to job update", "")
            place_requests.append(req)
        place_requests.extend(results.place)
        for pjob, tg_name, reqs in _bucket_requests(job, place_requests):
            asks.append(GroupAsk(ev, pjob, tg_name, reqs, plan=plan))
    return plans, asks


def _attach_outcome(
    state, evals: list[Evaluation], plans: dict[str, Plan], outcome
) -> None:
    """Fold a SolveOutcome back into the per-eval plans (phase B)."""
    for ev in evals:
        plan = plans[ev.id]
        job = state.job_by_id(ev.namespace, ev.job_id)
        deployment = plan.deployment or (
            state.latest_deployment_by_job(ev.namespace, ev.job_id)
            if job is not None
            else None
        )
        if deployment is not None and job is not None and (
            not getattr(deployment, "active", lambda: False)()
            or deployment.job_version != job.version
        ):
            deployment = None
        for alloc in outcome.placements.get(ev.id, []):
            if deployment is not None and job is not None and job.type == "service":
                tg = job.lookup_task_group(alloc.task_group)
                if tg is not None and tg.update is not None:
                    alloc.deployment_id = deployment.id
                    dstate = deployment.task_groups.get(alloc.task_group)
                    if dstate is not None and deployment is plan.deployment:
                        dstate.placed_allocs += 1
            if alloc.id not in outcome.pre_appended:
                # downgraded placements already carry their (old) job
                plan.append_fresh_alloc(alloc, alloc.job or job)
        for batch in outcome.batch_placements.get(ev.id, []):
            # SoA plan assembly: one append per batch; deployment id is
            # the shared column, placed-alloc accounting one increment
            if deployment is not None and job is not None and job.type == "service":
                tg = job.lookup_task_group(batch.task_group)
                if tg is not None and tg.update is not None:
                    batch.deployment_id = deployment.id
                    dstate = deployment.task_groups.get(batch.task_group)
                    if dstate is not None and deployment is plan.deployment:
                        dstate.placed_allocs += len(batch)
            plan.append_placement_batch(batch)
        for victim, by_id in outcome.preemptions.get(ev.id, []):
            # a pre-appended preemptOR already carried its victims in
            if by_id not in outcome.pre_appended:
                plan.append_preempted_alloc(victim, by_id)
        ev.failed_tg_allocs = outcome.failures.get(ev.id, {})
