"""Scheduler test harness.

Reference: scheduler/testing.go — Harness :43 wraps a real state store with a
fake Planner whose SubmitPlan applies the plan directly (:83), bypassing the
plan queue/applier; RejectPlan :18 forces the refresh path. This is the
primary TDD loop for both the host oracle and the TPU solver (differential
testing runs both against identical states).
"""

from __future__ import annotations

import itertools
import logging
from typing import Optional

from ..state import StateStore
from ..structs import Evaluation, Plan, PlanResult
from ..scheduler import new_scheduler

logger = logging.getLogger("nomad_tpu.harness")


class Harness:
    def __init__(self, state: Optional[StateStore] = None) -> None:
        self.state = state or StateStore()
        self._index = itertools.count(1000)
        self.plans: list[Plan] = []
        self.evals: list[Evaluation] = []  # evals created by the scheduler
        self.updates: list[Evaluation] = []  # eval status updates
        self.optimize_plan = False

    # -- Planner interface --------------------------------------------

    def next_index(self) -> int:
        return next(self._index)

    def submit_plan(self, plan: Plan):
        self.plans.append(plan)
        index = self.next_index()
        result = PlanResult(
            node_update=plan.node_update,
            node_allocation=plan.node_allocation,
            node_preemptions=plan.node_preemptions,
            deployment=plan.deployment,
            deployment_updates=plan.deployment_updates,
            alloc_index=index,
            alloc_batches=plan.alloc_batches,
        )
        self.state.upsert_plan_results(index, result)
        return result, None

    def update_eval(self, eval_obj: Evaluation) -> None:
        self.updates.append(eval_obj)

    def create_eval(self, eval_obj: Evaluation) -> None:
        self.evals.append(eval_obj)
        self.state.upsert_evals(self.next_index(), [eval_obj])

    def refresh_state(self, min_index: int):
        return self.state.snapshot()

    # -- driving ------------------------------------------------------

    def snapshot(self):
        return self.state.snapshot()

    def process(self, scheduler_name: str, eval_obj: Evaluation, config=None):
        """Run one scheduler pass for the eval against current state."""
        sched = new_scheduler(
            scheduler_name, logger, self.state.snapshot(), self, config
        )
        sched.process(eval_obj)
        return sched


class RejectPlanHarness(Harness):
    """Planner that rejects every plan, forcing state refresh + retry
    (reference: scheduler/testing.go RejectPlan :18)."""

    def submit_plan(self, plan: Plan):
        self.plans.append(plan)
        result = PlanResult(refresh_index=self.state.latest_index())
        return result, self.state.snapshot()


def build_cluster(n_nodes: int, n_jobs: int, count: int, constrained: bool,
                  job_prefix: str = "job", cpu: int = 250, mem: int = 128):
    """A :class:`Harness` holding ``n_nodes`` mock nodes (4,000 MHz /
    8,192 MB, dealt round-robin over 4 datacenters) and ``n_jobs`` mock
    jobs of one group of ``count`` asking ``cpu`` MHz / ``mem`` MB;
    ``constrained`` adds the c2m job's kernel-name constraint and
    datacenter spread. Returns ``(harness, jobs)``."""
    from .. import mock
    from ..gctune import paused_gc
    from ..structs import Constraint, Spread
    from ..structs.node_class import compute_node_class

    dcs = ["dc1", "dc2", "dc3", "dc4"]
    # One bounded allocation burst (the nodes + the job set), frozen on
    # exit: the built cluster IS resident heap, so it goes straight to
    # the permanent generation instead of being young-gen-scanned (with
    # every gc callback, jax's included) at the first post-build
    # collection (gctune.paused_gc).
    with paused_gc(freeze_on_exit=True):
        h = Harness()
        for i in range(n_nodes):
            n = mock.node()
            n.datacenter = dcs[i % len(dcs)]
            n.resources.cpu = 4000
            n.resources.memory_mb = 8192
            n.computed_class = compute_node_class(n)
            h.state.upsert_node(h.next_index(), n)
        jobs = []
        for j in range(n_jobs):
            job = mock.job(id=f"{job_prefix}-{j}")
            job.datacenters = dcs
            tg = job.task_groups[0]
            tg.count = count
            tg.tasks[0].resources.cpu = cpu
            tg.tasks[0].resources.memory_mb = mem
            tg.tasks[0].resources.networks = []
            if constrained:
                job.constraints.append(
                    Constraint("${attr.kernel.name}", "linux", "=")
                )
                job.spreads = [
                    Spread(attribute="${node.datacenter}", weight=50)
                ]
            h.state.upsert_job(h.next_index(), job)
            jobs.append(job)
    return h, jobs
