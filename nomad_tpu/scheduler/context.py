"""Per-evaluation scheduling context and caches.

Reference: scheduler/context.go — EvalContext :76, ProposedAllocs :120,
EvalEligibility :190. The context carries the state snapshot, the plan being
built, per-eval regex/version caches, and the computed-class eligibility
memoization that lets feasibility run once per node class instead of once per
node. The TPU solver reuses EvalEligibility results when building the
feasibility-mask tensor.
"""

from __future__ import annotations

import re
from typing import Optional

from ..structs import Allocation, Plan
from ..structs.funcs import filter_terminal_allocs
from ..structs.node_class import escaped_constraint_target

# Eligibility states for (job/tg, class) pairs.
ELIGIBILITY_UNKNOWN = 0
ELIGIBILITY_ELIGIBLE = 1
ELIGIBILITY_INELIGIBLE = 2
ELIGIBILITY_ESCAPED = 3  # constraints reference unique attrs; no memoization


class SchedulerConfig:
    """Cluster-operator scheduler knobs (reference: structs/operator.go
    SchedulerConfiguration, applied at rank.go:164-170)."""

    def __init__(
        self,
        algorithm: str = "binpack",  # binpack | spread
        preemption_service: bool = True,
        preemption_batch: bool = False,
        preemption_system: bool = True,
        preemption_sysbatch: bool = False,
        memory_oversubscription: bool = False,
        backend: str = "host",  # host | tpu — which placement backend to use
        small_batch_threshold: int = 48,
        inject_device_latency_s: float = 0.0,
        soa_placements: Optional[bool] = None,
        mesh_devices: Optional[int] = None,
        micro_solve_threshold: int = 8192,
    ) -> None:
        import os

        # Host microsolve bound (the interactive fast path): a small
        # batch whose node-count x group-count product is at or below
        # this solves with the numpy compact kernel (scheduler/tpu/
        # microsolve.py) — dense-path semantics, zero device round-trip.
        # 0 disables (every small batch keeps the host iterator stack).
        self.micro_solve_threshold = micro_solve_threshold

        # Multi-chip: shard the solve's node axis over this many devices
        # (scheduler/tpu/sharding.py). 0 = single chip. The sharded
        # kernels are bit-identical to the single-chip solver, so every
        # other knob composes unchanged.
        if mesh_devices is None:
            mesh_devices = int(
                os.environ.get("NOMAD_TPU_MESH_DEVICES", "0") or 0
            )
        self.mesh_devices = mesh_devices

        # Struct-of-arrays placements (structs/placement_batch.py): the
        # solver's fast-mint path emits PlacementBatch columns instead of
        # per-row Allocation objects, materialized lazily at API/client
        # boundaries. Default ON; NOMAD_TPU_SOA=0 (or soa_placements=
        # False) keeps the eager-object path — the differential identity
        # battery's comparator.
        if soa_placements is None:
            soa_placements = os.environ.get("NOMAD_TPU_SOA", "1") != "0"
        self.soa_placements = soa_placements

        self.algorithm = algorithm
        self.preemption_service = preemption_service
        self.preemption_batch = preemption_batch
        self.preemption_system = preemption_system
        self.preemption_sysbatch = preemption_sysbatch
        self.memory_oversubscription = memory_oversubscription
        self.backend = backend
        # Batches asking for fewer total placements than this skip the
        # tensor solve: the device round-trip dominates tiny solves, so
        # they run the host iterator stack instead (VERDICT r3 #3 —
        # reference per-eval latency: scheduler/generic_sched.go:125).
        self.small_batch_threshold = small_batch_threshold
        # Simulated device round-trip added to every dense kernel solve
        # (docs/pipeline.md): a sleep model of a serially-busy device,
        # so the worker's solve/commit overlap can be exercised on
        # XLA:CPU (ROADMAP D1 removes it). No environment variable sets
        # it: two tests pass it, and benchmarks/ reads it to refuse it.
        self.inject_device_latency_s = inject_device_latency_s

    def preemption_enabled(self, scheduler_type: str) -> bool:
        return {
            "service": self.preemption_service,
            "batch": self.preemption_batch,
            "system": self.preemption_system,
            "sysbatch": self.preemption_sysbatch,
        }.get(scheduler_type, False)


class EvalEligibility:
    """Computed-class feasibility memo (reference: context.go:190)."""

    def __init__(self) -> None:
        self.job: dict[str, int] = {}  # class -> eligibility
        self.job_escaped = False
        self.tg: dict[str, dict[str, int]] = {}  # tg -> class -> eligibility
        self.tg_escaped: dict[str, bool] = {}
        self.quota_reached: str = ""

    def set_job(self, job) -> None:
        self.job_escaped = any(
            escaped_constraint_target(c.ltarget) for c in job.constraints
        )
        for tg in job.task_groups:
            escaped = any(escaped_constraint_target(c.ltarget) for c in tg.constraints)
            if not escaped:
                for task in tg.tasks:
                    if any(
                        escaped_constraint_target(c.ltarget) for c in task.constraints
                    ):
                        escaped = True
                        break
            self.tg_escaped[tg.name] = escaped

    def job_status(self, klass: str) -> int:
        if self.job_escaped or not klass:
            return ELIGIBILITY_ESCAPED
        return self.job.get(klass, ELIGIBILITY_UNKNOWN)

    def set_job_eligibility(self, eligible: bool, klass: str) -> None:
        self.job[klass] = ELIGIBILITY_ELIGIBLE if eligible else ELIGIBILITY_INELIGIBLE

    def task_group_status(self, tg: str, klass: str) -> int:
        if self.tg_escaped.get(tg, False) or not klass:
            return ELIGIBILITY_ESCAPED
        return self.tg.get(tg, {}).get(klass, ELIGIBILITY_UNKNOWN)

    def set_task_group_eligibility(self, eligible: bool, tg: str, klass: str) -> None:
        self.tg.setdefault(tg, {})[klass] = (
            ELIGIBILITY_ELIGIBLE if eligible else ELIGIBILITY_INELIGIBLE
        )

    def has_escaped(self) -> bool:
        return self.job_escaped or any(self.tg_escaped.values())

    def get_classes(self) -> dict[str, bool]:
        """class -> eligible, for blocked-eval unblocking. Task-group
        verdicts outrank the job-level ones: a class that passed job
        constraints but failed every group's is NOT eligible
        (reference: context.go GetClasses)."""
        out: dict[str, bool] = {}
        for tg_classes in self.tg.values():
            for klass, status in tg_classes.items():
                if status == ELIGIBILITY_ELIGIBLE:
                    out[klass] = True
        for tg_classes in self.tg.values():
            for klass, status in tg_classes.items():
                if status == ELIGIBILITY_INELIGIBLE:
                    out.setdefault(klass, False)
        for klass, status in self.job.items():
            out.setdefault(klass, status == ELIGIBILITY_ELIGIBLE)
        return out


class EvalContext:
    """Everything one evaluation's scheduling pass needs."""

    def __init__(self, state, plan: Optional[Plan] = None, logger=None,
                 scheduler_config: Optional[SchedulerConfig] = None,
                 extra_plans: Optional[list] = None,
                 extra_usage=None) -> None:
        self.state = state  # StateSnapshot
        self.plan = plan
        # Other in-flight plans of the SAME batch solve (the small-batch
        # host path): proposed-alloc accounting must see their placements
        # or two evals in one batch double-book a node — the dense path
        # coordinates through its shared caches instead.
        self.extra_plans = extra_plans or []
        # Usage on a node beyond the snapshot and every plan, as
        # `.get(node_id)` -> (cpu, mem, disk) or None: what a batch still
        # in flight placed (the batch solver's host stack, beside it).
        # Held, never a preemption candidate.
        self.extra_usage = extra_usage
        self.logger = logger
        self.scheduler_config = scheduler_config or SchedulerConfig()
        self._regex_cache: dict[str, re.Pattern] = {}
        self._version_cache: dict[str, object] = {}
        self.eligibility = EvalEligibility()
        self.metrics_nodes_evaluated = 0
        # binpack rankings that found a node exhausted from the store's
        # usage total, without building its proposed allocs
        self.exhausted_by_usage = 0

    def set_plan(self, plan: Plan) -> None:
        self.plan = plan

    def regex(self, pattern: str) -> Optional[re.Pattern]:
        pat = self._regex_cache.get(pattern)
        if pat is None:
            try:
                pat = re.compile(pattern)
            except re.error:
                return None
            self._regex_cache[pattern] = pat
        return pat

    def plans(self) -> list[Plan]:
        """The plan being built, then the batch's other plans: what
        proposed_allocs applies over the snapshot, in that order."""
        plans = [self.plan] if self.plan is not None else []
        plans.extend(self.extra_plans)
        return plans

    def proposed_allocs(self, node_id: str) -> list[Allocation]:
        """The node's allocs if the current plan were applied.

        state allocs − plan.node_update − (updated ids) + plan.node_allocation,
        terminal filtered (reference: context.go:120).
        """
        existing = self.state.allocs_by_node_terminal(node_id, False)
        for plan in self.plans():
            update_ids = {a.id for a in plan.node_update.get(node_id, [])}
            preempt_ids = {a.id for a in plan.node_preemptions.get(node_id, [])}
            drop = update_ids | preempt_ids
            proposed_new = plan.node_allocation.get(node_id, [])
            new_ids = {a.id for a in proposed_new}
            existing = [a for a in existing if a.id not in drop and a.id not in new_ids]
            existing = existing + list(proposed_new)
        live, _ = filter_terminal_allocs(existing)
        return live
