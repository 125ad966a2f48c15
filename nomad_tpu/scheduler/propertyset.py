"""Alloc counts per node-attribute value, for distinct_property and spread.

Reference: scheduler/propertyset.go — propertySet :14, UsedCount :231,
GetCombinedUseMap :250.
"""

from __future__ import annotations

from typing import Optional

from ..structs import Constraint, Node
from .context import EvalContext
from .feasible import resolve_target


class PropertySet:
    """Counts existing + planned + proposed allocs per value of one node
    attribute, scoped to a job or one task group."""

    def __init__(self, ctx: EvalContext, job) -> None:
        self.ctx = ctx
        self.job = job
        self.namespace = job.namespace
        self.target_attribute: str = ""
        self.target_values: set[str] = set()  # spread explicit targets
        self.tg_name: str = ""  # empty = job scope
        self.allowed_count: int = 0  # distinct_property limit (0 = spread use)
        self._existing: Optional[dict[str, int]] = None
        self._cleared: dict[str, int] = {}
        # used_counts' map and the (plan, plan.writes) it was counted at
        self._combined: Optional[dict[str, int]] = None
        self._combined_at: tuple = (None, -1)

    def set_job_constraint(self, constraint: Constraint) -> None:
        self.target_attribute = constraint.ltarget
        self.allowed_count = int(constraint.rtarget) if constraint.rtarget else 1

    def set_tg_constraint(self, constraint: Constraint, tg_name: str) -> None:
        self.set_job_constraint(constraint)
        self.tg_name = tg_name

    def set_target_attribute(self, attribute: str, tg_name: str = "") -> None:
        self.target_attribute = attribute
        self.tg_name = tg_name

    def _relevant(self, alloc) -> bool:
        if alloc.job_id != self.job.id or alloc.namespace != self.namespace:
            return False
        if self.tg_name and alloc.task_group != self.tg_name:
            return False
        return True

    def _value_of(self, node: Optional[Node]) -> tuple[str, bool]:
        if node is None:
            return "", False
        return resolve_target(node, self.target_attribute)

    def _compute_existing(self) -> dict[str, int]:
        """The job's live allocs per value, read through the job's index
        as the reference does (propertyset.go populateExisting:
        AllocsByJob) — a walk of every alloc in the store cost a spread
        eval 10 ms at 26,000 allocs (PERF.md section 6, PR 36)."""
        counts: dict[str, int] = {}
        node_cache: dict[str, Optional[Node]] = {}
        for alloc in self.ctx.state.allocs_by_job(self.namespace, self.job.id):
            if alloc.terminal_status() or not self._relevant(alloc):
                continue
            node = node_cache.get(alloc.node_id, ...)
            if node is ...:
                node = self.ctx.state.node_by_id(alloc.node_id)
                node_cache[alloc.node_id] = node
            val, ok = self._value_of(node)
            if ok:
                counts[val] = counts.get(val, 0) + 1
        return counts

    def used_counts(self) -> dict[str, int]:
        """existing − plan stops + plan placements, per attribute value
        (reference: GetCombinedUseMap :250).

        Counted once a plan state: the map is kept while the plan's write
        count stands (a spread scores every ranked node against it), so
        callers read it and never write it."""
        plan = self.ctx.plan
        at = (plan, plan.writes if plan is not None else 0)
        if self._combined is not None and at[0] is self._combined_at[0] \
                and at[1] == self._combined_at[1]:
            return self._combined
        if self._existing is None:
            self._existing = self._compute_existing()
        combined = dict(self._existing)
        if plan is not None:
            for node_id, allocs in plan.node_allocation.items():
                node = self.ctx.state.node_by_id(node_id)
                val, ok = self._value_of(node)
                if not ok:
                    continue
                for alloc in allocs:
                    if self._relevant(alloc):
                        combined[val] = combined.get(val, 0) + 1
            for node_id, allocs in list(plan.node_update.items()) + list(
                plan.node_preemptions.items()
            ):
                node = self.ctx.state.node_by_id(node_id)
                val, ok = self._value_of(node)
                if not ok:
                    continue
                for alloc in allocs:
                    if self._relevant(alloc):
                        combined[val] = max(0, combined.get(val, 0) - 1)
        self._combined, self._combined_at = combined, at
        return combined

    def satisfies_distinct_property(self, node: Node) -> tuple[bool, str]:
        val, ok = self._value_of(node)
        if not ok:
            return False, f"missing property {self.target_attribute}"
        used = self.used_counts().get(val, 0)
        if used >= self.allowed_count:
            return (
                False,
                f"distinct_property: {self.target_attribute}={val} used by {used} allocs",
            )
        return True, ""
