"""Placement stacks: the composed feasibility → rank → select pipeline.

Reference: scheduler/stack.go — GenericStack :43 (shuffled source, log₂(n)
candidate limit :83-90), Select :117, SystemStack :183.
"""

from __future__ import annotations

import math
import random
from itertools import islice
from typing import Iterable, Optional

from ..structs import Constraint, Job, Node, TaskGroup
from ..structs.structs import (
    CONSTRAINT_DISTINCT_HOSTS,
    CONSTRAINT_DISTINCT_PROPERTY,
    JOB_TYPE_BATCH,
)
from .context import EvalContext
from .feasible import (
    ConstraintChecker,
    DeviceChecker,
    DistinctHostsChecker,
    DriverChecker,
    FeasibilityChecker,
    HostVolumeChecker,
    CSIVolumeChecker,
    NetworkChecker,
    feasibility_pipeline,
)
from .propertyset import PropertySet
from .rank import (
    RankedNode,
    RankMemo,
    binpack_rank,
    job_anti_affinity_rank,
    node_affinity_rank,
    node_resched_penalty_rank,
    score_normalization,
)
from .select import limit_select, max_score_select
from .spread import SpreadScorer, spread_rank


def _tg_drivers(tg: TaskGroup) -> set[str]:
    return {t.driver for t in tg.tasks}


def _distinct_property_constraints(
    constraints: list[Constraint],
) -> list[Constraint]:
    return [c for c in constraints if c.operand == CONSTRAINT_DISTINCT_PROPERTY]


def _has_distinct_hosts(constraints: list[Constraint]) -> bool:
    return any(c.operand == CONSTRAINT_DISTINCT_HOSTS for c in constraints)


def _distinct_property_checkers(ctx, job, tg) -> list:
    """Stateful distinct_property checkers for one task group — job,
    group, AND task level (lower.py folds task constraints into
    units_cap the same way, so both backends agree)."""
    post = []
    for c in _distinct_property_constraints(job.constraints):
        pset = PropertySet(ctx, job)
        pset.set_job_constraint(c)
        post.append(_DistinctPropertyChecker(pset))
    tg_level = list(tg.constraints)
    for t in tg.tasks:
        tg_level.extend(t.constraints)
    for c in _distinct_property_constraints(tg_level):
        pset = PropertySet(ctx, job)
        pset.set_tg_constraint(c, tg.name)
        post.append(_DistinctPropertyChecker(pset))
    return post


class _DistinctPropertyChecker(FeasibilityChecker):
    def __init__(self, pset: PropertySet) -> None:
        self.pset = pset

    def feasible(self, node: Node) -> tuple[bool, str]:
        return self.pset.satisfies_distinct_property(node)


class ShuffledNodes:
    """A uniformly random permutation of `src`, drawn as it is walked.

    Position i is drawn when a walk first reaches it (a sparse
    Fisher-Yates: j = randrange(i, n), the swap kept in a dict of the few
    positions that moved); the drawn prefix is kept, so every walk starts
    at the head of the SAME permutation. `src` is read, never written or
    copied: the solver shares one ready-nodes list between evals. A walk
    that goes past n/32 — a full cluster, a constraint most of the fleet
    fails — finishes the permutation the eager way (the remainder,
    `random.shuffle`d), which costs a node a third of what a lazy draw
    does."""

    __slots__ = ("_src", "_n", "_order", "_moved", "_eager_at", "eager")

    def __init__(self, src: list) -> None:
        self._src = src if isinstance(src, list) else list(src)
        self._n = len(self._src)
        self._order: list = []  # the permutation's drawn prefix
        self._moved: dict[int, int] = {}  # position -> index into src
        self._eager_at = self._n >> 5
        self.eager = False  # the permutation was finished eagerly

    def __len__(self) -> int:
        return self._n

    @property
    def drawn(self) -> int:
        """Positions of the permutation drawn so far."""
        return len(self._order)

    def __iter__(self):
        # a finished permutation is walked as the list it is
        return iter(self._order) if self.eager else self._walk()

    def _walk(self):
        order = self._order
        i = 0
        while not self.eager:
            if i == len(order):
                if i >= self._eager_at:
                    self._finish()
                    break
                self._draw()
            yield order[i]
            i += 1
        yield from islice(order, i, None)

    def _draw(self) -> None:
        moved = self._moved
        i = len(self._order)
        j = random.randrange(i, self._n)
        at_i = moved.pop(i, i)
        if j == i:
            pick = at_i
        else:
            pick = moved.get(j, j)
            moved[j] = at_i
        self._order.append(self._src[pick])

    def _finish(self) -> None:
        k = len(self._order)
        rest = self._src[k:]
        for pos, idx in self._moved.items():
            rest[pos - k] = self._src[idx]
        self._moved.clear()
        random.shuffle(rest)
        self._order.extend(rest)
        self.eager = True


class GenericStack:
    """Service/batch placement stack (reference: stack.go:43)."""

    def __init__(self, batch: bool, ctx: EvalContext) -> None:
        self.batch = batch
        self.ctx = ctx
        self.nodes: ShuffledNodes = ShuffledNodes([])
        self.limit = 2
        self.job: Optional[Job] = None
        # Per-eval caches: PropertySets scan their job's existing allocs
        # once; the plan delta is merged per call (reference caches these
        # on Context).
        self._post_checkers: dict[str, list[FeasibilityChecker]] = {}
        self._spread_scorers: dict[str, SpreadScorer] = {}
        # The walks' binpack rankings, kept between selects while the
        # node's plan writes stand; `ranked` / `reused` count them.
        self.ranks = RankMemo()

    def set_nodes(self, nodes: list[Node]) -> None:
        """Shuffle for scheduler-worker decorrelation and set the candidate
        limit: log₂(n) for service (power-of-N-choices), 2 for batch
        (reference: stack.go:71-90).

        The reference shuffles the whole list in place (scheduler/util.go
        shuffleNodes, an O(n) Fisher-Yates that costs Go ~50 µs at 10,000
        nodes); the same loop cost this interpreter 4-5 ms an eval, for a
        walk that looks at log₂(n) nodes a placement. A walk only ever
        sees a prefix of the permutation, and a Fisher-Yates draws its
        prefix first, so drawing position i when a walk reaches it is the
        same choice: every select of this stack walks one uniformly random
        permutation of `nodes` from its head, as far as it needs."""
        self.nodes = ShuffledNodes(nodes)
        n = len(nodes)
        if self.batch:
            self.limit = 2
        else:
            self.limit = max(2, int(math.ceil(math.log2(n)))) if n > 0 else 2

    def set_job(self, job: Job) -> None:
        self.job = job
        self.ctx.eligibility.set_job(job)
        self._post_checkers.clear()
        self._spread_scorers.clear()

    def select(
        self,
        tg: TaskGroup,
        penalty_nodes: Optional[set[str]] = None,
        metrics=None,
        selected_nodes: Optional[list[Node]] = None,
        evict: bool = False,
    ) -> Optional[RankedNode]:
        """Pick the best node for one instance of the task group.
        evict=True enables the preemption pass in binpack ranking."""
        job = self.job
        assert job is not None, "set_job must be called first"
        source: Iterable[Node] = (
            selected_nodes if selected_nodes is not None else self.nodes
        )

        job_checkers: list[FeasibilityChecker] = [
            ConstraintChecker(self.ctx, job.constraints),
        ]
        all_constraints = list(tg.constraints)
        for t in tg.tasks:
            all_constraints.extend(t.constraints)
        tg_checkers: list[FeasibilityChecker] = [
            DriverChecker(self.ctx, _tg_drivers(tg)),
            ConstraintChecker(self.ctx, all_constraints),
            HostVolumeChecker(self.ctx, tg.volumes, namespace=job.namespace),
            CSIVolumeChecker(self.ctx, tg.volumes, namespace=job.namespace),
            NetworkChecker(self.ctx, tg),
            DeviceChecker(self.ctx, tg),
        ]

        feasible = feasibility_pipeline(
            self.ctx, source, job_checkers, tg_checkers, tg.name, metrics
        )

        # Stateful per-plan checkers sit outside the class memoization.
        post = self._post_checkers.get(tg.name)
        if post is None:
            post = []
            if _has_distinct_hosts(job.constraints):
                post.append(DistinctHostsChecker(self.ctx, job.id, tg.name, True))
            elif _has_distinct_hosts(tg.constraints):
                post.append(DistinctHostsChecker(self.ctx, job.id, tg.name, False))
            post.extend(_distinct_property_checkers(self.ctx, job, tg))
            self._post_checkers[tg.name] = post
        if post:
            def _post_filter(nodes):
                for node in nodes:
                    ok = True
                    for checker in post:
                        good, reason = checker.feasible(node)
                        if not good:
                            if metrics is not None:
                                metrics.filter_node(node, reason)
                            ok = False
                            break
                    if ok:
                        yield node

            feasible = _post_filter(feasible)

        # a sticky try ranks its one preferred node afresh
        options = binpack_rank(
            self.ctx, feasible, tg, metrics, evict=evict, job=job,
            memo=self.ranks if selected_nodes is None else None,
        )
        options = job_anti_affinity_rank(
            self.ctx, options, job.id, tg.name, tg.count, metrics
        )
        if penalty_nodes:
            options = node_resched_penalty_rank(options, penalty_nodes, metrics)
        affinities = list(job.affinities) + list(tg.affinities)
        for t in tg.tasks:
            affinities.extend(t.affinities)
        options = node_affinity_rank(self.ctx, options, affinities, metrics)
        if tg.spreads or job.spreads:
            scorer = self._spread_scorers.get(tg.name)
            if scorer is None:
                scorer = SpreadScorer(self.ctx, job, tg, metrics)
                self._spread_scorers[tg.name] = scorer
            options = spread_rank(self.ctx, options, scorer, metrics)
        options = score_normalization(options, metrics)
        shortlist = limit_select(options, self.limit)
        return max_score_select(shortlist)


class SystemStack:
    """System/sysbatch stack: every feasible node, no shuffle/limit
    (reference: stack.go:183)."""

    def __init__(self, ctx: EvalContext) -> None:
        self.ctx = ctx
        self.nodes: list[Node] = []
        self.job: Optional[Job] = None
        self._post_checkers: dict[str, list] = {}

    def set_nodes(self, nodes: list[Node]) -> None:
        self.nodes = list(nodes)

    def set_job(self, job: Job) -> None:
        self.job = job
        self.ctx.eligibility.set_job(job)
        self._post_checkers = {}

    def select(
        self, tg: TaskGroup, node: Node, metrics=None, evict: bool = False
    ) -> Optional[RankedNode]:
        """Fit one instance of tg on one specific node."""
        job = self.job
        assert job is not None
        job_checkers = [ConstraintChecker(self.ctx, job.constraints)]
        all_constraints = list(tg.constraints)
        for t in tg.tasks:
            all_constraints.extend(t.constraints)
        tg_checkers = [
            DriverChecker(self.ctx, _tg_drivers(tg)),
            ConstraintChecker(self.ctx, all_constraints),
            HostVolumeChecker(self.ctx, tg.volumes, namespace=job.namespace),
            CSIVolumeChecker(self.ctx, tg.volumes, namespace=job.namespace),
            NetworkChecker(self.ctx, tg),
            DeviceChecker(self.ctx, tg),
        ]
        feasible = feasibility_pipeline(
            self.ctx, [node], job_checkers, tg_checkers, tg.name, metrics
        )
        # distinct_property budgets are shared across the walk's own
        # placements (reference SystemStack wires DistinctPropertyIterator
        # AFTER the feasibility chain, stack.go:197-259, so filter
        # metrics match the generic stack); PropertySet reads the live
        # plan so each placed node decrements the per-value budget.
        post = self._post_checkers.get(tg.name)
        if post is None:
            post = _distinct_property_checkers(self.ctx, job, tg)
            self._post_checkers[tg.name] = post
        if post:
            def _post_filter(nodes):
                for n in nodes:
                    ok = True
                    for checker in post:
                        good, reason = checker.feasible(n)
                        if not good:
                            if metrics is not None:
                                metrics.filter_node(n, reason)
                            ok = False
                            break
                    if ok:
                        yield n

            feasible = _post_filter(feasible)
        options = binpack_rank(
            self.ctx, feasible, tg, metrics, evict=evict, job=job
        )
        options = score_normalization(options, metrics)
        got = list(options)
        return got[0] if got else None
