"""Node scoring pipeline.

Reference: scheduler/rank.go — RankedNode :21, BinPackIterator.Next :193-527
(the reference's hot loop), JobAntiAffinityIterator :536,
NodeReschedulingPenaltyIterator :606, NodeAffinityIterator :650,
ScoreNormalizationIterator :740.

The host pipeline below is the correctness oracle; the TPU backend computes
the same scores for all (alloc, node) pairs at once in
nomad_tpu/scheduler/tpu/kernels.py. Keep formula changes mirrored there.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterator, Optional

from ..structs import (
    AllocatedResources,
    AllocatedTaskResources,
    NetworkIndex,
    Node,
    Resources,
    TaskGroup,
)
from ..structs.funcs import score_fit_binpack, score_fit_spread
from .context import EvalContext
from .device import DeviceAllocator

BINPACK_SCORER = "binpack"
JOB_ANTI_AFFINITY_SCORER = "job-anti-affinity"
NODE_RESCHED_PENALTY_SCORER = "node-reschedule-penalty"
NODE_AFFINITY_SCORER = "node-affinity"
SPREAD_SCORER = "allocation-spread"


@dataclass
class RankedNode:
    node: Node
    scores: dict[str, float] = field(default_factory=dict)
    final_score: float = 0.0
    task_resources: dict[str, AllocatedTaskResources] = field(default_factory=dict)
    alloc_resources: Optional[AllocatedResources] = None
    proposed_allocs: Optional[list] = None
    # allocs that must be evicted for this placement to fit
    # (reference rank.go:33 PreemptedAllocs)
    preempted_allocs: Optional[list] = None

    def add_score(self, name: str, value: float) -> None:
        self.scores[name] = value


def binpack_rank(
    ctx: EvalContext,
    candidates: Iterator[Node],
    tg: TaskGroup,
    metrics=None,
    algorithm: Optional[str] = None,
    evict: bool = False,
    job=None,
    memo: Optional["RankMemo"] = None,
) -> Iterator[RankedNode]:
    """Fit-check + score each candidate node for the task group.

    Per node: proposed utilization (existing − stops + placements), per-task
    network/device assignment, cumulative fit, ScoreFit. Infeasible nodes are
    recorded as exhausted and skipped. Reference: rank.go BinPackIterator.

    With evict=True (the scheduler's second pass after normal placement
    fails), a node that doesn't fit runs the Preemptor (reference
    rank.go:233): lower-priority allocs are chosen for eviction and the
    fit re-checked without them; picks land on RankedNode.preempted_allocs.
    Scope matches PreemptForTaskGroup (cpu/mem/disk); the network/device
    preemption paths are not implemented.

    With a `memo` (a host stack's), a node's ranking is computed once and
    replayed while nothing it read has changed (RankMemo); the options
    and the metrics are those of a ranking computed afresh.
    """
    algo = algorithm or ctx.scheduler_config.algorithm
    kept = memo.kept(ctx, tg, algo, evict) if memo is not None else None
    for node in candidates:
        if kept is not None:
            ranked, dim = memo.rank(ctx, kept, node, tg, algo)
        else:
            ranked, dim = binpack_node(ctx, node, tg, algo, evict, job)
            if memo is not None:
                memo.ranked += 1
        if ranked is None:
            if metrics is not None:
                metrics.exhausted_node(node, dim)
            continue
        if metrics is not None:
            metrics.score_node(
                node.id, BINPACK_SCORER, ranked.scores[BINPACK_SCORER]
            )
        yield ranked


def binpack_node(
    ctx: EvalContext,
    node: Node,
    tg: TaskGroup,
    algo: str,
    evict: bool = False,
    job=None,
) -> tuple[Optional[RankedNode], str]:
    """One node of binpack_rank: its option, or None and the dimension
    it is exhausted in.

    On a node that no plan of the context writes, the proposed allocs
    are the snapshot's live allocs, whose sum the store keeps
    (`node_usage`): the normal pass checks the fit against that total
    and builds the node's alloc list only where the ask fits."""
    available = node.available_resources()
    total_ask = tg.combined_resources()
    held = (
        ctx.extra_usage.get(node.id) if ctx.extra_usage is not None else None
    ) or (0, 0, 0)

    def _utilization(allocs, used=(0, 0, 0)):
        util = Resources(
            cpu=total_ask.cpu + held[0] + used[0],
            memory_mb=total_ask.memory_mb + held[1] + used[1],
            disk_mb=total_ask.disk_mb + held[2] + used[2],
        )
        for alloc in allocs:
            r = alloc.comparable_resources()
            util.cpu += r.cpu
            util.memory_mb += r.memory_mb
            util.disk_mb += r.disk_mb
        return util

    if not evict and _untouched(ctx, node.id):
        util = _utilization((), ctx.state.node_usage(node.id))
        ok, dim = available.superset(util)
        if not ok:
            ctx.exhausted_by_usage += 1
            return None, dim
        proposed = ctx.proposed_allocs(node.id)
    else:
        proposed = ctx.proposed_allocs(node.id)
        util = _utilization(proposed)
        ok, dim = available.superset(util)
    preempted_allocs = None
    if not ok and evict and job is not None:
        from .preemption import Preemptor

        preemptor = Preemptor(
            job.priority, job.namespace, job.id, ctx.plan
        )
        preemptor.set_node(node)
        preemptor.set_candidates(proposed)
        picks = preemptor.preempt_for_task_group(total_ask)
        if picks:
            picked_ids = {a.id for a in picks}
            without = [a for a in proposed if a.id not in picked_ids]
            util = _utilization(without)
            ok, dim = available.superset(util)
            if ok:
                preempted_allocs = picks
                proposed = without
    if not ok:
        return None, dim

    net_idx = NetworkIndex()
    net_idx.set_node(node)
    net_idx.add_allocs(proposed)

    dev_alloc = DeviceAllocator(ctx, node)
    dev_alloc.add_allocs(proposed)

    # Dedicated cores (reference rank.go: AllocatedCpuResources via
    # idset): free ids = node's cores minus every proposed alloc's
    # reservations; a `cores` task gets the lowest free ids and a
    # DERIVED cpu share (cores x node MHz/core) so MHz accounting
    # stays consistent with share-based tasks.
    free_cores: list = []
    mhz_per_core = 0
    if any(t.resources.cores > 0 for t in tg.tasks):
        from ..structs.funcs import node_core_pool

        free_cores, mhz_per_core = node_core_pool(node, proposed)

    # Per-task port/bandwidth + device assignment.
    task_resources: dict[str, AllocatedTaskResources] = {}
    for task in tg.tasks:
        tr = AllocatedTaskResources(
            cpu=task.resources.cpu, memory_mb=task.resources.memory_mb
        )
        if task.resources.cores > 0:
            if len(free_cores) < task.resources.cores:
                return None, "cores"
            tr.reserved_cores = free_cores[: task.resources.cores]
            free_cores = free_cores[task.resources.cores :]
            tr.cpu = task.resources.cores * mhz_per_core
            util.cpu += tr.cpu - task.resources.cpu
            ok, dim = available.superset(util)
            if not ok:
                return None, dim
        for ask in task.resources.networks:
            offer = net_idx.assign_network(ask)
            if offer is None:
                return None, "network"
            net_idx.add_reserved(offer)
            tr.networks.append(offer)
        for dev_ask in task.resources.devices:
            got = dev_alloc.assign(dev_ask)
            if got is None:
                return None, "devices"
            tr.devices.append(got)
        task_resources[task.name] = tr

    # Group-level networks (bridge/port asks at the group level).
    shared_networks = []
    for ask in tg.networks:
        offer = net_idx.assign_network(ask)
        if offer is None:
            return None, "network"
        net_idx.add_reserved(offer)
        shared_networks.append(offer)

    if algo == "spread":
        fit_score = score_fit_spread(node, util)
    else:
        fit_score = score_fit_binpack(node, util)
    # Normalize [0,18] → [0,1] like the reference (rank.go:504).
    return _ranked(
        node, tg, task_resources, shared_networks, proposed,
        preempted_allocs, fit_score / 18.0,
    ), ""


def _untouched(ctx: EvalContext, node_id: str) -> bool:
    """No plan of the context places, stops or preempts on the node:
    what proposed_allocs lays over the snapshot there is nothing."""
    for plan in ctx.plans():
        if (node_id in plan.node_allocation or node_id in plan.node_update
                or node_id in plan.node_preemptions):
            return False
    return True


def _ranked(node, tg, task_resources, shared_networks, proposed,
            preempted_allocs, score: float) -> RankedNode:
    ranked = RankedNode(
        node=node,
        task_resources=task_resources,
        alloc_resources=AllocatedResources(
            tasks=task_resources,
            shared_disk_mb=tg.ephemeral_disk.size_mb,
            shared_networks=shared_networks,
        ),
        proposed_allocs=proposed,
        preempted_allocs=preempted_allocs,
    )
    ranked.add_score(BINPACK_SCORER, score)
    return ranked


def rank_reusable(tg: TaskGroup) -> bool:
    """A group whose binpack reads nothing but the node and its proposed
    allocs, and draws nothing: no port or bandwidth ask (an offer may
    draw from `random`), no device, no reserved cores."""
    if tg.networks:
        return False
    for t in tg.tasks:
        r = t.resources
        if r.networks or r.devices or r.cores > 0:
            return False
    return True


class RankMemo:
    """One binpack ranking per node per host stack, while nothing it read
    has changed.

    Every select of a GenericStack walks one permutation from its head,
    so a deploy's k-th select ranks the nodes the k − 1 before it ranked.
    A node's binpack reads the snapshot, the node, the group's ask and the
    node's proposed allocs; between two selects of one eval only a plan
    write to that node changes them, and every plan write counts in
    `Plan.node_writes`. So a ranking is kept with the sum of the node's
    write counts over the context's plans — they only grow — and replayed
    while that sum stands: a fresh RankedNode with the kept score and
    proposed allocs, and the same metric calls. Kept for the normal pass
    of `rank_reusable` groups only; the evict pass, and any change of
    snapshot, plan list or algorithm, ranks afresh.

    `ranked` counts rankings computed, `reused` rankings replayed."""

    __slots__ = ("ranked", "reused", "_basis", "_groups")

    def __init__(self) -> None:
        self.ranked = 0
        self.reused = 0
        self._basis: tuple = ()
        # id(tg) -> (tg, node_id -> (node, writes, proposed, score, dim))
        self._groups: dict[int, tuple[TaskGroup, dict]] = {}

    def kept(self, ctx: EvalContext, tg: TaskGroup, algo: str,
             evict: bool) -> Optional[dict]:
        """The kept rankings of `tg` for this walk, or None where a
        ranking may not be kept."""
        if evict or not rank_reusable(tg):
            return None
        basis = (ctx.state, algo, *ctx.plans())
        if len(basis) != len(self._basis) or any(
            a is not b for a, b in zip(basis, self._basis)
        ):
            self._basis = basis
            self._groups.clear()
        got = self._groups.get(id(tg))
        if got is None or got[0] is not tg:
            got = self._groups[id(tg)] = (tg, {})
        return got[1]

    def rank(self, ctx: EvalContext, kept: dict, node: Node, tg: TaskGroup,
             algo: str) -> tuple[Optional[RankedNode], str]:
        writes = 0
        for plan in self._basis[2:]:
            writes += plan.node_writes.get(node.id, 0)
        entry = kept.get(node.id)
        if entry is not None and entry[0] is node and entry[1] == writes:
            self.reused += 1
            _, _, proposed, score, dim = entry
            if score is None:
                return None, dim
            tasks = {
                t.name: AllocatedTaskResources(
                    cpu=t.resources.cpu, memory_mb=t.resources.memory_mb
                )
                for t in tg.tasks
            }
            return _ranked(node, tg, tasks, [], proposed, None, score), ""
        self.ranked += 1
        ranked, dim = binpack_node(ctx, node, tg, algo)
        if ranked is None:
            kept[node.id] = (node, writes, None, None, dim)
        else:
            kept[node.id] = (
                node, writes, ranked.proposed_allocs,
                ranked.scores[BINPACK_SCORER], "",
            )
        return ranked, dim


def job_anti_affinity_rank(
    ctx: EvalContext,
    options: Iterator[RankedNode],
    job_id: str,
    tg_name: str,
    desired_count: int,
    metrics=None,
) -> Iterator[RankedNode]:
    """Penalize placing multiple allocs of one task group on a node
    (reference: rank.go:536)."""
    for option in options:
        proposed = (
            option.proposed_allocs
            if option.proposed_allocs is not None
            else ctx.proposed_allocs(option.node.id)
        )
        collisions = sum(
            1
            for a in proposed
            if a.job_id == job_id and a.task_group == tg_name
        )
        if collisions > 0 and desired_count > 0:
            penalty = -1.0 * float(collisions + 1) / float(desired_count)
            option.add_score(JOB_ANTI_AFFINITY_SCORER, penalty)
            if metrics is not None:
                metrics.score_node(option.node.id, JOB_ANTI_AFFINITY_SCORER, penalty)
        yield option


def node_resched_penalty_rank(
    options: Iterator[RankedNode],
    penalty_nodes: set[str],
    metrics=None,
) -> Iterator[RankedNode]:
    """Penalize the node a failed alloc is being rescheduled away from
    (reference: rank.go:606)."""
    for option in options:
        if option.node.id in penalty_nodes:
            option.add_score(NODE_RESCHED_PENALTY_SCORER, -1.0)
            if metrics is not None:
                metrics.score_node(option.node.id, NODE_RESCHED_PENALTY_SCORER, -1.0)
        yield option


def node_affinity_rank(
    ctx: EvalContext,
    options: Iterator[RankedNode],
    affinities: list,
    metrics=None,
) -> Iterator[RankedNode]:
    """Soft-preference scoring, normalized by total |weight|
    (reference: rank.go:650)."""
    from .feasible import node_matches_constraint

    if not affinities:
        yield from options
        return
    total_weight = sum(abs(a.weight) for a in affinities) or 1
    for option in options:
        total = 0.0
        for aff in affinities:
            if node_matches_constraint(ctx, option.node, aff):
                total += float(aff.weight)
        if total != 0.0:
            norm = total / float(total_weight)
            option.add_score(NODE_AFFINITY_SCORER, norm)
            if metrics is not None:
                metrics.score_node(option.node.id, NODE_AFFINITY_SCORER, norm)
        yield option


def score_normalization(
    options: Iterator[RankedNode], metrics=None
) -> Iterator[RankedNode]:
    """final = mean of component scores (reference: rank.go:740)."""
    for option in options:
        if option.scores:
            option.final_score = sum(option.scores.values()) / len(option.scores)
        if metrics is not None:
            metrics.score_node(option.node.id, "normalized", option.final_score)
        yield option
