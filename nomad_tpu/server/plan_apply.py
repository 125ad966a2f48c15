"""Plan applier: THE serialization point of the optimistic scheduler.

Reference: nomad/plan_apply.go — planApply :71, evaluatePlan :400,
evaluateNodePlan :631. Scheduler workers race against stale snapshots; the
applier re-verifies every touched node against the LATEST state and commits
only the subset that still fits. A partial commit sets refresh_index, which
forces the worker to refresh its snapshot and retry the remainder.

The reference parallelizes per-node verification over a worker pool
(plan_apply_pool.go:18) and pipelines verification of plan N+1 with the
Raft apply of plan N (plan_apply.go:54-63). Threads buy nothing under the
GIL, so the same two overlaps are won differently here:

- per-node verification is VECTORIZED: the state store maintains an
  incremental per-node usage aggregate (state/store.py IDX_NODE_USED), so
  each touched node's re-verification is an O(1) aggregate read plus one
  numpy compare over the whole plan's node set, instead of re-summing
  every node's allocs in interpreted loops. Nodes whose fit depends on
  ports/cores/volumes take the exact per-node path (evaluate_node_plan).
- the applier PIPELINES: verification of plan N+1 runs while the raft
  commit of plan N is still in flight, against the latest snapshot with
  plan N's result overlaid (OverlaySnapshot). Before responding to N's
  worker the applier hands the commit-wait to a side thread, so the
  verify loop never blocks on replication round-trips.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Optional

import numpy as np

from .. import metrics, trace
from ..gctune import paused_gc
from ..state.store import usage_contribution
from ..structs import Plan, PlanResult, allocs_fit
from ..structs.placement_batch import AllocRow as _row_handle
from ..structs.structs import NODE_STATUS_READY
from .plan_queue import PlanQueue

logger = logging.getLogger("nomad_tpu.plan_apply")


def _batch_rows_for_node(plan: Plan, node_id: str) -> list:
    """Materialize just one node's rows from the plan's SoA batches —
    the exact-verification path needs real Allocation views, but only
    for the (rare) nodes that fall off the vectorized fast path."""
    rows: list = []
    for b in plan.alloc_batches:
        for nid, ti, _cnt in b.touched_nodes():
            if nid == node_id:
                idx = np.nonzero(b.node_idx == ti)[0]
                rows.extend(b.row(int(i)) for i in idx)
                break
    return rows


def evaluate_node_plan(snapshot, plan: Plan, node_id: str) -> tuple[bool, str]:
    """Would this plan's changes to one node fit? (reference :631)."""
    proposed = list(plan.node_allocation.get(node_id, []))
    if plan.alloc_batches:
        proposed.extend(_batch_rows_for_node(plan, node_id))
    if not proposed:
        return True, ""  # stops/preemptions alone always apply
    node = snapshot.node_by_id(node_id)
    if node is None:
        return False, "node does not exist"
    if node.status != NODE_STATUS_READY:
        return False, f"node is {node.status}"

    existing = snapshot.allocs_by_node_terminal(node_id, False)
    remove = {a.id for a in plan.node_update.get(node_id, [])}
    remove |= {a.id for a in plan.node_preemptions.get(node_id, [])}
    update_ids = {a.id for a in proposed}
    keep = [a for a in existing if a.id not in remove and a.id not in update_ids]
    fit, dim, _ = allocs_fit(node, keep + list(proposed))
    if not fit:
        return False, dim
    return True, ""


class _VolRow:
    """A batch row's volume-claim identity (namespace, job, task group)
    — all the overcommit walk reads."""

    __slots__ = ("namespace", "job", "task_group")

    def __init__(self, namespace: str, job, task_group: str) -> None:
        self.namespace = namespace
        self.job = job
        self.task_group = task_group


def _volume_overcommitted_nodes(snapshot, plan: Plan) -> set[str]:
    """Nodes whose placements would exceed a registered volume's write
    capacity, counting claims already committed AND earlier placements in
    this same plan (first-come order by node id for determinism)."""
    if not hasattr(snapshot, "volumes_by_name"):
        return set()
    # Claims held by allocs this plan stops/evicts/replaces don't count
    # against the new placements (same rule evaluate_node_plan applies to
    # resource fit): a destructive update of the single writer must not
    # conflict with its own predecessor.
    removed: set[str] = set()
    for allocs in plan.node_update.values():
        removed.update(a.id for a in allocs)
    for allocs in plan.node_preemptions.values():
        removed.update(a.id for a in allocs)
    for allocs in plan.node_allocation.values():
        removed.update(a.id for a in allocs)  # in-place updates of selves
    writers: dict[tuple[str, str], int] = {}  # (ns, vol_id) -> new writers
    bad: set[str] = set()
    # SoA batch rows participate as (namespace, job, tg) x count per
    # node — a whole batch shares one volume-bearing task group, so no
    # rows materialize here. Batch-free plans walk node_allocation
    # directly (no per-node list copies on the eager path).
    per_node_rows: dict[str, list] = plan.node_allocation
    if plan.alloc_batches:
        merged = None
        for b in plan.alloc_batches:
            job = b.job or plan.job
            if job is None:
                continue
            tg = job.lookup_task_group(b.task_group)
            if tg is None or not tg.volumes:
                continue
            if merged is None:
                merged = per_node_rows = {
                    nid: list(allocs)
                    for nid, allocs in plan.node_allocation.items()
                }
            for nid, ti, cnt in b.touched_nodes():
                merged.setdefault(nid, []).extend(
                    _VolRow(b.namespace, job, b.task_group)
                    for _ in range(cnt)
                )
    for node_id in sorted(per_node_rows):
        for alloc in per_node_rows[node_id]:
            job = alloc.job or plan.job
            if job is None:
                continue
            tg = job.lookup_task_group(alloc.task_group)
            if tg is None or not tg.volumes:
                continue
            for req in tg.volumes.values():
                if req.read_only or req.type not in ("", "host"):
                    continue
                for vol in snapshot.volumes_by_name(
                    alloc.namespace, req.source
                ):
                    if vol.node_id not in ("", node_id):
                        continue
                    key = (vol.namespace, vol.id)
                    pending = writers.get(key, 0)
                    from ..structs.structs import (
                        VOLUME_ACCESS_READ_ONLY,
                        VOLUME_ACCESS_SINGLE_WRITER,
                    )

                    live_writers = sum(
                        1
                        for c in vol.write_claims()
                        if c.alloc_id not in removed
                    )
                    if vol.access_mode == VOLUME_ACCESS_READ_ONLY or (
                        vol.access_mode == VOLUME_ACCESS_SINGLE_WRITER
                        and (live_writers + pending) >= 1
                    ):
                        bad.add(node_id)
                    else:
                        writers[key] = pending + 1
                    break
    return bad


def _not_live(snapshot, alloc_id: str) -> bool:
    a = snapshot.alloc_by_id(alloc_id)
    return a is None or a.terminal_status()


def _fast_path_usage(snapshot, plan: Plan, node_id: str, node,
                     contrib: Optional[dict] = None):
    """Try to express one node's re-verification as a 3-vector compare.

    Returns (cpu, mem, disk) the node would hold after the plan, or None
    when the node needs the exact path: some involved alloc carries cores
    or port asks, or the node's own reserved ports could self-collide."""
    used = snapshot.node_usage(node_id)
    if used[3] > 0:
        return None  # a committed alloc on this node has cores/ports
    rp = node.reserved.reserved_ports
    if rp and len(rp) != len(set(rp)) and node.resources.networks:
        return None  # reserved-port self-collision is ip-dependent
    cpu, mem, disk = used[0], used[1], used[2]
    proposed = plan.node_allocation.get(node_id, [])
    remove_ids = {a.id for a in plan.node_update.get(node_id, [])}
    remove_ids |= {a.id for a in plan.node_preemptions.get(node_id, [])}
    remove_ids |= {a.id for a in proposed}
    for aid in remove_ids:
        stored = snapshot.alloc_by_id(aid)
        if stored is not None and stored.node_id == node_id:
            c = usage_contribution(stored)
            if c is not None:
                cpu -= c[0]
                mem -= c[1]
                disk -= c[2]
    for alloc in proposed:
        # fresh solver placements share one AllocatedResources per group
        # (solver fast-mint): memoize the contribution walk per distinct
        # (resources, status) across the whole plan
        ar = alloc.resources
        if contrib is not None and ar is not None:
            key = (id(ar), alloc.desired_status, alloc.client_status)
            c = contrib.get(key)
            if c is None and key not in contrib:
                c = contrib[key] = usage_contribution(alloc)
        else:
            c = usage_contribution(alloc)
        if c is None:
            continue
        if c[3]:
            return None  # proposed alloc asks for cores/ports
        cpu += c[0]
        mem += c[1]
        disk += c[2]
    return (cpu, mem, disk)


def evaluate_plan(snapshot, plan: Plan) -> PlanResult:
    """Re-verify the whole plan; return the committable subset
    (reference :400).

    Vectorized: nodes whose fit is a pure cpu/mem/disk question — the
    overwhelming majority — are verified with ONE numpy compare over the
    plan's node set, reading the store's incremental per-node usage
    aggregate. Only nodes involving ports, dedicated cores, or volume
    claims re-walk their allocs (evaluate_node_plan, the exact oracle
    this fast path is differential-tested against)."""
    result = PlanResult(
        node_update=dict(plan.node_update),
        node_allocation={},
        node_preemptions=dict(plan.node_preemptions),
        deployment=plan.deployment,
        deployment_updates=list(plan.deployment_updates),
    )
    # Volume single-writer admission across the WHOLE plan: the
    # feasibility screen saw committed state only, so two writers placed
    # in one plan would both pass it — count in-plan write claims here
    # and reject the overflowing node (reference: the CSI claim RPC
    # serializes this per volume; our claim point is plan apply).
    vol_rejected = _volume_overcommitted_nodes(snapshot, plan)
    rejected = False
    rejected_nodes: set[str] = set()

    def reject(node_id: str, reason: str) -> None:
        nonlocal rejected
        rejected = True
        rejected_nodes.add(node_id)
        # A rejected placement must not still evict its victims:
        # preemptions free capacity FOR that node's placements and
        # are meaningless without them.
        result.node_preemptions.pop(node_id, None)
        logger.debug("plan for node %s rejected: %s", node_id, reason)

    # SoA batches: per-node proposed additions come straight from the
    # columns — bincount-style (count x shared row contribution), no
    # row materialization. batch rows are fast-mint by construction
    # (complex=0), so they never force a node onto the exact path by
    # themselves.
    batches = plan.alloc_batches
    batch_add: dict[str, tuple[int, int, int]] = {}
    if batches:
        for b in batches:
            c = b.row_contribution()
            for nid, _ti, cnt in b.touched_nodes():
                cur = batch_add.get(nid)
                if cur is None:
                    batch_add[nid] = (c[0] * cnt, c[1] * cnt, c[2] * cnt)
                else:
                    batch_add[nid] = (
                        cur[0] + c[0] * cnt,
                        cur[1] + c[1] * cnt,
                        cur[2] + c[2] * cnt,
                    )

    fast_ids: list[str] = []
    fast_rows: list[tuple[int, int, int, int, int, int]] = []
    slow_ids: list[str] = []
    contrib: dict = {}  # per-plan shared-resources contribution memo

    # A victim the store no longer holds live — evicted or stopped by a
    # commit that landed first: a lane plan solved beside a batch in
    # flight that chose it too — is not evicted twice. The node's
    # placements counted on its room and are refused with it: the plan
    # is trimmed there, and its eval retried.
    victims_gone = {
        node_id for node_id, victims in plan.node_preemptions.items()
        if any(_not_live(snapshot, v.id) for v in victims)
    }

    def verify_node(node_id: str, proposed) -> None:
        if node_id in vol_rejected:
            reject(node_id, "volume write-claim conflict")
            return
        if node_id in victims_gone:
            reject(node_id, "a victim is no longer live")
            return
        add = batch_add.get(node_id)
        if not proposed and add is None:
            result.node_allocation[node_id] = proposed
            return
        node = snapshot.node_by_id(node_id)
        if node is None:
            reject(node_id, "node does not exist")
            return
        if node.status != NODE_STATUS_READY:
            reject(node_id, f"node is {node.status}")
            return
        usage = _fast_path_usage(snapshot, plan, node_id, node, contrib)
        if usage is None:
            slow_ids.append(node_id)
            return
        if add is not None:
            usage = (usage[0] + add[0], usage[1] + add[1], usage[2] + add[2])
        avail = node.available_resources()
        fast_ids.append(node_id)
        fast_rows.append(
            (usage[0], usage[1], usage[2], avail.cpu, avail.memory_mb, avail.disk_mb)
        )

    for node_id, proposed in plan.node_allocation.items():
        verify_node(node_id, proposed)
    for node_id in batch_add:
        if node_id not in plan.node_allocation:
            verify_node(node_id, [])
    for node_id in victims_gone - rejected_nodes:
        reject(node_id, "a victim is no longer live")
    if fast_rows:
        rows = np.asarray(fast_rows, dtype=np.int64)
        fits = (rows[:, :3] <= rows[:, 3:]).all(axis=1)
        for node_id, ok in zip(fast_ids, fits):
            if ok:
                if node_id in plan.node_allocation:
                    result.node_allocation[node_id] = plan.node_allocation[
                        node_id
                    ]
            else:
                reject(node_id, "resources exhausted")
    for node_id in slow_ids:
        ok, reason = evaluate_node_plan(snapshot, plan, node_id)
        if ok:
            if node_id in plan.node_allocation:
                result.node_allocation[node_id] = plan.node_allocation[node_id]
        else:
            reject(node_id, reason)

    # Batch verdicts: a rejected node drops ONLY its rows from each
    # batch (a boolean-mask view of the columns); untouched batches ride
    # through whole.
    if batches:
        committed_batches = []
        for b in batches:
            bad_tis = [
                ti
                for nid, ti, _cnt in b.touched_nodes()
                if nid in rejected_nodes
            ]
            if not bad_tis:
                committed_batches.append(b)
                continue
            keep = ~np.isin(b.node_idx, np.asarray(bad_tis, dtype=np.int32))
            if keep.any():
                committed_batches.append(b.take(keep))
        result.alloc_batches = committed_batches

    # plans verified, and those the verification cut: what a solve that
    # could not see a batch in flight costs shows as trimmed plans
    metrics.incr("nomad.plan_apply.plans_verified")
    if rejected:
        metrics.incr("nomad.plan_apply.plans_trimmed")
        if plan.all_at_once:
            # all-or-nothing jobs: reject the ENTIRE plan — stops,
            # preemptions, and deployment changes must not land without
            # their placements.
            result.node_allocation = {}
            result.node_update = {}
            result.node_preemptions = {}
            result.deployment = None
            result.deployment_updates = []
            result.alloc_batches = []
        result.refresh_index = snapshot.index
    return result


def _contribution_with_job(alloc, default_job):
    """usage_contribution for a plan alloc that may have been normalized
    (job detached onto the PlanResult): compute with the result's job
    temporarily re-attached, exactly as the FSM will see it at apply."""
    if alloc.job is None and default_job is not None and alloc.job_id == default_job.id:
        alloc.job = default_job
        try:
            return usage_contribution(alloc)
        finally:
            alloc.job = None
    return usage_contribution(alloc)


class OverlaySnapshot:
    """The latest committed snapshot with verified, not yet committed
    PlanResults laid over it in order: what the store WILL hold once
    they are applied. Two users. The pipelined applier verifies plan N+1
    on plan N's result while N replicates (reference
    plan_apply.go:54-63), without blocking on snapshotMinIndex. And the
    plans of one batch are verified one after another, each on the
    results of those before it (PlanApplier._commit_merged), and commit
    as one raft entry.

    Lazy: add() files a result under the nodes it writes and notes what
    it stops and places; a node's usage delta is summed when a later
    plan asks for that node, and kept — so a batch pays for the nodes
    its plans share, not for every row it places. Only the surface
    evaluate_plan reads is overlaid (allocs by id/node, per-node usage);
    everything else delegates to the base snapshot. Volume-touching
    plans never verify on an overlay (the applier drains the pipeline
    first, and keeps them out of a merged pass), so volume claims always
    read committed state."""

    def __init__(self, base, result: Optional[PlanResult] = None,
                 job=None) -> None:
        self.base = base
        self.index = base.index
        self.node_by_id = base.node_by_id  # evaluate_plan's hot read
        # node id -> [(result, its plan's job)] that write it, in order
        self._by_node: dict[str, list] = {}
        self._stopped: set[str] = set()
        self._placed: dict[str, object] = {}  # eager rows by id
        self._batches: list = []  # SoA batches: rows resolve on demand
        self._rows: Optional[dict] = None  # their id -> lazy row handle
        self._counts: dict[int, dict] = {}  # id(batch) -> {node: rows}
        # node id -> (results summed, [cpu, mem, disk, complex] delta vs
        # the base aggregate), mirroring exactly what the FSM's alloc
        # writes will do to it
        self._delta: dict[str, tuple[int, list]] = {}
        # alloc id -> what a summed result left it holding (None: nothing)
        self._held: dict[str, Optional[tuple]] = {}
        if result is not None:
            self.add(result, job)

    def add(self, result: PlanResult, job, nodes=None) -> None:
        """Lay one more verified result on top. `nodes`: the node ids it
        may write (its plan's partition key), when the caller has them."""
        if nodes is None:
            nodes = (
                set(result.node_update)
                | set(result.node_preemptions)
                | set(result.node_allocation)
            )
            for b in result.alloc_batches:
                nodes.update(nid for nid, _ti, _cnt in b.touched_nodes())
        entry = (result, job)
        by_node = self._by_node
        for node_id in nodes:
            writers = by_node.get(node_id)
            if writers is None:
                by_node[node_id] = [entry]
            else:
                writers.append(entry)
        for allocs in result.node_update.values():
            self._stopped.update(a.id for a in allocs)
        for allocs in result.node_preemptions.values():
            self._stopped.update(a.id for a in allocs)
        for allocs in result.node_allocation.values():
            for a in allocs:
                self._placed[a.id] = a
        if result.alloc_batches:
            self._batches.extend(result.alloc_batches)
            self._rows = None

    def __getattr__(self, name):
        return getattr(self.base, name)

    def stops_a_placement(self, plan: Plan) -> bool:
        """Does the plan stop or evict an alloc that a result laid over
        places or re-places — or one the base does not hold at all,
        which an earlier result's SoA rows may be minting? The store
        applies an entry's stops before its placements, so such a stop
        has to land in a LATER entry than the placement."""
        for table in (plan.node_update, plan.node_preemptions):
            for allocs in table.values():
                for a in allocs:
                    if (
                        a.id in self._placed
                        or self.base.alloc_by_id(a.id) is None
                    ):
                        return True
        return False

    def _release(self, d: list, alloc_id: str, node_id: str) -> None:
        """Take out of the node's delta what the alloc holds there now:
        what an earlier result of the overlay left it, else what the
        base stores. Nothing twice: two results may stop one alloc."""
        if alloc_id in self._held:
            c = self._held[alloc_id]
        else:
            stored = self.base.alloc_by_id(alloc_id)
            c = (
                usage_contribution(stored)
                if stored is not None and stored.node_id == node_id
                else None
            )
        self._held[alloc_id] = None
        if c is not None:
            for i in range(4):
                d[i] -= c[i]

    def _node_delta(self, node_id: str) -> Optional[list]:
        writers = self._by_node.get(node_id)
        if not writers:
            return None
        seen, d = self._delta.get(node_id, (0, None))
        if seen == len(writers):
            return d
        d = [0, 0, 0, 0] if d is None else list(d)
        for result, job in writers[seen:]:
            for a in result.node_update.get(node_id, ()):
                self._release(d, a.id, node_id)
            for a in result.node_preemptions.get(node_id, ()):
                self._release(d, a.id, node_id)
            for a in result.node_allocation.get(node_id, ()):
                self._release(d, a.id, node_id)
                c = self._held[a.id] = _contribution_with_job(a, job)
                if c is not None:
                    for i in range(4):
                        d[i] += c[i]
            for b in result.alloc_batches:
                # SoA rows: count x the batch's shared contribution
                counts = self._counts.get(id(b))
                if counts is None:
                    counts = self._counts[id(b)] = {
                        nid: cnt for nid, _ti, cnt in b.touched_nodes()
                    }
                cnt = counts.get(node_id)
                if cnt:
                    c = b.row_contribution()
                    d[0] += c[0] * cnt
                    d[1] += c[1] * cnt
                    d[2] += c[2] * cnt
        self._delta[node_id] = (len(writers), d)
        return d

    def node_usage(self, node_id: str):
        base = self.base.node_usage(node_id)
        d = self._node_delta(node_id)
        if d is None:
            return base
        return (base[0] + d[0], base[1] + d[1], base[2] + d[2], base[3] + d[3])

    def node_usage_many(self, node_ids: list[str]) -> list[tuple]:
        """node_usage for many nodes, overlaid as node_usage is (the
        base's bulk reader would skip the pending deltas). A node without
        a delta reads the base's very entry, so an identity reader
        (lower.UsageRows) rewrites only the overlaid nodes."""
        return list(map(self.node_usage, node_ids))

    def alloc_by_id(self, alloc_id: str):
        a = self._placed.get(alloc_id)
        if a is not None:
            return a
        a = self.base.alloc_by_id(alloc_id)
        if a is None:
            if not self._batches:
                return None
            if self._rows is None:
                # a later plan names a row an earlier one minted (rare):
                # handles for every batch row, once
                self._rows = {
                    uid: _row_handle(b, i)
                    for b in self._batches
                    for i, uid in enumerate(b.ids)
                }
            return self._rows.get(alloc_id)
        if alloc_id in self._stopped:
            from ..structs.structs import ALLOC_DESIRED_STATUS_STOP

            a = a.copy()
            a.desired_status = ALLOC_DESIRED_STATUS_STOP
        return a

    def allocs_by_node_terminal(self, node_id: str, terminal: bool = False):
        placed: dict[str, object] = {}
        for result, _job in self._by_node.get(node_id, ()):
            for a in result.node_allocation.get(node_id, ()):
                placed[a.id] = a
            if result.alloc_batches:
                for a in _batch_rows_for_node(result, node_id):
                    placed[a.id] = a
        out = []
        for a in self.base.allocs_by_node_terminal(node_id, terminal):
            if a.id in placed:
                continue
            if not terminal and a.id in self._stopped:
                continue
            out.append(a)
        out.extend(a for a in placed.values() if a.terminal_status() == terminal)
        return out


def _plan_partition_key(plan: Plan) -> tuple[set[str], bool, Optional[tuple]]:
    """(touched node set, touches_volumes, job key) — the plan facts the
    conflict partition branches on. Derived once per plan; the round
    loop in _commit_merged_rounds reuses them across rounds instead of
    rebuilding the sets and re-walking volumes O(rounds x plans)."""
    nodes = (
        set(plan.node_allocation)
        | set(plan.node_update)
        | set(plan.node_preemptions)
    )
    for b in plan.alloc_batches:
        nodes.update(nid for nid, _ti, _cnt in b.touched_nodes())
    job_key = (
        (plan.job.namespace, plan.job.id) if plan.job is not None else None
    )
    return nodes, _plan_touches_volumes(plan), job_key


def partition_plan_batch(
    plans: list[Plan],
    keys: Optional[list[tuple[set, bool, Optional[tuple]]]] = None,
) -> tuple[list[int], list[int]]:
    """Which plans of a same-snapshot batch verify and commit together.

    Returns (merged, serial) index lists. The merged plans are verified
    one after another in submission order, each on the committed
    snapshot with the results of those before it laid over
    (OverlaySnapshot), and commit as ONE raft entry — so a plan that
    shares a node with an earlier one is judged exactly as if everything
    had been serial: it stands on what the earlier plan placed, stopped
    and evicted there, and is refused where that plan was refused room
    it counted on.

    Two kinds of plan stay out. One that touches volumes (two plans can
    race one volume's write claim, and claims are read from committed
    state) falls back to the serial path, in submission order, AFTER the
    merged commit. And two plans for the SAME job never merge: the bulk
    commit collapses an entry's jobs by (namespace, id), so same-job
    plans at different job versions would re-attach one plan's allocs to
    the other's version — the later one waits for the next pass. The
    eval broker's one-in-flight-eval-per-job lock already makes this
    unreachable from the TPU worker, but enqueue_batch is public API —
    enforce it here rather than rely on the convention.

    keys — optional precomputed _plan_partition_key list parallel to
    plans."""
    if keys is None:
        keys = [_plan_partition_key(p) for p in plans]
    merged: list[int] = []
    serial: list[int] = []
    claimed_jobs: set[tuple] = set()
    for i, (_nodes, touches_volumes, job_key) in enumerate(keys):
        if touches_volumes or (
            job_key is not None and job_key in claimed_jobs
        ):
            serial.append(i)
            continue
        if job_key is not None:
            claimed_jobs.add(job_key)
        merged.append(i)
    return merged, serial


def _plan_touches_volumes(plan: Plan) -> bool:
    """Does any placement in this plan use task-group volumes? Such plans
    must verify against committed state (volume claims commit atomically
    with the plan that placed them, so an overlay could miss a pending
    single-writer claim)."""
    seen: set[tuple[int, str]] = set()
    for allocs in plan.node_allocation.values():
        for a in allocs:
            job = a.job or plan.job
            if job is None:
                continue
            key = (id(job), a.task_group)
            if key in seen:
                continue
            seen.add(key)
            tg = job.lookup_task_group(a.task_group)
            if tg is not None and tg.volumes:
                return True
    for b in plan.alloc_batches:
        # one (job, task group) per batch — no row walk
        job = b.job or plan.job
        if job is None:
            continue
        tg = job.lookup_task_group(b.task_group)
        if tg is not None and tg.volumes:
            return True
    return False


class PlanApplier:
    """Dequeues plans, verifies, applies through the raft layer.

    Pipelined (reference plan_apply.go:54-63): after submitting plan N's
    result to raft, the applier immediately verifies plan N+1 against the
    latest snapshot with N's result overlaid; a completion thread waits
    out N's commit and responds to its worker. At most one plan result is
    in flight — the depth the reference runs at."""

    def __init__(
        self,
        queue: PlanQueue,
        state,
        raft_apply: Callable,
        raft_apply_async: Optional[Callable] = None,
    ) -> None:
        self.queue = queue
        self.state = state  # live StateStore
        self.raft_apply = raft_apply  # (msg_type, payload) -> index
        # (msg_type, payload) -> (index, wait_fn) — wait_fn blocks until
        # committed+applied. None disables pipelining (serial fallback).
        self.raft_apply_async = raft_apply_async
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._cthread: Optional[threading.Thread] = None
        self._cq: list = []
        self._cq_cv = threading.Condition()
        self._outstanding = 0
        # Bumped on every start(): a completion thread from a previous
        # start/stop cycle that was stuck inside wait_fn past the join
        # timeout must not touch the restarted applier's queue/counter.
        self._gen = 0
        # Set by the completion thread when a commit fails (leadership
        # loss or timeout): the raft index whose fate is unknown. The
        # overlay built from it must be discarded, and the next
        # verification first gives the state store a short grace window
        # to catch up — a TIMED-OUT commit can still land, and verifying
        # without it would double-commit its capacity.
        self._commit_failed_index = 0
        # (raft index, PlanResult, job) of the not-yet-committed plan
        self._inflight: Optional[tuple[int, PlanResult, object]] = None

    def start(self) -> None:
        self._stop.clear()
        self._inflight = None
        with self._cq_cv:
            self._gen += 1
            gen = self._gen
            self._cq = []
            self._outstanding = 0
            self._commit_failed_index = 0
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="plan-applier"
        )
        self._thread.start()
        if self.raft_apply_async is not None:
            self._cthread = threading.Thread(
                target=self._completion_loop,
                args=(gen,),
                daemon=True,
                name="plan-applier-wait",
            )
            self._cthread.start()

    def stop(self) -> None:
        self._stop.set()
        with self._cq_cv:
            self._cq_cv.notify_all()
        if self._thread:
            self._thread.join(timeout=2)
        if self._cthread:
            self._cthread.join(timeout=2)
            self._cthread = None

    def _loop(self) -> None:
        while not self._stop.is_set():
            item = self.queue.dequeue(timeout_s=0.2)
            if item is None:
                continue
            plan, fut, tref = item
            # tref: (TraceContext, parent Span) handed through the queue
            # by the submitting worker — applier-side verify/apply spans
            # land on the SAME trace, nested under its plan.submit span.
            ctx = tref[0] if tref is not None else None
            if isinstance(plan, list):
                try:
                    with trace.use(ctx):
                        self._apply_batch(plan, fut, tref)
                except Exception as e:  # pragma: no cover - defensive
                    logger.exception("plan batch apply failed")
                    for f in fut:
                        if not f.done():
                            f.set_exception(e)
                continue
            try:
                with trace.use(ctx):
                    self._apply_pipelined(plan, fut, tref)
            except Exception as e:  # pragma: no cover - defensive
                logger.exception("plan apply failed")
                if not fut.done():
                    fut.set_exception(e)

    # -- pipelined path -------------------------------------------------

    def _apply_pipelined(self, plan: Plan, fut, tref=None) -> None:
        tctx, tparent = tref if tref is not None else (None, None)
        pipelining = self.raft_apply_async is not None
        self._absorb_commit_failure()
        if pipelining and self._inflight is not None and _plan_touches_volumes(plan):
            self._drain()
            self._absorb_commit_failure()
        snapshot = self.state.snapshot()
        if self._inflight is not None:
            idx, res, job = self._inflight
            if snapshot.index >= idx:
                self._inflight = None  # committed and applied; base is current
            else:
                snapshot = OverlaySnapshot(snapshot, res, job)
        # verification + normalization allocate in bulk at c2m scale —
        # same GC-pause rationale as the solver (gctune.py). ONLY the
        # allocation burst: the blocking raft waits below must not hold
        # the process-wide collector off (the raft/store paths pause
        # around their own bursts).
        with paused_gc():
            with trace.span(tctx, "plan.verify", parent=tparent, cpu=True):
                result = evaluate_plan(snapshot, plan)
            if result.is_no_op():
                fut.set_result(result)
                return
            result.preemption_evals = self._preemption_evals(result)
            self._normalize(plan, result)
        if not pipelining:
            with trace.span(tctx, "plan.raft_apply", parent=tparent):
                index = self.raft_apply("apply_plan_results", result)
            result.alloc_index = index
            fut.set_result(result)
            return
        with trace.span(tctx, "plan.raft_apply", parent=tparent):
            index, wait_fn = self.raft_apply_async(
                "apply_plan_results", result
            )
        # Depth-1 pipeline: wait out the PREVIOUS commit (its replication
        # overlapped with the verification we just finished) before
        # recording this one as in flight.
        self._drain()
        self._inflight = (index, result, plan.job)
        with self._cq_cv:
            self._cq.append((index, wait_fn, result, fut))
            self._outstanding += 1
            self._cq_cv.notify_all()

    # -- merged batch path ----------------------------------------------

    @staticmethod
    def _trim_duplicate_mints(
        results: list[PlanResult], seen: set, snapshot
    ) -> int:
        """Same-eval/same-alloc-name dedup across one merged commit.

        The r15/r17 soak duplicate-alloc forensics proved both duplicate
        ids are minted by the SAME eval inside ONE merged plan-apply
        raft entry (apply_plan_results_batch — same create_index): an
        eval solved twice with both outcomes landing in one round, or
        one plan carrying a name twice across its eager rows and SoA
        batches. Per-node capacity verification cannot catch it — two
        ids for one (eval, name) are not a capacity violation — so the
        merge round guards the identity invariant itself: the FIRST
        entrant in commit order keeps the name, every later entrant is
        trimmed before the raft apply. A trimmed result gets a
        refresh_index, so its worker sees a partial commit and requeues
        the eval, which then re-reconciles against state that already
        holds the first entrant. ``seen`` spans the whole batch (all
        rounds), so a round-2 re-mint of a round-1 name trims too."""
        trimmed = 0
        for result in results:
            hit = False
            for nid, allocs in list(result.node_allocation.items()):
                keep = []
                for a in allocs:
                    if a.create_index:
                        # an UPDATE of an existing alloc (inplace,
                        # attr annotation) keeps its original minting
                        # eval_id/name — it is not a mint and two plans
                        # touching it in one batch are last-writer-wins,
                        # not duplicates
                        keep.append(a)
                        continue
                    key = (a.eval_id, a.name)
                    if key in seen:
                        trimmed += 1
                        hit = True
                        continue
                    seen.add(key)
                    keep.append(a)
                if len(keep) != len(allocs):
                    if keep:
                        result.node_allocation[nid] = keep
                    else:
                        del result.node_allocation[nid]
            if result.alloc_batches:
                new_batches = []
                for b in result.alloc_batches:
                    mask = np.ones(len(b), dtype=bool)
                    for ri, name in enumerate(b.names):
                        key = (b.eval_id, name)
                        if key in seen:
                            mask[ri] = False
                            trimmed += 1
                            hit = True
                        else:
                            seen.add(key)
                    if mask.all():
                        new_batches.append(b)
                    elif mask.any():
                        new_batches.append(b.take(mask))
                result.alloc_batches = new_batches
            if hit:
                result.refresh_index = max(
                    result.refresh_index, snapshot.index
                )
        if trimmed:
            from .. import blackbox

            metrics.incr("nomad.plan_apply.dup_mint_trimmed", trimmed)
            # flight-recorder journal: the dup-mint-invariant trigger
            # captures an incident off this counter, and the journal row
            # ties the trim to its minting evals for the timeline
            blackbox.record(
                blackbox.KIND_DUP_MINT, "plan_apply", trimmed=trimmed,
                rel=[
                    f"eval:{e}" for e in sorted(
                        {ev for ev, _ in seen}
                    )[:8]
                ],
            )
            logger.warning(
                "merged plan round minted %d duplicate (eval, name) "
                "alloc(s); trimmed the later entrant(s)", trimmed,
            )
        return trimmed

    def _commit_merged(
        self, plans: list[Plan], merged_idx: list[int], snapshot,
        keys: list, tref=None, round_no: int = 0,
        seen_mints: Optional[set] = None,
    ) -> tuple[dict[int, PlanResult], list[int]]:
        """Verify the merged subset in submission order, each plan on
        the snapshot with the results of those before it laid over, and
        commit every non-no-op result as ONE raft entry backed by one
        bulk store transaction. Returns the results and the indices put
        off to the next pass (OverlaySnapshot.stops_a_placement)."""
        tctx, tparent = tref if tref is not None else (None, None)
        results: dict[int, PlanResult] = {}
        verified: list[tuple[int, PlanResult]] = []
        to_commit: list[tuple[int, PlanResult]] = []
        put_off: list[int] = []
        overlay = OverlaySnapshot(snapshot)
        claimed: set[str] = set()  # nodes a verified plan of this pass writes
        with paused_gc():
            with trace.span(
                tctx, "plan.verify", parent=tparent, cpu=True,
                round=round_no, plans=len(merged_idx),
            ):
                for i in merged_idx:
                    plan = plans[i]
                    nodes = keys[i][0]
                    if claimed and overlay.stops_a_placement(plan):
                        put_off.append(i)
                        continue
                    # a plan alone on its nodes reads the snapshot itself
                    view = snapshot if claimed.isdisjoint(nodes) else overlay
                    result = evaluate_plan(view, plan)
                    claimed |= nodes
                    if result.is_no_op():
                        results[i] = result
                        continue
                    overlay.add(result, plan.job, nodes)
                    verified.append((i, result))
            # identity guard BEFORE preemption evals / normalization: a
            # trimmed row must not leave its preemption or job wiring
            # behind (satellite: the r15/r17 duplicate-alloc race)
            self._trim_duplicate_mints(
                [r for _, r in verified],
                seen_mints if seen_mints is not None else set(),
                snapshot,
            )
            for i, result in verified:
                if result.is_no_op():
                    results[i] = result
                    continue
                result.preemption_evals = self._preemption_evals(result)
                self._normalize(plans[i], result)
                to_commit.append((i, result))
        if to_commit:
            with trace.span(
                tctx, "plan.raft_apply", parent=tparent,
                round=round_no, plans=len(to_commit),
            ):
                index = self.raft_apply(
                    "apply_plan_results_batch", [r for _, r in to_commit]
                )
            for i, r in to_commit:
                r.alloc_index = index
                results[i] = r
        return results, put_off

    def _commit_merged_rounds(
        self, plans: list[Plan], snapshot, tref=None
    ) -> tuple[dict[int, PlanResult], list[int]]:
        """Merged commit in passes: a pass verifies every remaining plan
        that may merge (partition_plan_batch) in submission order and
        commits them as one raft entry; what it put off — a second plan
        of one job, a stop of an alloc the pass itself places — rides
        the next pass, on a fresh snapshot. One pass is the rule.
        Volume-touching plans never merge; their indices are returned
        for the caller's true serial path."""
        t0 = time.perf_counter()
        results: dict[int, PlanResult] = {}
        remaining = list(range(len(plans)))
        keys = [_plan_partition_key(p) for p in plans]
        merged_total = 0
        rounds = 0
        # (eval_id, alloc name) minted anywhere in this batch — the
        # duplicate-mint guard's memory across passes
        seen_mints: set = set()
        while remaining:
            rel_merged, rel_rest = partition_plan_batch(
                [plans[i] for i in remaining],
                keys=[keys[i] for i in remaining],
            )
            if not rel_merged:
                break  # only volume plans left — serial path
            if rounds > 0:
                snapshot = self.state.snapshot()
            round_idx = [remaining[r] for r in rel_merged]
            done, put_off = self._commit_merged(
                plans, round_idx, snapshot, keys, tref=tref,
                round_no=rounds, seen_mints=seen_mints,
            )
            results.update(done)
            merged_total += len(done)
            rounds += 1
            remaining = sorted(put_off + [remaining[r] for r in rel_rest])
        metrics.observe("nomad.plan_apply.batch_merged", merged_total)
        metrics.observe("nomad.plan_apply.batch_rounds", rounds)
        metrics.observe("nomad.plan_apply.batch_serial", len(remaining))
        metrics.observe(
            "nomad.plan_apply.batch_seconds", time.perf_counter() - t0
        )
        return results, remaining

    def _apply_batch(self, plans: list[Plan], futs: list, tref=None) -> None:
        """Queue-dequeued batch: round-partitioned merged commits for
        everything node-partitionable, serial fallback (in order) for
        the volume-touching rest.

        The batch verifies against COMMITTED state only, so any pipelined
        single-plan commit still in flight is drained first — the merged
        commit is itself one synchronous apply for N plans, which already
        amortizes what the depth-1 pipeline would have hidden."""
        self._drain()
        self._absorb_commit_failure()
        if self._stop.is_set():
            err = RuntimeError("plan applier stopping")
            for f in futs:
                if not f.done():
                    f.set_exception(err)
            return
        snapshot = self.state.snapshot()
        if self._inflight is not None:
            idx, res, job = self._inflight
            if snapshot.index >= idx:
                self._inflight = None
            else:  # pragma: no cover - drain above makes this unreachable
                snapshot = OverlaySnapshot(snapshot, res, job)
        results, serial_idx = self._commit_merged_rounds(
            plans, snapshot, tref=tref
        )
        for i, r in results.items():
            futs[i].set_result(r)
        # Volume-touching plans re-verify against post-merge state via
        # the standard (pipelined) serial path and refresh/reject exactly
        # as they always did.
        for i in serial_idx:
            try:
                self._apply_pipelined(plans[i], futs[i], tref)
            except Exception as e:  # pragma: no cover - defensive
                logger.exception("serial fallback apply failed")
                if not futs[i].done():
                    futs[i].set_exception(e)

    def apply_batch(self, plans: list[Plan]) -> list[PlanResult]:
        """Synchronous merged verify+commit of a plan batch (direct
        callers and tests; the dequeue loop routes queue batches through
        the same partition/merge core)."""
        # Same preamble as the queue batch path: a pipelined single-plan
        # commit still in flight is invisible to a fresh committed-state
        # snapshot — verifying without draining it would double-book the
        # node it landed on. No-ops when nothing is outstanding.
        self._drain()
        self._absorb_commit_failure()
        results, serial_idx = self._commit_merged_rounds(
            plans, self.state.snapshot()
        )
        for i in serial_idx:
            results[i] = self.apply_one(plans[i])
        return [results[i] for i in range(len(plans))]

    def _absorb_commit_failure(self) -> None:
        """If an in-flight commit failed, discard its overlay — after
        giving the state store a short window to catch up, since a commit
        that failed by TIMEOUT may still land and verifying without its
        effects would double-commit capacity. If the index never arrives
        the entry is presumed truncated (leadership moved): subsequent
        submits fail leader checks, so nothing stale can commit."""
        with self._cq_cv:
            failed_idx = self._commit_failed_index
            self._commit_failed_index = 0
        if not failed_idx:
            return
        try:
            self.state.snapshot_min_index(failed_idx, timeout_s=1.0)
        except TimeoutError:
            pass
        self._inflight = None

    def _drain(self) -> None:
        """Block until every submitted result has committed (or failed)
        and its worker has been answered."""
        with self._cq_cv:
            while self._outstanding > 0:
                self._cq_cv.wait(0.5)
                if self._stop.is_set():
                    return

    def _completion_loop(self, gen: int) -> None:
        while True:
            with self._cq_cv:
                while (
                    not self._cq
                    and not self._stop.is_set()
                    and gen == self._gen
                ):
                    self._cq_cv.wait(0.5)
                if gen != self._gen:
                    return  # superseded by a restart; a new thread owns _cq
                if self._stop.is_set() and not self._cq:
                    return
                index, wait_fn, result, fut = self._cq.pop(0)
            try:
                result.alloc_index = wait_fn()
                fut.set_result(result)
            except Exception as e:
                with self._cq_cv:
                    if gen == self._gen:
                        self._commit_failed_index = index
                if not fut.done():
                    fut.set_exception(e)
            finally:
                with self._cq_cv:
                    if gen == self._gen:
                        self._outstanding -= 1
                        self._cq_cv.notify_all()

    @staticmethod
    def _normalize(plan: Plan, result: PlanResult) -> None:
        # Normalize before the log encodes the payload: embedded Job copies
        # would serialize once PER ALLOCATION (a c2m-scale plan would pack
        # ~100k Jobs). The scheduled job version rides ONCE on the result
        # and the FSM re-attaches it to every alloc that referenced it —
        # NOT the jobs table's current version, which may have moved while
        # the plan sat in the queue, and NOT the stored alloc's old
        # version, which would silently revert in-place updates. Allocs
        # referencing some OTHER version (e.g. followup-eval annotations
        # of old allocs) keep their job embedded.
        result.job = plan.job
        if result.job is not None:
            for allocs in result.node_allocation.values():
                for a in allocs:
                    if a.job is result.job:
                        a.job = None
            for b in result.alloc_batches:
                # one shared job slot per batch, not one per row
                if b.job is result.job:
                    b.job = None

    def apply_one(self, plan: Plan) -> PlanResult:
        """Serial verify+commit of one plan (direct callers and tests;
        the dequeue loop runs the pipelined path)."""
        snapshot = self.state.snapshot()
        result = evaluate_plan(snapshot, plan)
        if result.is_no_op():
            return result
        result.preemption_evals = self._preemption_evals(result)
        self._normalize(plan, result)
        index = self.raft_apply("apply_plan_results", result)
        result.alloc_index = index
        return result

    def _preemption_evals(self, result: PlanResult):
        """One follow-up eval per job losing allocs to preemption, so the
        preempted work reschedules elsewhere (reference plan_apply.go:278)."""
        from ..structs import Evaluation, generate_uuids
        from ..structs.structs import (
            EVAL_STATUS_PENDING,
            EVAL_TRIGGER_PREEMPTION,
            now_ns,
        )

        seen: set[tuple[str, str]] = set()
        for allocs in result.node_preemptions.values():
            for a in allocs:
                seen.add((a.namespace, a.job_id))
        if not seen:
            return []
        evals = []
        # bulk id minting: one entropy draw + one format pass for the
        # whole preemption wave (generate_uuids, ISSUE 12 satellite)
        ids = generate_uuids(len(seen))
        for uid, (ns, job_id) in zip(ids, seen):
            # preempted plan rows carry job=None; resolve from state
            job = self.state.job_by_id(ns, job_id)
            evals.append(
                Evaluation(
                    id=uid,
                    namespace=ns,
                    priority=job.priority if job else 50,
                    type=job.type if job else "service",
                    triggered_by=EVAL_TRIGGER_PREEMPTION,
                    job_id=job_id,
                    status=EVAL_STATUS_PENDING,
                    create_time=now_ns(),
                    modify_time=now_ns(),
                )
            )
        return evals
