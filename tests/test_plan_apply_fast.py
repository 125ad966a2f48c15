"""Vectorized plan verification + pipelined applier tests.

The applier's fast path (server/plan_apply.py evaluate_plan) reads the
state store's incremental per-node usage aggregate and verifies a plan's
node set with one numpy compare; nodes involving ports/cores/volumes take
the exact per-node path. These tests pin three things:

1. the aggregate never drifts from a from-scratch recompute under
   randomized alloc churn (the invariant every fast-path answer rests on);
2. the vectorized evaluate_plan is behaviorally identical to the exact
   per-node oracle on randomized plans (reference analog:
   nomad/plan_apply_test.go TestPlanApply_EvalPlan_*);
3. the pipeline (verify plan N+1 while plan N's raft commit is in
   flight, reference plan_apply.go:54-63) never double-commits capacity:
   plan N+1 sees plan N's result through the overlay.
"""

import random
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server.plan_apply import (
    OverlaySnapshot,
    PlanApplier,
    _volume_overcommitted_nodes,
    evaluate_node_plan,
    evaluate_plan,
)
from nomad_tpu.server.plan_queue import PlanQueue
from nomad_tpu.state import StateStore
from nomad_tpu.state.store import (
    IDX_NODE_USED,
    rebuild_node_usage,
    usage_contribution,
)
from nomad_tpu.structs import Plan, PlanResult
from nomad_tpu.structs.structs import (
    NetworkResource,
    Port,
)


def exact_evaluate_plan(snapshot, plan: Plan) -> PlanResult:
    """The pre-vectorization applier loop: every node re-verified with
    evaluate_node_plan. The oracle the fast path must match."""
    result = PlanResult(
        node_update=dict(plan.node_update),
        node_allocation={},
        node_preemptions=dict(plan.node_preemptions),
        deployment=plan.deployment,
        deployment_updates=list(plan.deployment_updates),
    )
    vol_rejected = _volume_overcommitted_nodes(snapshot, plan)
    rejected = False
    for node_id in plan.node_allocation:
        ok, _reason = (
            (False, "volume write-claim conflict")
            if node_id in vol_rejected
            else evaluate_node_plan(snapshot, plan, node_id)
        )
        if ok:
            result.node_allocation[node_id] = plan.node_allocation[node_id]
        else:
            rejected = True
            result.node_preemptions.pop(node_id, None)
    if rejected:
        if plan.all_at_once:
            result.node_allocation = {}
            result.node_update = {}
            result.node_preemptions = {}
            result.deployment = None
            result.deployment_updates = []
        result.refresh_index = snapshot.index
    return result


# ---------------------------------------------------------------------------
# 1. Aggregate invariant under churn
# ---------------------------------------------------------------------------


def _check_aggregate(store: StateStore) -> None:
    from nomad_tpu.state.store import (
        IDX_NODE_TIERS,
        IDX_PRIO_COUNT,
        TABLE_ALLOCS,
        rebuild_priority_indexes,
    )

    got = store._tables[IDX_NODE_USED]
    want = rebuild_node_usage(store._tables[TABLE_ALLOCS])
    assert got == want, f"usage aggregate drifted: {got} != {want}"
    gotp = store._tables[IDX_PRIO_COUNT]
    wantp, wantt = rebuild_priority_indexes(store._tables[TABLE_ALLOCS])
    assert gotp == wantp, f"priority counts drifted: {gotp} != {wantp}"
    gott = store._tables[IDX_NODE_TIERS]
    assert gott == wantt, f"node tiers drifted: {gott} != {wantt}"
    # and the tiers of a node are its usage, split: the accessor's view
    for nid, used in got.items():
        (tiers,) = store.node_tier_usage([nid])
        assert [t[0] for t in tiers] == sorted({t[0] for t in tiers})
        assert all(t[4] > 0 for t in tiers)
        assert tuple(sum(t[k] for t in tiers) for k in (1, 2, 3)) == used[:3]
    assert set(gott) >= set(got)


def test_usage_aggregate_tracks_alloc_churn():
    rng = random.Random(7)
    store = StateStore()
    nodes = [mock.node() for _ in range(6)]
    for i, n in enumerate(nodes):
        store.upsert_node(i + 1, n)
    job = mock.job()
    store.upsert_job(10, job)
    live = []
    index = 20
    for round_ in range(30):
        index += 1
        op = rng.random()
        if op < 0.5 or not live:
            # place a fresh batch (some with cores/ports to exercise the
            # complex counter)
            batch = []
            for _ in range(rng.randint(1, 4)):
                a = mock.alloc(job, rng.choice(nodes), index=rng.randint(0, 99))
                if rng.random() < 0.3:
                    tr = next(iter(a.resources.tasks.values()))
                    tr.reserved_cores = [0, 1]
                elif rng.random() < 0.3:
                    tr = next(iter(a.resources.tasks.values()))
                    tr.networks = [
                        NetworkResource(
                            ip="10.0.0.1",
                            reserved_ports=[Port("http", rng.randint(2000, 60000))],
                        )
                    ]
                batch.append(a)
            store.upsert_allocs(index, batch)
            live.extend(batch)
        elif op < 0.8:
            # client reports some allocs terminal
            victims = rng.sample(live, min(len(live), 2))
            updates = []
            for v in victims:
                u = v.copy()
                u.client_status = rng.choice(["complete", "failed", "lost"])
                updates.append(u)
                live.remove(v)
            store.update_allocs_from_client(index, updates)
        else:
            # GC an alloc outright
            v = rng.choice(live)
            live.remove(v)
            store.delete_evals(index, [], [v.id])
        _check_aggregate(store)


def test_usage_aggregate_survives_restore():
    store = StateStore()
    node = mock.node()
    store.upsert_node(1, node)
    job = mock.job()
    store.upsert_job(2, job)
    store.upsert_allocs(3, [mock.alloc(job, node, index=i) for i in range(4)])
    raw = store.serialize()
    restored = StateStore()
    restored.restore_from(raw)
    _check_aggregate(restored)
    assert restored.node_usage(node.id) == store.node_usage(node.id)


# Every way an alloc is written, against a store that holds three tiers
# on shared nodes; after each, every aggregate equals its rebuild.


def _tiered_store():
    """(store, nodes, {priority: job}, live allocs): four nodes, each
    with allocs of priority 20, 50 and 70 jobs."""
    store = StateStore()
    nodes = [mock.node() for _ in range(4)]
    for i, n in enumerate(nodes):
        store.upsert_node(i + 1, n)
    jobs = {p: mock.job(id=f"tier-{p}", priority=p) for p in (20, 50, 70)}
    for k, job in enumerate(jobs.values()):
        store.upsert_job(10 + k, job)
    live = [mock.alloc(job, n, index=i)
            for i, n in enumerate(nodes) for job in jobs.values()]
    store.upsert_allocs(20, live)
    return store, nodes, jobs, live


def _tiers_of(store, node):
    return {t[0]: t[1:] for t in store.node_tier_usage([node.id])[0]}


def _write_eager_upsert(store, nodes, jobs, live):
    store.upsert_allocs(30, [mock.alloc(jobs[20], nodes[0], index=9),
                             mock.alloc(jobs[70], nodes[0], index=9)])
    assert _tiers_of(store, nodes[0])[20][3] == 2


def _write_update_in_place(store, nodes, jobs, live):
    before = _tiers_of(store, nodes[0])[20]
    a = live[0].copy()
    next(iter(a.resources.tasks.values())).cpu += 100
    store.upsert_allocs(30, [a])
    after = _tiers_of(store, nodes[0])[20]
    assert after == (before[0] + 100, before[1], before[2], before[3])


def _write_node_move(store, nodes, jobs, live):
    a = live[0].copy()
    a.node_id = nodes[1].id
    store.upsert_allocs(30, [a])
    assert 20 not in _tiers_of(store, nodes[0])
    assert _tiers_of(store, nodes[1])[20][3] == 2


def _write_terminal_transition(store, nodes, jobs, live):
    done = live[0].copy()
    done.client_status = "complete"
    store.update_allocs_from_client(30, [done])
    stopped = live[1].copy()
    stopped.desired_status = "stop"
    store.upsert_allocs(31, [stopped])
    assert set(_tiers_of(store, nodes[0])) == {70}
    back = live[0].copy()  # and an alloc written terminal stays out
    back.id = mock.alloc(jobs[20], nodes[0]).id
    back.client_status = "failed"
    store.upsert_allocs(32, [back])
    assert set(_tiers_of(store, nodes[0])) == {70}


def _write_delete(store, nodes, jobs, live):
    store.delete_evals(30, [], [a.id for a in live[:3]])
    assert store.node_tier_usage([nodes[0].id, "no such node"]) == [(), ()]
    assert nodes[0].id not in store._tables[IDX_NODE_USED]


def _write_priority_differs(store, nodes, jobs, live):
    """The same alloc id written again under a job of another priority:
    out of the tier of the row that was there, into the new row's."""
    a = live[0].copy()
    a.job = jobs[20].copy()
    a.job.priority = 60
    store.upsert_allocs(30, [a])
    assert set(_tiers_of(store, nodes[0])) == {50, 60, 70}
    assert store.alloc_priority_tiers() == [20, 50, 60, 70]


def _write_snapshot_restore(store, nodes, jobs, live):
    held = store.snapshot()
    ids = [n.id for n in nodes]
    was = held.node_tier_usage(ids)
    store.delete_evals(30, [], [live[0].id])
    assert held.node_tier_usage(ids) == was  # copy on write
    assert store.node_tier_usage(ids) == [was[0][1:]] + was[1:]
    restored = StateStore()
    restored.restore_from(store.serialize())
    _check_aggregate(restored)
    assert restored.node_tier_usage(ids) == store.node_tier_usage(ids)


def _solved_plan(h, job):
    from nomad_tpu.scheduler.context import SchedulerConfig
    from nomad_tpu.scheduler.tpu import solve_eval_batch

    for t in job.task_groups[0].tasks:
        t.resources.networks = []
    h.state.upsert_job(h.next_index(), job)
    ev = mock.eval_for_job(job)
    return solve_eval_batch(
        h.snapshot(), h, [ev],
        SchedulerConfig(backend="tpu", small_batch_threshold=0))[ev.id]


def _harness_over(store):
    from nomad_tpu.testing import Harness

    h = Harness()
    h.state = store
    for _ in range(store.latest_index()):
        h.next_index()
    return h


def _write_soa_insert(store, nodes, jobs, live):
    """The solver's SoA placement batches: one update a touched node at
    the batch's one priority."""
    h = _harness_over(store)
    job = mock.job(id="soa", priority=25)  # nothing 10 under it: compact
    job.task_groups[0].count = 6
    job.task_groups[0].tasks[0].resources.cpu = 100
    plan = _solved_plan(h, job)
    assert plan.alloc_batches, "the fast-mint path must emit SoA batches"
    h.submit_plan(plan)
    placed = sum(_tiers_of(store, n).get(25, (0, 0, 0, 0))[3] for n in nodes)
    assert placed == 6


def _write_plan_with_preemptions(store, nodes, jobs, live):
    h = _harness_over(store)
    fill = []  # every node full of priority 20
    for n in nodes:
        free = n.available_resources().cpu - store.node_usage(n.id)[0]
        a = mock.alloc(jobs[20], n, index=50)
        next(iter(a.resources.tasks.values())).cpu = free
        fill.append(a)
    store.upsert_allocs(h.next_index(), fill)
    job = mock.job(id="production", priority=90)
    job.task_groups[0].count = 2
    plan = _solved_plan(h, job)
    victims = [a for v in plan.node_preemptions.values() for a in v]
    assert victims, "the plan must evict"
    before = store._tables[IDX_NODE_USED].copy()
    h.submit_plan(plan)
    assert store._tables[IDX_NODE_USED] != before
    assert all(store.alloc_by_id(v.id).terminal_status() for v in victims)


_ALLOC_WRITES = {
    fn.__name__[len("_write_"):]: fn
    for fn in (
        _write_eager_upsert, _write_soa_insert, _write_update_in_place,
        _write_node_move, _write_terminal_transition, _write_delete,
        _write_plan_with_preemptions, _write_snapshot_restore,
        _write_priority_differs,
    )
}


@pytest.mark.parametrize("write", sorted(_ALLOC_WRITES))
def test_every_alloc_write_keeps_usage_by_priority(write):
    store, nodes, jobs, live = _tiered_store()
    _check_aggregate(store)
    assert set(_tiers_of(store, nodes[0])) == {20, 50, 70}
    _ALLOC_WRITES[write](store, nodes, jobs, live)
    _check_aggregate(store)


# ---------------------------------------------------------------------------
# 2. Vectorized evaluate_plan ≡ exact oracle (randomized differential)
# ---------------------------------------------------------------------------


def _random_cluster(rng: random.Random):
    store = StateStore()
    nodes = []
    index = 1
    for i in range(rng.randint(4, 10)):
        n = mock.node()
        if rng.random() < 0.2:
            n.status = "down"
        if rng.random() < 0.15:
            # duplicate reserved ports: the self-collision case that must
            # force the exact path
            n.reserved.reserved_ports = [22, 22]
        store.upsert_node(index, n)
        if n.status == "down":
            store.update_node_status(index, n.id, "down")
        nodes.append(n)
        index += 1
    job = mock.job()
    store.upsert_job(index, job)
    index += 1
    existing = []
    for n in nodes:
        for i in range(rng.randint(0, 6)):
            a = mock.alloc(job, n, index=rng.randint(0, 999))
            if rng.random() < 0.2:
                a.client_status = rng.choice(["complete", "failed"])
            if rng.random() < 0.2:
                tr = next(iter(a.resources.tasks.values()))
                tr.reserved_cores = [i % 4]
            if rng.random() < 0.2:
                tr = next(iter(a.resources.tasks.values()))
                tr.networks = [
                    NetworkResource(
                        ip=n.resources.networks[0].ip,
                        reserved_ports=[Port("p", 3000 + i)],
                    )
                ]
            existing.append(a)
    store.upsert_allocs(index, existing)
    return store, nodes, job, existing, index + 1


def _random_plan(rng: random.Random, nodes, job, existing) -> Plan:
    plan = Plan(eval_id="e", job=job, all_at_once=rng.random() < 0.2)
    live = [a for a in existing if not a.terminal_status()]
    for v in rng.sample(live, min(len(live), rng.randint(0, 3))):
        plan.append_stopped_alloc(v, "test stop")
    for v in rng.sample(live, min(len(live), rng.randint(0, 2))):
        plan.append_preempted_alloc(v, "preempting-alloc-id")
    for _ in range(rng.randint(1, 12)):
        n = rng.choice(nodes)
        a = mock.alloc(job, n, index=rng.randint(0, 999))
        # oversize some placements to force overcommit rejections
        if rng.random() < 0.3:
            for tr in a.resources.tasks.values():
                tr.cpu = rng.choice([2000, 4000, 8000])
        if rng.random() < 0.15:
            tr = next(iter(a.resources.tasks.values()))
            tr.networks = [
                NetworkResource(
                    ip=n.resources.networks[0].ip,
                    reserved_ports=[Port("p", rng.choice([3000, 3001, 9999]))],
                )
            ]
        if rng.random() < 0.15:
            tr = next(iter(a.resources.tasks.values()))
            tr.reserved_cores = [rng.randint(0, 5)]
        plan.append_alloc(a, job)
    return plan


def test_evaluate_plan_matches_exact_oracle():
    for seed in range(40):
        rng = random.Random(seed)
        store, nodes, job, existing, _ = _random_cluster(rng)
        plan = _random_plan(rng, nodes, job, existing)
        snap = store.snapshot()
        fast = evaluate_plan(snap, plan)
        exact = exact_evaluate_plan(snap, plan)
        assert set(fast.node_allocation) == set(exact.node_allocation), (
            f"seed {seed}: accepted-node sets differ"
        )
        assert set(fast.node_preemptions) == set(exact.node_preemptions), (
            f"seed {seed}: preemption sets differ"
        )
        assert (fast.refresh_index > 0) == (exact.refresh_index > 0), (
            f"seed {seed}: refresh_index disagreement"
        )
        assert fast.node_update.keys() == exact.node_update.keys()


# ---------------------------------------------------------------------------
# 3. Pipeline: overlay correctness + commit handoff
# ---------------------------------------------------------------------------


class _SlowRaft:
    """Applies to the store immediately but delays the commit
    acknowledgment, simulating replication latency — the window the
    overlay must cover is between submit and local apply, so we also
    support deferring the apply itself."""

    def __init__(self, store: StateStore, defer_apply: bool = False) -> None:
        self.store = store
        self.index = 100
        self.defer_apply = defer_apply
        self.deferred: list = []
        self.lock = threading.Lock()
        self.commit_delay_s = 0.05

    def apply_async(self, msg_type: str, payload):
        assert msg_type == "apply_plan_results"
        with self.lock:
            self.index += 1
            index = self.index
        if self.defer_apply:
            with self.lock:
                self.deferred.append((index, payload))
        else:
            self.store.upsert_plan_results(index, payload)

        def wait(index=index, payload=payload):
            time.sleep(self.commit_delay_s)
            if self.defer_apply:
                with self.lock:
                    if (index, payload) in self.deferred:
                        self.deferred.remove((index, payload))
                        self.store.upsert_plan_results(index, payload)
            return index

        return index, wait

    def apply_sync(self, msg_type: str, payload):
        index, wait = self.apply_async(msg_type, payload)
        return wait()


def test_pipeline_overlay_prevents_double_commit():
    """Two plans that each fit the node alone but not together, submitted
    back to back: with the commit of plan 1 still in flight (state not yet
    updated), plan 2 must still be rejected — the overlay carries plan 1's
    placements."""
    store = StateStore()
    node = mock.node()  # 4000 cpu
    store.upsert_node(1, node)
    job = mock.job()
    store.upsert_job(2, job)
    raft = _SlowRaft(store, defer_apply=True)
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, store, raft.apply_sync, raft.apply_async)
    applier.start()
    try:
        def big_plan(eval_id):
            plan = Plan(eval_id=eval_id, job=job)
            for i in range(6):  # 6 x 500 cpu = 3000: two such plans > 4000
                plan.append_alloc(mock.alloc(job, node, index=i), job)
            return plan

        fut1 = queue.enqueue(big_plan("e1"))
        fut2 = queue.enqueue(big_plan("e2"))
        r1 = fut1.result(timeout=5)
        r2 = fut2.result(timeout=5)
        placed1 = sum(len(v) for v in r1.node_allocation.values())
        placed2 = sum(len(v) for v in r2.node_allocation.values())
        assert placed1 == 6
        assert placed2 == 0, "plan 2 double-committed capacity past plan 1"
        assert r2.refresh_index > 0
    finally:
        applier.stop()
        queue.set_enabled(False)
    # once everything lands, committed state must hold exactly plan 1
    live = [a for a in store.allocs() if not a.terminal_status()]
    assert len(live) == 6


def test_pipeline_sequential_fills_node_exactly():
    """Plans that together exactly fit must BOTH commit while pipelined."""
    store = StateStore()
    node = mock.node()  # 4000 cpu, 8192 mem
    store.upsert_node(1, node)
    job = mock.job()
    store.upsert_job(2, job)
    raft = _SlowRaft(store, defer_apply=True)
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, store, raft.apply_sync, raft.apply_async)
    applier.start()
    try:
        futs = []
        for e in range(4):
            plan = Plan(eval_id=f"e{e}", job=job)
            for i in range(2):  # 2 x 500 cpu per plan; 4 plans = 4000 exactly
                plan.append_alloc(mock.alloc(job, node, index=e * 2 + i), job)
            futs.append(queue.enqueue(plan))
        results = [f.result(timeout=5) for f in futs]
        for i, r in enumerate(results):
            placed = sum(len(v) for v in r.node_allocation.values())
            assert placed == 2, f"plan {i} rejected but capacity was free"
    finally:
        applier.stop()
        queue.set_enabled(False)
    live = [a for a in store.allocs() if not a.terminal_status()]
    assert len(live) == 8


def test_pipeline_commit_failure_reaches_worker():
    store = StateStore()
    node = mock.node()
    store.upsert_node(1, node)
    job = mock.job()
    store.upsert_job(2, job)

    def apply_async(msg_type, payload):
        def wait():
            raise RuntimeError("leadership lost")

        return 101, wait

    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, store, None, apply_async)
    applier.start()
    try:
        plan = Plan(eval_id="e", job=job)
        plan.append_alloc(mock.alloc(job, node), job)
        fut = queue.enqueue(plan)
        try:
            fut.result(timeout=5)
            raised = False
        except RuntimeError:
            raised = True
        assert raised
    finally:
        applier.stop()
        queue.set_enabled(False)


# ---------------------------------------------------------------------------
# OverlaySnapshot view semantics
# ---------------------------------------------------------------------------


def test_overlay_snapshot_views():
    store = StateStore()
    node = mock.node()
    store.upsert_node(1, node)
    job = mock.job()
    store.upsert_job(2, job)
    committed = [mock.alloc(job, node, index=i) for i in range(3)]
    store.upsert_allocs(3, committed)
    base = store.snapshot()

    placed = mock.alloc(job, node, index=9)
    result = PlanResult(
        node_update={node.id: [committed[0].copy()]},
        node_allocation={node.id: [placed]},
        node_preemptions={},
    )
    ov = OverlaySnapshot(base, result, job)

    # stopped alloc reads back terminal; placed alloc resolvable by id
    assert ov.alloc_by_id(committed[0].id).terminal_status()
    assert ov.alloc_by_id(placed.id) is placed
    assert ov.alloc_by_id(committed[1].id) is not None

    live = ov.allocs_by_node_terminal(node.id, False)
    live_ids = {a.id for a in live}
    assert committed[0].id not in live_ids
    assert placed.id in live_ids
    assert committed[1].id in live_ids

    # usage = base - stopped + placed
    want = list(base.node_usage(node.id))
    for i, c in enumerate(usage_contribution(committed[0])):
        want[i] -= c
    for i, c in enumerate(usage_contribution(placed)):
        want[i] += c
    assert ov.node_usage(node.id) == tuple(want)

    # delegation for everything un-overlaid
    assert ov.node_by_id(node.id) is base.node_by_id(node.id)
    assert ov.index == base.index
