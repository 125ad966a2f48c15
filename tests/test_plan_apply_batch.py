"""Differential tests for the merged/batched plan-apply path.

The batched applier (plan_apply.py: partition_plan_batch + apply_batch /
enqueue_batch) commits a whole TPU batch's node-disjoint plans as ONE
raft entry backed by one bulk store transaction. These tests pin the
invariant the merge rides on: the final state — allocs, secondary
indexes, usage aggregates, eval statuses — is IDENTICAL to applying the
same plans one-by-one through the serial path, across both backends'
plan shapes, a forced node-conflict partition, and a partial-commit
retry.
"""

import pytest

from nomad_tpu import codec, mock
from nomad_tpu.server.plan_apply import (
    PlanApplier,
    partition_plan_batch,
)
from nomad_tpu.server.plan_queue import PlanQueue
from nomad_tpu.server.raft import FSM, InmemLog
from nomad_tpu.scheduler.tpu import solve_eval_batch
from nomad_tpu.state import StateStore
from nomad_tpu.structs import Plan, PlanResult
from nomad_tpu.testing import Harness

BACKENDS = ["host", "tpu"]


def build_state(n_nodes=10, n_jobs=4, count=5, cpu=500, mem=256):
    h = Harness()
    for _ in range(n_nodes):
        n = mock.node()
        n.resources.cpu = 4000
        n.resources.memory_mb = 8192
        h.state.upsert_node(h.next_index(), n)
    jobs = []
    for j in range(n_jobs):
        job = mock.job(id=f"batch-{j}")
        tg = job.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.cpu = cpu
        tg.tasks[0].resources.memory_mb = mem
        tg.tasks[0].resources.networks = []
        h.state.upsert_job(h.next_index(), job)
        jobs.append(job)
    return h, jobs


def solve_plans(h, jobs, backend):
    """One plan per job against one snapshot. backend parametrizes the
    plan SHAPE: the tpu dense kernel spreads jobs over disjoint node
    ranges while the host stack's binpack piles onto the same best
    nodes — the merge/conflict partition must be identity-preserving
    for both."""
    from nomad_tpu.scheduler.context import SchedulerConfig

    snap = h.snapshot()
    evals = [mock.eval_for_job(j) for j in jobs]
    # small_batch_threshold routes the whole batch through the host
    # GenericStack (backend=host shape) or the dense kernel (tpu shape)
    cfg = SchedulerConfig(
        backend="tpu",
        small_batch_threshold=(10**9 if backend == "host" else 0),
    )
    plans = solve_eval_batch(snap, h, evals, cfg)
    return [plans[ev.id] for ev in evals]


def copy_plans(plans):
    """Deep copies via the wire codec: the store's owned-alloc path
    stamps submitted objects in place, so each apply run needs its own
    object graph."""
    return [codec.unpack(codec.pack(p)) for p in plans]


def make_applier(state):
    log = InmemLog(FSM(state), start_index=state.latest_index())
    queue = PlanQueue()
    queue.set_enabled(True)
    return PlanApplier(queue, state, log.apply, log.apply_async), queue


def state_fingerprint(state):
    """Everything identity-relevant, minus raft indexes (a merged commit
    is one log entry where serial was N — indexes legitimately differ)."""
    allocs = {}
    for a in state.allocs():
        r = a.comparable_resources()
        allocs[a.id] = (
            a.job_id,
            a.name,
            a.node_id,
            a.task_group,
            a.desired_status,
            a.client_status,
            r.cpu,
            r.memory_mb,
        )
    by_node = {
        n.id: sorted(a.id for a in state.allocs_by_node(n.id))
        for n in state.nodes()
    }
    by_job = {
        (j.namespace, j.id): sorted(
            a.id for a in state.allocs_by_job(j.namespace, j.id)
        )
        for j in state.jobs()
    }
    usage = {n.id: state.node_usage(n.id) for n in state.nodes()}
    evals = {e.id: e.status for e in state.evals()}
    return allocs, by_node, by_job, usage, evals


def clone_store(state) -> StateStore:
    s = StateStore()
    s.restore_from(state.serialize())
    return s


@pytest.mark.parametrize("backend", BACKENDS)
def test_merged_batch_state_identical_to_serial(backend):
    h, jobs = build_state()
    plans = solve_plans(h, jobs, backend)

    serial_state = clone_store(h.state)
    batch_state = clone_store(h.state)

    applier_s, _ = make_applier(serial_state)
    serial_results = [applier_s.apply_one(p) for p in copy_plans(plans)]

    applier_b, _ = make_applier(batch_state)
    batch_results = applier_b.apply_batch(copy_plans(plans))

    # per-plan commit outcomes match (full/partial and committed counts)
    for p, rs, rb in zip(plans, serial_results, batch_results):
        assert rs.full_commit(p)[1:] == rb.full_commit(p)[1:]
        assert (rs.refresh_index > 0) == (rb.refresh_index > 0)

    fs = state_fingerprint(serial_state)
    fb = state_fingerprint(batch_state)
    # alloc ids differ per solve only if plans differed — here the SAME
    # plans were applied, so identity is exact, ids included
    assert fs == fb


@pytest.mark.parametrize("backend", BACKENDS)
def test_queue_batch_path_matches_direct(backend):
    """enqueue_batch → applier loop produces the same state as the
    direct apply_batch call (exercises the dequeue routing + the
    pipelined serial fallback)."""
    h, jobs = build_state()
    plans = solve_plans(h, jobs, backend)

    direct_state = clone_store(h.state)
    applier_d, _ = make_applier(direct_state)
    applier_d.apply_batch(copy_plans(plans))

    queued_state = clone_store(h.state)
    applier_q, queue = make_applier(queued_state)
    applier_q.start()
    try:
        futs = queue.enqueue_batch(copy_plans(plans))
        results = [f.result(timeout=30) for f in futs]
    finally:
        applier_q.stop()
    assert all(isinstance(r, PlanResult) for r in results)
    assert state_fingerprint(direct_state) == state_fingerprint(queued_state)


def _manual_plan(job, allocs_spec):
    """A hand-built plan placing (node, cpu, mem) allocs for `job`."""
    from nomad_tpu.structs import (
        AllocatedResources,
        AllocatedTaskResources,
        Allocation,
        generate_uuid,
    )

    plan = Plan(eval_id=generate_uuid(), priority=job.priority, job=job)
    for node, cpu, mem in allocs_spec:
        alloc = Allocation(
            id=generate_uuid(),
            namespace=job.namespace,
            eval_id=plan.eval_id,
            name=f"{job.id}.web[0]",
            node_id=node.id,
            node_name=node.name,
            job_id=job.id,
            task_group=job.task_groups[0].name,
            resources=AllocatedResources(
                tasks={"web": AllocatedTaskResources(cpu=cpu, memory_mb=mem)}
            ),
        )
        plan.append_fresh_alloc(alloc, job)
    return plan


def test_same_job_plans_never_merge():
    """Node-disjoint plans for the SAME job must not merge: the bulk
    commit collapses each round's jobs by (namespace, id), so merging
    two versions of one job would re-attach the older plan's allocs to
    the newer version. The broker's per-job lock makes this unreachable
    from the worker; the partition enforces it for direct callers."""
    h, jobs = build_state(n_nodes=4, n_jobs=2, count=1)
    nodes = h.state.nodes()
    plan_a = _manual_plan(jobs[0], [(nodes[0], 400, 128)])
    plan_b = _manual_plan(jobs[0], [(nodes[1], 400, 128)])
    merged, serial = partition_plan_batch([plan_a, plan_b])
    assert merged == [0] and serial == [1]
    # different jobs on disjoint nodes still merge
    plan_c = _manual_plan(jobs[1], [(nodes[2], 400, 128)])
    merged2, serial2 = partition_plan_batch([plan_a, plan_c])
    assert merged2 == [0, 1] and serial2 == []


def test_merged_round_trims_duplicate_eval_name_mint():
    """The r15/r17 soak duplicate-alloc race, pinned: one plan carrying
    the same (eval, name) twice — or two merge-eligible plans minting it
    — must commit exactly ONE alloc per (eval, name). The later entrant
    is trimmed before the raft apply, the result reads as a partial
    commit (refresh set), and the trim counter fires."""
    from nomad_tpu import metrics
    from nomad_tpu.metrics import Registry

    old = metrics._install_registry(Registry())
    try:
        h, jobs = build_state(n_nodes=4, n_jobs=1, count=1)
        nodes = h.state.nodes()
        # one plan, TWO fresh allocs with the same name on different
        # nodes (the "one plan carrying the name twice" shape)
        plan = _manual_plan(
            jobs[0], [(nodes[0], 400, 128), (nodes[1], 400, 128)]
        )
        applier, _ = make_applier(h.state)
        (res,) = applier.apply_batch([plan])
        committed = [
            a for allocs in res.node_allocation.values() for a in allocs
        ]
        assert len(committed) == 1, committed
        assert not res.full_commit(plan)[0]
        assert res.refresh_index > 0
        stored = [
            a
            for a in h.state.allocs_by_job(jobs[0].namespace, jobs[0].id)
            if not a.terminal_status()
        ]
        assert len(stored) == 1
        c = metrics.snapshot()["counters"]
        assert c.get("nomad.plan_apply.dup_mint_trimmed") == 1
    finally:
        metrics._install_registry(old)


def test_merged_round_trims_duplicate_across_plans():
    """Two plans for the same eval (the second job-detached, so the
    same-job merge exclusion cannot catch it) minting the same name in
    one batch: the second entrant's row is trimmed even when it lands
    in a later merge round."""
    h, jobs = build_state(n_nodes=4, n_jobs=1, count=1)
    nodes = h.state.nodes()
    plan_a = _manual_plan(jobs[0], [(nodes[0], 400, 128)])
    plan_b = _manual_plan(jobs[0], [(nodes[1], 400, 128)])
    # same eval, same alloc name, different ids — the forensics shape
    for allocs in plan_b.node_allocation.values():
        for a in allocs:
            a.eval_id = plan_a.eval_id
    plan_b.eval_id = plan_a.eval_id
    plan_b.job = None  # job-detached: merges despite the same job id
    applier, _ = make_applier(h.state)
    res_a, res_b = applier.apply_batch([plan_a, plan_b])
    assert res_a.full_commit(plan_a)[0]
    assert not res_b.full_commit(plan_b)[0]
    names = [
        (a.eval_id, a.name)
        for a in h.state.allocs_by_job(jobs[0].namespace, jobs[0].id)
        if not a.terminal_status()
    ]
    assert len(names) == len(set(names)) == 1


def test_merged_round_never_trims_existing_alloc_updates():
    """Updates of EXISTING allocs (inplace updates, followup-eval
    annotations) keep their original minting eval_id/name — two plans
    in one batch carrying the same stored alloc are last-writer-wins,
    never 'duplicate mints': the guard must not trim them."""
    from nomad_tpu import metrics
    from nomad_tpu.metrics import Registry

    h, jobs = build_state(n_nodes=2, n_jobs=1, count=1)
    nodes = h.state.nodes()
    # commit one real alloc first
    seed = _manual_plan(jobs[0], [(nodes[0], 400, 128)])
    applier, _ = make_applier(h.state)
    (res0,) = applier.apply_batch([seed])
    assert res0.full_commit(seed)[0]
    stored = next(
        a
        for a in h.state.allocs_by_job(jobs[0].namespace, jobs[0].id)
        if not a.terminal_status()
    )
    assert stored.create_index > 0

    def update_plan():
        p = Plan(eval_id=stored.eval_id, priority=50, job=None)
        annotated = stored.copy()
        annotated.followup_eval_id = "follow-" + annotated.id[:8]
        p.append_alloc(annotated, annotated.job)
        return p

    old = metrics._install_registry(Registry())
    try:
        res_a, res_b = applier.apply_batch([update_plan(), update_plan()])
        committed = [
            a
            for r in (res_a, res_b)
            for allocs in r.node_allocation.values()
            for a in allocs
        ]
        assert len(committed) == 2, "an existing-alloc update was trimmed"
        c = metrics.snapshot()["counters"]
        assert not c.get("nomad.plan_apply.dup_mint_trimmed")
    finally:
        metrics._install_registry(old)


def test_forced_node_conflict_partitions_and_matches_serial():
    """Two plans fighting over one node: both ride the merged pass (the
    second is verified on the first's result, PR 35), and the final
    state (including the loser's rejection) must match all-serial
    application."""
    h, jobs = build_state(n_nodes=2, n_jobs=2, count=1)
    nodes = h.state.nodes()
    target = nodes[0]
    # each plan asks for 3000 cpu on the SAME node; only one fits
    plan_a = _manual_plan(jobs[0], [(target, 3000, 512)])
    plan_b = _manual_plan(jobs[1], [(target, 3000, 512)])

    merged, serial = partition_plan_batch([plan_a, plan_b])
    assert merged == [0, 1] and serial == []

    serial_state = clone_store(h.state)
    applier_s, _ = make_applier(serial_state)
    sa, sb = [applier_s.apply_one(p) for p in copy_plans([plan_a, plan_b])]

    batch_state = clone_store(h.state)
    applier_b, _ = make_applier(batch_state)
    ba, bb = applier_b.apply_batch(copy_plans([plan_a, plan_b]))

    assert sa.full_commit(plan_a)[0] and ba.full_commit(plan_a)[0]
    # the conflicting plan is rejected with a refresh in BOTH paths
    assert not sb.full_commit(plan_b)[0] and sb.refresh_index > 0
    assert not bb.full_commit(plan_b)[0] and bb.refresh_index > 0
    assert state_fingerprint(serial_state) == state_fingerprint(batch_state)


def test_partial_commit_retry_converges_identically():
    """A partially-rejected plan retried against refreshed state lands
    its remainder identically through both paths (the worker's
    partial-commit → retry-eval flow at the applier level)."""
    h, jobs = build_state(n_nodes=2, n_jobs=2, count=1)
    n0, n1 = h.state.nodes()
    plan_a = _manual_plan(jobs[0], [(n0, 3000, 512)])
    # B places on BOTH nodes; the n0 placement loses to A, n1 commits
    plan_b = _manual_plan(jobs[1], [(n0, 3000, 512), (n1, 3000, 512)])
    # the retry for B's uncommitted remainder, built ONCE so both paths
    # apply the same object graph (ids included) and exact identity holds
    retry = _manual_plan(jobs[1], [(n1, 500, 128)])

    def run(state, batched: bool):
        applier, _ = make_applier(state)
        if batched:
            ra, rb = applier.apply_batch(copy_plans([plan_a, plan_b]))
        else:
            ra = applier.apply_one(copy_plans([plan_a])[0])
            rb = applier.apply_one(copy_plans([plan_b])[0])
        assert ra.full_commit(plan_a)[0]
        assert not rb.full_commit(plan_b)[0] and rb.refresh_index > 0
        # retry the remainder on the surviving node, as the worker's
        # requeued eval would after its snapshot refresh
        rt = copy_plans([retry])[0]
        rr = applier.apply_batch([rt])[0] if batched else applier.apply_one(rt)
        assert rr.full_commit(retry)[0]
        return state

    fs = state_fingerprint(run(clone_store(h.state), batched=False))
    fb = state_fingerprint(run(clone_store(h.state), batched=True))
    assert fs == fb


def test_merged_batch_with_stops_and_disjoint_updates():
    """Stops (node_update) ride the merge too: a batch mixing fresh
    placements and stop-plans for disjoint nodes commits in one entry
    with the same final state as serial."""
    h, jobs = build_state(n_nodes=6, n_jobs=3, count=4)
    plans = solve_plans(h, jobs, "tpu")
    # land the initial placements
    base = clone_store(h.state)
    applier0, _ = make_applier(base)
    applier0.apply_batch(copy_plans(plans))

    # now stop job 0's allocs and place job 1's second wave
    stop_plan = Plan(eval_id="stop-ev", priority=50, job=jobs[0])
    for a in base.allocs_by_job(jobs[0].namespace, jobs[0].id):
        stop_plan.append_stopped_alloc(a, "test stop", "")
    nodes_used = {a.node_id for a in base.allocs()}
    free_nodes = [n for n in base.nodes() if n.id not in nodes_used]
    place_plan = _manual_plan(jobs[1], [(free_nodes[0], 400, 128)])

    serial_state = clone_store(base)
    applier_s, _ = make_applier(serial_state)
    for p in copy_plans([stop_plan, place_plan]):
        applier_s.apply_one(p)

    batch_state = clone_store(base)
    applier_b, _ = make_applier(batch_state)
    merged, serial = partition_plan_batch([stop_plan, place_plan])
    assert serial == []  # disjoint nodes: everything merges
    applier_b.apply_batch(copy_plans([stop_plan, place_plan]))

    assert state_fingerprint(serial_state) == state_fingerprint(batch_state)


# ---------------------------------------------------------------------------
# Plans that share a node ride ONE pass, in submission order (PR 35): a
# plan is judged on what the plans before it placed, stopped and evicted,
# exactly as if everything had been serial. An eviction stands in its
# preemptor's plan alone; a plan that stands on the room another plan's
# victim left over is refused wherever that plan was.
# ---------------------------------------------------------------------------


def _standing(h, job, node, cpu, index):
    a = mock.alloc(job_=job, node_=node)
    a.name = f"{job.id}.web[{index}]"
    a.resources.tasks["web"].cpu = cpu
    a.resources.tasks["web"].memory_mb = 64
    a.resources.tasks["web"].networks = []
    a.client_status = "running"
    h.state.upsert_allocs(h.next_index(), [a])
    return h.state.alloc_by_id(a.id)


def _eviction_cell():
    """One node of 4,000 MHz: a victim of 3,000, a bystander of 600, 400
    free. Plan A evicts the victim for a placement of 1,000 and leaves
    2,000 over; plan B places 2,000 there, which only that leftover
    holds. A second, empty node for whatever needs one."""
    h, jobs = build_state(n_nodes=2, n_jobs=4, count=1)
    n0, n1 = h.state.nodes()
    victim = _standing(h, jobs[2], n0, 3000, 0)
    _standing(h, jobs[3], n0, 600, 0)
    plan_a = _manual_plan(jobs[0], [(n0, 1000, 64)])
    by = plan_a.node_allocation[n0.id][0].id
    plan_a.append_preempted_alloc(victim, by)
    plan_b = _manual_plan(jobs[1], [(n0, 2000, 64)])
    return h, jobs, (n0, n1), victim, plan_a, plan_b


def _same_store(a, b) -> bool:
    """state_fingerprint, but for the evals, which are counted: a
    preempted job's follow-up eval is minted with a fresh id each time."""
    fa, fb = state_fingerprint(a), state_fingerprint(b)
    return fa[:4] == fb[:4] and sorted(fa[4].values()) == sorted(
        fb[4].values())


def _rounds_of(fn):
    from nomad_tpu import metrics
    from nomad_tpu.metrics import Registry

    reg = Registry()
    old = metrics._install_registry(reg)
    try:
        out = fn()
        return out, reg.histogram_raw("nomad.plan_apply.batch_rounds")
    finally:
        metrics._install_registry(old)


def test_a_plan_stands_on_an_earlier_plans_eviction_in_one_pass():
    h, jobs, (n0, _), victim, plan_a, plan_b = _eviction_cell()
    serial_state = clone_store(h.state)
    applier_s, _ = make_applier(serial_state)
    for p in copy_plans([plan_a, plan_b]):
        applier_s.apply_one(p)

    batch_state = clone_store(h.state)
    applier_b, _ = make_applier(batch_state)
    (ra, rb), rounds = _rounds_of(
        lambda: applier_b.apply_batch(copy_plans([plan_a, plan_b])))
    assert ra.full_commit(plan_a)[0] and rb.full_commit(plan_b)[0]
    assert ra.alloc_index == rb.alloc_index  # one raft entry
    assert rounds["count"] == 1 and rounds["max"] == 1
    assert batch_state.alloc_by_id(victim.id).desired_status == "evict"
    assert batch_state.node_usage(n0.id)[0] == 600 + 1000 + 2000
    assert _same_store(serial_state, batch_state)


def test_the_plan_that_draws_is_refused_where_it_comes_first():
    h, jobs, (n0, _), victim, plan_a, plan_b = _eviction_cell()
    applier, _ = make_applier(h.state)
    rb, ra = applier.apply_batch([plan_b, plan_a])
    assert not rb.full_commit(plan_b)[0] and rb.refresh_index > 0
    assert ra.full_commit(plan_a)[0]
    assert h.state.node_usage(n0.id)[0] == 600 + 1000


def test_the_plan_that_draws_is_refused_where_the_preemptors_plan_was():
    """The preemptor's plan is refused (all-at-once, and its other node
    has no room): its eviction does not happen, and the plan behind it,
    which lists no victim of its own, finds no room and is refused on
    the node too. Nothing is evicted for a preemptor that never lived."""
    h, jobs, (n0, n1), victim, plan_a, plan_b = _eviction_cell()
    _standing(h, jobs[3], n1, 3900, 1)
    extra = _manual_plan(jobs[0], [(n1, 1000, 64)])
    for allocs in extra.node_allocation.values():
        for a in allocs:
            a.eval_id = plan_a.eval_id
            a.name = f"{jobs[0].id}.web[1]"
            plan_a.append_fresh_alloc(a, jobs[0])
    plan_a.all_at_once = True
    serial_state = clone_store(h.state)
    applier_s, _ = make_applier(serial_state)
    for p in copy_plans([plan_a, plan_b]):
        applier_s.apply_one(p)

    batch_state = clone_store(h.state)
    applier_b, _ = make_applier(batch_state)
    ra, rb = applier_b.apply_batch(copy_plans([plan_a, plan_b]))
    assert ra.is_no_op() and ra.refresh_index > 0
    assert not rb.full_commit(plan_b)[0] and rb.refresh_index > 0
    assert batch_state.alloc_by_id(victim.id).desired_status == "run"
    assert batch_state.node_usage(n0.id)[0] == 3000 + 600
    assert _same_store(serial_state, batch_state)


def test_a_refused_preemptor_does_not_stop_a_plan_that_fits_by_itself():
    h, jobs, (n0, _), victim, _, _ = _eviction_cell()
    plan_a = _manual_plan(jobs[0], [(n0, 3900, 64)])  # 600 + 3900 > 4,000
    plan_a.append_preempted_alloc(
        victim, plan_a.node_allocation[n0.id][0].id)
    plan_b = _manual_plan(jobs[1], [(n0, 300, 64)])   # the free 400 holds it
    applier, _ = make_applier(h.state)
    ra, rb = applier.apply_batch([plan_a, plan_b])
    assert not ra.full_commit(plan_a)[0] and not ra.node_preemptions
    assert rb.full_commit(plan_b)[0]
    assert h.state.alloc_by_id(victim.id).desired_status == "run"
    assert h.state.node_usage(n0.id)[0] == 3000 + 600 + 300


def test_an_alloc_two_plans_stop_frees_its_room_once():
    """The victim is evicted by plan A and stopped by its own job's plan
    S; plan B behind them may count its 3,000 MHz once: 2,000 + 1,500
    over the 400 free and the 2,000 left over does not fit."""
    h, jobs, (n0, _), victim, plan_a, _ = _eviction_cell()
    plan_s = Plan(eval_id="stop-ev", priority=50, job=jobs[2])
    plan_s.append_stopped_alloc(victim, "job stopped", "")
    plan_b = _manual_plan(jobs[1], [(n0, 2000, 64)])
    plan_c = _manual_plan(jobs[3], [(n0, 1500, 64)])
    serial_state = clone_store(h.state)
    applier_s, _ = make_applier(serial_state)
    for p in copy_plans([plan_a, plan_s, plan_b, plan_c]):
        applier_s.apply_one(p)
    batch_state = clone_store(h.state)
    applier_b, _ = make_applier(batch_state)
    ra, rs, rb, rc = applier_b.apply_batch(
        copy_plans([plan_a, plan_s, plan_b, plan_c]))
    assert ra.full_commit(plan_a)[0] and rb.full_commit(plan_b)[0]
    assert not rc.full_commit(plan_c)[0] and rc.refresh_index > 0
    assert batch_state.node_usage(n0.id)[0] == 600 + 1000 + 2000
    assert _same_store(serial_state, batch_state)


def test_a_stop_of_what_the_pass_places_waits_for_the_next_pass():
    """The store applies an entry's stops before its placements: a plan
    that evicts an alloc an earlier plan of the batch places must land in
    a later entry, or the victim would outlive its eviction."""
    h, jobs = build_state(n_nodes=1, n_jobs=2, count=1)
    (n0,) = h.state.nodes()
    plan_a = _manual_plan(jobs[0], [(n0, 3000, 64)])
    fresh = plan_a.node_allocation[n0.id][0]
    plan_b = _manual_plan(jobs[1], [(n0, 3500, 64)])
    plan_b.append_preempted_alloc(fresh, plan_b.node_allocation[n0.id][0].id)
    serial_state = clone_store(h.state)
    applier_s, _ = make_applier(serial_state)
    for p in copy_plans([plan_a, plan_b]):
        applier_s.apply_one(p)
    batch_state = clone_store(h.state)
    applier_b, _ = make_applier(batch_state)
    (ra, rb), rounds = _rounds_of(
        lambda: applier_b.apply_batch(copy_plans([plan_a, plan_b])))
    assert ra.full_commit(plan_a)[0] and rb.full_commit(plan_b)[0]
    assert rounds["max"] == 2 and rb.alloc_index > ra.alloc_index
    assert batch_state.alloc_by_id(fresh.id).desired_status == "evict"
    assert batch_state.node_usage(n0.id)[0] == 3500
    assert _same_store(serial_state, batch_state)


@pytest.mark.parametrize("seed", [1, 2, 3, 5, 8, 3_000_000_019])
def test_plans_that_fight_over_nodes_commit_as_a_sequence_does(seed):
    """Seeded plans over three crowded nodes — placements of every
    size, stops, evictions of standing allocs, some for room that only
    an earlier plan's stop makes — through the merged pass and one after
    another: the same verdict for every plan, the same store."""
    import random

    rng = random.Random(seed)
    h, jobs = build_state(n_nodes=3, n_jobs=12, count=1)
    nodes = h.state.nodes()
    standing = [
        _standing(h, jobs[9 + i % 3], nodes[i % 3], rng.choice(
            [500, 800, 1200]), i)
        for i in range(9)
    ]
    plans = []
    for j in range(9):
        spec = [(rng.choice(nodes), rng.choice([300, 900, 1500, 2600]), 64)
                for _ in range(rng.randint(1, 3))]
        plan = _manual_plan(jobs[j], spec)
        for k, allocs in enumerate(plan.node_allocation.values()):
            for i, a in enumerate(allocs):
                a.name = f"{jobs[j].id}.web[{k}.{i}]"
        for v in rng.sample(standing, rng.randint(0, 2)):
            here = plan.node_allocation.get(v.node_id)
            if here and rng.random() < 0.7:
                plan.append_preempted_alloc(v, here[0].id)
            else:
                plan.append_stopped_alloc(v, "seeded stop", "")
        plans.append(plan)
    serial_state = clone_store(h.state)
    applier_s, _ = make_applier(serial_state)
    serial = [applier_s.apply_one(p) for p in copy_plans(plans)]
    batch_state = clone_store(h.state)
    applier_b, _ = make_applier(batch_state)
    batch = applier_b.apply_batch(copy_plans(plans))
    for p, rs, rb in zip(plans, serial, batch):
        assert rs.full_commit(p)[1:] == rb.full_commit(p)[1:]
        assert sorted(rs.node_preemptions) == sorted(rb.node_preemptions)
    assert any(not r.full_commit(p)[0] for p, r in zip(plans, batch))
    assert _same_store(serial_state, batch_state)
    for n in nodes:
        assert batch_state.node_usage(n.id)[0] <= 4000


# ---------------------------------------------------------------------------
# SoA/lazy vs eager-object differential identity battery (ISSUE 12): the
# array-native data plane (Plan.alloc_batches -> codec fold -> lazy store
# rows) must be INDISTINGUISHABLE from the eager per-row path. One solve
# produces the plans; codec copies feed each path its own object graph
# (ids included), so identity is exact. The eager comparator is
# Plan.materialize_batches() — the same rows, minted per-object.
# ---------------------------------------------------------------------------


def make_applier_with_log(state):
    log = InmemLog(FSM(state), start_index=state.latest_index())
    queue = PlanQueue()
    queue.set_enabled(True)
    return PlanApplier(queue, state, log.apply, log.apply_async), queue, log


def _soa_plans(h, jobs):
    plans = solve_plans(h, jobs, "tpu")
    assert any(p.alloc_batches for p in plans), (
        "precondition: the tpu fast-mint path must emit PlacementBatches"
    )
    return plans


def _eager_copy(plans):
    out = copy_plans(plans)
    for p in out:
        p.materialize_batches()
        assert not p.alloc_batches
    return out


def _alloc_bytes(state):
    """Per-row wire bytes keyed by id: every stored alloc byte-identical,
    independent of table iteration order."""
    return {a.id: codec.pack(a) for a in state.allocs()}


@pytest.mark.parametrize("native", ["c", "fallback"])
@pytest.mark.parametrize("mode", ["serial", "batch", "queue"])
def test_soa_vs_eager_identity(mode, native, monkeypatch):
    """Raft entries and store state are byte-identical between the SoA
    and eager paths, across the merged-plan-apply matrix (serial
    apply_one, merged apply_batch, and the queue's enqueue_batch
    routing) — with the store's bulk id-index insert running through
    the fastpack C entry point AND force-disabled onto the pure-Python
    loop. Wall-clock stamps are pinned so the two runs are
    bit-comparable."""
    import nomad_tpu.state.store as store_mod

    if native == "c":
        if not codec.warm_native():
            pytest.skip("no C toolchain on this box")
        assert codec.native_module() is not None
    else:
        # force the fallback: native_module() -> None, so
        # _upsert_batches_txn takes _store_rows_py
        monkeypatch.setattr(codec, "_fastpack", False)
        assert codec.native_module() is None

    monkeypatch.setattr(store_mod, "now_ns", lambda: 1_234_567_890)

    h, jobs = build_state(n_nodes=8, n_jobs=4, count=6)
    plans = _soa_plans(h, jobs)
    soa = copy_plans(plans)
    eager = _eager_copy(plans)

    def run(batch_plans):
        state = clone_store(h.state)
        applier, queue, log = make_applier_with_log(state)
        if mode == "serial":
            results = [applier.apply_one(p) for p in batch_plans]
        elif mode == "batch":
            results = applier.apply_batch(batch_plans)
        else:
            applier.start()
            try:
                futs = queue.enqueue_batch(batch_plans)
                results = [f.result(timeout=30) for f in futs]
            finally:
                applier.stop()
        return state, results, list(log._entries)

    s_state, s_results, s_entries = run(soa)
    e_state, e_results, e_entries = run(eager)

    # every plan fully committed through both paths
    for p, rs, re_ in zip(plans, s_results, e_results):
        assert rs.full_commit(p)[0] and re_.full_commit(p)[0]

    # raft entries: same count, same message types, BYTE-identical
    # payloads — the codec's PlanResult encoder folds batches into the
    # eager wire form exactly
    assert len(s_entries) == len(e_entries)
    for (si, st, sraw), (ei, et, eraw) in zip(s_entries, e_entries):
        assert (si, st) == (ei, et)
        assert sraw == eraw, f"raft entry {si} ({st}) diverged"

    # store state: semantic fingerprint AND per-row wire bytes
    assert state_fingerprint(s_state) == state_fingerprint(e_state)
    assert _alloc_bytes(s_state) == _alloc_bytes(e_state)
    # fast-mint-only plans insert in identical table order too: the
    # whole-store serialization is bit-equal
    assert s_state.serialize() == e_state.serialize()


def test_soa_rows_materialize_lazily_and_cache(monkeypatch):
    """The store holds AllocRow handles for batch rows until a reader
    crosses the materialization boundary; materialized views are cached
    (repeated reads return the same objects)."""
    from nomad_tpu.state.store import TABLE_ALLOCS
    from nomad_tpu.structs.placement_batch import AllocRow

    h, jobs = build_state(n_nodes=6, n_jobs=2, count=5)
    plans = _soa_plans(h, jobs)
    state = clone_store(h.state)
    applier, _, _ = make_applier_with_log(state)
    applier.apply_batch(copy_plans(plans))

    rows = [
        v
        for v in state._tables[TABLE_ALLOCS].values()
        if v.__class__ is AllocRow
    ]
    assert rows, "batch rows should land as lazy handles"
    # handles answer the hot fields from columns without materializing
    r = rows[0]

    def _cached(row):
        cache = getattr(row.b, "_rows", None)
        return cache is not None and cache[row.i] is not None

    assert not _cached(r)
    assert r.id and r.node_id and not r.terminal_status()
    assert not _cached(r)

    # the read mixin materializes; repeated reads share the cached view
    a1 = state.alloc_by_id(r.id)
    a2 = state.alloc_by_id(r.id)
    assert type(a1).__name__ == "Allocation"
    assert a1 is a2
    by_job = state.allocs_by_job(a1.namespace, a1.job_id)
    assert any(x is a1 for x in by_job)


def test_soa_partial_rejection_trims_batch_rows():
    """A node-level rejection drops exactly that node's batch rows (the
    take() mask) and sets refresh, mirroring the eager path's per-node
    drop."""
    import numpy as np

    from nomad_tpu.scheduler.context import SchedulerConfig
    from nomad_tpu.server.plan_apply import evaluate_plan

    h, jobs = build_state(n_nodes=3, n_jobs=1, count=9, cpu=1200, mem=256)
    plans = _soa_plans(h, jobs)
    plan = copy_plans(plans)[0]
    assert plan.alloc_batches
    # consume one target node almost fully so the plan's rows there no
    # longer fit at verification time (the stale-snapshot race)
    b = plan.alloc_batches[0]
    victim_nid, _ti, cnt = b.touched_nodes()[0]
    node = h.state.node_by_id(victim_nid)
    filler = _manual_plan(mock.job(id="filler"), [(node, 3600, 7000)])
    state = clone_store(h.state)
    state.upsert_job(state.latest_index() + 1, filler.job)
    applier, _, _ = make_applier_with_log(state)
    assert applier.apply_one(filler).full_commit(filler)[0]

    result = evaluate_plan(state.snapshot(), plan)
    assert result.refresh_index > 0
    kept = sum(len(bb) for bb in result.alloc_batches) + sum(
        len(v) for v in result.node_allocation.values()
    )
    total = sum(len(bb) for bb in plan.alloc_batches)
    assert kept == total - cnt
    for bb in result.alloc_batches:
        assert victim_nid not in {nid for nid, _t, _c in bb.touched_nodes()}


@pytest.mark.parametrize("soa", ["1", "0"])
def test_soa_chaos_kill_leader_during_replay(soa, tmp_path, monkeypatch):
    """The identity battery's chaos leg: the kill-leader-during-replay
    scenario (the harness's hardest replay race) holds its invariants —
    no acked write lost, no duplicate alloc — with SoA placements ON
    and OFF; the lazy data plane changes no durability semantics."""
    monkeypatch.setenv("NOMAD_TPU_SOA", soa)
    from tests.test_chaos import test_leader_kill_during_log_replay

    test_leader_kill_during_log_replay(tmp_path)


def test_leadership_transfer_mid_remote_solve_nacks_not_drops():
    """Solver-pool regression (docs/solver-pool.md): a leadership
    transfer aborts in-flight pool dispatches, and the commit stage must
    NACK the aborted batch — its evals redeliver on the new leader —
    never ack it or drop it on the floor. The abort path raises
    CancelledError (not a retriable DeviceFault), so it must NOT trip
    the host-fallback re-solve either: the new leader owns the re-solve."""
    import threading

    from nomad_tpu.server.solver_pool import (
        RemotePendingBatch, SolverPool, _Dispatch,
    )
    from nomad_tpu.server.worker import TPUBatchWorker

    class _Broker:
        def __init__(self):
            self.nacked, self.acked = [], []

        def nack(self, eid, tok):
            self.nacked.append(eid)

        def ack(self, eid, tok):
            self.acked.append(eid)

    class _Srv:
        plan_queue = None

        def __init__(self):
            self.eval_broker = _Broker()

    class _Cluster:
        node_id = "s0"

    srv = _Srv()
    w = TPUBatchWorker(srv, batch_size=4)
    pool = SolverPool(_Cluster())
    try:
        ev = mock.evaluation()
        d = _Dispatch("s1", ("127.0.0.1", 1))
        pool._inflight.add(d)
        pending = RemotePendingBatch(pool, d, None, [ev], None, w.config)

        # the leader-change hook (_on_leader_change) aborts in-flight
        # dispatches before revoking leadership
        assert pool.abort_inflight() == 1
        assert pool.aborted == 1

        committed = threading.Event()
        outcome = {}
        w._commit([(ev, "tok")], pending, None, committed, outcome, None)

        assert srv.eval_broker.nacked == [ev.id], "aborted eval not nacked"
        assert srv.eval_broker.acked == []
        assert outcome["ok"] is False
        assert committed.is_set(), "chain cutoff must fire on abort"
        # no host fallback ran: the batch has no plans, only a nack
        assert pending._finished is False
    finally:
        pool.stop()
