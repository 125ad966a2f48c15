"""Server pipeline tests: broker, plan queue/applier, workers, blocked
evals, heartbeats (reference analogs: nomad/eval_broker_test.go,
nomad/plan_apply_test.go, nomad/worker_test.go)."""

import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server import EvalBroker, Server, evaluate_plan
from nomad_tpu.server.eval_broker import FAILED_QUEUE
from nomad_tpu.structs import Plan, PlanResult


# ---------------------------------------------------------------------------
# Broker
# ---------------------------------------------------------------------------


def test_broker_priority_and_fifo():
    b = EvalBroker()
    b.set_enabled(True)
    low = mock.evaluation(priority=10)
    high = mock.evaluation(priority=90)
    mid1 = mock.evaluation(priority=50)
    mid2 = mock.evaluation(priority=50)
    for e in (low, mid1, mid2, high):
        b.enqueue(e)
    got = [b.dequeue(["service"], timeout_s=1)[0].id for _ in range(4)]
    assert got == [high.id, mid1.id, mid2.id, low.id]
    b.set_enabled(False)


def test_broker_per_job_serialization():
    b = EvalBroker()
    b.set_enabled(True)
    job_id = "serial-job"
    e1 = mock.evaluation(job_id=job_id)
    e2 = mock.evaluation(job_id=job_id)
    b.enqueue(e1)
    b.enqueue(e2)
    got1, tok1 = b.dequeue(["service"], timeout_s=1)
    assert got1.id == e1.id
    # e2 must NOT be dequeueable while e1 is in flight
    got_none, _ = b.dequeue(["service"], timeout_s=0.1)
    assert got_none is None
    b.ack(e1.id, tok1)
    got2, tok2 = b.dequeue(["service"], timeout_s=1)
    assert got2.id == e2.id
    b.ack(e2.id, tok2)
    b.set_enabled(False)


def test_broker_nack_requeues_then_fails():
    b = EvalBroker(nack_delay_s=0.01, delivery_limit=2)
    b.set_enabled(True)
    e = mock.evaluation()
    b.enqueue(e)
    got, tok = b.dequeue(["service"], timeout_s=1)
    b.nack(got.id, tok)
    got2, tok2 = b.dequeue(["service"], timeout_s=2)
    assert got2.id == e.id
    b.nack(got2.id, tok2)  # second nack hits the delivery limit
    got3, _ = b.dequeue(["service"], timeout_s=0.3)
    assert got3 is None  # went to failed queue, not service
    failed, _ = b.dequeue([FAILED_QUEUE], timeout_s=0.5)
    assert failed is not None and failed.id == e.id
    b.set_enabled(False)


def test_broker_scheduler_type_routing():
    b = EvalBroker()
    b.set_enabled(True)
    svc = mock.evaluation(type="service")
    sys_ = mock.evaluation(type="system")
    b.enqueue(svc)
    b.enqueue(sys_)
    got, tok = b.dequeue(["system"], timeout_s=1)
    assert got.id == sys_.id
    b.ack(got.id, tok)
    got2, tok2 = b.dequeue(["service"], timeout_s=1)
    assert got2.id == svc.id
    b.set_enabled(False)


def test_broker_delayed_eval():
    from nomad_tpu.structs import now_ns

    b = EvalBroker()
    b.set_enabled(True)
    e = mock.evaluation(wait_until_ns=now_ns() + int(0.2 * 1e9))
    b.enqueue(e)
    got, _ = b.dequeue(["service"], timeout_s=0.05)
    assert got is None  # not ready yet
    got2, tok = b.dequeue(["service"], timeout_s=2)
    assert got2 is not None and got2.id == e.id
    b.ack(got2.id, tok)
    b.set_enabled(False)


def test_broker_token_mismatch():
    b = EvalBroker()
    b.set_enabled(True)
    e = mock.evaluation()
    b.enqueue(e)
    got, tok = b.dequeue(["service"], timeout_s=1)
    with pytest.raises(ValueError):
        b.ack(got.id, "wrong-token")
    b.ack(got.id, tok)
    b.set_enabled(False)


# ---------------------------------------------------------------------------
# Plan applier verification
# ---------------------------------------------------------------------------


def test_evaluate_plan_rejects_overcommit():
    from nomad_tpu.state import StateStore

    s = StateStore()
    node = mock.node()
    s.upsert_node(1, node)
    job = mock.job()
    s.upsert_job(2, job)
    # existing allocs fill the node (8 x 500)
    existing = [mock.alloc(job, node, index=i) for i in range(8)]
    s.upsert_allocs(3, existing)
    plan = Plan(eval_id="e", job=job)
    overflow = mock.alloc(job, node, index=9)
    plan.append_alloc(overflow, job)
    result = evaluate_plan(s.snapshot(), plan)
    assert result.node_allocation == {}
    assert result.refresh_index > 0

    # stopping an alloc frees room: same plan plus a stop is accepted
    plan2 = Plan(eval_id="e2", job=job)
    plan2.append_stopped_alloc(existing[0], "making room")
    plan2.append_alloc(overflow, job)
    result2 = evaluate_plan(s.snapshot(), plan2)
    assert len(result2.node_allocation.get(node.id, [])) == 1


def test_evaluate_plan_rejects_down_node():
    from nomad_tpu.state import StateStore

    s = StateStore()
    node = mock.node()
    s.upsert_node(1, node)
    s.update_node_status(2, node.id, "down")
    job = mock.job()
    plan = Plan(eval_id="e", job=job)
    plan.append_alloc(mock.alloc(job, node), job)
    result = evaluate_plan(s.snapshot(), plan)
    assert result.node_allocation == {}


# ---------------------------------------------------------------------------
# Full single-process pipeline through the Server
# ---------------------------------------------------------------------------


@pytest.fixture
def server():
    s = Server(num_workers=2)
    s.establish_leadership()
    yield s
    s.shutdown()


def test_server_job_register_to_allocs(server):
    for _ in range(5):
        server.node_register(mock.node())
    job = mock.job()
    eval_id = server.job_register(job)
    assert eval_id
    assert server.wait_for_evals(10)
    allocs = server.state.allocs_by_job(job.namespace, job.id)
    assert len(allocs) == 10
    ev = server.state.eval_by_id(eval_id)
    assert ev.status == "complete"
    assert server.state.job_by_id(job.namespace, job.id).status == "running"


def test_server_deregister_stops(server):
    for _ in range(3):
        server.node_register(mock.node())
    job = mock.job()
    server.job_register(job)
    server.wait_for_evals(10)
    server.job_deregister(job.namespace, job.id)
    server.wait_for_evals(10)
    live = [
        a
        for a in server.state.allocs_by_job(job.namespace, job.id)
        if not a.terminal_status()
    ]
    assert live == []


def test_server_blocked_eval_unblocks_on_capacity(server):
    node = server_node = mock.node()
    server.node_register(node)
    job = mock.job()  # 10 x 500MHz; one node fits 8
    server.job_register(job)
    server.wait_for_evals(10)
    placed = [
        a
        for a in server.state.allocs_by_job(job.namespace, job.id)
        if not a.terminal_status()
    ]
    assert len(placed) == 8
    assert server.blocked_evals.blocked_count() == 1

    # new node arrives -> blocked eval unblocks -> remaining 2 place
    server.node_register(mock.node())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        live = [
            a
            for a in server.state.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()
        ]
        if len(live) == 10:
            break
        time.sleep(0.05)
    assert len(live) == 10


def test_server_node_down_reschedules(server):
    n1 = mock.node()
    n2 = mock.node()
    server.node_register(n1)
    server.node_register(n2)
    job = mock.job()
    job.task_groups[0].count = 2
    server.job_register(job)
    server.wait_for_evals(10)
    server.node_update_status(n1.id, "down")
    server.wait_for_evals(10)
    live = [
        a
        for a in server.state.allocs_by_job(job.namespace, job.id)
        if not a.terminal_status()
    ]
    assert len(live) == 2
    assert all(a.node_id == n2.id for a in live)


def test_server_failed_alloc_creates_reschedule_eval(server):
    server.node_register(mock.node())
    job = mock.job()
    job.task_groups[0].count = 1
    job.task_groups[0].reschedule_policy.delay_s = 0
    server.job_register(job)
    server.wait_for_evals(10)
    alloc = server.state.allocs_by_job(job.namespace, job.id)[0]
    failed = alloc.copy()
    failed.client_status = "failed"
    server.update_allocs_from_client([failed])
    server.wait_for_evals(10)
    pending = [
        a
        for a in server.state.allocs_by_job(job.namespace, job.id)
        if not a.terminal_status()
    ]
    assert len(pending) == 1
    assert pending[0].id != alloc.id
    assert pending[0].previous_allocation == alloc.id


def test_server_system_job_on_new_node(server):
    server.node_register(mock.node())
    job = mock.system_job()
    server.job_register(job)
    server.wait_for_evals(10)
    assert len(server.state.allocs_by_job(job.namespace, job.id)) == 1
    server.node_register(mock.node())
    server.wait_for_evals(10)
    live = [
        a
        for a in server.state.allocs_by_job(job.namespace, job.id)
        if not a.terminal_status()
    ]
    assert len(live) == 2


def test_server_tpu_batch_worker():
    s = Server(use_tpu_batch_worker=True)
    s.establish_leadership()
    try:
        for _ in range(10):
            s.node_register(mock.node())
        jobs = []
        for i in range(5):
            job = mock.job(id=f"tpu-batch-{i}")
            job.task_groups[0].count = 4
            s.job_register(job)
            jobs.append(job)
        assert s.wait_for_evals(30)
        for job in jobs:
            live = [
                a
                for a in s.state.allocs_by_job(job.namespace, job.id)
                if not a.terminal_status()
            ]
            assert len(live) == 4, job.id
    finally:
        s.shutdown()


def test_tpu_commit_chain_parent_failure_nacks_follower():
    """A batch that solved against the chained used' tensor of a batch
    whose commit FAILED baked phantom placements into its view —
    committing it would mint blocked evals for capacity that is free.
    The commit stage must nack it (evals redeliver, re-solve clean)
    without ever touching the device results."""
    import threading

    s = Server(use_tpu_batch_worker=True)
    w = s.tpu_worker
    broker = s.eval_broker
    broker.nack_delay_s = 0.01
    broker.set_enabled(True)
    ev = mock.evaluation()
    broker.enqueue(ev)
    got, tok = broker.dequeue(["service"], timeout_s=1)
    assert got is not None

    class MustNotFinish:
        def finish(self):
            raise AssertionError("finish() must not run when parent failed")

    committed = threading.Event()
    outcome = {"ok": None}
    w._commit(
        [(got, tok)], MustNotFinish(), None, committed, outcome,
        chained_on=({"ok": False}, 7),
    )
    assert committed.is_set()
    assert outcome["ok"] is False
    again, _ = broker.dequeue(["service"], timeout_s=2)
    assert again is not None and again.id == ev.id


def test_tpu_commit_partial_commit_fails_chain_verdict():
    """A partially-committed batch (applier trimmed/rejected some plans)
    must record a FAILED chain verdict: the trimmed placements are baked
    into the chained used' tensor but never landed, so a follower that
    chained on it has to re-solve just as for a full commit failure."""
    import threading

    s = Server(use_tpu_batch_worker=True)
    w = s.tpu_worker
    broker = s.eval_broker
    broker.nack_delay_s = 0.01
    broker.set_enabled(True)
    ev = mock.evaluation()
    broker.enqueue(ev)
    got, tok = broker.dequeue(["service"], timeout_s=1)
    assert got is not None

    class NoPlans:
        def finish(self):
            return {}

    w._commit_batch = (
        lambda evals, plans, snapshot, blocked_basis=None, lane="batch":
        False  # partial
    )
    committed = threading.Event()
    outcome = {"ok": None}
    w._commit([(got, tok)], NoPlans(), None, committed, outcome, None)
    assert committed.is_set()
    assert outcome["ok"] is False
    # the batch itself is still acked: the committed subset landed and
    # the partial-commit path requeues retry evals for the remainder —
    # only the CHAIN verdict is a failure
    with pytest.raises(ValueError):
        broker.ack(got.id, tok)


def test_tpu_commit_cancelled_future_nacks_batch():
    """concurrent.futures.CancelledError is BaseException since py3.8:
    plan futures cancelled by a queue disable (leadership loss) must
    still nack the batch and record the failed outcome, not escape the
    commit stage's guard and kill the tpu-batch-commit thread."""
    import threading
    from concurrent.futures import CancelledError

    s = Server(use_tpu_batch_worker=True)
    w = s.tpu_worker
    broker = s.eval_broker
    broker.nack_delay_s = 0.01
    broker.set_enabled(True)
    ev = mock.evaluation()
    broker.enqueue(ev)
    got, tok = broker.dequeue(["service"], timeout_s=1)
    assert got is not None

    class CancelledPending:
        def finish(self):
            raise CancelledError()

    committed = threading.Event()
    outcome = {"ok": None}
    w._commit(
        [(got, tok)], CancelledPending(), None, committed, outcome,
        chained_on=None,
    )
    assert committed.is_set()
    assert outcome["ok"] is False
    again, _ = broker.dequeue(["service"], timeout_s=2)
    assert again is not None and again.id == ev.id


def test_blocked_evals_missed_unblock():
    """Capacity that appears BETWEEN the scheduler snapshot and the
    block() call must re-enqueue immediately (reference
    blocked_evals.go missedUnblock — the lost-wakeup race)."""
    from nomad_tpu.server.blocked_evals import BlockedEvals
    from nomad_tpu.structs import Evaluation, generate_uuid

    requeued = []
    be = BlockedEvals(requeued.append)
    be.set_enabled(True)

    def mk_eval(snapshot_index, classes=None, escaped=False):
        return Evaluation(
            id=generate_uuid(),
            namespace="default",
            job_id="j1",
            type="service",
            status="blocked",
            snapshot_index=snapshot_index,
            class_eligibility=classes or {},
            escaped_computed_class=escaped,
        )

    # Node of class c1 became ready at index 10.
    be.unblock("c1", index=10)
    assert requeued == []  # nothing was blocked yet

    # Eval snapshotted at index 5 (before the capacity change): missed.
    be.block(mk_eval(5, {"c1": True}))
    assert len(requeued) == 1 and requeued[0].status == "pending"

    # Eval snapshotted at index 15 (after): genuinely blocked.
    be.block(mk_eval(15, {"c1": True}))
    assert len(requeued) == 1
    assert be.blocked_count() == 1

    # Escaped eval with an old snapshot: any capacity change counts.
    be.untrack("default", "j1")
    be.block(mk_eval(5, escaped=True))
    assert len(requeued) == 2

    # Ineligible class does not count as missed capacity.
    be.untrack("default", "j1")
    be.block(mk_eval(5, {"c1": False}))
    assert len(requeued) == 2
    assert be.blocked_count() == 1


def test_server_inplace_update_keeps_new_job_version(server):
    """Plan payloads are denormalized (alloc.job stripped, re-attached on
    apply): an in-place update must store the NEW job version, not revert
    to the existing alloc's old one (regression: plan normalization)."""
    for _ in range(3):
        server.node_register(mock.node())
    job = mock.job()
    job.task_groups[0].count = 3
    server.job_register(job)
    assert server.wait_for_evals(10)
    v0 = server.state.job_by_id(job.namespace, job.id).version

    update = job.copy()
    update.priority = job.priority + 10  # non-destructive: in-place update
    server.job_register(update)
    assert server.wait_for_evals(10)
    stored_job = server.state.job_by_id(job.namespace, job.id)
    assert stored_job.version == v0 + 1
    allocs = [
        a
        for a in server.state.allocs_by_job(job.namespace, job.id)
        if not a.terminal_status()
    ]
    assert len(allocs) == 3
    for a in allocs:
        assert a.job is not None
        assert a.job.version == stored_job.version, (
            f"alloc {a.id} reverted to job version {a.job.version}"
        )


def test_enabled_schedulers_shards_worker_pool():
    """Scheduler-type sharding (reference EnabledSchedulers,
    config.go:159 / worker.go:146): a server whose workers serve only
    sysbatch leaves service evals queued, while sysbatch work flows —
    the per-type partitioning VERDICT r4 item 7 requires."""
    import time as _time

    s = Server(num_workers=2, enabled_schedulers=["sysbatch"])
    s.establish_leadership()
    try:
        assert s.enabled_schedulers == ["sysbatch"]
        for w in s.workers:
            assert "service" not in w.schedulers
            assert "sysbatch" in w.schedulers
        for _ in range(3):
            s.node_register(mock.node())
        # a sysbatch job completes on the dedicated pool
        sysjob = mock.sysbatch_job(id="shard-sysbatch")
        s.job_register(sysjob)
        deadline = _time.monotonic() + 10
        while _time.monotonic() < deadline:
            allocs = s.state.allocs_by_job("default", sysjob.id)
            if len(allocs) == 3:
                break
            _time.sleep(0.05)
        assert len(s.state.allocs_by_job("default", sysjob.id)) == 3
        # a service job's eval stays PENDING: no worker serves its type
        svc = mock.job(id="shard-service")
        eval_id = s.job_register(svc)
        _time.sleep(1.0)
        ev = s.state.eval_by_id(eval_id)
        assert ev.status == "pending", (
            "service evals must sit queued on a sysbatch-only server"
        )
        assert s.state.allocs_by_job("default", svc.id) == []
    finally:
        s.shutdown()


def test_enabled_schedulers_rejects_unknown_type():
    with pytest.raises(ValueError, match="unknown types"):
        Server(num_workers=1, enabled_schedulers=["servise"])


def test_tpu_worker_interactive_lane_jumps_mega_batches():
    """ISSUE 15 priority lanes: an interactive (>= lane priority) eval
    arriving while mega-batches with a modeled device RTT stream
    through the TPU worker must be classified into the lane, solved
    alone via the host microsolve (zero device round-trip), and
    committed without riding any mega-batch — its wall time stays far
    under the batch cadence the RTT imposes."""
    import time

    from nomad_tpu import metrics
    from nomad_tpu.metrics import Registry
    from nomad_tpu.scheduler.context import SchedulerConfig

    from nomad_tpu.scheduler.tpu import solve_eval_batch
    from nomad_tpu.testing import Harness

    # warm the jit cache at the mega-batch shapes OUTSIDE the measured
    # window (the first dense solve otherwise compiles ~1s mid-test)
    wh = Harness()
    for _ in range(30):
        wh.state.upsert_node(wh.next_index(), mock.node())
    wjob = mock.job(id="warm")
    wjob.task_groups[0].count = 60
    wjob.task_groups[0].tasks[0].resources.networks = []
    wh.state.upsert_job(wh.next_index(), wjob)
    solve_eval_batch(
        wh.snapshot(), wh, [mock.eval_for_job(wjob)],
        SchedulerConfig(backend="tpu", small_batch_threshold=0),
    )

    old = metrics._install_registry(Registry())
    s = Server(
        use_tpu_batch_worker=True,
        scheduler_config=SchedulerConfig(
            backend="tpu", inject_device_latency_s=0.3
        ),
    )
    s.establish_leadership()
    try:
        for _ in range(30):
            s.node_register(mock.node())
        # mega stream: each job's 60 requests exceed the small-batch
        # threshold, so every batch runs the dense path and pays the
        # 0.3s modeled RTT
        for i in range(4):
            job = mock.job(id=f"mega-{i}")
            job.task_groups[0].count = 60
            job.task_groups[0].tasks[0].resources.cpu = 100
            job.task_groups[0].tasks[0].resources.memory_mb = 32
            job.task_groups[0].tasks[0].resources.networks = []
            s.job_register(job)
        time.sleep(0.1)  # let the first mega batch occupy the worker
        ia = mock.job(id="interactive-1")
        ia.priority = 70
        ia.task_groups[0].count = 1
        ia.task_groups[0].tasks[0].resources.networks = []
        t0 = time.perf_counter()
        s.job_register(ia)
        deadline = t0 + 20
        while time.perf_counter() < deadline:
            if any(
                not a.terminal_status()
                for a in s.state.allocs_by_job(ia.namespace, ia.id)
            ):
                break
            time.sleep(0.002)
        ia_wall = time.perf_counter() - t0
        assert any(
            not a.terminal_status()
            for a in s.state.allocs_by_job(ia.namespace, ia.id)
        ), "interactive eval never placed"
        # the lane histogram lands a beat after the plan commit that
        # made the alloc visible — settle before reading the registry
        deadline = time.perf_counter() + 10
        while time.perf_counter() < deadline:
            if "nomad.worker.lane.interactive_seconds" in (
                metrics.snapshot()["samples"]
            ):
                break
            time.sleep(0.01)
        snap = metrics.snapshot()
        counters = snap["counters"]
        assert counters.get("nomad.worker.lane.interactive", 0) >= 1
        assert counters.get("nomad.worker.lane.micro", 0) >= 1
        assert "nomad.worker.lane.interactive_seconds" in snap["samples"]
        # generous bound for a loaded 2-cpu box: still far under the
        # ~0.3s-per-batch cadence the mega stream pays (4 batches
        # would be >= 1.2s if the eval had to ride the stream's tail)
        assert ia_wall < 1.2, f"interactive eval took {ia_wall:.2f}s"
        assert s.wait_for_evals(60)
    finally:
        s.shutdown()
        metrics._install_registry(old)
