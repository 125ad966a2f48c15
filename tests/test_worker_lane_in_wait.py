"""The interactive lane served where the batch lane's solve thread blocks
on a commit (worker.py `TPUBatchWorker._serve_lane`; docs/pipeline.md
§ Priority lanes).

A batch that may preempt waits in `chain.wait` for the commit of the
batch in flight before it takes its snapshot. An interactive eval that
arrives meanwhile is solved and committed there: its commit lands before
the waiting batch's snapshot, so that batch sees the lane's placements
and evictions in the store.

The lane's solve reads the committed store: the batch in flight is
solved but not committed. Where the lane's plan and that batch's evict
the same victim, the applier evicts it once: the plan verified second is
refused on that node and trimmed, and its eval retried.

No test here sleeps: the worker's solve thread is the test's own thread,
and the commit in flight lands when the lane's commit has been recorded.
"""

import threading

import pytest

from nomad_tpu import metrics, mock, trace
from nomad_tpu.metrics import Registry
from nomad_tpu.server.eval_broker import EvalBroker
from nomad_tpu.server.plan_apply import PlanApplier
from nomad_tpu.server.plan_queue import PlanQueue
from nomad_tpu.server.raft import FSM, InmemLog
from nomad_tpu.server.worker import TPUBatchWorker, _Committed
from nomad_tpu.structs import Plan
from nomad_tpu.testing import Harness

WAIT_S = 30  # bound on every event wait and join; never slept through


class _Snapshot:
    def __init__(self, index):
        self.index = index

    def alloc_by_id(self, alloc_id):
        return None  # the batch in flight has committed nothing yet


class _State:
    def __init__(self, order):
        self.order = order
        self.index = 9

    def alloc_priority_tiers(self):
        return [10, 30, 50]

    def snapshot_min_index(self, index, timeout_s=None):
        self.order.append(("snapshot", self.index))
        return _Snapshot(self.index)

    def latest_index(self):
        return self.index


class _Broker:
    """One priority-70 eval, ready the first time the solve thread asks
    for an interactive eval — from inside the wait, whatever the wait."""

    def __init__(self, lane_eval):
        self.lane_eval = lane_eval
        self.asked = []
        self.acked = []

    def dequeue_ready(self, schedulers, timeout_s=None, min_priority=0):
        self.asked.append((timeout_s, min_priority))
        if self.lane_eval is not None and min_priority:
            ev, self.lane_eval = self.lane_eval, None
            return ev, "tok-lane", trace.now_ns()
        return None, "", 0

    def ack(self, eval_id, token):
        self.acked.append(eval_id)

    def nack(self, eval_id, token):
        raise AssertionError(f"{eval_id} nacked")

    def annotate_trace(self, eval_id, **attrs):
        pass


class _PlanQueue:
    def depth(self):
        return 0


class _Srv:
    def __init__(self, order, lane_eval):
        self.state = _State(order)
        self.eval_broker = _Broker(lane_eval)
        self.plan_queue = _PlanQueue()


class _Pending:
    used_micro = False
    chain_accepted = False
    solved_in_begin = False
    chain = None

    def finish(self):
        return {}


@pytest.fixture
def fresh():
    old = metrics._install_registry(Registry())
    trace.set_enabled(True)
    trace.recorder().clear()
    yield
    trace.set_enabled(False)
    metrics._install_registry(old)


def test_a_batch_in_chain_wait_serves_an_arriving_lane_eval_first(
        fresh, monkeypatch):
    order: list = []
    lane_ev = mock.evaluation(priority=70)
    w = TPUBatchWorker(_Srv(order, lane_ev), pipeline=True, lane_priority=60)
    w.prepare = lambda: None
    given = []

    def begin(snapshot, planner, evals, config, used_chain=None, **kw):
        given.append(([e.id for e in evals], dict(kw, used_chain=used_chain)))
        return _Pending()

    monkeypatch.setattr(
        "nomad_tpu.scheduler.tpu.solve_eval_batch_begin", begin)

    # the batch in flight: its commit lands once the lane's has been
    # recorded
    committed, lane_committed = threading.Event(), threading.Event()
    w._prev = (_Pending(), committed, {"ok": None}, 7)

    def commit_batch(evals, plans_, snapshot, blocked_basis=None,
                     lane="batch"):
        assert lane == "interactive"
        w.server.state.index += 1  # the lane's plan lands in the store
        order.append(("lane-commit", w.server.state.index))
        lane_committed.set()
        return True

    w._commit_batch = commit_batch

    def parent_commits():
        assert lane_committed.wait(WAIT_S)
        order.append(("committed", w.server.state.index))
        committed.set()

    t = threading.Thread(target=parent_commits)
    t.start()
    waiting = mock.evaluation(priority=50)  # a production batch
    w._solve_batch([waiting])
    t.join(WAIT_S)
    assert not t.is_alive()

    # the lane solved and committed inside the wait, before the waiting
    # batch took its snapshot — which holds the lane's commit
    assert order == [("snapshot", 9), ("lane-commit", 10),
                     ("committed", 10), ("snapshot", 10)]
    assert [ids for ids, _ in given] == [[lane_ev.id], [waiting.id]]
    # neither solve chained: the lane never does, and the waiting batch
    # took its snapshot after the commit it waited for
    assert [kw["used_chain"] for _, kw in given] == [None, None]
    assert w.server.eval_broker.acked == [lane_ev.id]
    # asked without waiting, for the lane's priority and up
    assert w.server.eval_broker.asked[0] == (0, 60)

    counters = metrics.snapshot()["counters"]
    assert counters["nomad.worker.lane.served_in_wait"] == 1
    assert counters["nomad.worker.lane.interactive"] == 1
    assert counters["nomad.worker.chain.waited"] == 1
    samples = metrics.snapshot()["samples"]
    assert samples["nomad.worker.lane.queue_seconds"]["count"] == 1
    assert samples["nomad.worker.lane.interactive_seconds"]["count"] == 1
    # the lane's trace says what the solve thread was doing when the
    # eval became ready
    rec = trace.recorder()
    (lane_trace,) = [rec.get(s["id"]) for s in rec.list(limit=100)
                     if s["name"] == "tpu.interactive"]
    (queue,) = [s for s in lane_trace["spans"] if s["name"] == "lane.queue"]
    assert queue["attrs"] == {"behind": "chain.wait"}


def test_a_stalled_batch_lane_serves_the_lane(fresh, monkeypatch):
    """The backpressure stall: the plan queue is deep, the batch lane
    takes nothing, and an interactive eval is served all the same."""
    order: list = []
    lane_ev = mock.evaluation(priority=70)
    srv = _Srv(order, lane_ev)
    stop = threading.Event()
    w = TPUBatchWorker(srv, pipeline=False, lane_priority=60)
    w._stop = stop
    served = []

    def run_interactive(ev, token, t_deq, idle=None, queue=None):
        served.append((ev.id, queue[1]))
        stop.set()

    w._run_interactive = run_interactive

    class _Deep:
        def depth(self):
            return w.backpressure.stall_depth

    srv.plan_queue = _Deep()
    w._run(stop)
    assert served == [(lane_ev.id, "stall")]
    counters = metrics.snapshot()["counters"]
    assert counters["nomad.worker.lane.served_in_wait"] == 1


# -- the wake: a wait ends when there is something to do ---------------------

@pytest.mark.parametrize("how, priority, woken", [
    ("enqueue", 70, True),
    ("enqueue", 60, True),   # the lane priority itself
    ("enqueue", 50, False),  # production rides the batch lane
    ("enqueue_all", 70, True),
    ("enqueue_all", 50, False),
    ("unwatched", 70, False),
])
def test_the_broker_wakes_a_watcher_when_an_interactive_eval_is_ready(
        how, priority, woken):
    broker = EvalBroker()
    broker.set_enabled(True)
    try:
        wake = threading.Event()
        broker.watch_ready(60, wake)
        ev = mock.evaluation(priority=priority)
        if how == "enqueue_all":
            broker.enqueue_all([mock.evaluation(priority=10), ev])
        else:
            if how == "unwatched":
                broker.unwatch_ready(wake)
            broker.enqueue(ev)
        assert wake.is_set() is woken
    finally:
        broker.set_enabled(False)


class _Wake(threading.Event):
    """The worker's wake, recording whether each wait was woken (True)
    or ran out (False); `first_wait` is set as the first wait begins."""

    def __init__(self):
        super().__init__()
        self.first_wait = threading.Event()
        self.woken: list[bool] = []

    def wait(self, timeout=None):
        self.first_wait.set()
        got = super().wait(timeout)
        self.woken.append(got)
        return got


def test_chain_wait_is_woken_by_the_lane_eval_and_by_the_commit(
        fresh, monkeypatch):
    """No wait of `chain.wait` runs out: the lane eval's arrival in the
    broker wakes it, and so does the commit it waits for."""
    order: list = []
    srv = _Srv(order, None)
    srv.eval_broker = broker = EvalBroker()
    broker.set_enabled(True)
    w = TPUBatchWorker(srv, pipeline=True, lane_priority=60)
    w.prepare = lambda: None
    w._wake = wake = _Wake()
    broker.watch_ready(60, wake)
    monkeypatch.setattr("nomad_tpu.scheduler.tpu.solve_eval_batch_begin",
                        lambda *a, **kw: _Pending())
    committed = _Committed(wake)
    w._prev = (_Pending(), committed, {"ok": None}, 7)

    def commit_batch(evals, plans_, snapshot, blocked_basis=None,
                     lane="batch"):
        order.append(("lane-commit", [e.priority for e in evals]))
        committed.set()  # the batch in flight lands after the lane
        return True

    w._commit_batch = commit_batch
    lane_ev = mock.evaluation(priority=70)

    def arrive():
        assert wake.first_wait.wait(WAIT_S)
        broker.enqueue(lane_ev)

    t = threading.Thread(target=arrive)
    t.start()
    try:
        w._solve_batch([mock.evaluation(priority=50)])
        t.join(WAIT_S)
    finally:
        broker.set_enabled(False)
    assert order == [("snapshot", 9), ("lane-commit", [70]),
                     ("snapshot", 9)]
    assert wake.woken and all(wake.woken)


class _OneBatch:
    """One production eval, then nothing: the blocking dequeue that finds
    nothing ends the solve loop."""

    def __init__(self, ev, stop):
        self.evs, self.stop = [ev], stop

    def dequeue_ready(self, schedulers, timeout_s=None, min_priority=0):
        if self.evs and not min_priority:
            return self.evs.pop(), "tok", 0
        if timeout_s:
            self.stop.set()
        return None, "", 0

    def annotate_trace(self, eval_id, **attrs):
        pass


def test_a_blocked_hand_off_is_woken_by_the_commit_stages_take(fresh):
    """The commit queue holds the batch before: the hand-off waits, and
    the commit stage's take of that batch wakes it — it does not run out
    its 0.2 s."""
    stop = threading.Event()
    ev = mock.evaluation(priority=50)
    srv = _Srv([], None)
    srv.eval_broker = _OneBatch(ev, stop)
    w = TPUBatchWorker(srv, pipeline=True, lane_priority=60)
    w._stop = stop
    w._wake = wake = _Wake()
    committed = []
    w._solve_batch = lambda evals, allow_chain=True: (
        _Pending(), _Snapshot(9), None)
    w._commit = lambda batch, *a, **kw: committed.append(
        [e.id for e, _ in batch])
    # the batch before, not yet taken by the commit stage
    w._commit_q.put(([], None, None, threading.Event(), {"ok": None},
                     None, None, 0, 0))

    def commit_stage():
        assert wake.first_wait.wait(WAIT_S)
        w._commit_loop(stop, w._commit_q)

    t = threading.Thread(target=commit_stage, daemon=True)
    t.start()
    w._run(stop)
    w._commit_q.put(None)
    t.join(WAIT_S)
    assert not t.is_alive()
    assert committed == [[], [ev.id]]
    assert wake.woken == [True]


# -- the applier: one victim, two plans --------------------------------------

def cell():
    """One node of 4,000 MHz held whole by a gratis alloc: a production
    plan and a lane plan each place 2,000 MHz there by evicting it."""
    h = Harness()
    node = mock.node()
    node.resources.cpu, node.resources.memory_mb = 4000, 8192
    node.reserved.cpu = node.reserved.memory_mb = 0
    h.state.upsert_node(h.next_index(), node)
    jobs = {}
    for name, priority in (("gratis", 10), ("production", 50),
                           ("monitoring", 70)):
        job = mock.job(id=name, priority=priority)
        h.state.upsert_job(h.next_index(), job)
        jobs[name] = job
    victim = mock.alloc(job_=jobs["gratis"], node_=node)
    victim.resources.tasks["web"].cpu = 4000
    victim.resources.tasks["web"].memory_mb = 1024
    victim.resources.tasks["web"].networks = []
    h.state.upsert_allocs(h.next_index(), [victim])

    def plan(name):
        p = Plan(job=jobs[name], priority=jobs[name].priority)
        a = mock.alloc(job_=jobs[name], node_=node)
        a.resources.tasks["web"].cpu = 2000
        a.resources.tasks["web"].memory_mb = 1024
        a.resources.tasks["web"].networks = []
        a.preempted_allocations = [victim.id]
        p.append_fresh_alloc(a, jobs[name])
        p.append_preempted_alloc(victim, a.id)
        return p, a

    return h, node, victim, plan("production"), plan("monitoring")


@pytest.mark.parametrize("first", ["production", "monitoring"])
def test_a_victim_two_plans_chose_is_evicted_once(first):
    h, node, victim, prod, lane = cell()
    order = [prod, lane] if first == "production" else [lane, prod]
    log = InmemLog(FSM(h.state), start_index=h.state.latest_index())
    applier = PlanApplier(PlanQueue(), h.state, log.apply, log.apply_async)
    # one after another, as two commits: the second is verified on the
    # store the first left
    (winner, w_alloc), (loser, _) = order
    (won,) = applier.apply_batch([winner])
    (lost,) = applier.apply_batch([loser])
    assert won.full_commit(winner)[0]
    assert not lost.full_commit(loser)[0]  # trimmed
    assert lost.refresh_index > 0  # its eval is retried
    # one eviction, by the winner's placement; nothing over capacity
    stored = h.state.alloc_by_id(victim.id)
    assert stored.desired_status == "evict"
    assert stored.preempted_by_allocation == w_alloc.id
    live = [a for a in h.state.allocs_by_node_terminal(node.id, False)]
    assert [a.id for a in live] == [w_alloc.id]
    assert h.state.node_usage(node.id)[0] <= node.resources.cpu

