"""Which solve behind a batch in flight waits for its commit.

The pipelined worker solves batch N+1 while batch N commits, and keeps
the two conflict-free by chaining N+1 on what N offers
(`PendingEvalBatch.chain`, a `solver.UsageChain`): a kernel batch its
post-solve usage tensor, a microsolve batch the same rows on the host,
a host-stack batch the rows it read with its own placements added. So
no batch waits behind a small batch solved whole on
the host: it chains on it, and commits, being FIFO, keep the verdicts in
order. A batch with a kernel or a pool RPC in flight that offers nothing
(the dense kernel of a custom solve_fn, a `RemotePendingBatch`) is not
waited for either: the overlap with it is the pipeline's point
(docs/pipeline.md, docs/solver-pool.md).

A solve that MAY PREEMPT waits for whatever is in flight, before its
snapshot: it reads its tiers, its exact room and its victims from the
store, which holds the parent's plan only once it is committed. The
preempt solve itself offers its used' tensor, so a batch behind it that
cannot preempt chains on it and does not wait.
"""

import threading
import time

import pytest

from nomad_tpu import metrics, mock
from nomad_tpu.metrics import Registry
from nomad_tpu.scheduler.context import SchedulerConfig
from nomad_tpu.scheduler.tpu import solve_eval_batch_begin
from nomad_tpu.scheduler.tpu.kernels import solve_placement
from nomad_tpu.server.solver_pool import RemotePendingBatch, _Dispatch
from nomad_tpu.server.worker import TPUBatchWorker
from nomad_tpu.testing import Harness


class _Snapshot:
    index = 9


class _State:
    tiers = [50]  # the priorities with a live alloc: one band

    def __init__(self, order):
        self.order = order

    def alloc_priority_tiers(self):
        return self.tiers

    def snapshot_min_index(self, index, timeout_s=None):
        self.order.append("snapshot")
        return _Snapshot()


class _PlanQueue:
    def depth(self):
        return 0


class _NoLane:
    """A broker with no interactive eval ready: the lane a wait serves
    finds nothing."""

    def dequeue_ready(self, schedulers, timeout_s=None, min_priority=0):
        return None, "", 0


class _Srv:
    def __init__(self, order):
        self.eval_broker = _NoLane()
        self.plan_queue = _PlanQueue()
        self.state = _State(order)


class _Pending:
    chain_accepted = False

    def __init__(self, chain=None, solved_in_begin=True):
        self.chain = chain
        self.solved_in_begin = solved_in_begin


@pytest.fixture
def worker(monkeypatch):
    order: list = []
    w = TPUBatchWorker(_Srv(order), pipeline=True)
    w.prepare = lambda: None
    given = []

    def begin(snapshot, planner, evals, config, used_chain=None, **kw):
        given.append(used_chain)
        return _Pending()

    monkeypatch.setattr(
        "nomad_tpu.scheduler.tpu.solve_eval_batch_begin", begin)
    old = metrics._install_registry(Registry())
    yield w, order, given
    metrics._install_registry(old)


def in_flight(w, order, chain=None, commit_after_s=0.15, pending=None):
    """A previous batch whose commit lands `commit_after_s` from now."""
    committed = threading.Event()
    if pending is None:
        pending = _Pending(chain)
    w._prev = (pending, committed, {"ok": None}, 7)

    def commit():
        time.sleep(commit_after_s)
        order.append("committed")
        committed.set()

    t = threading.Thread(target=commit)
    t.start()
    return t


def waited() -> int:
    return metrics.snapshot()["counters"].get("nomad.worker.chain.waited", 0)


def test_a_solve_behind_a_chainless_batch_in_flight_waits_for_its_commit(
        worker):
    """A batch solved whole on the host that offers nothing placed
    nothing and had nothing in flight under it: the solve behind it has
    nothing to wait for, and chains on nothing. (Named for what it
    checked while the host paths offered no chain: it waited then.)"""
    w, order, given = worker
    t = in_flight(w, order, chain=None)
    pending, _, chained_on = w._solve_batch([mock.evaluation()])
    assert order == ["snapshot"]  # the parent's commit is still pending
    t.join()
    assert given == [None] and chained_on is None
    assert waited() == 0


def test_a_solve_behind_a_batch_that_offers_its_tensor_does_not_wait(worker):
    w, order, given = worker
    chain = (("n1",), object())
    t = in_flight(w, order, chain=chain)
    w._solve_batch([mock.evaluation()])
    assert order == ["snapshot"]  # the parent's commit is still pending
    t.join()
    assert given == [chain]
    assert waited() == 0


@pytest.mark.parametrize("type_, priority, tiers, waits", [
    ("service", 50, [10, 30, 50], True),   # 40 over the lowest band
    ("service", 50, [45, 50], False),      # nothing 10 under it
    ("batch", 50, [10, 30, 50], False),    # batch jobs may not preempt
    ("batch", 10, [10, 30, 50], False),    # an evicted job's follow-up
])
def test_a_solve_that_may_preempt_waits_for_whatever_is_in_flight(
        worker, type_, priority, tiers, waits):
    w, order, given = worker
    w.server.state.tiers = tiers
    chain = (("n1",), object())
    t = in_flight(w, order, chain=chain)
    ev = mock.evaluation()
    ev.type, ev.priority = type_, priority
    w._solve_batch([ev, mock.evaluation(type="batch")])
    # it waits before its snapshot is taken, and chains on nothing
    assert order == (["committed", "snapshot"] if waits else ["snapshot"])
    t.join()
    assert given == [None if waits else chain]
    assert waited() == (1 if waits else 0)


def test_the_interactive_lane_never_waits(worker):
    w, order, given = worker
    t = in_flight(w, order, chain=None)
    w._solve_batch([mock.evaluation()], allow_chain=False)
    assert order == ["snapshot"]
    t.join()
    assert given == [None] and waited() == 0


def test_nothing_in_flight_nothing_to_wait_for(worker):
    w, order, given = worker
    w._solve_batch([mock.evaluation()])
    committed = threading.Event()
    committed.set()
    w._prev = (_Pending(None), committed, {"ok": True}, 7)
    w._solve_batch([mock.evaluation()])
    assert order == ["snapshot", "snapshot"] and waited() == 0
    assert w._prev is None  # a committed parent is dropped


# -- the real pendings: which of them is waited for -------------------------

def real_pending(kind: str):
    """A batch begun and not finished, as the worker's `_prev` holds it:
    `host` (a small job: the host stack), `micro` (the same through the
    microsolve), `compact` (the compact kernel, which offers its used'
    tensor), `dense` (a custom solve_fn: the dense kernel, dispatched and
    not read back, no chain), `remote` (a pool dispatch)."""
    if kind == "remote":
        return RemotePendingBatch(
            None, _Dispatch("s1", ("127.0.0.1", 1)), None, [], None, None)
    h = Harness()
    for _ in range(4):
        h.state.upsert_node(h.next_index(), mock.node())
    job = mock.job(id=f"chain-wait-{kind}")
    job.task_groups[0].count = 2
    job.task_groups[0].tasks[0].resources.networks = []
    h.state.upsert_job(h.next_index(), job)
    cfg = {
        "host": SchedulerConfig(micro_solve_threshold=0),
        "micro": SchedulerConfig(),
        "compact": SchedulerConfig(small_batch_threshold=0),
        "dense": SchedulerConfig(small_batch_threshold=0),
    }[kind]
    solve_fn = None
    if kind == "dense":
        def solve_fn(*args, **kwargs):
            return solve_placement(*args, **kwargs)
    return solve_eval_batch_begin(
        h.snapshot(), h, [mock.eval_for_job(job)], cfg, solve_fn=solve_fn)


@pytest.mark.parametrize("kind, solved_in_begin, offers_chain, waits", [
    ("host", True, True, False),
    ("micro", True, True, False),
    ("compact", False, True, False),
    ("dense", False, False, False),
    ("remote", False, False, False),
])
def test_only_a_batch_solved_whole_on_the_host_is_waited_for(
        worker, kind, solved_in_begin, offers_chain, waits):
    """Every real pending is chained on or overlapped; none is waited
    for. (Named for what it checked while the host paths offered no
    chain: the host stack and the microsolve were waited for then.)"""
    w, order, given = worker
    pending = real_pending(kind)
    assert pending.solved_in_begin is solved_in_begin
    assert (pending.chain is not None) is offers_chain
    if kind == "micro":
        assert pending.used_micro
    t = in_flight(w, order, pending=pending)
    w._solve_batch([mock.evaluation()])
    assert order == (["committed", "snapshot"] if waits else ["snapshot"])
    t.join()
    assert waited() == (1 if waits else 0)
    assert given == [pending.chain if offers_chain else None]


def test_a_batch_sent_to_the_pool_behind_a_remote_batch_overlaps_it(worker):
    """docs/solver-pool.md: the two-stage pipeline overlaps remote solves
    like local dispatches — the next batch is dispatched while the remote
    batch before it is still out."""
    w, order, given = worker
    sent = []

    class _Pool:
        def dispatch_batch(self, evals, snapshot, planner, config, **kw):
            sent.append(len(evals))
            order.append("dispatched")
            return real_pending("remote")

    w.solver_pool = _Pool()
    t = in_flight(w, order, pending=real_pending("remote"))
    pending, _, chained_on = w._solve_batch([mock.evaluation()])
    assert order == ["snapshot", "dispatched"]  # before the parent commits
    t.join()
    assert isinstance(pending, RemotePendingBatch) and chained_on is None
    assert sent == [1] and given == [] and waited() == 0
