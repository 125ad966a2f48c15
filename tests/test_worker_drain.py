"""The TPU batch worker's drain (worker.py `TPUBatchWorker._run`, span
`broker.drain`): after the blocking dequeue has returned the first eval
the worker takes what is ready NOW, up to its limit. After a batch of
one it never waits on an empty broker — a quiet cluster pays nothing;
after a batch of several it waits `STRAGGLER_WAIT_S` for one more,
because evals that arrive together solve conflict-free in one batch.

No test here sleeps or reads a clock: the solve loop runs on the test's
own thread against a broker stand-in that ends the loop when the
BLOCKING dequeue finds nothing left, and the threaded tests are
sequenced by events."""

import itertools
import threading
from collections import deque

import pytest

from nomad_tpu import metrics, mock, trace
from nomad_tpu.metrics import Registry
from nomad_tpu.server import Server
from nomad_tpu.server.worker import (
    DEQUEUE_TIMEOUT_S,
    STRAGGLER_WAIT_S,
    TPUBatchWorker,
)

WAIT_S = 30  # bound on every event wait and join; never slept through
DRAIN = "broker.drain"


@pytest.fixture()
def fresh_registry():
    old = metrics._install_registry(Registry())
    yield metrics.registry()
    metrics._install_registry(old)


@pytest.fixture()
def tracing():
    """Spans on: a dequeue made inside `broker.drain` sees that name as
    its thread's innermost open span."""
    trace.set_enabled(True)
    yield
    trace.set_enabled(False)


class ScriptedBroker:
    """Stand-in for the eval broker. Evals become ready a WAVE at a
    time: the next wave arrives when the worker's blocking dequeue finds
    nothing (the worker had gone idle), and when no wave is left that
    dequeue stops the loop. A dequeue inside the drain never waits here,
    whatever timeout it asks for; every call is recorded as (timeout_s,
    innermost open span, answered)."""

    def __init__(self, waves, stop):
        self.waves = deque(deque(w) for w in waves)
        self.ready = self.waves.popleft() if self.waves else deque()
        self.stop = stop
        self.calls = []
        self.acked, self.nacked = [], []
        self.on_drain_dequeue = None  # hook: called inside the drain

    def dequeue_ready(self, schedulers, timeout_s=None, min_priority=0):
        span = trace.thread_spans().get(threading.get_ident())
        if span == DRAIN and self.on_drain_dequeue is not None:
            self.on_drain_dequeue()
        if not self.ready and span != DRAIN and self.waves:
            self.ready = self.waves.popleft()
        answer = self.ready.popleft() if self.ready else (None, "")
        self.calls.append((timeout_s, span, answer[0] is not None))
        if answer[0] is None and span != DRAIN:
            self.stop.set()
        return (*answer, 0)  # no ready instant: `lane.queue` is not timed

    def drain_calls(self):
        return [c for c in self.calls if c[1] == DRAIN]

    def watch_ready(self, min_priority, event):
        pass  # no eval becomes ready but at a dequeue

    def unwatch_ready(self, event):
        pass

    def ack(self, eval_id, token):
        self.acked.append(eval_id)

    def nack(self, eval_id, token):
        self.nacked.append(eval_id)

    def annotate_trace(self, eval_id, **attrs):
        pass


class _PlanQueue:
    def depth(self):
        return 0


class _Srv:
    def __init__(self, broker):
        self.eval_broker = broker
        self.plan_queue = _PlanQueue()


def scripted_worker(*waves, batch_size=64, pipeline=False):
    """A worker over a ScriptedBroker (one list of priorities a wave)
    whose solve and commit stages only record what reached them:
    (lane, [eval ids]) per solve, in order."""
    stop = threading.Event()
    evals = [[mock.evaluation(priority=p) for p in wave] for wave in waves]
    n = itertools.count()
    broker = ScriptedBroker(
        [[(ev, f"tok-{next(n)}") for ev in wave] for wave in evals], stop
    )
    w = TPUBatchWorker(
        _Srv(broker), batch_size=batch_size, pipeline=pipeline,
        lane_priority=60,
    )
    solved = []

    class _Pending:
        used_micro = False

    def solve(batch_evals, allow_chain=True):
        lane = "batch" if allow_chain else "interactive"
        solved.append((lane, [e.id for e in batch_evals]))
        return _Pending(), None, None

    def commit(batch, *args, **kwargs):
        for ev, tok in batch:
            broker.ack(ev.id, tok)

    w._solve_batch = solve
    w._commit = commit
    flat = [ev for wave in evals for ev in wave]
    return w, broker, flat, solved, stop


@pytest.mark.parametrize("n_ready", [1, 3])
def test_a_first_drain_is_non_blocking_and_ends_at_the_first_empty_answer(
    tracing, n_ready
):
    w, broker, evals, solved, stop = scripted_worker([50] * n_ready)
    w._run(stop)
    first, *rest = broker.calls
    assert first[:2] == (DEQUEUE_TIMEOUT_S, None)  # the one that may wait
    drain = broker.drain_calls()
    assert drain == rest[:len(drain)] and len(drain) == n_ready
    assert [t for t, _, _ in drain] == [0] * n_ready
    # the drain ends at the first empty answer: it is its last call
    assert [got for _, _, got in drain] == [True] * (n_ready - 1) + [False]
    assert solved == [("batch", [e.id for e in evals])]
    # after the batch, back to the blocking dequeue — outside the span
    assert rest[len(drain):] == [(DEQUEUE_TIMEOUT_S, None, False)]


def test_one_operator_never_waits_in_the_drain(tracing):
    """One operator, closed loop: one eval a batch, the broker empty
    behind it, deploy after deploy. Every drain asks once, without
    waiting, and is told no."""
    w, broker, evals, solved, stop = scripted_worker([50], [50], [50], [50])
    w._run(stop)
    assert broker.drain_calls() == [(0, DRAIN, False)] * 4
    assert [ids for _, ids in solved] == [[e.id] for e in evals]
    assert broker.acked == [e.id for e in evals]


def test_the_drain_waits_for_a_straggler_only_after_a_batch_of_several(
    tracing
):
    """What the worker itself just observed decides: a batch of three
    says evals are arriving together, so the NEXT drain waits for one
    more; that batch held one, so the one after does not wait again."""
    w, broker, evals, solved, stop = scripted_worker(
        [50, 50, 50], [50], [50], [50, 50], [50]
    )
    w._run(stop)
    assert [len(ids) for _, ids in solved] == [3, 1, 1, 2, 1]
    waits = [t for t, _, got in broker.drain_calls() if not got]
    assert waits == [0, STRAGGLER_WAIT_S, 0, 0, STRAGGLER_WAIT_S]
    # inside one drain every dequeue asks for the same wait
    assert {t for t, _, _ in broker.drain_calls()} == {0, STRAGGLER_WAIT_S}
    assert [t for t, s, _ in broker.calls if s != DRAIN] == (
        [DEQUEUE_TIMEOUT_S] * 6
    )


@pytest.mark.parametrize(
    "n_ready,batch_size,want",
    [(5, 64, [5]), (5, 3, [3, 2]), (6, 3, [3, 3]), (1, 1, [1])],
)
def test_drain_takes_what_is_ready_up_to_the_limit(
    tracing, n_ready, batch_size, want
):
    w, broker, evals, solved, stop = scripted_worker(
        [50] * n_ready, batch_size=batch_size
    )
    w._run(stop)
    assert [len(ids) for _, ids in solved] == want
    assert [i for _, ids in solved for i in ids] == [e.id for e in evals]
    # a full batch never asks the broker for one more
    full = sum(1 for n in want if n == batch_size)
    assert sum(1 for _, _, got in broker.drain_calls() if not got) == (
        len(want) - full
    )


class _StopWhenIdle:
    """The real broker, recording the timeout each dequeue asked for. A
    blocking dequeue with nothing ready ends the loop; a drain's dequeue
    is answered at once, whatever it was ready to wait. Everything else
    is the broker's own."""

    def __init__(self, broker, stop):
        self._broker = broker
        self._stop = stop
        self.asked = []

    def dequeue_ready(self, schedulers, timeout_s=None, min_priority=0):
        self.asked.append(timeout_s)
        if timeout_s == DEQUEUE_TIMEOUT_S and not self._broker.ready_count():
            self._stop.set()
            return None, "", 0
        return self._broker.dequeue_ready(schedulers, timeout_s=0)

    def __getattr__(self, name):
        return getattr(self._broker, name)


BLOCK, NOW, STRAGGLER = DEQUEUE_TIMEOUT_S, 0, STRAGGLER_WAIT_S


@pytest.mark.parametrize(
    "batch_size,want,asked",
    [
        (64, [5], [BLOCK, NOW, NOW, NOW, NOW, NOW, BLOCK]),
        (2, [2, 2, 1],
         [BLOCK, NOW, BLOCK, STRAGGLER, BLOCK, STRAGGLER, BLOCK]),
    ],
)
def test_evals_ready_before_the_worker_starts_are_one_batch(
    fresh_registry, batch_size, want, asked
):
    """The served path end to end — real broker, solver, applier: five
    jobs registered while the worker is down come out as ONE batch (or
    as many as the limit cuts), and `nomad.tpu.batch_evals` reads it."""
    s = Server(use_tpu_batch_worker=True)
    s.establish_leadership()
    try:
        w = s.tpu_worker
        w.stop()
        for _ in range(10):
            s.node_register(mock.node())
        jobs = []
        for i in range(5):
            job = mock.job(id=f"drain-{i}")
            job.task_groups[0].count = 2
            s.job_register(job)
            jobs.append(job)
        assert s.eval_broker.ready_count() == 5
        stop = threading.Event()
        s.eval_broker = _StopWhenIdle(s.eval_broker, stop)
        w.batch_size = batch_size
        w.pipeline = False  # commit inline: the loop is one thread
        w._run(stop)
        hist = fresh_registry.histogram_raw("nomad.tpu.batch_evals")
        assert hist["count"] == len(want) and hist["sum"] == 5
        assert hist["max"] == want[0] and hist["min"] == want[-1]
        assert w.processed == 5
        assert s.eval_broker.asked == asked
        for job in jobs:
            live = [
                a for a in s.state.allocs_by_job(job.namespace, job.id)
                if not a.terminal_status()
            ]
            assert len(live) == 2, job.id
    finally:
        s.shutdown()


def test_interactive_eval_ready_at_drain_time_ends_the_drain(
    tracing, fresh_registry
):
    """Priority >= lane_priority found by the drain: never baked into
    the batch — held, solved FIRST next cycle on the lane, counted."""
    w, broker, evals, solved, stop = scripted_worker([50, 50, 70, 50])
    a, b, hot, d = (e.id for e in evals)
    w._run(stop)
    assert solved == [
        ("batch", [a, b]), ("interactive", [hot]), ("batch", [d]),
    ]
    counters = fresh_registry.snapshot()["counters"]
    assert counters["nomad.worker.lane.drain_preempted"] == 1
    assert counters["nomad.worker.lane.interactive"] == 1
    # the preempted drain stopped asking: 2 answered calls, no empty one
    assert broker.calls[1:3] == [(0, DRAIN, True)] * 2
    # the held eval cost no dequeue: the next call is d's blocking one
    assert broker.calls[3] == (DEQUEUE_TIMEOUT_S, None, True)
    assert w._held is None
    assert sorted(broker.acked) == sorted(e.id for e in evals)


def test_interactive_first_eval_never_drains(tracing):
    w, broker, evals, solved, stop = scripted_worker([70, 50])
    w._run(stop)
    assert solved == [
        ("interactive", [evals[0].id]), ("batch", [evals[1].id]),
    ]
    assert broker.drain_calls() == [(0, DRAIN, False)]


def stop_while_parked_in_the_drain(w, broker):
    """Start the worker, park its solve thread inside the drain's first
    dequeue, call stop() from another thread, and let the drain go on
    only once stop() has set the flag and is joining."""
    in_drain, stopping = threading.Event(), threading.Event()

    def park():
        if not in_drain.is_set():
            in_drain.set()
            assert stopping.wait(WAIT_S)

    broker.on_drain_dequeue = park
    w.start()
    broker.stop = w._stop  # start() made a fresh event
    assert in_drain.wait(WAIT_S)
    stopper = threading.Thread(target=w.stop)
    stopper.start()
    assert w._stop.wait(WAIT_S)
    stopping.set()
    stopper.join(WAIT_S)
    assert not stopper.is_alive()
    assert w._thread is None and w._cthread is None


def test_stop_during_a_drain_nacks_what_was_taken(tracing):
    """stop() lands while the solve thread is inside the drain: the
    batch it took never reaches the commit stage and is nacked, so the
    evals redeliver; nothing is acked, nothing stays held."""
    w, broker, evals, solved, _ = scripted_worker(
        [50, 50, 50], pipeline=True
    )
    stop_while_parked_in_the_drain(w, broker)
    taken = [e.id for e in evals]
    assert solved == [("batch", taken)]
    assert sorted(broker.nacked) == sorted(taken)
    assert broker.acked == []
    assert w._held is None and w._prev is None


def test_stop_nacks_an_interactive_eval_the_drain_was_holding(tracing):
    w, broker, evals, solved, _ = scripted_worker([50, 70], pipeline=True)
    stop_while_parked_in_the_drain(w, broker)
    assert solved == [("batch", [evals[0].id])]
    assert sorted(broker.nacked) == sorted(e.id for e in evals)
    assert broker.acked == [] and w._held is None
