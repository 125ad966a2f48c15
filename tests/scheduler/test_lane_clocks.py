"""The interactive lane's two clocks and its trimmed count
(server/worker.py; docs/pipeline.md § Priority lanes).

`nomad.worker.lane.interactive_seconds` starts at the dequeue;
`nomad.worker.lane.wait_seconds` starts at the eval's creation and stops
where the lane solve starts, so the time an urgent eval sits in the
broker while the solve thread is busy is on a histogram;
`nomad.worker.lane.trimmed` counts the lane plans the applier cut.
"""

import threading
import time

import pytest

from nomad_tpu import metrics, mock, trace
from nomad_tpu.metrics import Registry
from nomad_tpu.scheduler.context import SchedulerConfig
from nomad_tpu.server import Server
from nomad_tpu.server.worker import TPUBatchWorker


@pytest.fixture()
def fresh_registry():
    old = metrics._install_registry(Registry())
    yield metrics.registry()
    metrics._install_registry(old)


def _job(job_id: str, priority: int, count: int):
    job = mock.job(id=job_id)
    job.priority = priority
    job.task_groups[0].count = count
    job.task_groups[0].tasks[0].resources.networks = []
    return job


def _wait_placed(s, job, deadline_s=30.0):
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        live = [a for a in s.state.allocs_by_job(job.namespace, job.id)
                if not a.terminal_status()]
        if len(live) >= job.task_groups[0].count:
            return
        time.sleep(0.005)
    raise AssertionError(f"{job.id} was not placed")


def test_a_lane_deploy_over_default_priority_work_is_timed_from_its_creation(
        fresh_registry):
    """A priority-70 deploy onto a cluster that holds priority-50 work —
    every deploy of the lane's own users — is solved alone on the lane,
    and both clocks read it."""
    s = Server(use_tpu_batch_worker=True,
               scheduler_config=SchedulerConfig(backend="tpu"))
    s.establish_leadership()
    try:
        for _ in range(6):
            s.node_register(mock.node())
        standing = _job("standing", 50, 4)
        s.job_register(standing)
        _wait_placed(s, standing)
        urgent = _job("urgent", 70, 2)
        s.job_register(urgent)
        _wait_placed(s, urgent)
        assert s.wait_for_evals(30)
        snap = metrics.snapshot()
        counters, samples = snap["counters"], snap["samples"]
        assert counters["nomad.worker.lane.interactive"] == 1
        assert counters.get("nomad.worker.lane.trimmed", 0) == 0
        wait = samples["nomad.worker.lane.wait_seconds"]
        assert wait["count"] == 1 and 0.0 <= wait["max"] < 30.0
        assert samples["nomad.worker.lane.interactive_seconds"]["count"] == 1
    finally:
        s.shutdown()


class _Broker:
    def __init__(self):
        self.acked = []

    def ack(self, eval_id, token):
        self.acked.append(eval_id)

    def nack(self, eval_id, token):
        raise AssertionError("a trimmed plan is retried, not nacked")


class _PlanQueue:
    def depth(self):
        return 0


class _Srv:
    def __init__(self):
        self.eval_broker = _Broker()
        self.plan_queue = _PlanQueue()


class _Pending:
    def finish(self):
        return {}


@pytest.mark.parametrize("lane, all_full, trimmed", [
    ("interactive", False, 1),
    ("interactive", True, 0),
    ("batch", False, 0),
])
def test_a_lane_plan_the_applier_cut_is_counted(
        fresh_registry, lane, all_full, trimmed):
    w = TPUBatchWorker(_Srv(), pipeline=False, lane_priority=60)
    w._commit_batch = lambda *a, **kw: all_full
    ev = mock.evaluation(priority=70 if lane == "interactive" else 50)
    outcome = {"ok": None}
    w._commit([(ev, "tok")], _Pending(), None, threading.Event(), outcome,
              None, None, lane=lane, t_deq=trace.now_ns())
    assert outcome["ok"] is all_full
    assert w.server.eval_broker.acked == [ev.id]
    counters = metrics.snapshot()["counters"]
    assert counters.get("nomad.worker.lane.trimmed", 0) == trimmed
