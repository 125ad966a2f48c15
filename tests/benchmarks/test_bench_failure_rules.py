"""When an operation has failed, one case for each rule (benchmarks/
harness/ops.py): only a definitive wrong outcome fails it, never timing.
The observer and the rules run here against a stand-in store and hub."""

import threading
import time
from types import SimpleNamespace

import pytest

from benchmarks.harness import ops
from benchmarks.harness.observer import Observer
from benchmarks.reference import store_check


class FakeState:
    def __init__(self):
        self.subs = []
        self.evals = {}

    def subscribe(self, fn):
        self.subs.append(fn)

    def commit(self, index, allocs):
        for fn in self.subs:
            fn(index, "allocs", allocs, "PlanResult")

    def eval_by_id(self, eval_id):
        return self.evals.get(eval_id)


class FakeHub:
    """Routes every commit at once, except to the nodes in `deaf`."""

    def __init__(self, deaf=()):
        self.deaf = set(deaf)

    def wait_for_node(self, node_id, min_index, timeout_s):
        return node_id not in self.deaf


def alloc(i, job, node="n1", desired="run"):
    return SimpleNamespace(id=f"{job}-a{i}", job_id=job, node_id=node,
                           desired_status=desired)


@pytest.fixture
def rig(monkeypatch):
    monkeypatch.setattr(
        "nomad_tpu.state.store.TABLE_ALLOCS", "allocs", raising=True)
    state = FakeState()
    made = []

    def make(hub=None, seconds=0.2):
        obs = Observer(state, hub or FakeHub(), hang_s=0.3)
        made.append(obs)
        ctx = ops.RunContext(config={}, params={}, seed=0, seconds=seconds,
                             http=("127.0.0.1", 1), observer=obs)
        return ctx, obs

    yield state, make
    for obs in made:
        obs.stop()


def acked(ctx, job, asked, eval_id="e1"):
    op = ctx.new_op(job, asked, kind="small")
    op.sent, op.status, op.t_sent = True, 200, time.monotonic()
    op.watch.t_sent, op.watch.eval_id = op.t_sent, eval_id
    return op


def wait_idle(obs):
    end = time.monotonic() + 5
    while not obs.idle() and time.monotonic() < end:
        time.sleep(0.005)
    time.sleep(0.02)


def test_a_trimmed_plan_completed_by_its_follow_up_is_a_slow_success(rig):
    state, make = rig
    ctx, obs = make()
    ctx.open_window()
    op = acked(ctx, "j1", asked=4)
    state.commit(10, [alloc(0, "j1"), alloc(1, "j1")])  # the applier trimmed
    wait_idle(obs)
    assert not op.watch.done.is_set()
    time.sleep(0.05)
    state.commit(11, [alloc(2, "j1"), alloc(3, "j1")])  # the follow-up eval
    ctx.await_visible(op)
    assert op.watch.done.is_set() and op.watch.commits == 2
    assert ops.judge(ctx, state) == {
        "front_door": 0, "eval_failed": 0, "not_visible": 0}
    assert not op.failed
    # its whole time counts, from when it was sent
    assert op.watch.t_visible - op.t_sent >= 0.05


def test_a_deploy_in_flight_at_the_windows_end_is_waited_for(rig):
    state, make = rig
    ctx, obs = make(seconds=0.05)
    ctx.open_window()
    op = acked(ctx, "j1", asked=1)
    late = threading.Timer(0.25, state.commit, (10, [alloc(0, "j1")]))
    late.start()
    ctx.await_visible(op)  # returns after the window has closed
    late.join()
    assert time.monotonic() > ctx.t_end
    assert op.watch.done.is_set()
    assert op.watch.t_visible - op.t_sent >= 0.2  # the latency counts
    assert sum(ops.judge(ctx, state).values()) == 0


def test_no_answer_or_a_non_2xx_answer_at_the_front_door_fails(rig):
    state, make = rig
    ctx, obs = make()
    ctx.open_window()
    op = ctx.new_op("j1", 2, kind="small")
    ctx.send(op, b"{}")  # nothing listens on port 1
    assert op.sent and op.status == 0 and op.failed == "front_door"
    assert ops.judge(ctx, state)["front_door"] == 1


def test_an_eval_that_ends_failed_fails_its_operation(rig):
    state, make = rig
    ctx, obs = make()
    ctx.open_window()
    acked(ctx, "j1", asked=2, eval_id="e-dead")
    state.evals["e-dead"] = SimpleNamespace(status="failed")
    assert ops.judge(ctx, state) == {
        "front_door": 0, "eval_failed": 1, "not_visible": 0}


def test_not_visible_at_the_drains_deadline_fails_and_nothing_sooner(rig):
    state, make = rig
    ctx, obs = make(hub=FakeHub(deaf={"n-deaf"}))
    ctx.open_window()
    slow = acked(ctx, "slow", asked=1)
    deaf = acked(ctx, "deaf", asked=1, eval_id="e2")
    state.evals["e2"] = SimpleNamespace(status="complete")
    state.commit(10, [alloc(0, "deaf", node="n-deaf")])
    state.commit(11, [alloc(0, "slow")])
    ctx.await_visible(slow)
    wait_idle(obs)
    assert ops.judge(ctx, state)["not_visible"] == 1
    assert deaf.failed == "not_visible" and not slow.failed
    assert obs.never_visible == 1


def test_allocs_that_are_not_to_run_do_not_count_as_placed(rig):
    state, make = rig
    ctx, obs = make()
    ctx.open_window()
    op = acked(ctx, "j1", asked=1)
    state.commit(10, [alloc(0, "j1", desired="stop")])
    wait_idle(obs)
    assert not op.watch.done.is_set() and op.visible == 0


# -- the store's invariants (reference/store_check.py) ------------------

ASK = {"cpu_mhz": 250, "memory_mb": 128, "disk_mb": 300}


def cluster(n_allocs=4):
    nodes = [{"id": f"n{i}", "datacenter": f"dc{i % 2 + 1}", "cpu": 1000,
              "mem": 8192, "disk": 10000,
              "attributes": {"kernel.name": "linux"}} for i in range(2)]
    allocs = [{"id": f"a{i}", "name": f"j1.web[{i}]", "job": "j1",
               "node": f"n{i % 2}", "cpu": 250, "mem": 128, "disk": 300}
              for i in range(n_allocs)]
    jobs = {"j1": {"datacenters": ["dc1", "dc2"], "constraints": [
        ("${attr.kernel.name}", "=", "linux")]}}
    return {"nodes": nodes, "allocs": allocs, "jobs": jobs}


def test_a_correct_placement_has_no_fault():
    assert store_check.check(cluster(), {"j1": 4}, ASK) == []


def break_duplicate_id(c):
    c["allocs"][1]["id"] = c["allocs"][0]["id"]


def break_duplicate_name(c):
    c["allocs"][1]["name"] = c["allocs"][0]["name"]


def break_capacity(c):
    for a in c["allocs"]:
        a["node"] = "n0"
    c["allocs"].append(dict(c["allocs"][0], id="a9", name="j1.web[9]"))


def break_missing_job(c):
    del c["jobs"]["j1"]


def break_datacenter(c):
    c["jobs"]["j1"]["datacenters"] = ["dc1"]


def break_constraint(c):
    c["nodes"][0]["attributes"]["kernel.name"] = "plan9"


def break_count(c):
    c["allocs"].pop()


def break_ask(c):
    c["allocs"][0]["cpu"] = 100


@pytest.mark.parametrize("breaker, says", [
    (break_duplicate_id, "ids are held twice"),
    (break_duplicate_name, "placed twice"),
    (break_capacity, "over their cpu"),
    (break_missing_job, "not in the store"),
    (break_datacenter, "outside the job's datacenters"),
    (break_constraint, "break a constraint"),
    (break_count, "another number of allocs than asked"),
    (break_ask, "do not carry the configuration's ask"),
])
def test_each_broken_invariant_is_a_fault(breaker, says):
    c = cluster()
    breaker(c)
    faults = store_check.check(c, {"j1": 4 if breaker is not break_capacity
                                   else 5}, ASK)
    assert any(says in f for f in faults), faults
