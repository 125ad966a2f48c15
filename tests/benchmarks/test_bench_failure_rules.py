"""When an operation has failed, one case for each rule (benchmarks/
harness/ops.py): only a definitive wrong outcome fails it, never timing.
The observer and the rules run here against a stand-in store and hub.
Then the reference rules (benchmarks/reference/rules/), each held to a
sound cluster and to the broken ones it has to find."""

import json
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.harness import ops, spec
from benchmarks.harness.observer import Observer

BENCH_DIR = Path(__file__).resolve().parents[2] / "benchmarks"


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
        ctx = ops.RunContext(config={"ask": ASK}, params={}, seed=0,
                             seconds=seconds,
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


# -- the store's invariants (reference/rules/) ---------------------------

ASK = {"cpu_mhz": 250, "memory_mb": 128, "disk_mb": 300}
RULES = ("acked_jobs_held", "unique_allocs", "node_capacity",
         "job_feasibility", "asks_carried", "watch_visibility")


def cluster(n_allocs=4):
    nodes = [{"id": f"n{i}", "datacenter": f"dc{i % 2 + 1}", "cpu": 1000,
              "mem": 8192, "disk": 10000, "class": "", "devices": [],
              "attributes": {"kernel.name": "linux"}} for i in range(2)]
    allocs = [{"id": f"a{i}", "name": f"j1.web[{i}]", "job": "j1",
               "node": f"n{i % 2}", "cpu": 250, "mem": 128, "disk": 300}
              for i in range(n_allocs)]
    jobs = {"j1": {"datacenters": ["dc1", "dc2"], "priority": 50,
                   "type": "service", "constraints": [
                       ("${attr.kernel.name}", "=", "linux")]}}
    return {"nodes": nodes, "allocs": allocs, "terminal_allocs": [],
            "jobs": jobs, "observed": {"never_visible": 0}}


def check(rule: str, snap: dict, expected: dict) -> list[str]:
    return spec.load_module(spec.RULES, rule).check(snap, expected, {})


@pytest.mark.parametrize("rule", RULES)
def test_a_correct_placement_has_no_fault(rule):
    assert check(rule, cluster(), {"j1": (4, ASK)}) == []


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


def break_unknown_node(c):
    c["allocs"][0]["node"] = "n-gone"


def break_watch(c):
    c["observed"]["never_visible"] = 3


BROKEN = [
    (break_duplicate_id, "unique_allocs", "ids are held twice"),
    (break_duplicate_name, "unique_allocs", "placed twice"),
    (break_capacity, "node_capacity", "over their cpu"),
    (break_unknown_node, "node_capacity", "nodes the store does not hold"),
    (break_missing_job, "acked_jobs_held", "not in the store"),
    (break_datacenter, "job_feasibility", "outside the job's datacenters"),
    (break_constraint, "job_feasibility", "break a constraint"),
    (break_count, "acked_jobs_held", "another number of allocs than asked"),
    (break_ask, "asks_carried", "do not carry the configuration's ask"),
    (break_watch, "watch_visibility", "3 node watches never saw a commit"),
]


@pytest.mark.parametrize("breaker, rule, says", BROKEN)
def test_each_broken_invariant_is_a_fault(breaker, rule, says):
    c = cluster()
    breaker(c)
    expected = {"j1": (5 if breaker is break_capacity else 4, ASK)}
    faults = check(rule, c, expected)
    assert any(says in f for f in faults), faults
    # and it is that rule's alone to find: of the others, none that the
    # same breakage does not also break says a word
    if breaker in (break_duplicate_id, break_duplicate_name, break_ask,
                   break_watch, break_datacenter, break_constraint):
        for other in RULES:
            if other != rule:
                assert check(other, c, expected) == [], other


def test_every_rule_a_configuration_names_is_one_of_the_files():
    """Both configurations keep every guarantee, each with its rule; the
    five checks the old store_check made exist once, as files."""
    on_disk = {p.stem for p in (BENCH_DIR / spec.RULES).glob("*.py")}
    assert on_disk == set(RULES)
    assert not (BENCH_DIR / "reference" / "store_check.py").exists()
    for name in ("c1m-5k", "c2m-10k"):
        config = json.loads(
            (BENCH_DIR / "configs" / f"{name}.json").read_text())
        assert [g["rule"] for g in config["guarantees"]] == list(RULES)
        assert "may_remain" not in config and "standing" not in config
        spec.check_config(config, BENCH_DIR)


@pytest.mark.parametrize("guarantees, says", [
    (["no alloc is placed twice"], "names no rule"),
    ([{"says": "no alloc is placed twice"}], "names no rule"),
    ([{"rule": "no_such_rule", "says": "x"}], "no_such_rule.py is missing"),
    ([{"rule": "../run", "says": "x"}], "is missing"),
])
def test_a_guarantee_without_a_rule_file_is_a_spec_error(guarantees, says):
    config = {"name": "t", "ask": ASK, "guarantees": guarantees}
    with pytest.raises(spec.SpecError, match=says):
        spec.check_config(config, BENCH_DIR)


def test_only_what_the_harness_knows_may_remain():
    with pytest.raises(spec.SpecError, match="may_remain"):
        spec.check_config({"guarantees": [], "may_remain": ["broker"]},
                          BENCH_DIR)
    spec.check_config({"guarantees": [], "may_remain": ["blocked_evals"]},
                      BENCH_DIR)


# -- what may remain after the drain (ops.settle) ------------------------

class StillBusy:
    """A cluster whose in-flight counts never change."""

    def __init__(self, **in_flight):
        self._in_flight = in_flight

    def in_flight(self) -> dict:
        return dict(self._in_flight)


@pytest.mark.parametrize("in_flight, may_remain, left", [
    ({"blocked_evals": 3}, (), {"blocked_evals": 3}),  # the default
    ({"blocked_evals": 3}, ("blocked_evals",), {}),
    ({"blocked_evals": 3, "broker": 1, "plan_queue": 2}, ("blocked_evals",),
     {"broker": 1, "plan_queue": 2}),
    ({"broker": 0, "plan_queue": 0, "blocked_evals": 0}, (), {}),
])
def test_settle_ignores_what_may_remain_and_nothing_else(rig, in_flight,
                                                         may_remain, left):
    _, make = rig
    ctx, _ = make()
    assert ops.settle(StillBusy(**in_flight), ctx, deadline_s=0.0,
                      may_remain=may_remain) == left


def test_settle_never_ignores_an_operation_that_is_not_visible(rig):
    _, make = rig
    ctx, _ = make()
    acked(ctx, "j1", asked=2)
    assert ops.settle(StillBusy(blocked_evals=1), ctx, deadline_s=0.0,
                      may_remain=("blocked_evals",)) == {"ops_not_visible": 1}
