"""The deployment watcher's pass costs what changed (PR 34).

(a) `allocs_by_deployment` through the job's index reads what a scan of
    the whole allocs table reads, on seeded stores;
(b) the watcher, which judges a deployment only when the store says it
    was touched or a deadline it computed is due, sends the same raft
    messages on the same passes as a reference that judges every active
    deployment on every pass — the parent's loop and `_judge`, kept
    here, reading through a scan of the whole table — over scripted
    histories on a fake clock;
(c) a pass over untouched deployments reads no alloc, and a SoA batch's
    commit marks its deployment once, not once a row.
"""

import threading

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.server import deployment_watcher as dw
from nomad_tpu.server.deployment_watcher import DeploymentsWatcher
from nomad_tpu.server.raft import FSM, InmemLog
from nomad_tpu.state import StateStore
from nomad_tpu.state import store as store_mod
from nomad_tpu.structs.placement_batch import AllocRow, PlacementBatch
from nomad_tpu.structs.structs import (
    DEPLOYMENT_STATUS_FAILED,
    DEPLOYMENT_STATUS_SUCCESSFUL,
    AllocDeploymentStatus,
    DeploymentState,
    DeploymentStatusUpdate,
    PlanResult,
    UpdateStrategy,
    new_deployment,
)

T0 = 1_000_000 * 10**9


class Clock:
    def __init__(self) -> None:
        self.ns = T0

    def __call__(self) -> int:
        return self.ns

    def advance(self, seconds: float) -> None:
        self.ns += int(seconds * 1e9)


class World:
    """Store + FSM + one-node log, with every id given by the script so
    two runs of one history build the same world."""

    def __init__(self, monkeypatch) -> None:
        self.clock = Clock()
        monkeypatch.setattr(dw, "now_ns", self.clock)
        monkeypatch.setattr(store_mod, "now_ns", self.clock)
        self.state = StateStore()
        self.log = InmemLog(FSM(self.state))
        self.apply = self.log.apply
        self.nodes = []
        for i in range(4):
            n = mock.node()
            n.id, n.name = f"node-{i}", f"node-{i}"
            self.apply("node_register", n)
            self.nodes.append(n)

    def job(self, jid, env=None, stable=False, **update):
        job = mock.job(id=jid)
        job.update = UpdateStrategy(**update)
        job.task_groups[0].update = job.update.copy()
        if env:
            job.task_groups[0].tasks[0].env["V"] = env
        job.stable = stable
        job.canonicalize()
        self.apply("job_register", (job, None))
        return self.state.job_by_id(job.namespace, jid)

    def deployment(self, did, job, desired=2, canaries=0, progress_in=0.0):
        d = new_deployment(job)
        d.id = did
        d.task_groups[job.task_groups[0].name] = DeploymentState(
            auto_revert=job.update.auto_revert,
            auto_promote=job.update.auto_promote,
            desired_canaries=canaries,
            desired_total=desired,
            placed_allocs=desired,
            require_progress_by_ns=(
                self.clock.ns + int(progress_in * 1e9) if progress_in else 0
            ),
        )
        self.apply("deployment_upsert", d)
        return d

    def allocs(self, job, did, ids, healthy=None, canary=False,
               client_status="running"):
        out = []
        for i, aid in enumerate(ids):
            a = mock.alloc(
                job_=job, node_=self.nodes[i % len(self.nodes)], index=i,
                id=aid, eval_id=f"ev-{did or job.id}", deployment_id=did,
                client_status=client_status, create_time=0, modify_time=0,
            )
            if healthy is not None or canary:
                a.deployment_status = AllocDeploymentStatus(
                    healthy=healthy, canary=canary
                )
            out.append(a)
        return out

    def place(self, job, did, ids, **kw):
        self.apply("alloc_update", self.allocs(job, did, ids, **kw))

    def plan(self, job, did, ids, deployment=None, **kw):
        """Eager allocs through the plan-result transaction (the one
        that records placed canaries)."""
        by_node: dict = {}
        for a in self.allocs(job, did, ids, client_status="pending", **kw):
            by_node.setdefault(a.node_id, []).append(a)
        self.apply("apply_plan_results", PlanResult(
            job=job, deployment=deployment, node_allocation=by_node,
        ))

    def batch(self, job, did, ids, deployment=None):
        """A SoA batch: the rows land as lazy AllocRow handles."""
        a0 = mock.alloc(job_=job, node_=self.nodes[0])
        idx = np.arange(len(ids), dtype=np.int32) % len(self.nodes)
        b = PlacementBatch(
            namespace=job.namespace, eval_id=f"ev-{did or job.id}",
            job_id=job.id, job=job,
            task_group=job.task_groups[0].name, resources=a0.resources,
            metrics=a0.metrics, deployment_id=did, ids=list(ids),
            names=[f"{job.id}.web[{i}]" for i in range(len(ids))],
            node_idx_raw=idx.tobytes(),
            node_ids=[n.id for n in self.nodes],
            node_names=[n.name for n in self.nodes],
        )
        self.apply("apply_plan_results", PlanResult(
            job=job, deployment=deployment, alloc_batches=[b],
        ))

    def report(self, ids, healthy=None, client_status="running"):
        """What a client's alloc-health watcher sends."""
        ups = []
        for aid in ids:
            u = self.state.alloc_by_id(aid).copy()
            u.client_status = client_status
            u.deployment_status = (
                AllocDeploymentStatus(healthy=healthy, timestamp_ns=self.clock.ns)
                if healthy is not None else None
            )
            ups.append(u)
        self.apply("alloc_client_update", ups)

    def stop(self, ids):
        ups = []
        for aid in ids:
            u = self.state.alloc_by_id(aid).copy()
            u.desired_status = "stop"
            ups.append(u)
        self.apply("alloc_update", ups)


def full_scan(state, deployment_id):
    """The parent's reader: the whole allocs table, filtered."""
    return [a for a in state.allocs() if a.deployment_id == deployment_id]


# ---------------------------------------------------------------------------
# (a) the reader
# ---------------------------------------------------------------------------


def _seed_eager(w):
    job = w.job("j-eager")
    w.deployment("d-eager", job, desired=3)
    w.place(job, "d-eager", ["e-0", "e-1", "e-2"], healthy=True)


def _seed_lazy(w):
    job = w.job("j-lazy")
    d = new_deployment(job)
    d.id = "d-lazy"
    d.task_groups["web"] = DeploymentState(desired_total=50)
    w.batch(job, "d-lazy", [f"l-{i}" for i in range(50)], deployment=d)
    rows = w.state._tables[store_mod.TABLE_ALLOCS]
    assert all(rows[f"l-{i}"].__class__ is AllocRow for i in range(50))


def _seed_two_versions(w):
    v0 = w.job("j-two")
    w.deployment("d-v0", v0, desired=2)
    w.place(v0, "d-v0", ["v0-0", "v0-1"], healthy=True)
    v1 = w.job("j-two", env="2")
    assert v1.version == 1
    w.deployment("d-v1", v1, desired=2)
    w.place(v1, "d-v1", ["v1-0", "v1-1"])
    w.batch(v1, "d-v1", ["v1-2", "v1-3"])


def _seed_without_deployment(w):
    job = w.job("j-mixed")
    w.deployment("d-mixed", job, desired=2)
    w.place(job, "", ["m-bare-0", "m-bare-1"])
    w.place(job, "d-mixed", ["m-0", "m-1"])
    other = w.job("j-bare")
    w.place(other, "", ["b-0"])


def _seed_stopped(w):
    _seed_eager(w)
    w.stop(["e-1"])
    assert w.state.alloc_by_id("e-1").terminal_status()


def _seed_gc(w):
    _seed_eager(w)
    _seed_lazy(w)
    w.apply("eval_delete", ([], ["e-0", "l-7", "l-8"]))
    assert w.state.alloc_by_id("l-7") is None


def _seed_restored(w):
    _seed_two_versions(w)
    _seed_lazy(w)
    raw = w.state.serialize()
    w.place(w.state.job_by_id("default", "j-two"), "d-v1", ["v1-late"])
    w.state.restore_from(raw)
    assert w.state.alloc_by_id("v1-late") is None


def _seed_moved(w):
    """An alloc updated in place from one deployment to the next."""
    _seed_two_versions(w)
    moved = w.state.alloc_by_id("v0-0").copy()
    moved.deployment_id = "d-v1"
    w.apply("alloc_update", [moved])


SEEDS = {
    "eager": _seed_eager,
    "lazy_soa_rows": _seed_lazy,
    "two_versions_two_deployments": _seed_two_versions,
    "allocs_without_a_deployment": _seed_without_deployment,
    "an_alloc_stopped": _seed_stopped,
    "deleted_by_gc": _seed_gc,
    "snapshot_restored": _seed_restored,
    "moved_between_deployments": _seed_moved,
}


@pytest.mark.parametrize("seed", SEEDS)
def test_allocs_by_deployment_through_the_jobs_index_equals_the_full_scan(
    seed, monkeypatch
):
    w = World(monkeypatch)
    SEEDS[seed](w)
    deployments = w.state.deployments()
    assert deployments
    for view in (w.state, w.state.snapshot()):
        total = 0
        for d in deployments:
            want = full_scan(view, d.id)
            got = view.allocs_by_deployment(d.id)
            # the same allocs in the table's order — the same objects,
            # but after a restore, whose index tables unpack as copies
            assert [a.id for a in got] == [a.id for a in want]
            assert all(g is x or (seed == "snapshot_restored" and g == x)
                       for g, x in zip(got, want))
            assert not any(a.__class__ is AllocRow for a in got)
            lazy = view.allocs_by_deployment(d.id, lazy=True)
            assert [a.id for a in lazy] == [a.id for a in want]
            total += len(got)
        assert total > 0
        assert view.allocs_by_deployment("no-such-deployment") == []
        assert view.allocs_by_deployment("") == []


def test_a_lazy_row_answers_what_the_judge_reads_without_materialising(
    monkeypatch,
):
    w = World(monkeypatch)
    _seed_lazy(w)
    minted = []
    real_row = PlacementBatch.row
    monkeypatch.setattr(
        PlacementBatch, "row",
        lambda self, i: minted.append(i) or real_row(self, i),
    )
    rows = w.state.allocs_by_deployment("d-lazy", lazy=True)
    assert len(rows) == 50 and rows[0].__class__ is AllocRow
    for r in rows:
        assert (r.terminal_status(), r.deployment_status, r.task_group,
                r.client_status, r.create_time, r.modify_time) == \
            (False, None, "web", "pending", T0, T0)
        assert r.id.startswith("l-") and r.job is not None
    watcher = DeploymentsWatcher(w.state, w.apply)
    assert watcher.run_once() == 0
    assert minted == []
    # and the materialised row says the same
    a = w.state.alloc_by_id("l-3")
    assert (a.deployment_status, a.create_time, a.modify_time) == \
        (None, T0, T0)


def test_the_reader_does_not_die_of_a_bulk_commit_beside_it(monkeypatch):
    """The parent's reader iterated the live allocs table with a Python
    predicate; this one copies the job's inner dict in one C call."""
    w = World(monkeypatch)
    job = w.job("j-race")
    w.deployment("d-race", job, desired=10**6)
    errors, done = [], threading.Event()

    def read():
        try:
            while not done.is_set():
                for a in w.state.allocs_by_deployment("d-race", lazy=True):
                    a.deployment_id
        except Exception as e:  # pragma: no cover - the failure itself
            errors.append(e)

    t = threading.Thread(target=read)
    t.start()
    try:
        for k in range(30):
            w.batch(job, "d-race", [f"r-{k}-{i}" for i in range(400)])
    finally:
        done.set()
        t.join(10)
    assert not errors
    assert len(w.state.allocs_by_deployment("d-race")) == 12_000


# ---------------------------------------------------------------------------
# (b) the differential
# ---------------------------------------------------------------------------


class EveryPassWatcher(DeploymentsWatcher):
    """The watcher as the parent commit had it: every pass judges every
    active deployment and marks every successful one's job stable, and
    `_judge` reads the deployment's allocs by a scan of the whole
    table. Its actions (`_fail`, `promote`, `_mark_job_stable`,
    `_new_eval`) are the class's own: they did not change."""

    def run_once(self) -> int:
        acted = 0
        for d in self.state.deployments():
            if d.status == DEPLOYMENT_STATUS_SUCCESSFUL:
                self._mark_job_stable(d)
                continue
            if not d.active() or d.status == "paused":
                continue
            if self._judge(d):
                acted += 1
        return acted

    def _judge(self, d) -> bool:
        allocs = full_scan(self.state, d.id)
        healthy = {g: 0 for g in d.task_groups}
        unhealthy_ids = []
        canary_healthy = {g: 0 for g in d.task_groups}
        now = dw.now_ns()
        for a in allocs:
            if a.terminal_status():
                continue
            ds = a.deployment_status
            g = a.task_group
            if g not in d.task_groups:
                continue
            dstate = d.task_groups[g]
            if ds is not None and ds.is_healthy():
                healthy[g] += 1
                if a.id in dstate.placed_canaries:
                    canary_healthy[g] += 1
            elif ds is not None and ds.is_unhealthy():
                unhealthy_ids.append(a.id)
            else:
                deadline = self._parent_healthy_deadline_ns(d, a)
                if deadline and now > deadline and not a.terminal_status():
                    unhealthy_ids.append(a.id)
                elif a.client_status == "failed":
                    unhealthy_ids.append(a.id)
        if unhealthy_ids:
            self._fail(d, unhealthy_ids)
            return True
        for g, dstate in d.task_groups.items():
            if (
                dstate.require_progress_by_ns
                and now > dstate.require_progress_by_ns
                and healthy[g] < dstate.desired_total
            ):
                self._fail(d, [], desc=dw.DESC_PROGRESS_DEADLINE)
                return True
        if d.requires_promotion() and d.has_auto_promote():
            ready = all(
                canary_healthy[g] >= s.desired_canaries
                for g, s in d.task_groups.items()
                if s.desired_canaries > 0
            )
            if ready:
                self.promote(d)
                return True
        drift = any(
            d.task_groups[g].healthy_allocs != healthy[g]
            for g in d.task_groups
        )
        if drift:
            healthy_ids = [
                a.id for a in allocs
                if a.deployment_status is not None
                and a.deployment_status.is_healthy()
            ]
            self.raft_apply("deployment_alloc_health", {
                "deployment_id": d.id,
                "healthy_ids": healthy_ids,
                "unhealthy_ids": [],
                "eval": self._new_eval(d),
            })
            return True
        complete = all(
            healthy[g] >= s.desired_total for g, s in d.task_groups.items()
        ) and not d.requires_promotion()
        if complete and d.task_groups:
            self.raft_apply("deployment_status_update", DeploymentStatusUpdate(
                deployment_id=d.id,
                status=DEPLOYMENT_STATUS_SUCCESSFUL,
                status_description="Deployment completed successfully",
            ))
            self._mark_job_stable(d)
            return True
        return False

    def _parent_healthy_deadline_ns(self, d, alloc) -> int:
        job = alloc.job or self.state.job_by_id(d.namespace, d.job_id)
        if job is None:
            return 0
        tg = job.lookup_task_group(alloc.task_group)
        if tg is None or tg.update is None:
            return 0
        base = alloc.create_time or alloc.modify_time
        if not base:
            return 0
        return base + int(tg.update.healthy_deadline_s * 1e9)


def _eval_key(ev):
    return None if ev is None else (
        ev.namespace, ev.priority, ev.type, ev.triggered_by, ev.job_id,
        ev.deployment_id, ev.status, ev.create_time,
    )


def _message(msg_type, payload):
    """A raft message without the ids minted for it."""
    if msg_type == "deployment_alloc_health":
        su = payload.get("status_update")
        rj = payload.get("revert_job")
        return (
            msg_type, payload["deployment_id"],
            tuple(payload["healthy_ids"]), tuple(payload["unhealthy_ids"]),
            None if su is None else (su.status, su.status_description),
            None if rj is None else (rj.id, rj.version, rj.stable),
            _eval_key(payload.get("eval")),
        )
    if msg_type == "deployment_status_update":
        return (msg_type, payload.deployment_id, payload.status,
                payload.status_description)
    if msg_type == "deployment_promote":
        did, groups, ev = payload
        return (msg_type, did, groups, _eval_key(ev))
    if msg_type == "job_register":
        job, ev = payload
        return (msg_type, job.id, job.version, job.stable, _eval_key(ev))
    raise AssertionError(f"a message the watcher never sent: {msg_type}")


def _h_healthy_to_success(w, watcher):
    job = w.job("j")
    w.deployment("d", job, desired=3)
    w.place(job, "d", ["a-0", "a-1", "a-2"])
    yield
    yield
    w.report(["a-0"], healthy=True)
    yield
    w.report(["a-1", "a-2"], healthy=True)
    yield  # drift resync
    yield  # successful, job marked stable
    yield
    yield


def _h_unhealthy_autoreverts(w, watcher):
    w.job("j", stable=True, auto_revert=True)
    v1 = w.job("j", env="2", auto_revert=True)
    w.deployment("d", v1, desired=2)
    w.place(v1, "d", ["a-0", "a-1"])
    yield
    w.report(["a-0"], healthy=True)
    yield
    w.report(["a-1"], healthy=False)
    yield
    yield
    yield


def _h_failed_alloc(w, watcher):
    job = w.job("j")
    w.deployment("d", job, desired=2)
    w.place(job, "d", ["a-0", "a-1"])
    yield
    w.report(["a-1"], client_status="failed")
    yield  # terminal: it no longer counts, its replacement will
    w.place(job, "d", ["a-2"])
    w.report(["a-0"], healthy=True)
    yield
    w.report(["a-2"], healthy=True)
    yield
    yield
    yield


def _h_healthy_deadline_with_nothing_written(w, watcher):
    job = w.job("j", healthy_deadline_s=300)
    w.deployment("d", job, desired=2)
    w.place(job, "d", ["a-0"])
    w.clock.advance(100)
    w.place(job, "d", ["a-1"])
    yield
    w.clock.advance(150)
    yield
    w.clock.advance(49.9)
    yield  # not yet: a-0 is 299.9 s old
    w.clock.advance(0.2)
    yield  # a-0 past its deadline, a-1 not
    yield
    w.clock.advance(1000)
    yield


def _h_progress_deadline_with_nothing_written(w, watcher):
    job = w.job("j", healthy_deadline_s=3000)
    w.deployment("d", job, desired=2, progress_in=60)
    w.place(job, "d", ["a-0", "a-1"])
    yield
    w.report(["a-0"], healthy=True)
    yield
    yield
    w.clock.advance(59)
    yield
    w.clock.advance(2)
    yield  # past require_progress_by with one of two healthy
    yield


def _h_auto_promote(w, watcher):
    w.job("j", stable=True, canary=1, auto_promote=True)
    v1 = w.job("j", env="2", canary=1, auto_promote=True)
    w.deployment("d", v1, desired=2, canaries=1)
    w.plan(v1, "d", ["c-0"], canary=True)
    assert w.state.deployment_by_id("d").task_groups["web"] \
        .placed_canaries == ["c-0"]
    yield
    w.report(["c-0"], healthy=True)
    yield  # promote
    yield  # drift
    w.place(v1, "d", ["a-1"])
    yield
    w.report(["a-1"], healthy=True)
    yield
    yield
    yield


def _h_pause_and_resume(w, watcher):
    job = w.job("j")
    w.deployment("d", job, desired=2)
    w.place(job, "d", ["a-0", "a-1"])
    yield
    watcher.pause(w.state.deployment_by_id("d"), True)
    yield
    w.report(["a-0"], healthy=False)
    yield  # paused: nobody judges it
    yield
    watcher.pause(w.state.deployment_by_id("d"), False)
    yield  # resumed: fails on the report made while paused
    yield


def _h_manual_fail_autoreverts(w, watcher):
    w.job("j", stable=True, auto_revert=True)
    v1 = w.job("j", env="2", auto_revert=True)
    w.deployment("d", v1, desired=2)
    w.place(v1, "d", ["a-0", "a-1"], healthy=True)
    yield
    watcher.fail_deployment(w.state.deployment_by_id("d"))
    yield
    yield


def _h_job_registered_again_mid_deployment(w, watcher):
    v0 = w.job("j")
    w.deployment("d0", v0, desired=2)
    w.place(v0, "d0", ["a-0", "a-1"], healthy=True)
    yield  # drift
    v1 = w.job("j", env="2")  # a new version while d0 runs
    w.deployment("d1", v1, desired=1)
    yield  # d0 successful; its version is no longer the job's
    yield
    w.place(v1, "d1", ["b-0"], healthy=True)
    yield
    yield  # d1 successful, v1 stable
    yield
    # the same version registered again, unstable: stability follows
    # again though no deployment was written
    again = w.state.job_by_id("default", "j").copy()
    again.stable = False
    w.apply("job_register", (again, None))
    assert not w.state.job_by_id("default", "j").stable
    yield
    yield
    yield


def _h_leadership_lost_and_regained(w, watcher):
    job = w.job("j")
    w.deployment("d", job, desired=2)
    w.place(job, "d", ["a-0", "a-1"])
    yield
    w.report(["a-0"], healthy=True)
    watcher.stop()
    w.report(["a-1"], healthy=True)
    watcher.start()  # polls once an hour: the script makes the passes
    yield
    yield
    watcher.stop()
    watcher.start()
    yield
    yield
    watcher.stop()


def _h_restore_from(w, watcher):
    job = w.job("j")
    w.deployment("d", job, desired=2)
    w.place(job, "d", ["a-0", "a-1"])
    other = w.job("k")
    w.deployment("e", other, desired=1)
    w.place(other, "e", ["k-0"], healthy=True)
    yield
    raw = w.state.serialize()
    w.report(["a-0", "a-1"], healthy=True)
    yield
    yield
    yield
    w.state.restore_from(raw)  # back to before the reports and `e` done
    yield
    yield
    w.report(["a-0"], healthy=False)
    yield
    yield


def _h_two_jobs_gc_and_soa_rows(w, watcher):
    ja, jb = w.job("ja"), w.job("jb", healthy_deadline_s=120)
    da = new_deployment(ja)
    da.id = "da"
    da.task_groups["web"] = DeploymentState(desired_total=6)
    w.batch(ja, "da", [f"a-{i}" for i in range(6)], deployment=da)
    w.deployment("db", jb, desired=2)
    yield
    w.place(jb, "db", ["b-0", "b-1"])
    yield
    w.report(["a-0", "a-1"], healthy=True)
    yield
    w.stop(["a-1"])  # a healthy alloc stopped: the count drifts back
    yield
    yield
    w.apply("eval_delete", ([], ["a-0"]))  # and one collected
    yield
    yield
    w.clock.advance(121)
    yield  # db's allocs pass their healthy deadline; da's have 300 s
    w.apply("deployment_delete", ["db"])
    yield
    w.clock.advance(200)
    yield
    yield


HISTORIES = {
    "placements_and_healthy_reports": _h_healthy_to_success,
    "unhealthy_report_autoreverts": _h_unhealthy_autoreverts,
    "a_failed_alloc": _h_failed_alloc,
    "healthy_deadline_nothing_written": _h_healthy_deadline_with_nothing_written,
    "progress_deadline_nothing_written": _h_progress_deadline_with_nothing_written,
    "auto_promote": _h_auto_promote,
    "pause_and_resume": _h_pause_and_resume,
    "manual_fail_autoreverts": _h_manual_fail_autoreverts,
    "job_registered_again_mid_deployment": _h_job_registered_again_mid_deployment,
    "leadership_lost_and_regained": _h_leadership_lost_and_regained,
    "restore_from": _h_restore_from,
    "two_jobs_gc_and_soa_rows": _h_two_jobs_gc_and_soa_rows,
}


def _drive(cls, history, monkeypatch):
    w = World(monkeypatch)
    sent = []
    passes = [0]

    def apply(msg_type, payload):
        sent.append((passes[0], _message(msg_type, payload)))
        return w.apply(msg_type, payload)

    watcher = cls(w.state, apply, poll_interval_s=3600.0)
    for _ in history(w, watcher):
        passes[0] += 1
        watcher.run_once()
        w.clock.advance(0.25)
    end = sorted(
        (d.id, d.status, d.status_description,
         tuple((g, s.healthy_allocs, s.unhealthy_allocs, s.promoted)
               for g, s in d.task_groups.items()))
        for d in w.state.deployments()
    ) + sorted(
        (j.id, j.version, j.stable) for j in w.state.jobs()
    )
    return sent, end


@pytest.mark.parametrize("history", HISTORIES)
def test_the_same_raft_messages_on_the_same_passes_as_judging_everything(
    history, monkeypatch
):
    want, want_end = _drive(EveryPassWatcher, HISTORIES[history], monkeypatch)
    got, got_end = _drive(DeploymentsWatcher, HISTORIES[history], monkeypatch)
    assert want, "a history in which the watcher never acts shows nothing"
    assert got == want
    assert got_end == want_end


def test_the_histories_reach_the_outcomes_they_are_named_for(monkeypatch):
    """The differential compares two watchers; this holds the script
    itself to what it claims to exercise."""
    def kinds(name):
        sent, end = _drive(DeploymentsWatcher, HISTORIES[name], monkeypatch)
        return [m[1] for m in sent], end

    msgs, end = kinds("placements_and_healthy_reports")
    assert ("d", DEPLOYMENT_STATUS_SUCCESSFUL) == end[0][:2]
    assert ("job_register", "j", 0, True, None) in msgs
    msgs, end = kinds("unhealthy_report_autoreverts")
    assert end[0][1] == DEPLOYMENT_STATUS_FAILED
    assert "rolling back to job version 0" in end[0][2]
    msgs, end = kinds("healthy_deadline_nothing_written")
    assert [m[3] for m in msgs if m[0] == "deployment_alloc_health"] \
        == [("a-0",)]
    msgs, end = kinds("progress_deadline_nothing_written")
    assert end[0][2] == dw.DESC_PROGRESS_DEADLINE
    msgs, end = kinds("auto_promote")
    assert "deployment_promote" in [m[0] for m in msgs]
    assert end[0][1] == DEPLOYMENT_STATUS_SUCCESSFUL
    msgs, end = kinds("job_registered_again_mid_deployment")
    assert [m[:4] for m in msgs if m[0] == "job_register"] == [
        ("job_register", "j", 1, True), ("job_register", "j", 1, True),
    ]


# ---------------------------------------------------------------------------
# (c) what a pass reads
# ---------------------------------------------------------------------------


def _count_reads(state, monkeypatch):
    reads = []
    real = state.allocs_by_deployment
    monkeypatch.setattr(
        state, "allocs_by_deployment",
        lambda did, **kw: reads.append(did) or real(did, **kw),
    )
    return reads


def test_a_pass_over_untouched_deployments_reads_no_alloc(monkeypatch):
    w = World(monkeypatch)
    jobs = []
    for k in range(400):
        job = w.job(f"j-{k}")
        d = new_deployment(job)
        d.id = f"d-{k}"
        d.task_groups["web"] = DeploymentState(desired_total=250)
        w.batch(job, d.id, [f"a-{k}-{i}" for i in range(250)], deployment=d)
        jobs.append(job)
    assert len(w.state._tables[store_mod.TABLE_ALLOCS]) == 100_000
    watcher = DeploymentsWatcher(w.state, w.apply)
    reads = _count_reads(w.state, monkeypatch)
    assert watcher.run_once() == 0
    assert sorted(reads) == sorted(f"d-{k}" for k in range(400))
    # nothing written: four passes judge none and read no alloc
    del reads[:]
    for _ in range(4):
        w.clock.advance(0.25)
        assert watcher.run_once() == 0
    assert reads == []
    # one batch committed: exactly its deployment is judged, once
    w.batch(jobs[17], "d-17", [f"late-{i}" for i in range(250)])
    w.clock.advance(0.25)
    assert watcher.run_once() == 0
    assert reads == ["d-17"]
    w.clock.advance(0.25)
    watcher.run_once()
    assert reads == ["d-17"]
    # a write that carries no deployment's id wakes nobody
    w.place(jobs[3], "", ["bare-0"])
    w.apply("node_register", mock.node())
    watcher.run_once()
    assert reads == ["d-17"]
    # the clock alone does: every deployment's healthy deadline passes
    w.clock.advance(301)
    assert watcher.run_once() == 400
    # the judge's read, and the health transaction's own recount
    assert len(reads) == 1 + 2 * 400


@pytest.mark.parametrize("rows", [1, 1000])
def test_a_soa_batch_marks_its_deployment_once_not_once_a_row(
    rows, monkeypatch
):
    w = World(monkeypatch)
    job = w.job("j")
    d = new_deployment(job)
    d.id = "d"
    d.task_groups["web"] = DeploymentState(desired_total=rows)
    marks = []
    real = StateStore._touch_deployment
    monkeypatch.setattr(
        StateStore, "_touch_deployment",
        lambda self, did: marks.append(did) or real(self, did),
    )
    w.batch(job, "d", [f"a-{i}" for i in range(rows)], deployment=d)
    # the deployment's own write, and the batch
    assert marks == ["d", "d"]
    seq = w.state.deployments_touched()["d"]
    w.batch(job, "d", [f"b-{i}" for i in range(rows)])
    assert marks == ["d", "d", "d"]
    assert w.state.deployments_touched()["d"] > seq


@pytest.mark.parametrize("write", [
    "alloc_placed", "client_report", "alloc_stopped", "alloc_deleted",
    "deployment_status", "deployment_upsert", "alloc_health", "promotion",
    "plan_with_canary", "restore",
])
def test_every_write_a_judgment_reads_moves_the_touch_sequence(
    write, monkeypatch
):
    w = World(monkeypatch)
    job = w.job("j", canary=1)
    w.deployment("d", job, desired=2, canaries=1)
    w.plan(job, "d", ["c-0"], canary=True, healthy=True)
    other = w.job("k")
    w.deployment("e", other, desired=1)
    before = w.state.deployments_touched()
    assert set(before) == {"d", "e"}
    if write == "alloc_placed":
        w.place(job, "d", ["a-0"])
    elif write == "client_report":
        w.report(["c-0"], healthy=False)
    elif write == "alloc_stopped":
        w.stop(["c-0"])
    elif write == "alloc_deleted":
        w.apply("eval_delete", ([], ["c-0"]))
    elif write == "deployment_status":
        w.apply("deployment_status_update", DeploymentStatusUpdate(
            deployment_id="d", status="paused", status_description="p"))
    elif write == "deployment_upsert":
        w.apply("deployment_upsert", w.state.deployment_by_id("d"))
    elif write == "alloc_health":
        w.apply("deployment_alloc_health", {
            "deployment_id": "d", "healthy_ids": [], "unhealthy_ids": []})
    elif write == "promotion":
        w.apply("deployment_promote", ("d", None, None))
    elif write == "plan_with_canary":
        w.plan(job, "d", ["c-1"], canary=True)
    elif write == "restore":
        w.state.restore_from(w.state.serialize())
    after = w.state.deployments_touched()
    assert after["d"] > before["d"]
    # and the deployment beside it is left alone (a restore touches all)
    assert (after["e"] > before["e"]) == (write == "restore")
    w.apply("deployment_delete", ["d"])
    assert set(w.state.deployments_touched()) == {"e"}
    # an alloc still carrying a collected deployment's id marks nothing
    w.place(job, "d", ["a-late"])
    assert set(w.state.deployments_touched()) == {"e"}
