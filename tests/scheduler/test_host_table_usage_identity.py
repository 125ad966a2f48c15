"""`lower.table`'s usage rows by identity, against a fresh build.

Through the resident skeleton (`ResidentClusterState.host_table`) a
batch's `used` comes from `lower.UsageRows`: the store's per-node usage
read in bulk (`node_usage_many`), only the nodes whose entry is another
object than last time rewritten, and the batch's own stops, host
partition placements and interactive-lane usage added to a copy. The
skeleton is kept without the fingerprint walk while the node list is the
very list last proven. Whatever happened between two solves, the table
must read what a fresh `build_node_table(..., usage_of=...)` reads on the
same snapshot (a solver without a resident state), element for element,
and the counters must say which way it went.
"""

import random

import numpy as np
import pytest

from nomad_tpu import metrics, mock
from nomad_tpu.metrics import Registry
from nomad_tpu.scheduler.context import SchedulerConfig
from nomad_tpu.scheduler.tpu import solve_eval_batch
from nomad_tpu.scheduler.tpu.scheduler import _reconcile_eval_batch
from nomad_tpu.scheduler.tpu.solver import (
    BatchSolver,
    ResidentClusterState,
    SolveOutcome,
)
from nomad_tpu.server.plan_apply import OverlaySnapshot
from nomad_tpu.structs import DrainStrategy, PlanResult
from nomad_tpu.testing import Harness

CONFIG = SchedulerConfig(backend="tpu", small_batch_threshold=0)
N = 24
DCS = ["dc1", "dc2"]
WALKS = "nomad.tpu.lower_fingerprint_walks"
REWRITTEN = "nomad.tpu.lower_usage_rewritten"
ANY = object()  # an expectation not checked


def _alloc(job, node, cpu: int = 500, memory_mb: int = 256):
    a = mock.alloc(job_=job, node_=node)
    a.resources.tasks["web"].cpu = cpu
    a.resources.tasks["web"].memory_mb = memory_mb
    a.resources.tasks["web"].networks = []
    a.client_status = "running"
    return a


class Cluster:
    """N nodes over two datacenters, a standing job's allocs dealt over
    two thirds of them (the rest hold nothing: no entry in the store's
    usage table), and one resident state that every lowering shares."""

    def __init__(self, seed: int) -> None:
        self.rng = random.Random(seed)
        self.h = h = Harness()
        self.nodes = []
        for i in range(N):
            node = mock.node(datacenter=DCS[i % 2])
            node.reserved.cpu = 0
            node.reserved.memory_mb = 0
            h.state.upsert_node(h.next_index(), node)
            self.nodes.append(node)
        self.standing = mock.job(id="standing", priority=50)
        self.standing.datacenters = list(DCS)
        h.state.upsert_job(h.next_index(), self.standing)
        self.place(self.rng.sample(self.nodes, 2 * N // 3))
        self.resident = ResidentClusterState()
        self.deploys = 0
        self.prev = None  # the last resident table, and its rows as read

    def place(self, nodes) -> set:
        """Commit one alloc of a dealt size on each of `nodes`; the ids
        of the nodes written."""
        allocs = [_alloc(self.standing, n, self.rng.choice((250, 500)),
                         self.rng.choice((128, 256))) for n in nodes]
        self.h.state.upsert_allocs(self.h.next_index(), allocs)
        return {n.id for n in nodes}

    def live(self) -> list:
        return [a for a in self.h.state.allocs() if not a.terminal_status()]

    def job(self, datacenters=DCS):
        """A new job a deploy, as the closed loop sends them."""
        self.deploys += 1
        job = mock.job(id=f"deploy-{self.deploys}", priority=50)
        job.datacenters = list(datacenters)
        job.task_groups[0].count = 3
        job.task_groups[0].tasks[0].resources.networks = []
        self.h.state.upsert_job(self.h.next_index(), job)
        return job

    def lower(self, jobs, snap=None, stops=(), placed=(), extra=None,
              want_skeleton=None, want_rewritten=ANY, want_walks=None):
        """Lower one batch of `jobs` on `snap` (by default the store as
        it is now) through the resident state and without one; assert the
        two tables bit-equal and the counters as given (`want_skeleton`:
        `kept` without a walk, `walked` by the fingerprint to the same
        skeleton, `rebuilt`). Returns the resident table."""
        evs = [mock.eval_for_job(j) for j in jobs]
        if snap is None:
            snap = self.h.snapshot()

        def table(resident):
            plans, asks = _reconcile_eval_batch(snap, self.h, evs, CONFIG)
            for a in stops:
                plans[evs[0].id].append_stopped_alloc(a, "stopped")
            solver = BatchSolver(snap, CONFIG, resident=resident,
                                 extra_usage=extra)
            solver._partition_placed = list(placed)
            kind, low = solver._lower_batch(asks, SolveOutcome())
            assert kind == "dense"
            return low.table

        want = table(None)
        skel = self.resident._host_table
        reg = Registry()
        old = metrics._install_registry(reg)
        handle = reg.enable_timing_capture()
        try:
            got = table(self.resident)
            walks = metrics.snapshot()["counters"].get(WALKS, 0)
            rewritten = reg.drain_timings(handle).get(REWRITTEN, [])
        finally:
            metrics._install_registry(old)
        how = ("kept" if not walks
               else "walked" if self.resident._host_table is skel
               else "rebuilt")

        assert [n.id for n in got.nodes] == [n.id for n in want.nodes]
        for name in ("cap", "used", "datacenters"):
            a, b = getattr(got, name), getattr(want, name)
            assert a.dtype == b.dtype and a.shape == b.shape, name
            assert np.array_equal(a, b), name
        # a table handed out earlier (finish(N) still reads it while
        # begin(N+1) refreshes) keeps its rows
        if self.prev is not None:
            assert np.array_equal(self.prev[0].used, self.prev[1])
        self.prev = (got, got.used.copy())
        if want_skeleton is not None:
            assert how == want_skeleton
        if want_walks is not None:
            assert walks == want_walks
        if want_rewritten is not ANY:
            assert rewritten == (
                [] if want_rewritten is None else [want_rewritten])
        return got


def case_plans_committed(c: Cluster) -> None:
    """Solved plans committed between solves: a warm read rewrites the
    nodes the plan wrote and no other."""
    c.lower([c.job()], want_skeleton="rebuilt",
            want_walks=1)
    # the first read after a rebuild writes every row
    c.lower([c.job()], want_skeleton="kept",
            want_rewritten=N, want_walks=0)
    for _ in range(3):
        job = c.job()
        snap = c.h.snapshot()
        ev = mock.eval_for_job(job)
        plan = solve_eval_batch(snap, c.h, [ev], CONFIG,
                                resident=c.resident)[ev.id]
        before = c.h.state.node_usage_many([n.id for n in c.nodes])
        c.h.submit_plan(plan)
        after = c.h.state.node_usage_many([n.id for n in c.nodes])
        written = sum(a is not b for a, b in zip(before, after))
        assert written
        c.lower([c.job()], want_skeleton="kept",
                want_rewritten=written, want_walks=0)


def case_unchanged_cluster(c: Cluster) -> None:
    """Nothing written between two reads: nothing rewritten — a node
    without usage reads the one shared default, not a new tuple."""
    c.lower([c.job()])
    c.lower([c.job()], want_rewritten=N)
    c.lower([c.job()], want_skeleton="kept",
            want_rewritten=0, want_walks=0)
    c.lower([c.job()], want_rewritten=0)


def case_stops_of_the_batch(c: Cluster) -> None:
    c.lower([c.job()])
    c.lower([c.job()])
    stops = c.rng.sample(c.live(), 4)
    got = c.lower([c.job()], stops=stops,
                  want_skeleton="kept", want_rewritten=0)
    assert got.used.min() >= 0
    # the resident base is the store's: the next batch has no stops
    c.lower([c.job()], want_rewritten=0)


def case_host_partition_placements(c: Cluster) -> None:
    c.lower([c.job()])
    placed = [_alloc(c.standing, n, 125, 64)
              for n in c.rng.sample(c.nodes, 5)]
    c.lower([c.job()], placed=placed,
            want_skeleton="kept", want_rewritten=N)
    c.lower([c.job()], placed=placed[:2],
            want_rewritten=0)


def case_extra_usage(c: Cluster) -> None:
    """The interactive lane's ledger, on a node of the table and on one
    outside it (the batch's job admits dc1 alone)."""
    c.lower([c.job(["dc1"])])
    dc1 = [n for n in c.nodes if n.datacenter == "dc1"]
    dc2 = [n for n in c.nodes if n.datacenter == "dc2"]
    extra = {dc1[0].id: [300, 100, 0], dc1[1].id: [-50, 0, 10],
             dc2[0].id: [1000, 1000, 0]}
    got = c.lower([c.job(["dc1"])], extra=extra,
                  want_skeleton="kept", want_rewritten=N // 2)
    assert got.n == N // 2


def case_usage_drops_to_zero(c: Cluster) -> None:
    """A node's last alloc finishes: its entry leaves the store's table
    and the row reads zero again."""
    c.lower([c.job()])
    c.lower([c.job()])
    node_id = c.live()[0].node_id
    done = []
    for a in c.live():
        if a.node_id == node_id:
            a = a.copy()
            a.client_status = "complete"
            done.append(a)
    c.h.state.update_allocs_from_client(c.h.next_index(), done)
    assert c.h.state.node_usage(node_id) == (0, 0, 0, 0)
    got = c.lower([c.job()], want_skeleton="kept",
                  want_rewritten=1)
    assert not got.used[got.index_of[node_id]].any()


def case_snapshots_out_of_order(c: Cluster) -> None:
    """An older snapshot read after a newer one: the rows go back."""
    c.lower([c.job()])
    job = c.job()
    old = c.h.snapshot()
    c.lower([job], old, want_rewritten=N)
    written = c.place(c.rng.sample(c.nodes, 3))
    new = c.h.snapshot()
    for snap, rewritten in ((new, len(written)), (old, len(written)),
                            (new, len(written)), (new, 0)):
        c.lower([job], snap, want_skeleton="kept",
                want_rewritten=rewritten, want_walks=0)


def case_node_drained(c: Cluster) -> None:
    """A node drained between two solves: another universe, rebuilt."""
    c.lower([c.job()])
    c.lower([c.job()], want_skeleton="kept")
    c.h.state.update_node_drain(c.h.next_index(), c.nodes[3].id,
                                DrainStrategy(deadline_s=600))
    got = c.lower([c.job()], want_skeleton="rebuilt",
                  want_walks=1)
    assert got.n == N - 1
    c.lower([c.job()], want_skeleton="kept",
            want_rewritten=N - 1, want_walks=0)


def case_node_re_registered(c: Cluster) -> None:
    """The same node registered again: its modify index moves."""
    c.lower([c.job()])
    c.lower([c.job()])
    c.h.state.upsert_node(c.h.next_index(), c.nodes[5].copy())
    c.lower([c.job()], want_skeleton="rebuilt",
            want_walks=1)
    c.lower([c.job()], want_skeleton="kept",
            want_rewritten=N)


def case_two_datacenter_sets(c: Cluster) -> None:
    """A batch of two jobs with different datacenter sets lowers over a
    union list made anew each time: the fingerprint is walked every time,
    and proves the skeleton unchanged without re-interning it."""
    def batch():
        return [c.job(["dc1"]), c.job(["dc2"])]

    c.lower(batch(), want_skeleton="rebuilt",
            want_walks=1)
    vers = c.resident._host_vers
    c.lower(batch(), want_skeleton="walked",
            want_rewritten=N, want_walks=1)
    written = c.place(c.rng.sample(c.nodes, 2))
    c.lower(batch(), want_skeleton="walked",
            want_rewritten=len(written), want_walks=1)
    assert c.resident._host_vers is vers  # the lowered-skeleton cache's key


def case_overlay_snapshot(c: Cluster) -> None:
    """A snapshot with a verified plan laid over it, not yet committed
    (the applier's OverlaySnapshot): the rows read its usage, not the
    base's, and an overlaid node's entry is made anew on every read."""
    c.lower([c.job()], want_skeleton="rebuilt")
    job = c.job()
    node = c.nodes[1]
    result = PlanResult(
        node_update={},
        node_allocation={node.id: [_alloc(c.standing, node, 125, 64)]},
        node_preemptions={},
    )
    ov = OverlaySnapshot(c.h.snapshot(), result, c.standing)
    assert ov.node_usage(node.id) != c.h.state.node_usage(node.id)
    c.lower([job], ov, want_skeleton="kept", want_rewritten=N,
            want_walks=0)
    c.lower([job], ov, want_skeleton="kept", want_rewritten=1)
    # the base again: the overlaid row goes back
    c.lower([job], want_skeleton="kept", want_rewritten=1)


CASES = {f.__name__[len("case_"):]: f for f in (
    case_plans_committed, case_unchanged_cluster, case_stops_of_the_batch,
    case_host_partition_placements, case_extra_usage,
    case_usage_drops_to_zero, case_snapshots_out_of_order,
    case_node_drained, case_node_re_registered, case_two_datacenter_sets,
    case_overlay_snapshot,
)}


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_resident_usage_rows_are_a_fresh_builds(case, seed):
    CASES[case](Cluster(seed))
