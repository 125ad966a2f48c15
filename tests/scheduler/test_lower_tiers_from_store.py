"""`lower.table` from the store's usage by (node, priority) against the
alloc walk it replaces.

A batch that may preempt reads its tiers from the store's index
(`node_tier_usage`) in O(nodes); the walk over every live alloc is what
it read before, and what a batch that asks for cores still reads. On
the same snapshot, stops and host-partition placements both must give
the same `NodeTable` — `used`, `tier_prios`, `tier_used` element for
element — and so the same placements and the same victims. The walk is
had here by showing the solver the snapshot without the index: what its
own `hasattr` sees of a state that has none.
"""

import random

import numpy as np
import pytest

from nomad_tpu import metrics, mock, trace
from nomad_tpu.metrics import Registry
from nomad_tpu.scheduler.context import SchedulerConfig
from nomad_tpu.scheduler.tpu.scheduler import _reconcile_eval_batch
from nomad_tpu.scheduler.tpu.solver import (
    BatchSolver,
    ResidentClusterState,
    SolveOutcome,
)

CONFIG = SchedulerConfig(backend="tpu", small_batch_threshold=0)
ASK = (500, 256)   # cpu MHz, memory MB, as test_preempt_lowest_first's


class WithoutTheIndex:
    """The snapshot, to a reader that finds no usage by priority in it."""

    def __init__(self, snap) -> None:
        self._snap = snap

    def __getattr__(self, name):
        if name == "node_tier_usage":
            raise AttributeError(name)
        return getattr(self._snap, name)


def _sized(alloc, cpu: int, memory_mb: int):
    alloc.resources.tasks["web"].cpu = cpu
    alloc.resources.tasks["web"].memory_mb = memory_mb
    alloc.resources.tasks["web"].networks = []
    return alloc


def churn(h, rng: random.Random) -> tuple[list, list]:
    """Make the built cluster uneven and keep it as full, and deal the
    batch its own view of it: (allocs the batch's plan stops, allocs its
    host partition placed). Unequal asks (an alloc in six shrunk in
    place, what it gave up taken by a filler of a third tier on the same
    node), a tier whose ONLY alloc the batch stops, and a tier that
    exists only in the host partition's placements."""
    by_id = {n.id: n for n in h.state.nodes()}
    live = [a for a in h.state.allocs() if not a.terminal_status()]
    jobs = {}
    for prio in (5, 30, 35):
        jobs[prio] = mock.job(id=f"tier-{prio}", priority=prio)
        jobs[prio].datacenters = ["dc1", "dc2"]
        h.state.upsert_job(h.next_index(), jobs[prio])
    shrunk, fillers = [], []
    for a in rng.sample(live, len(live) // 6):
        cpu, mem = rng.choice((125, 250, 375)), rng.choice((64, 128))
        shrunk.append(_sized(a.copy(), cpu, mem))
        job = jobs[35 if fillers else 5]
        fillers.append(_sized(mock.alloc(job_=job, node_=by_id[a.node_id]),
                              ASK[0] - cpu, ASK[1] - mem))
    h.state.upsert_allocs(h.next_index(), shrunk)
    h.state.upsert_allocs(h.next_index(), fillers)
    stops = rng.sample(live, 6) + fillers[:2]
    prio_20 = next(a.job for a in live if a.job.priority == 20)
    placed = [
        _sized(mock.alloc(job_=job, node_=by_id[a.node_id]), 125, 64)
        for a, job in zip(stops, (jobs[30], jobs[30], prio_20, jobs[35]))
    ]
    return stops, placed


class Batch:
    """One production job over a built cluster, lowered or solved from
    whatever state it is shown, every time from a fresh reconcile."""

    def __init__(self, h, k: int, datacenters, stops=(), placed=(),
                 cores: int = 0, job_id: str = "production") -> None:
        job = mock.job(id=job_id, priority=70)
        job.datacenters = list(datacenters)
        tg = job.task_groups[0]
        tg.count = k
        tg.tasks[0].resources.cpu = ASK[0]
        tg.tasks[0].resources.memory_mb = ASK[1]
        tg.tasks[0].resources.networks = []
        tg.tasks[0].resources.cores = cores
        h.state.upsert_job(h.next_index(), job)
        self.h, self.ev = h, mock.eval_for_job(job)
        self.stops, self.placed = list(stops), list(placed)
        self.snap = h.snapshot()

    def _solver(self, state, resident=None):
        plans, asks = _reconcile_eval_batch(state, self.h, [self.ev], CONFIG)
        for a in self.stops:
            plans[self.ev.id].append_stopped_alloc(a, "stopped by the batch")
        solver = BatchSolver(state, CONFIG, resident=resident)
        solver._partition_placed = list(self.placed)
        return solver, asks

    def table(self, state, resident=None):
        solver, asks = self._solver(state, resident)
        kind, low = solver._lower_batch(asks, SolveOutcome())
        assert kind == "dense"
        return low.table

    def solve(self, state):
        """({placement name: (node, its victims)}, victims in order)"""
        solver, asks = self._solver(state)
        out = solver.solve(asks)
        placed = {a.name: (a.node_id, tuple(a.preempted_allocations))
                  for a in out.placements.get(self.ev.id, [])}
        victims = [(v.id, v.node_id)
                   for v, _by in out.preemptions.get(self.ev.id, [])]
        return placed, victims


def same_table(got, want) -> None:
    assert [n.id for n in got.nodes] == [n.id for n in want.nodes]
    assert got.tier_prios == want.tier_prios
    assert got.tiers_above == want.tiers_above
    for name in ("cap", "used", "tier_used", "datacenters"):
        a, b = getattr(got, name), getattr(want, name)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.array_equal(a, b), name


def counted(fn, *args):
    old = metrics._install_registry(Registry())
    try:
        return fn(*args), metrics.snapshot()["counters"]
    finally:
        metrics._install_registry(old)


@pytest.mark.parametrize("shape", ["as_built", "churned"])
@pytest.mark.parametrize("seed", [7, 3_000_000_019])
@pytest.mark.parametrize("case", [
    "uneven_tiers", "a_dc_with_no_low_tier_left",
    "more_than_the_lowest_tier", "only_one_datacenter_admitted",
    "more_than_everything_preemptible", "free_slots_first"])
def test_the_table_from_the_index_is_the_walks(case, seed, shape):
    import test_preempt_lowest_first as tiers

    make, k_of, datacenters = tiers.CASES[case]
    cluster = make(seed)
    h = tiers.build(cluster)
    stops, placed = (churn(h, random.Random(seed))
                     if shape == "churned" else ([], []))
    batch = Batch(h, k_of(cluster), datacenters, stops, placed)

    want, walked = counted(batch.table, WithoutTheIndex(batch.snap))
    assert walked["nomad.tpu.lower_alloc_walks"] == 1
    assert "nomad.tpu.lower_tiers_from_store" not in walked
    got, read = counted(batch.table, batch.snap)
    assert read["nomad.tpu.lower_tiers_from_store"] == 1
    assert "nomad.tpu.lower_alloc_walks" not in read
    same_table(got, want)
    assert len(want.tier_prios) >= 2 and want.tier_used.any()
    if shape == "churned":
        # the tier whose only alloc the batch stops is gone; the one
        # only the host partition placed is there, where the job admits
        # a node it placed on
        admitted = {n.id for n in want.nodes}
        assert 5 not in want.tier_prios
        assert (30 in want.tier_prios) == any(
            a.job.priority == 30 and a.node_id in admitted for a in placed)
    # through the resident skeleton, built (cold) and refreshed (warm)
    resident = ResidentClusterState()
    same_table(batch.table(batch.snap, resident), want)
    warm = batch.table(batch.snap, resident)
    same_table(warm, want)
    assert warm.cap is resident._host_table.cap
    assert warm._allocs_by_node is not None

    # the same placements on the same nodes, the same victims
    want_placed, want_victims = batch.solve(WithoutTheIndex(batch.snap))
    got_placed, got_victims = batch.solve(batch.snap)
    assert want_placed and want_victims
    assert got_placed == want_placed
    assert got_victims == want_victims


def lower_table_span(fn, *args):
    """(what `fn` returns, the one `lower.table` span it recorded)"""
    trace.set_enabled(True)
    try:
        ctx = trace.start_trace("test.lower")
        with trace.use(ctx):
            out = fn(*args)
        ctx.finish("ok")
    finally:
        trace.set_enabled(False)
    (span,) = [s for s in ctx.spans if s.name == "lower.table"]
    return out, span


def test_a_batch_that_asks_for_cores_still_walks_the_allocs():
    import test_preempt_lowest_first as tiers

    cluster = tiers.with_free_slots(7)
    h = tiers.build(cluster)
    batch = Batch(h, 3, ["dc1", "dc2"], cores=1)
    (table, counters), span = lower_table_span(
        counted, batch.table, batch.snap)
    assert counters["nomad.tpu.lower_alloc_walks"] == 1
    assert "nomad.tpu.lower_tiers_from_store" not in counters
    assert span.attrs["alloc_walk"] == "cores"
    # the core pools no aggregate has: 4 a mock node, none granted
    assert table.cores_free.tolist() == [4] * len(cluster)
    assert table.tier_prios == [20, 50]
    # the walk's reason where it is the state's, and none where the
    # index was read
    batch = Batch(h, 3, ["dc1", "dc2"])
    _, span = lower_table_span(batch.table, WithoutTheIndex(batch.snap))
    assert span.attrs["alloc_walk"] == "no_index"
    _, span = lower_table_span(batch.table, batch.snap)
    assert "alloc_walk" not in span.attrs


def test_a_warm_table_carries_no_tiers_of_an_earlier_solve():
    """The skeleton is shared from solve to solve; the tiers are a
    solve's own, as the usage rows are: a batch that cannot preempt
    gets none, whatever the solve before it read."""
    import test_preempt_lowest_first as tiers

    cluster = tiers.uneven(11)
    h = tiers.build(cluster)
    resident = ResidentClusterState()
    may_preempt = Batch(h, 5, ["dc1", "dc2"])
    assert may_preempt.table(may_preempt.snap, resident).tier_prios == [
        20, 50]

    job = mock.job(id="batch", priority=20)
    job.datacenters = ["dc1", "dc2"]
    job.task_groups[0].tasks[0].resources.networks = []
    h.state.upsert_job(h.next_index(), job)
    ev = mock.eval_for_job(job)
    snap = h.snapshot()
    _plans, asks = _reconcile_eval_batch(snap, h, [ev], CONFIG)
    solver = BatchSolver(snap, CONFIG, resident=resident)
    (table, counters) = counted(
        lambda: solver._lower_batch(asks, SolveOutcome())[1].table)
    assert table.cap is resident._host_table.cap  # the warm path
    assert table.tier_prios == [] and table.tier_used.shape[0] == 0
    assert "nomad.tpu.lower_tiers_from_store" not in counters
    assert "nomad.tpu.lower_alloc_walks" not in counters


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
def test_the_resident_tiers_follow_the_store_from_solve_to_solve(seed):
    """One resident state over a run of solves whose plans land between
    them (placements at a new priority, evictions lowest first), with an
    alloc deleted, one moved to another node and one finished besides:
    the tiers it keeps by what changed are, every time, the walk's."""
    import test_preempt_lowest_first as tiers
    from nomad_tpu.scheduler.tpu import solve_eval_batch

    rng = random.Random(seed)
    cluster = tiers.uneven(seed)
    h = tiers.build(cluster)
    resident = ResidentClusterState()
    for round_ in range(4):
        batch = Batch(h, 9 + round_, ["dc1", "dc2"],
                      job_id=f"production-{round_}")
        got = batch.table(batch.snap, resident)
        if round_:
            assert got.cap is resident._host_table.cap  # refreshed, not built
        same_table(got, batch.table(WithoutTheIndex(batch.snap)))
        plan = solve_eval_batch(
            batch.snap, h, [batch.ev], CONFIG, resident=resident)[batch.ev.id]
        assert plan.node_preemptions
        h.submit_plan(plan)
        live = [a for a in h.state.allocs() if not a.terminal_status()]
        gone, moved, done = rng.sample(live, 3)
        h.state.delete_evals(h.next_index(), [], [gone.id])
        moved = moved.copy()
        moved.node_id = rng.choice(
            [n.id for n in h.state.nodes() if n.id != moved.node_id])
        h.state.upsert_allocs(h.next_index(), [moved])
        done = done.copy()
        done.client_status = "complete"
        h.state.update_allocs_from_client(h.next_index(), [done])
    # the placements' own priority stands now, and is above what a
    # production batch may evict: kept by the slabs, left out of the
    # batch's tiers by the ceiling (solver._lower_table)
    assert 70 in resident._host_tiers.prios
    assert 70 not in got.tier_prios and got.tiers_above == 1
