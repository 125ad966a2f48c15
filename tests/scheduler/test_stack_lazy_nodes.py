"""The generic stack's nodes are a random permutation drawn as it is
walked (scheduler/stack.py: `ShuffledNodes`, `GenericStack.set_nodes`;
scheduler/tpu/solver.py: `_solve_host`'s counters).

`set_nodes` used to copy and `random.shuffle` every ready node for a walk
that looks at log₂(n) of them a placement. Now position i of the
permutation is drawn when a walk first reaches it, the drawn prefix is
replayed by every later walk, and a walk that goes past n/32 finishes the
permutation the eager way. Every case below holds for the shuffle it
replaced too, except the counts of what was drawn. No sleeps, no clock.
"""

import random
from collections import Counter
from itertools import islice

import pytest

from nomad_tpu import metrics, mock, trace
from nomad_tpu.metrics import Registry
from nomad_tpu.scheduler.context import SchedulerConfig
from nomad_tpu.scheduler.stack import GenericStack, ShuffledNodes
from nomad_tpu.scheduler.tpu import ResidentClusterState
from nomad_tpu.scheduler.tpu.scheduler import _reconcile_eval_batch
from nomad_tpu.scheduler.tpu.solver import BatchSolver
from nomad_tpu.testing import Harness

DRAWN = "nomad.sched.stack.nodes_drawn"
EAGER = "nomad.sched.stack.eager_finishes"
# the host stack for every small batch, whatever the cluster's size
HOST_ONLY = SchedulerConfig(micro_solve_threshold=0)


@pytest.fixture()
def registry():
    old = metrics._install_registry(Registry())
    yield metrics.registry()
    metrics._install_registry(old)


def stack_on(nodes: list, batch: bool = False) -> GenericStack:
    stack = GenericStack(batch, None)
    stack.set_nodes(nodes)
    return stack


# -- (a) a permutation ------------------------------------------------------

@pytest.mark.parametrize("n", [0, 1, 2, 17, 10_000])
def test_a_full_walk_yields_every_node_exactly_once(n):
    random.seed(n)
    src = list(range(n))
    stack = stack_on(src)
    assert len(stack.nodes) == n
    got = list(stack.nodes)
    assert sorted(got) == src
    assert stack.nodes.drawn == n
    if n > 16:
        assert got != src  # and it is not the identity


@pytest.mark.parametrize("n,limit", [(0, 2), (1, 2), (2, 2), (17, 5),
                                     (10_000, 14)])
def test_the_limit_is_computed_from_the_length_as_before(n, limit):
    assert stack_on(list(range(n))).limit == limit
    assert stack_on(list(range(n)), batch=True).limit == 2


# -- (b) one permutation a stack, replayed ------------------------------------

@pytest.mark.parametrize("n", [2, 17, 64, 1_000, 10_000])
def test_two_walks_of_one_stack_yield_the_same_order(n):
    random.seed(n)
    view = stack_on(list(range(n))).nodes
    assert list(view) == list(view)


@pytest.mark.parametrize("n,k", [(17, 5), (64, 1), (64, 2), (64, 3),
                                 (1_000, 14), (1_000, 31), (1_000, 32),
                                 (10_000, 14), (10_000, 311), (10_000, 313)])
def test_a_walk_cut_short_is_the_head_of_the_full_walk(n, k):
    """Below the eager threshold, on it and past it."""
    random.seed(k)
    view = stack_on(list(range(n))).nodes
    head = list(islice(view, k))
    assert view.drawn == (k if k <= n >> 5 else n)
    assert view.eager == (k > n >> 5)
    again = list(islice(view, k))
    full = list(view)
    assert head == again == full[:k]
    assert sorted(full) == list(range(n))


def test_walks_that_interleave_agree():
    """A walk left suspended (the limit iterator abandons its source)
    and a later, longer one read the same permutation."""
    random.seed(3)
    view = stack_on(list(range(10_000))).nodes
    first, second = iter(view), iter(view)
    a = [next(first) for _ in range(5)]
    b = [next(second) for _ in range(400)]  # past the threshold: eager
    a += [next(first) for _ in range(600)]
    assert a[:400] == b and a == list(view)[:605]


# -- (c) the input is read, not written or copied -----------------------------

def test_the_input_list_is_neither_mutated_nor_copied():
    src = list(range(10_000))
    stack = stack_on(src)
    assert stack.nodes._src is src
    list(islice(stack.nodes, 40))
    assert src == list(range(10_000)) and stack.nodes.drawn == 40
    list(stack.nodes)  # the eager finish shuffles a slice of its own
    assert src == list(range(10_000))


def empty_cluster(n: int) -> Harness:
    h = Harness()
    for _ in range(n):
        h.state.upsert_node(h.next_index(), mock.node(datacenter="dc1"))
    return h


def deploy(h: Harness, count: int = 8, cpu: int = 500):
    job = mock.job(datacenters=["dc1"])
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.networks = []
    tg.tasks[0].resources.cpu = cpu
    h.state.upsert_job(h.next_index(), job)
    return job, mock.eval_for_job(job)


class Kept(BatchSolver):
    """The solver as it is; the lists `_ready_nodes` gave are kept."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.ready = []

    def _ready_nodes(self, datacenters):
        got = super()._ready_nodes(datacenters)
        self.ready.append((got[0], list(got[0])))
        return got


def host_solve(h: Harness, ev, cls=BatchSolver, resident=None, seed=None):
    snap = h.snapshot()
    _, asks = _reconcile_eval_batch(snap, h, [ev], HOST_ONLY)
    solver = cls(snap, HOST_ONLY, resident=resident)
    if seed is not None:
        random.seed(seed)
    return solver, solver.solve(asks)


def placed(out, ev) -> list:
    return sorted((a.name, a.node_id) for a in out.placements.get(ev.id, []))


def hist(reg, name: str) -> dict:
    raw = reg.histogram_raw(name) or {}
    return {"count": raw.get("count", 0), "sum": raw.get("sum", 0)}


def test_a_deploy_on_2000_empty_nodes_draws_under_100_of_them(registry):
    h = empty_cluster(2_000)
    _, ev = deploy(h)
    resident = ResidentClusterState()
    solver, out = host_solve(h, ev, cls=Kept, resident=resident, seed=5)
    assert len(placed(out, ev)) == 8
    assert (out.stack_nodes, hist(registry, DRAWN)["count"]) == (2_000, 1)
    drawn = hist(registry, DRAWN)["sum"]
    assert 11 <= drawn == out.stack_nodes_drawn < 100  # log2(2000) = 11
    assert registry.snapshot()["counters"].get(EAGER, 0) == 0
    # the list the resident state keeps for the next eval is as it was
    for given, copy in solver.ready:
        assert given == copy and len(given) == 2_000
    again, _ = host_solve(h, ev, cls=Kept, resident=resident, seed=6)
    assert again.ready[0][0] is solver.ready[0][0]


# -- (d) the same distribution as random.shuffle ------------------------------

def chi2(counts: Counter, cells: int, draws: int) -> float:
    want = draws / cells
    return sum((counts.get(c, 0) - want) ** 2 / want for c in range(cells))


# the 99.9th percentile of chi-squared at n - 1 degrees of freedom; the
# seed is fixed, so a pass is a pass for good
CHI2_999 = {8: 24.32, 64: 103.44, 96: 143.34}


@pytest.mark.parametrize("n", [8, 64, 96])
def test_the_first_draws_are_uniform(n):
    """8 nodes finish eagerly at once (n/32 is 0), 64 draw two positions
    lazily and 96 three: the first node's counts, the second's, and the
    pair's pass for each."""
    random.seed(36)
    src = list(range(n))
    draws = 20_000
    firsts, seconds, pairs = Counter(), Counter(), Counter()
    for _ in range(draws):
        a, b = islice(stack_on(src).nodes, 2)
        firsts[a] += 1
        seconds[b] += 1
        pairs[a, b] += 1
    assert chi2(firsts, n, draws) < CHI2_999[n]
    assert chi2(seconds, n, draws) < CHI2_999[n]
    assert all(a != b for a, b in pairs)
    if n == 8:
        assert len(pairs) == 56  # every ordered pair occurs
    else:
        # 5 and 2.2 draws an ordered pair: nearly all and 89 % of them occur
        assert len(pairs) > 0.8 * n * (n - 1)


@pytest.mark.parametrize("n,node", [(64, 0), (64, 63), (96, 1), (96, 50)])
def test_where_a_node_lands_is_uniform_across_the_eager_junction(n, node):
    """The lazy head and the eagerly shuffled remainder are one uniform
    permutation: a node lands on every position equally often, whether
    the lazy draws moved it or not."""
    random.seed(n + node)
    src = list(range(n))
    draws = 20_000
    at = Counter(list(stack_on(src).nodes).index(node) for _ in range(draws))
    assert chi2(at, n, draws) < CHI2_999[n]


# -- (e) a walk that runs long finishes the permutation eagerly ---------------

def full_cluster(n: int) -> Harness:
    """`n` nodes, each held whole by one alloc of a standing job."""
    h = Harness()
    standing = mock.job(datacenters=["dc1"])
    h.state.upsert_job(h.next_index(), standing)
    allocs = []
    for _ in range(n):
        node = mock.node(datacenter="dc1")
        h.state.upsert_node(h.next_index(), node)
        a = mock.alloc(job_=standing, node_=node)
        a.resources.tasks["web"].cpu = node.resources.cpu - node.reserved.cpu
        allocs.append(a)
    h.state.upsert_allocs(h.next_index(), allocs)
    return h


@pytest.mark.parametrize("n", [40, 320, 1_000])
def test_a_full_cluster_finishes_eagerly_and_visits_every_node_once(
        registry, monkeypatch, n):
    h = full_cluster(n)
    _, ev = deploy(h, count=3)
    visited = []
    walk = ShuffledNodes.__iter__

    def seen(self):
        for node in walk(self):
            visited.append(node.id)
            yield node

    monkeypatch.setattr(ShuffledNodes, "__iter__", seen)
    _, out = host_solve(h, ev, seed=n)
    assert placed(out, ev) == []
    metric = out.failures[ev.id]["web"]
    # the three requests are walked for once (PR 35): to place, then to
    # evict; each walk visits every node once, in the same order
    assert (metric.nodes_evaluated, metric.coalesced_failures) == (2 * n, 2)
    assert metric.nodes_exhausted == 2 * n
    assert visited[:n] == visited[n:] and len(set(visited[:n])) == n
    assert registry.snapshot()["counters"][EAGER] == 1
    assert hist(registry, DRAWN) == {"count": 1, "sum": n}
    assert (out.stack_nodes, out.stack_nodes_drawn) == (n, n)


def test_a_cluster_with_room_finishes_nothing_eagerly(registry):
    h = empty_cluster(640)
    for _ in range(3):
        _, ev = deploy(h, count=4)
        _, out = host_solve(h, ev)
        assert len(placed(out, ev)) == 4
    assert registry.snapshot()["counters"].get(EAGER, 0) == 0
    assert hist(registry, DRAWN)["count"] == 3
    assert hist(registry, DRAWN)["sum"] < 3 * 20  # 640 >> 5


# -- (f) random.seed still fixes the walk -------------------------------------

@pytest.mark.parametrize("n", [40, 640])
def test_the_same_seed_gives_the_same_placements(n):
    h = empty_cluster(n)
    _, ev = deploy(h, count=6)
    runs = [placed(host_solve(h, ev, seed=s)[1], ev) for s in (36, 36, 37)]
    assert len(runs[0]) == 6
    assert runs[0] == runs[1]
    assert runs[0] != runs[2]  # and another seed another walk


# -- the span carries what its stacks drew -----------------------------------

def test_the_host_solve_span_carries_nodes_and_nodes_drawn():
    h = empty_cluster(640)
    _, ev = deploy(h, count=4)
    ctx = trace.TraceContext("tpu.batch")
    with trace.use(ctx):
        _, out = host_solve(h, ev, seed=1)
    spans = [s for s in ctx.spans if s.name == "host_solve"]
    assert len(spans) == 1
    assert spans[0].attrs == {
        "nodes": 640, "nodes_drawn": out.stack_nodes_drawn,
        "ranked": out.stack_ranked, "reused": out.stack_reused,
        "by_usage": 0,  # an empty cluster: no node exhausted
        "chain": False}  # nothing in flight: no chain read
    assert 10 <= out.stack_nodes_drawn < 20
