"""The route of a small batch, and when its verdict is taken
(scheduler/tpu/solver.py: `_lower_batch`, `_past_micro_bound`,
`_solve_host`, `_ready_nodes`).

A batch of at most `small_batch_threshold` requests wants the microsolve
and gets it while nodes × groups stays within `micro_solve_threshold`;
past it the host iterator stack takes the batch. The verdict falls as
soon as it is certain — nodes × asks is a lower bound of nodes × groups —
so a small deploy on a large cluster is not lowered first
(`nomad.tpu.lower_skipped`), and the route of every batch is the one the
verdict after lowering alone gives: `LateOnly` below is that solver, made
by test construction. No sleeps, no clock.
"""

import random

import pytest

from nomad_tpu import metrics, mock
from nomad_tpu.metrics import Registry
from nomad_tpu.scheduler import stack as stack_mod
from nomad_tpu.scheduler import util as util_mod
from nomad_tpu.scheduler.context import SchedulerConfig
from nomad_tpu.scheduler.reconcile import PlacementRequest
from nomad_tpu.scheduler.tpu import ResidentClusterState, solve_eval_batch
from nomad_tpu.scheduler.tpu import solver as solver_mod
from nomad_tpu.scheduler.tpu.scheduler import _reconcile_eval_batch
from nomad_tpu.scheduler.tpu.solver import BatchSolver, GroupAsk
from nomad_tpu.structs import Spread
from nomad_tpu.testing import Harness

SKIPPED = "nomad.tpu.lower_skipped"
HOST = "nomad.tpu.small_batch_requests"
MICRO = "nomad.tpu.micro_batch_requests"
# the bound, lowered so that a few hundred nodes stand where 10,000 do
BOUND = 256
DCS = ["dc1", "dc2", "dc3", "dc4"]


class Probe(BatchSolver):
    """The solver as it is, its verdicts kept; `random` is seeded where
    the host stack starts, because `set_nodes` shuffles."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.kinds = []
        self.tables = 0

    def _lower_batch(self, asks, out):
        kind, low = super()._lower_batch(asks, out)
        self.kinds.append(kind)
        return kind, low

    def _lower_table(self, nodes, asks, micro_wanted):
        self.tables += 1
        return super()._lower_table(nodes, asks, micro_wanted)

    def _solve_host(self, asks):
        random.seed(28)
        return super()._solve_host(asks)


class LateOnly(Probe):
    """The parent's routing: every verdict after the lowering."""

    def _past_micro_bound(self, nodes, asks):
        return False


@pytest.fixture()
def registry():
    old = metrics._install_registry(Registry())
    yield metrics.registry()
    metrics._install_registry(old)


def count_of(name: str) -> int:
    return metrics.snapshot()["samples"].get(name, {}).get("count", 0)


def cluster(sizes: dict) -> Harness:
    """A harness with `sizes[dc]` ready nodes in each datacenter."""
    h = Harness()
    for dc, n in sizes.items():
        for _ in range(n):
            h.state.upsert_node(h.next_index(), mock.node(datacenter=dc))
    return h


def even(n_nodes: int) -> dict:
    return {dc: n_nodes // len(DCS) for dc in DCS}


def deploy(h: Harness, count: int = 8, dcs=DCS, spread: bool = False,
           cores: int = 0, sticky: bool = False, **job_fields):
    job = mock.job(datacenters=list(dcs), **job_fields)
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.networks = []
    tg.tasks[0].resources.cores = cores
    tg.ephemeral_disk.sticky = sticky
    if spread:
        tg.spreads = [Spread(attribute="${node.datacenter}", weight=50)]
    h.state.upsert_job(h.next_index(), job)
    return job, mock.eval_for_job(job)


def solve(cls, h: Harness, evals: list, config: SchedulerConfig,
          resident=None, asks=None, **solver_args):
    """One solve of `evals` (or of `asks` as given) on a solver of
    `cls`: (solver, outcome). Nothing is submitted, so a second solve of
    the same evals reconciles the same placements."""
    snap = h.snapshot()
    if asks is None:
        _, asks = _reconcile_eval_batch(snap, h, evals, config)
    solver = cls(snap, config, resident=resident, **solver_args)
    return solver, solver.solve(asks)


def placed(out) -> dict:
    """eval id -> sorted (alloc name, node id) of everything placed."""
    got: dict[str, list] = {}
    for eval_id, allocs in out.placements.items():
        got.setdefault(eval_id, []).extend(
            (a.name, a.node_id) for a in allocs)
    for eval_id, batches in out.batch_placements.items():
        for b in batches:
            got.setdefault(eval_id, []).extend(
                zip(b.names, (b.node_ids[i] for i in b.node_idx.tolist())))
    return {k: sorted(v) for k, v in got.items() if v}


def same_route(h, evals, config, asks_of=None, **solver_args):
    """Solve with the early verdict and with the late one alone: the
    verdicts, the placed count of every eval and the nodes are equal.
    Returns the early solver and how far `lower_skipped` moved."""
    runs = []
    for cls in (Probe, LateOnly):
        resident = None if "mesh" in solver_args else ResidentClusterState()
        before = count_of(SKIPPED)
        solver, out = solve(cls, h, evals, config, resident=resident,
                            asks=asks_of() if asks_of else None,
                            **solver_args)
        runs.append((solver, out, count_of(SKIPPED) - before))
    (early, out_e, moved_e), (late, out_l, moved_l) = runs
    assert moved_l == 0
    assert early.kinds == late.kinds
    # names and nodes, so the placed count of every eval too
    assert placed(out_e) == placed(out_l)
    assert sorted(out_e.failures) == sorted(out_l.failures)
    return early, moved_e


# -- (a) the route is the late verdict's, whenever the verdict falls -------

@pytest.mark.parametrize("spread", [False, True], ids=["plain", "spread4"])
@pytest.mark.parametrize("n_asks", [1, 3])
@pytest.mark.parametrize("n_nodes", [64, 160, 300])
def test_route_and_placements_equal_the_late_verdicts(
        registry, n_nodes, n_asks, spread):
    h = cluster(even(n_nodes))
    evals = [deploy(h, count=8, spread=spread)[1] for _ in range(n_asks)]
    config = SchedulerConfig(micro_solve_threshold=BOUND)
    early, moved = same_route(h, evals, config)
    groups = n_asks * (4 if spread else 1)
    assert early.kinds == ["host" if n_nodes * groups > BOUND else "dense"]
    assert early.used_micro == (n_nodes * groups <= BOUND)
    # the verdict falls early exactly where nodes x asks proves it
    assert moved == (1 if n_nodes * n_asks > BOUND else 0)
    assert early.tables == (0 if moved else 1)


def test_route_equivalence_holds_with_the_early_verdict_disabled(registry):
    """`LateOnly` against itself: the construction the comparison above
    leans on is a solver that still routes and places."""
    h = cluster(even(300))
    _, ev = deploy(h, count=8)
    config = SchedulerConfig(micro_solve_threshold=BOUND)
    a, out_a = solve(LateOnly, h, [ev], config)
    b, out_b = solve(LateOnly, h, [ev], config)
    assert a.kinds == b.kinds == ["host"] and a.tables == b.tables == 1
    assert placed(out_a) == placed(out_b) and len(placed(out_a)[ev.id]) == 8
    assert count_of(SKIPPED) == 0 and count_of(HOST) == 2


# -- (b) what the early verdict leaves out, at the cells' own sizes --------

@pytest.fixture(scope="module")
def ten_thousand():
    """10,000 nodes: 8,000 in four datacenters, 2,000 in two more, so a
    job's datacenters choose 5,000, 8,000 or 10,000 of them."""
    return cluster({"dc1": 2000, "dc2": 2000, "dc3": 2000, "dc4": 2000,
                    "dc5": 1000, "dc6": 1000})


@pytest.fixture()
def spies(monkeypatch):
    calls = {"lower_group": 0, "ready_nodes_in_dcs": 0}

    def spy(name, orig):
        def counting(*args, **kwargs):
            calls[name] += 1
            return orig(*args, **kwargs)
        return counting

    monkeypatch.setattr(solver_mod, "lower_group",
                        spy("lower_group", solver_mod.lower_group))
    walk = spy("ready_nodes_in_dcs", util_mod.ready_nodes_in_dcs)
    monkeypatch.setattr(solver_mod, "ready_nodes_in_dcs", walk)
    # ResidentClusterState.ready_nodes imports it where it runs
    monkeypatch.setattr(util_mod, "ready_nodes_in_dcs", walk)
    return calls


def test_a_small_deploy_on_10000_nodes_is_not_lowered(
        registry, spies, ten_thousand):
    h = ten_thousand
    dcs = ["dc1", "dc2", "dc3", "dc4", "dc5", "dc6"]
    _, ev = deploy(h, count=12, dcs=dcs, spread=True)
    resident = ResidentClusterState()
    snap = h.snapshot()
    nodes, counts = resident.ready_nodes(snap, tuple(dcs))  # the worker's
    assert len(nodes) == 10_000 and spies["ready_nodes_in_dcs"] == 1
    solver, out = solve(Probe, h, [ev], SchedulerConfig(), resident=resident)
    assert solver.kinds == ["host"] and solver.tables == 0
    assert spies == {"lower_group": 0, "ready_nodes_in_dcs": 1}
    assert count_of(SKIPPED) == 1 and count_of(HOST) == 1
    assert metrics.snapshot()["samples"][SKIPPED]["sum"] == 12  # requests
    assert len(placed(out)[ev.id]) == 12
    assert out.placements[ev.id][0].metrics.nodes_available == counts


def test_5000_nodes_and_one_group_take_the_microsolve(
        registry, spies, ten_thousand):
    _, ev = deploy(ten_thousand, count=12, dcs=["dc1", "dc2", "dc5"])
    solver, out = solve(Probe, ten_thousand, [ev], SchedulerConfig(),
                        resident=ResidentClusterState())
    assert solver.kinds == ["dense"] and solver.used_micro
    assert solver.tables == 1 and spies["lower_group"] == 1
    assert count_of(SKIPPED) == 0 and count_of(HOST) == 0
    assert count_of(MICRO) == 1 and len(placed(out)[ev.id]) == 12


def test_8000_nodes_split_four_ways_are_lowered_then_sent_to_the_host(
        registry, spies, ten_thousand):
    """8,000 x 1 ask is within the bound, 8,000 x 4 groups is not: only
    the lowering can say so, and the late verdict still does."""
    _, ev = deploy(ten_thousand, count=12, dcs=DCS, spread=True)
    solver, out = solve(Probe, ten_thousand, [ev], SchedulerConfig(),
                        resident=ResidentClusterState())
    assert solver.kinds == ["host"] and not solver.used_micro
    assert solver.tables == 1 and spies["lower_group"] == 1
    assert count_of(SKIPPED) == 0 and count_of(HOST) == 1
    assert len(placed(out)[ev.id]) == 12
    # the node universe was found once for both halves
    assert spies["ready_nodes_in_dcs"] == 1


# -- (c) an ask that lowers to no group does not count ----------------------

@pytest.mark.parametrize("hollow", ["no_task_group", "no_requests"])
def test_an_ask_without_a_group_or_a_request_is_not_counted(registry, hollow):
    h = cluster(even(160))  # 160 x 1 within the bound, 160 x 2 past it
    job, ev = deploy(h, count=8)
    config = SchedulerConfig(micro_solve_threshold=BOUND)

    def asks_of():
        _, asks = _reconcile_eval_batch(h.snapshot(), h, [ev], config)
        real = asks[0]
        if hollow == "no_task_group":
            other = GroupAsk(ev, job, "no-such-group", list(real.requests),
                             plan=real.plan)
        else:
            other = GroupAsk(ev, job, real.tg_name, [], plan=real.plan)
        return [other, real]

    snap = h.snapshot()
    nodes = util_mod.ready_nodes_in_dcs(snap, DCS)[0]
    solver = BatchSolver(snap, config)
    assert not solver._past_micro_bound(nodes, asks_of())
    assert solver._past_micro_bound(nodes, asks_of()[1:] * 2)  # two real
    early, moved = same_route(h, [ev], config, asks_of=asks_of)
    assert early.kinds == ["dense"] and early.used_micro and moved == 0


# -- (d) the batches that never wanted the microsolve keep their routes -----

def _standing_low_priority(h):
    filler, ev0 = deploy(h, count=1, priority=10)
    plans = solve_eval_batch(h.snapshot(), h, [ev0],
                             SchedulerConfig(preemption_service=False))
    h.submit_plan(plans[ev0.id])


@pytest.mark.parametrize("n_nodes", [160, 300], ids=["within", "past"])
@pytest.mark.parametrize("shape", ["sticky", "cores", "mesh", "may_preempt"])
def test_sticky_cores_mesh_and_preempting_batches_keep_their_routes(
        registry, shape, n_nodes):
    h = cluster(even(n_nodes))
    config = SchedulerConfig(micro_solve_threshold=BOUND)
    solver_args, asks_of = {}, None
    if shape == "may_preempt":
        _standing_low_priority(h)
    job, ev = deploy(h, count=4, priority=70 if shape == "may_preempt" else 50,
                     cores=int(shape == "cores"), sticky=shape == "sticky")
    if shape == "mesh":
        from nomad_tpu.scheduler.tpu.sharding import solver_mesh

        solver_args["mesh"] = solver_mesh(2)
    elif shape == "sticky":
        tg = job.task_groups[0]
        home = next(iter(h.state.nodes()))
        prev = mock.alloc(job, home)

        def asks_of():
            reqs = [PlacementRequest(name=prev.name, task_group=tg,
                                     previous_alloc=prev)]
            return [GroupAsk(ev, job, tg.name, reqs, plan=ev.make_plan(job))]

    if shape == "may_preempt" and n_nodes > BOUND:
        # past the bound the tier kernel's (PR 35), and the bound IS the
        # verdict: the host stack draws its node from a shuffled sample
        # and may take a higher band there while a lower one stands
        # elsewhere. (`LateOnly` has no bound, so nothing to compare.)
        before = count_of(SKIPPED)
        early, out = solve(Probe, h, [ev], config,
                           resident=ResidentClusterState())
        assert early.kinds == ["dense"] and early.tables == 1
        assert count_of(SKIPPED) == before and count_of(HOST) == 0
        assert sum(len(v) for v in placed(out).values()) == 4
        assert not early.used_micro
        return
    early, moved = same_route(h, [ev], config, asks_of=asks_of, **solver_args)
    if shape == "sticky":
        # the host partition, before any verdict on size
        assert early.kinds == ["sticky"] and (moved, early.tables) == (0, 0)
        assert count_of(HOST) == 0
    elif shape in ("cores", "mesh"):
        # small and not wanted by the microsolve: the host stack at once
        assert early.kinds == ["host"] and (moved, early.tables) == (0, 0)
    else:
        # within the bound the host stack's, by `_lower_table`, which
        # builds nothing for such a batch
        assert early.kinds == ["host"]
        assert (moved, early.tables) == (0, 1)
    assert not early.used_micro


# -- (e) one node universe, with a resident state and without ---------------

@pytest.mark.parametrize("dcs", [DCS, ["dc2", "dc4"], ["dc*"], ["nowhere"]],
                         ids=["all", "two", "glob", "none"])
def test_the_host_stack_sees_the_same_nodes_with_and_without_a_resident_state(
        registry, monkeypatch, dcs):
    h = cluster({"dc1": 90, "dc2": 80, "dc3": 70, "dc4": 60})
    _, ev = deploy(h, count=4, dcs=dcs)
    config = SchedulerConfig(micro_solve_threshold=BOUND // 8)
    seen = []
    orig = stack_mod.GenericStack.set_nodes

    def set_nodes(self, nodes):
        seen.append([n.id for n in nodes])
        return orig(self, nodes)

    monkeypatch.setattr(stack_mod.GenericStack, "set_nodes", set_nodes)
    resident = ResidentClusterState()
    outs = []
    for res in (None, resident, resident):  # cold, cold cache, warm cache
        solver, out = solve(Probe, h, [ev], config, resident=res)
        outs.append(out)
        assert solver.kinds == (["done"] if dcs == ["nowhere"] else ["host"])
    want_nodes, want_counts = util_mod.ready_nodes_in_dcs(h.snapshot(), dcs)
    if dcs == ["nowhere"]:
        assert seen == [] and not want_nodes
        return
    assert seen == [[n.id for n in want_nodes]] * 3
    for out in outs:
        assert placed(out) == placed(outs[0])
        assert [a.metrics.nodes_available for a in out.placements[ev.id]] \
            == [want_counts] * 4


def test_the_bound_and_the_modelled_device_are_not_read_from_the_environment(
        monkeypatch):
    """No caller set either variable: the microsolve's bound is the
    constant, the device model is off, unless the constructor is told."""
    monkeypatch.setenv("NOMAD_TPU_MICRO_NG", "17")
    monkeypatch.setenv("NOMAD_TPU_INJECT_DEVICE_LATENCY_S", "0.5")
    config = SchedulerConfig()
    assert config.micro_solve_threshold == 8192
    assert config.inject_device_latency_s == 0.0
    told = SchedulerConfig(micro_solve_threshold=BOUND,
                           inject_device_latency_s=0.25)
    assert told.micro_solve_threshold == BOUND
    assert told.inject_device_latency_s == 0.25
