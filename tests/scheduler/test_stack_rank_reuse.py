"""A host stack ranks a node once and replays the ranking while nothing
it read has changed (scheduler/rank.py `RankMemo`, keyed by
`Plan.node_writes`; scheduler/propertyset.py `used_counts` kept while
`Plan.writes` stands).

The reference below is a frozen copy of the select chain as it was before
the rankings were kept: `binpack_rank` ranking every visited node afresh,
and a property set recounting the plan for every node it scores. For the
same `random` seed, snapshot and plans, the stack must choose the same
node for every request and fill the same `AllocMetric`. No sleeps, no
clock.
"""

from __future__ import annotations

import dataclasses
import math
import random
from typing import Optional

import pytest

from nomad_tpu import metrics, mock, trace
from nomad_tpu.metrics import Registry
from nomad_tpu.scheduler import stack as stack_mod
from nomad_tpu.scheduler.context import EvalContext, SchedulerConfig
from nomad_tpu.scheduler.feasible import (
    ConstraintChecker,
    CSIVolumeChecker,
    DeviceChecker,
    DistinctHostsChecker,
    DriverChecker,
    HostVolumeChecker,
    NetworkChecker,
    feasibility_pipeline,
    resolve_target,
)
from nomad_tpu.scheduler.propertyset import PropertySet
from nomad_tpu.scheduler.rank import (
    BINPACK_SCORER,
    RankedNode,
    job_anti_affinity_rank,
    node_affinity_rank,
    node_resched_penalty_rank,
    score_normalization,
)
from nomad_tpu.scheduler.select import limit_select, max_score_select
from nomad_tpu.scheduler.spread import SpreadScorer, spread_rank
from nomad_tpu.scheduler.stack import (
    GenericStack,
    _distinct_property_constraints,
    _has_distinct_hosts,
    _tg_drivers,
)
from nomad_tpu.scheduler.tpu.scheduler import _reconcile_eval_batch
from nomad_tpu.scheduler.tpu.solver import BatchSolver
from nomad_tpu.structs import (
    Affinity,
    AllocatedResources,
    AllocatedTaskResources,
    Allocation,
    Constraint,
    NetworkIndex,
    Plan,
    Resources,
    Spread,
    SpreadTarget,
)
from nomad_tpu.structs.funcs import score_fit_binpack, score_fit_spread
from nomad_tpu.structs.node_class import compute_node_class
from nomad_tpu.structs.structs import Port, RequestedDevice
from nomad_tpu.testing import Harness
from nomad_tpu.testing.harness import build_cluster

HOST_ONLY = SchedulerConfig(micro_solve_threshold=0)


# -- the frozen reference: the chain as it ranked before --------------------

def frozen_binpack_rank(ctx, candidates, tg, metrics=None, algorithm=None,
                        evict=False, job=None):
    from nomad_tpu.scheduler.device import DeviceAllocator

    algo = algorithm or ctx.scheduler_config.algorithm
    for node in candidates:
        proposed = ctx.proposed_allocs(node.id)
        available = node.available_resources()
        total_ask = tg.combined_resources()

        def _utilization(allocs):
            util = Resources(
                cpu=total_ask.cpu,
                memory_mb=total_ask.memory_mb,
                disk_mb=total_ask.disk_mb,
            )
            for alloc in allocs:
                r = alloc.comparable_resources()
                util.cpu += r.cpu
                util.memory_mb += r.memory_mb
                util.disk_mb += r.disk_mb
            return util

        util = _utilization(proposed)
        preempted_allocs = None
        ok, dim = available.superset(util)
        if not ok and evict and job is not None:
            from nomad_tpu.scheduler.preemption import Preemptor

            preemptor = Preemptor(
                job.priority, job.namespace, job.id, ctx.plan
            )
            preemptor.set_node(node)
            preemptor.set_candidates(proposed)
            picks = preemptor.preempt_for_task_group(total_ask)
            if picks:
                picked_ids = {a.id for a in picks}
                without = [a for a in proposed if a.id not in picked_ids]
                util = _utilization(without)
                ok, dim = available.superset(util)
                if ok:
                    preempted_allocs = picks
                    proposed = without
        if not ok:
            if metrics is not None:
                metrics.exhausted_node(node, dim)
            continue

        net_idx = NetworkIndex()
        net_idx.set_node(node)
        net_idx.add_allocs(proposed)

        dev_alloc = DeviceAllocator(ctx, node)
        dev_alloc.add_allocs(proposed)

        free_cores: list = []
        mhz_per_core = 0
        if any(t.resources.cores > 0 for t in tg.tasks):
            from nomad_tpu.structs.funcs import node_core_pool

            free_cores, mhz_per_core = node_core_pool(node, proposed)

        task_resources: dict = {}
        feasible = True
        for task in tg.tasks:
            tr = AllocatedTaskResources(
                cpu=task.resources.cpu, memory_mb=task.resources.memory_mb
            )
            if task.resources.cores > 0:
                if len(free_cores) < task.resources.cores:
                    if metrics is not None:
                        metrics.exhausted_node(node, "cores")
                    feasible = False
                    break
                tr.reserved_cores = free_cores[: task.resources.cores]
                free_cores = free_cores[task.resources.cores:]
                tr.cpu = task.resources.cores * mhz_per_core
                util.cpu += tr.cpu - task.resources.cpu
                ok, dim = available.superset(util)
                if not ok:
                    if metrics is not None:
                        metrics.exhausted_node(node, dim)
                    feasible = False
                    break
            for ask in task.resources.networks:
                offer = net_idx.assign_network(ask)
                if offer is None:
                    if metrics is not None:
                        metrics.exhausted_node(node, "network")
                    feasible = False
                    break
                net_idx.add_reserved(offer)
                tr.networks.append(offer)
            if not feasible:
                break
            for dev_ask in task.resources.devices:
                got = dev_alloc.assign(dev_ask)
                if got is None:
                    if metrics is not None:
                        metrics.exhausted_node(node, "devices")
                    feasible = False
                    break
                tr.devices.append(got)
            if not feasible:
                break
            task_resources[task.name] = tr
        if not feasible:
            continue

        shared_networks = []
        for ask in tg.networks:
            offer = net_idx.assign_network(ask)
            if offer is None:
                if metrics is not None:
                    metrics.exhausted_node(node, "network")
                feasible = False
                break
            net_idx.add_reserved(offer)
            shared_networks.append(offer)
        if not feasible:
            continue

        if algo == "spread":
            fit_score = score_fit_spread(node, util)
        else:
            fit_score = score_fit_binpack(node, util)
        normalized = fit_score / 18.0

        ranked = RankedNode(
            node=node,
            task_resources=task_resources,
            alloc_resources=AllocatedResources(
                tasks=task_resources,
                shared_disk_mb=tg.ephemeral_disk.size_mb,
                shared_networks=shared_networks,
            ),
            proposed_allocs=proposed,
            preempted_allocs=preempted_allocs,
        )
        ranked.add_score(BINPACK_SCORER, normalized)
        if metrics is not None:
            metrics.score_node(node.id, BINPACK_SCORER, normalized)
        yield ranked


def frozen_used_counts(pset: PropertySet) -> dict:
    if pset._existing is None:
        pset._existing = pset._compute_existing()
    combined = dict(pset._existing)
    plan = pset.ctx.plan
    if plan is not None:
        for node_id, allocs in plan.node_allocation.items():
            node = pset.ctx.state.node_by_id(node_id)
            val, ok = pset._value_of(node)
            if not ok:
                continue
            for alloc in allocs:
                if pset._relevant(alloc):
                    combined[val] = combined.get(val, 0) + 1
        for node_id, allocs in list(plan.node_update.items()) + list(
            plan.node_preemptions.items()
        ):
            node = pset.ctx.state.node_by_id(node_id)
            val, ok = pset._value_of(node)
            if not ok:
                continue
            for alloc in allocs:
                if pset._relevant(alloc):
                    combined[val] = max(0, combined.get(val, 0) - 1)
    return combined


class FrozenSpreadScorer(SpreadScorer):
    def boost_for(self, node) -> float:
        if not self.spreads:
            return 0.0
        total = 0.0
        for s in self.spreads:
            pset = self.psets[s.attribute]
            val, ok = resolve_target(node, s.attribute)
            if not ok:
                continue
            counts = frozen_used_counts(pset)
            if s.targets:
                boost = self._target_boost(s, val, counts)
            else:
                boost = self._even_boost(val, counts)
            total += boost * (s.weight / self.sum_weights)
        return total


class FrozenDistinctProperty:
    def __init__(self, pset: PropertySet) -> None:
        self.pset = pset

    def feasible(self, node):
        pset = self.pset
        val, ok = pset._value_of(node)
        if not ok:
            return False, f"missing property {pset.target_attribute}"
        used = frozen_used_counts(pset).get(val, 0)
        if used >= pset.allowed_count:
            return (
                False,
                f"distinct_property: {pset.target_attribute}={val} used by "
                f"{used} allocs",
            )
        return True, ""


def frozen_distinct_property_checkers(ctx, job, tg) -> list:
    post = []
    for c in _distinct_property_constraints(job.constraints):
        pset = PropertySet(ctx, job)
        pset.set_job_constraint(c)
        post.append(FrozenDistinctProperty(pset))
    tg_level = list(tg.constraints)
    for t in tg.tasks:
        tg_level.extend(t.constraints)
    for c in _distinct_property_constraints(tg_level):
        pset = PropertySet(ctx, job)
        pset.set_tg_constraint(c, tg.name)
        post.append(FrozenDistinctProperty(pset))
    return post


class FrozenStack(GenericStack):
    """GenericStack.select as it was: every visited node ranked afresh."""

    def select(self, tg, penalty_nodes=None, metrics=None,
               selected_nodes=None, evict=False) -> Optional[RankedNode]:
        job = self.job
        source = selected_nodes if selected_nodes is not None else self.nodes
        job_checkers = [ConstraintChecker(self.ctx, job.constraints)]
        all_constraints = list(tg.constraints)
        for t in tg.tasks:
            all_constraints.extend(t.constraints)
        tg_checkers = [
            DriverChecker(self.ctx, _tg_drivers(tg)),
            ConstraintChecker(self.ctx, all_constraints),
            HostVolumeChecker(self.ctx, tg.volumes, namespace=job.namespace),
            CSIVolumeChecker(self.ctx, tg.volumes, namespace=job.namespace),
            NetworkChecker(self.ctx, tg),
            DeviceChecker(self.ctx, tg),
        ]
        feasible = feasibility_pipeline(
            self.ctx, source, job_checkers, tg_checkers, tg.name, metrics
        )
        post = self._post_checkers.get(tg.name)
        if post is None:
            post = []
            if _has_distinct_hosts(job.constraints):
                post.append(DistinctHostsChecker(self.ctx, job.id, tg.name, True))
            elif _has_distinct_hosts(tg.constraints):
                post.append(DistinctHostsChecker(self.ctx, job.id, tg.name, False))
            post.extend(frozen_distinct_property_checkers(self.ctx, job, tg))
            self._post_checkers[tg.name] = post
        if post:
            def _post_filter(nodes):
                for node in nodes:
                    ok = True
                    for checker in post:
                        good, reason = checker.feasible(node)
                        if not good:
                            if metrics is not None:
                                metrics.filter_node(node, reason)
                            ok = False
                            break
                    if ok:
                        yield node

            feasible = _post_filter(feasible)

        options = frozen_binpack_rank(
            self.ctx, feasible, tg, metrics, evict=evict, job=job
        )
        options = job_anti_affinity_rank(
            self.ctx, options, job.id, tg.name, tg.count, metrics
        )
        if penalty_nodes:
            options = node_resched_penalty_rank(options, penalty_nodes, metrics)
        affinities = list(job.affinities) + list(tg.affinities)
        for t in tg.tasks:
            affinities.extend(t.affinities)
        options = node_affinity_rank(self.ctx, options, affinities, metrics)
        if tg.spreads or job.spreads:
            scorer = self._spread_scorers.get(tg.name)
            if scorer is None:
                scorer = FrozenSpreadScorer(self.ctx, job, tg, metrics)
                self._spread_scorers[tg.name] = scorer
            options = spread_rank(self.ctx, options, scorer, metrics)
        options = score_normalization(options, metrics)
        shortlist = limit_select(options, self.limit)
        return max_score_select(shortlist)


# -- clusters and jobs ---------------------------------------------------------

DCS = ["dc1", "dc2", "dc3", "dc4"]


def cluster(n: int, seed: int, *, fill: float = 0.0, priority: int = 50,
            tpu: bool = False) -> tuple[Harness, list]:
    """`n` nodes over four datacenters and three racks; each holds
    standing allocs of one job at `priority` up to about `fill` of its
    CPU, dealt from the seed."""
    rng = random.Random(seed)
    h = Harness()
    nodes = []
    for i in range(n):
        node = mock.tpu_node() if tpu else mock.node()
        node.datacenter = DCS[i % 4]
        node.meta = {"rack": f"r{rng.randrange(3)}"}
        node.computed_class = compute_node_class(node)
        h.state.upsert_node(h.next_index(), node)
        nodes.append(node)
    if fill > 0:
        standing = mock.job(id=f"standing-{seed}", priority=priority)
        standing.task_groups[0].tasks[0].resources.networks = []
        h.state.upsert_job(h.next_index(), standing)
        allocs = []
        for node in nodes:
            share = fill if fill >= 1 else rng.uniform(0, fill)
            cpu = int((node.resources.cpu - node.reserved.cpu) * share)
            if cpu <= 0:
                continue
            a = mock.alloc(job_=standing, node_=node, index=len(allocs))
            a.resources.tasks["web"].cpu = cpu
            a.resources.tasks["web"].memory_mb = 64
            allocs.append(a)
        h.state.upsert_allocs(h.next_index(), allocs)
    return h, nodes


def service(count: int, cpu: int = 500, job_id: str = "under-test",
            priority: int = 50, ports: bool = False):
    job = mock.job(id=job_id, priority=priority)
    job.datacenters = list(DCS)
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.cpu = cpu
    tg.tasks[0].resources.memory_mb = 128
    if not ports:
        tg.tasks[0].resources.networks = []
    return job


def _even_spread(job):
    job.spreads = [Spread(attribute="${node.datacenter}", weight=50)]


def _targeted_spread(job):
    job.task_groups[0].spreads = [Spread(
        attribute="${node.datacenter}", weight=80,
        targets=[SpreadTarget("dc1", 50), SpreadTarget("dc2", 30)],
    )]


def _distinct_hosts(job):
    job.constraints.append(Constraint(operand="distinct_hosts"))


def _distinct_property(job):
    job.task_groups[0].constraints.append(Constraint(
        ltarget="${meta.rack}", rtarget="4", operand="distinct_property"))


def _affinity(job):
    job.affinities = [Affinity("${node.datacenter}", "dc2", "=", 60)]
    job.task_groups[0].affinities = [Affinity("${meta.rack}", "r1", "=", -30)]


def _cores(job):
    job.task_groups[0].tasks[0].resources.cores = 1


def _devices(job):
    job.task_groups[0].tasks[0].resources.devices = [
        RequestedDevice(name="tpu", count=1)]


def _group_port(job):
    from nomad_tpu.structs import NetworkResource

    job.task_groups[0].networks = [
        NetworkResource(dynamic_ports=[Port(label="admin")])]


# -- the runs ----------------------------------------------------------------

def _metric(m) -> dict:
    d = dataclasses.asdict(m)
    d.pop("allocation_time_ns")
    return d


def _option(o: Optional[RankedNode]):
    if o is None:
        return None
    return (
        o.node.id,
        o.final_score,
        dict(o.scores),
        dataclasses.asdict(o.alloc_resources),
        sorted(a.id for a in o.preempted_allocs or ()),
    )


class Runner:
    """One job's requests through a stack, the generic scheduler's way:
    place, else evict; each placement appended to the stack's plan."""

    def __init__(self, cls, snap, job, plan: Plan, extra=(), nodes=None,
                 preempt: bool = True) -> None:
        self.job = job
        self.plan = plan
        self.preempt = preempt
        ctx = EvalContext(snap, plan, None, SchedulerConfig(),
                          extra_plans=list(extra))
        self.ctx = ctx
        self.stack = cls(False, ctx)
        self.stack.set_nodes(nodes if nodes is not None
                             else [n for n in snap.nodes() if n.ready()])
        self.stack.set_job(job)
        self.log: list = []
        self.k = 0

    def place(self, tg, penalty=None, sticky=None) -> None:
        from nomad_tpu.structs import AllocMetric

        metric = AllocMetric()
        option = None
        if sticky is not None:
            option = self.stack.select(tg, penalty_nodes=penalty,
                                       metrics=metric, selected_nodes=[sticky])
        if option is None:
            option = self.stack.select(tg, penalty_nodes=penalty,
                                       metrics=metric)
        if option is None and self.preempt:
            option = self.stack.select(tg, penalty_nodes=penalty,
                                       metrics=metric, evict=True)
        metric.nodes_evaluated = self.ctx.metrics_nodes_evaluated
        self.log.append((tg.name, _option(option), _metric(metric)))
        if option is None:
            return
        alloc = Allocation(
            id=f"{self.job.id}-{self.k}", namespace=self.job.namespace,
            name=f"{self.job.id}.{tg.name}[{self.k}]",
            node_id=option.node.id, node_name=option.node.name,
            job_id=self.job.id, job=self.job, task_group=tg.name,
            resources=option.alloc_resources, metrics=metric,
            desired_status="run", client_status="pending",
        )
        self.k += 1
        for p in option.preempted_allocs or ():
            self.plan.append_preempted_alloc(p, alloc.id)
        self.plan.append_alloc(alloc, self.job)


def one_job(seed: int, build) -> dict:
    """The frozen chain's log and the stack's, on one snapshot."""
    h, job, requests, kw = build(seed)
    snap = h.snapshot()
    out = {}
    for cls in (FrozenStack, GenericStack):
        random.seed(seed)
        run = Runner(cls, snap, job, Plan(job=job), **kw)
        for tg, penalty, sticky in requests(snap):
            run.place(tg, penalty, sticky)
        out[cls] = run.log
    ranks = run.stack.ranks
    return out[FrozenStack], out[GenericStack], (ranks.ranked, ranks.reused)


def _requests(job, n: int):
    tg = job.task_groups[0]
    return lambda snap: [(tg, None, None)] * n


def case_spread_even(seed):
    h, _ = cluster(640, seed, fill=0.6)
    job = service(12)
    _even_spread(job)
    return h, job, _requests(job, 12), {}


def case_spread_targeted(seed):
    h, _ = cluster(200, seed, fill=0.5)
    job = service(10)
    _targeted_spread(job)
    return h, job, _requests(job, 10), {}


def case_distinct_hosts(seed):
    h, _ = cluster(24, seed, fill=0.3)
    job = service(30, cpu=200)
    _distinct_hosts(job)
    return h, job, _requests(job, 30), {}


def case_distinct_property(seed):
    h, _ = cluster(60, seed, fill=0.3)
    job = service(16, cpu=200)
    _distinct_property(job)
    _even_spread(job)
    return h, job, _requests(job, 16), {}


def case_affinity_and_penalty(seed):
    h, nodes = cluster(96, seed, fill=0.7)
    job = service(10, cpu=900)
    _affinity(job)
    rng = random.Random(seed)
    penalty = {n.id for n in rng.sample(nodes, 40)}
    tg = job.task_groups[0]
    return h, job, lambda snap: [(tg, penalty, None)] * 10, {}


def case_sticky_then_walk(seed):
    h, nodes = cluster(48, seed, fill=0.2)
    job = service(6, cpu=2200)  # one to a node
    _even_spread(job)
    tg = job.task_groups[0]
    rng = random.Random(seed)
    # a preferred node taken by an earlier request sends the next walking
    stickies = [rng.choice(nodes[:2]) for _ in range(6)]
    return h, job, lambda snap: [(tg, None, s) for s in stickies], {}


def case_fills_up(seed):
    """More asks than the cluster holds: nodes fill, then every walk
    meets exhausted nodes, some kept as exhausted."""
    h, _ = cluster(12, seed, fill=0.5)
    job = service(40, cpu=1500)
    _even_spread(job)
    return h, job, _requests(job, 40), {"preempt": False}


def case_ports(seed):
    h, _ = cluster(64, seed, fill=0.4)
    job = service(10, ports=True)
    _even_spread(job)
    return h, job, _requests(job, 10), {}


def case_group_port(seed):
    h, _ = cluster(64, seed, fill=0.4)
    job = service(8)
    _group_port(job)
    return h, job, _requests(job, 8), {}


def case_cores(seed):
    h, _ = cluster(16, seed, fill=0.2)
    job = service(20, cpu=100)
    _cores(job)
    return h, job, _requests(job, 20), {}


def case_devices(seed):
    h, _ = cluster(8, seed, fill=0.2, tpu=True)
    job = service(40, cpu=100)
    _devices(job)
    return h, job, _requests(job, 40), {}


def case_evict_on_full_cluster(seed):
    h, _ = cluster(40, seed, fill=1.0, priority=20)
    job = service(5, cpu=1000, priority=70)
    _even_spread(job)
    return h, job, _requests(job, 5), {}


CASES = {
    "spread-even": (case_spread_even, True),
    "spread-targeted": (case_spread_targeted, True),
    "distinct-hosts": (case_distinct_hosts, True),
    "distinct-property": (case_distinct_property, True),
    "affinity-and-penalty": (case_affinity_and_penalty, True),
    "sticky-then-walk": (case_sticky_then_walk, True),
    "fills-up": (case_fills_up, True),
    "ports": (case_ports, False),
    "group-port": (case_group_port, False),
    "cores": (case_cores, False),
    "devices": (case_devices, False),
    "evict-on-full-cluster": (case_evict_on_full_cluster, True),
}


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_stack_chooses_and_records_what_the_frozen_chain_does(case, seed):
    build, reusable = CASES[case]
    want, got, (ranked, reused) = one_job(seed, build)
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g == w, f"request {i}"
    assert any(opt is not None for _, opt, _ in got)
    if reusable:
        assert reused > 0
    else:
        assert reused == 0  # bypassed: every ranking computed
    assert ranked > 0


def test_a_full_cluster_walk_evicts_as_before():
    """The evict pass ranks afresh; its preemptions are today's. The
    first request's normal pass finds every node exhausted (kept); a
    victim frees more than an ask, so later requests may fit in it."""
    want, got, (ranked, reused) = one_job(3, case_evict_on_full_cluster)
    assert got == want
    preempted = [opt[4] for _, opt, _ in got if opt is not None]
    assert len(preempted) == 5 and preempted[0]
    first_metric = got[0][2]
    assert first_metric["nodes_exhausted"] >= 40  # the normal pass, all
    assert reused >= 40  # the second request's normal pass replays them


# -- two stacks sharing nodes through extra_plans -----------------------------

def interleaved(cls, snap, seed: int) -> list:
    """Two evals' stacks over one small cluster, each seeing the other's
    plan: A places, B places on what A left, A again — a stack used
    after another stack wrote to its nodes. Both groups are named
    `web`."""
    a_job = service(9, cpu=800, job_id="a")
    b_job = service(9, cpu=800, job_id="b")
    _even_spread(a_job)
    random.seed(seed)
    a_plan, b_plan = Plan(job=a_job), Plan(job=b_job)
    a = Runner(cls, snap, a_job, a_plan, extra=[b_plan])
    b = Runner(cls, snap, b_job, b_plan, extra=[a_plan])
    for _ in range(9):
        a.place(a_job.task_groups[0])
        b.place(b_job.task_groups[0])
    return a.log + b.log, (a.stack.ranks.reused, b.stack.ranks.reused)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_two_stacks_that_write_to_each_others_nodes(seed):
    snap = cluster(20, seed, fill=0.5)[0].snapshot()
    want, _ = interleaved(FrozenStack, snap, seed)
    got, (a_reused, b_reused) = interleaved(GenericStack, snap, seed)
    assert got == want
    assert a_reused > 0 and b_reused > 0


def batch_of_evals(cls, h, evs, seed: int, monkeypatch) -> list:
    """Three evals in one host solve (`BatchSolver._solve_host`): their
    stacks see each other's plans through `extra_plans`."""
    monkeypatch.setattr(stack_mod, "GenericStack", cls)
    snap = h.snapshot()
    _, asks = _reconcile_eval_batch(snap, h, evs, HOST_ONLY)
    random.seed(seed)
    out = BatchSolver(snap, HOST_ONLY)._solve_host(asks)
    got = []
    for ev in evs:
        for a in out.placements.get(ev.id, []):
            got.append((a.name, a.node_id, _metric(a.metrics)))
        for tg, m in sorted(out.failures.get(ev.id, {}).items()):
            got.append((tg, None, _metric(m)))
    return got


@pytest.mark.parametrize("seed", [1, 2])
def test_a_batch_of_evals_places_as_before(seed, monkeypatch):
    h, _ = cluster(30, seed, fill=0.6)
    evs = []
    for i, count in enumerate((5, 7, 4)):
        job = service(count, cpu=900, job_id=f"batch-{i}")
        if i != 1:
            _even_spread(job)
        h.state.upsert_job(h.next_index(), job)
        evs.append(mock.eval_for_job(job))
    want = batch_of_evals(FrozenStack, h, evs, seed, monkeypatch)
    got = batch_of_evals(GenericStack, h, evs, seed, monkeypatch)
    assert got == want and len(got) == 16


# -- the generic oracle on an existing fixture ---------------------------------

@pytest.mark.parametrize("seed", [5, 6])
def test_the_generic_oracle_places_as_before(seed, monkeypatch):
    from nomad_tpu.mock import factories
    from nomad_tpu.scheduler import generic

    runs = []
    for cls in (FrozenStack, GenericStack):
        monkeypatch.setattr(generic, "GenericStack", cls)
        # the same node ids in both builds
        ids = iter(range(10**6))
        monkeypatch.setattr(factories, "generate_uuid",
                            lambda: f"00000000-{next(ids):08d}")
        h, jobs = build_cluster(200, 4, 6, constrained=True)
        placed = []
        random.seed(seed)
        for job in jobs:
            h.process("service", mock.eval_for_job(job))
            plan = h.plans[-1]
            for allocs in plan.node_allocation.values():
                for a in allocs:
                    placed.append((job.id, a.name, a.node_id))
        runs.append(sorted(placed))
    assert runs[0] == runs[1] and len(runs[0]) == 24


# -- the counters ---------------------------------------------------------------

@pytest.fixture()
def registry():
    old = metrics._install_registry(Registry())
    yield metrics.registry()
    metrics._install_registry(old)


def empty_cluster(n: int) -> Harness:
    h = Harness()
    for i in range(n):
        node = mock.node(datacenter=DCS[i % 4])
        h.state.upsert_node(h.next_index(), node)
    return h


def host_solve(h: Harness, ev, seed: int):
    snap = h.snapshot()
    _, asks = _reconcile_eval_batch(snap, h, [ev], HOST_ONLY)
    random.seed(seed)
    return BatchSolver(snap, HOST_ONLY).solve(asks)


def hist(reg, name: str) -> dict:
    raw = reg.histogram_raw(name) or {}
    return {"count": raw.get("count", 0), "sum": raw.get("sum", 0)}


def test_an_8_alloc_spread_deploy_ranks_each_drawn_node_about_once(registry):
    h = empty_cluster(2_000)
    job = service(8)
    _even_spread(job)
    h.state.upsert_job(h.next_index(), job)
    ev = mock.eval_for_job(job)
    ctx = trace.TraceContext("tpu.batch")
    with trace.use(ctx):
        out = host_solve(h, ev, seed=7)
    assert len(out.placements[ev.id]) == 8
    drawn, ranked, reused = (out.stack_nodes_drawn, out.stack_ranked,
                             out.stack_reused)
    # the first walk ranks what it draws; each later one re-ranks the
    # node that took the placement before it, and what it draws anew
    assert ranked <= drawn + 8
    assert reused >= 7 * math.ceil(math.log2(2_000)) - ranked
    assert reused / (ranked + reused) > 0.6
    assert hist(registry, "nomad.sched.stack.ranked") == {
        "count": 1, "sum": ranked}
    assert hist(registry, "nomad.sched.stack.rank_reused") == {
        "count": 1, "sum": reused}
    span, = [s for s in ctx.spans if s.name == "host_solve"]
    assert (span.attrs["ranked"], span.attrs["reused"]) == (ranked, reused)


def test_a_group_with_ports_reuses_none(registry):
    h = empty_cluster(2_000)
    job = service(8, ports=True)
    _even_spread(job)
    h.state.upsert_job(h.next_index(), job)
    ev = mock.eval_for_job(job)
    out = host_solve(h, ev, seed=7)
    assert len(out.placements[ev.id]) == 8
    assert out.stack_reused == 0
    assert out.stack_ranked >= 8 * math.ceil(math.log2(2_000))
    assert hist(registry, "nomad.sched.stack.rank_reused") == {
        "count": 1, "sum": 0}



# -- rankings the store's usage total decided (nomad.sched.stack.exhausted_by_usage)

def half_full_cluster(n: int, priority: int = 50) -> tuple[Harness, set]:
    """`n` nodes over four datacenters; every other one holds a standing
    alloc of its whole CPU. Returns the harness and the full nodes' ids."""
    h = empty_cluster(n)
    standing = mock.job(id="standing", priority=priority)
    standing.task_groups[0].tasks[0].resources.networks = []
    h.state.upsert_job(h.next_index(), standing)
    allocs = []
    for node in sorted(h.state.nodes(), key=lambda x: x.name)[::2]:
        a = mock.alloc(job_=standing, node_=node, index=len(allocs))
        a.resources.tasks["web"].cpu = node.available_resources().cpu
        a.resources.tasks["web"].memory_mb = 64
        allocs.append(a)
    h.state.upsert_allocs(h.next_index(), allocs)
    return h, {a.node_id for a in allocs}


def spy_rankings(monkeypatch) -> list:
    """Every binpack ranking computed, as (node id, evict, exhausted)."""
    from nomad_tpu.scheduler import rank as rank_mod

    calls = []
    real = rank_mod.binpack_node

    def spy(ctx, node, tg, algo, evict=False, job=None):
        ranked, dim = real(ctx, node, tg, algo, evict, job)
        calls.append((node.id, evict, ranked is None))
        return ranked, dim

    monkeypatch.setattr(rank_mod, "binpack_node", spy)
    return calls


def test_a_batch_over_a_half_full_cluster_counts_the_full_nodes_it_ranked(
        registry, monkeypatch):
    h, full = half_full_cluster(2_000)
    evs = []
    for i in range(2):
        job = service(4, job_id=f"deploy-{i}")
        _even_spread(job)
        h.state.upsert_job(h.next_index(), job)
        evs.append(mock.eval_for_job(job))
    calls = spy_rankings(monkeypatch)
    snap = h.snapshot()
    _, asks = _reconcile_eval_batch(snap, h, evs, HOST_ONLY)
    random.seed(3)
    ctx = trace.TraceContext("tpu.batch")
    with trace.use(ctx):
        out = BatchSolver(snap, HOST_ONLY).solve(asks)
    assert sum(len(out.placements[ev.id]) for ev in evs) == 8
    on_full = [c for c in calls if c[0] in full]
    assert all(exhausted and not evict for _, evict, exhausted in on_full)
    assert len(calls) == out.stack_ranked  # no sticky try ranked
    by_usage = out.stack_by_usage
    assert by_usage == len(on_full) > 0
    # a shuffled walk meets a full node about every other draw
    assert 0.3 < by_usage / out.stack_ranked < 0.7
    assert hist(registry, "nomad.sched.stack.exhausted_by_usage") == {
        "count": 2, "sum": by_usage}  # one observation a stack
    span, = [s for s in ctx.spans if s.name == "host_solve"]
    assert span.attrs["by_usage"] == by_usage


def test_an_empty_cluster_reads_no_ranking_by_usage(registry):
    h = empty_cluster(2_000)
    job = service(8)
    h.state.upsert_job(h.next_index(), job)
    ev = mock.eval_for_job(job)
    ctx = trace.TraceContext("tpu.batch")
    with trace.use(ctx):
        out = host_solve(h, ev, seed=7)
    assert len(out.placements[ev.id]) == 8 and out.stack_ranked > 0
    assert out.stack_by_usage == 0
    assert hist(registry, "nomad.sched.stack.exhausted_by_usage") == {
        "count": 1, "sum": 0}
    span, = [s for s in ctx.spans if s.name == "host_solve"]
    assert span.attrs["by_usage"] == 0


def test_the_evict_pass_counts_no_ranking_by_usage(monkeypatch):
    """A cluster full of priority-20 allocs: the normal pass ends every
    ranking on the usage total; the evict pass builds each node's list
    for the Preemptor and counts none."""
    from nomad_tpu.structs import AllocMetric

    h, full = half_full_cluster(64, priority=20)
    job = service(1, cpu=1_000, priority=70)
    snap = h.snapshot()
    nodes = [n for n in snap.nodes() if n.id in full]
    calls = spy_rankings(monkeypatch)
    got = {}
    for evict in (False, True):
        ctx = EvalContext(snap, Plan(job=job), None, SchedulerConfig())
        stack = GenericStack(False, ctx)
        stack.set_nodes(nodes)
        stack.set_job(job)
        random.seed(1)
        option = stack.select(job.task_groups[0], metrics=AllocMetric(),
                              evict=evict)
        got[evict] = option, ctx.exhausted_by_usage
    normal, evicting = got[False], got[True]
    assert normal == (None, 32)  # every full node, each once
    assert evicting[0] is not None and evicting[0].preempted_allocs
    assert evicting[1] == 0
    assert sum(1 for _, evict, _ in calls if evict) > 0


# -- the plan's write counters --------------------------------------------------

def _alloc(node_id: str, i: int = 0) -> Allocation:
    return Allocation(id=f"a{i}", node_id=node_id, job_id="j",
                      task_group="web", resources=AllocatedResources())


@pytest.mark.parametrize("write", [
    "append_alloc", "append_fresh_alloc", "append_stopped_alloc",
    "append_preempted_alloc", "pop_update", "materialize_batches",
])
def test_every_plan_write_counts_for_its_node_and_the_plan(write):
    plan = Plan()
    plan.append_stopped_alloc(_alloc("n0", 9), "setup")  # something to pop
    before = dict(plan.node_writes), plan.writes
    a = _alloc("n1")
    if write == "append_alloc":
        plan.append_alloc(a)
    elif write == "append_fresh_alloc":
        plan.append_fresh_alloc(a)
    elif write == "append_stopped_alloc":
        plan.append_stopped_alloc(a, "stop")
    elif write == "append_preempted_alloc":
        plan.append_preempted_alloc(a, "other")
    elif write == "pop_update":
        a = _alloc("n1", 9)
        plan.append_stopped_alloc(a, "stop")
        before = dict(plan.node_writes), plan.writes
        plan.pop_update(a)
        assert "n1" not in plan.node_update  # the list emptied
    else:
        import numpy as np

        from nomad_tpu.structs.placement_batch import PlacementBatch

        plan.append_placement_batch(PlacementBatch(
            job_id="j", task_group="web", resources=AllocatedResources(),
            ids=["a1"], names=["j.web[0]"],
            node_idx_raw=np.array([1], dtype=np.int32).tobytes(),
            node_ids=["n0", "n1"], node_names=["node-0", "node-1"],
        ))
        assert (plan.node_writes, plan.writes) == before  # not yet written
        plan.materialize_batches()
        assert [x.id for x in plan.node_allocation["n1"]] == ["a1"]
    assert plan.node_writes["n1"] == before[0].get("n1", 0) + 1
    assert plan.writes == before[1] + 1
    assert plan.node_writes["n0"] == before[0]["n0"]  # other nodes stand


def test_a_pop_of_nothing_writes_nothing():
    plan = Plan()
    plan.pop_update(_alloc("n1"))
    assert (plan.node_writes, plan.writes) == ({}, 0)
