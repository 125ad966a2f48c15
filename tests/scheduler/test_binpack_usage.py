"""A binpack ranking on a node that no plan touches checks the fit
against the store's usage total (scheduler/rank.py `binpack_node`,
`StateSnapshot.node_usage`) and builds the node's proposed allocs only
where the ask fits.

`list_rank` below is the rule as it was: the node's proposed allocs
summed one by one, plus what a batch in flight holds there, plus the
ask. On seeded clusters whose even nodes hold 200 allocs of 20 MHz /
40 MB (full) and whose odd nodes hold fewer, `binpack_node` must give
that rule's option — node, scores, proposed ids, resources, preemptions
— or its `(None, dim)` on every node, whatever the plans of the context
write; and `binpack_rank` must give the same options and metrics with
and without a `RankMemo`. No sleeps, no clock.
"""

from __future__ import annotations

import dataclasses
import random
from typing import Callable, NamedTuple, Optional

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.context import EvalContext, SchedulerConfig
from nomad_tpu.scheduler.device import DeviceAllocator
from nomad_tpu.scheduler.preemption import Preemptor
from nomad_tpu.scheduler.rank import (
    RankMemo,
    binpack_node,
    binpack_rank,
)
from nomad_tpu.state.store import IDX_ALLOCS_NODE
from nomad_tpu.structs import (
    AllocatedResources,
    AllocatedTaskResources,
    AllocMetric,
    NetworkIndex,
    NetworkResource,
    Plan,
    PlanResult,
    Resources,
)
from nomad_tpu.structs.funcs import (
    node_core_pool,
    score_fit_binpack,
    score_fit_spread,
)
from nomad_tpu.structs.placement_batch import AllocRow, PlacementBatch
from nomad_tpu.structs.structs import Port, RequestedDevice
from nomad_tpu.testing import Harness

NODES = 6
ALLOC_CPU, ALLOC_MEM = 20, 40  # the CI cell's standing alloc


# -- the rule as it was: the proposed allocs summed ------------------------

def list_rank(ctx, node, tg, algo, evict=False, job=None):
    proposed = ctx.proposed_allocs(node.id)
    available = node.available_resources()
    total_ask = tg.combined_resources()
    held = (
        ctx.extra_usage.get(node.id) if ctx.extra_usage is not None else None
    ) or (0, 0, 0)

    def _utilization(allocs):
        util = Resources(
            cpu=total_ask.cpu + held[0],
            memory_mb=total_ask.memory_mb + held[1],
            disk_mb=total_ask.disk_mb + held[2],
        )
        for alloc in allocs:
            r = alloc.comparable_resources()
            util.cpu += r.cpu
            util.memory_mb += r.memory_mb
            util.disk_mb += r.disk_mb
        return util

    util = _utilization(proposed)
    preempted = None
    ok, dim = available.superset(util)
    if not ok and evict and job is not None:
        preemptor = Preemptor(job.priority, job.namespace, job.id, ctx.plan)
        preemptor.set_node(node)
        preemptor.set_candidates(proposed)
        picks = preemptor.preempt_for_task_group(total_ask)
        if picks:
            picked_ids = {a.id for a in picks}
            without = [a for a in proposed if a.id not in picked_ids]
            util = _utilization(without)
            ok, dim = available.superset(util)
            if ok:
                preempted = picks
                proposed = without
    if not ok:
        return None, dim

    net_idx = NetworkIndex()
    net_idx.set_node(node)
    net_idx.add_allocs(proposed)
    dev_alloc = DeviceAllocator(ctx, node)
    dev_alloc.add_allocs(proposed)
    free_cores, mhz_per_core = [], 0
    if any(t.resources.cores > 0 for t in tg.tasks):
        free_cores, mhz_per_core = node_core_pool(node, proposed)
    tasks = {}
    for task in tg.tasks:
        tr = AllocatedTaskResources(
            cpu=task.resources.cpu, memory_mb=task.resources.memory_mb)
        if task.resources.cores > 0:
            if len(free_cores) < task.resources.cores:
                return None, "cores"
            tr.reserved_cores = free_cores[: task.resources.cores]
            free_cores = free_cores[task.resources.cores:]
            tr.cpu = task.resources.cores * mhz_per_core
            util.cpu += tr.cpu - task.resources.cpu
            ok, dim = available.superset(util)
            if not ok:
                return None, dim
        for ask in task.resources.networks:
            offer = net_idx.assign_network(ask)
            if offer is None:
                return None, "network"
            net_idx.add_reserved(offer)
            tr.networks.append(offer)
        for dev_ask in task.resources.devices:
            got = dev_alloc.assign(dev_ask)
            if got is None:
                return None, "devices"
            tr.devices.append(got)
        tasks[task.name] = tr
    shared = []
    for ask in tg.networks:
        offer = net_idx.assign_network(ask)
        if offer is None:
            return None, "network"
        net_idx.add_reserved(offer)
        shared.append(offer)
    fit = (score_fit_spread if algo == "spread" else score_fit_binpack)(
        node, util)
    return (node.id, fit / 18.0, sorted(a.id for a in proposed),
            dataclasses.asdict(AllocatedResources(
                tasks=tasks, shared_disk_mb=tg.ephemeral_disk.size_mb,
                shared_networks=shared)),
            sorted(a.id for a in preempted or ())), ""


def _option(ranked):
    return (
        ranked.node.id, ranked.scores["binpack"],
        sorted(a.id for a in ranked.proposed_allocs),
        dataclasses.asdict(ranked.alloc_resources),
        sorted(a.id for a in ranked.preempted_allocs or ()),
    )


def usage_rank(ctx, node, tg, algo, evict=False, job=None):
    ranked, dim = binpack_node(ctx, node, tg, algo, evict, job)
    return (None, dim) if ranked is None else (_option(ranked), "")


# -- clusters -------------------------------------------------------------------

class World(NamedTuple):
    h: Harness
    standing: object
    nodes: list
    allocs: dict  # node id -> its standing allocs


def standing_job(priority: int = 50):
    job = mock.job(id="standing", priority=priority)
    res = job.task_groups[0].tasks[0].resources
    res.cpu, res.memory_mb, res.networks = ALLOC_CPU, ALLOC_MEM, []
    return job


def world(seed: int, *, priority: int = 50, tpu: bool = False,
          soa: bool = False) -> World:
    """Even nodes hold 200 standing allocs (their CPU, full); odd nodes
    a seeded 1-150. With `soa` they land as one PlacementBatch's rows,
    which nothing materializes (`allocs` is then left empty)."""
    rng = random.Random(seed)
    h = Harness()
    standing = standing_job(priority)
    h.state.upsert_job(h.next_index(), standing)
    nodes = []
    counts = []
    for i in range(NODES):
        node = mock.tpu_node() if tpu else mock.node()
        h.state.upsert_node(h.next_index(), node)
        nodes.append(node)
        full = node.available_resources().cpu // ALLOC_CPU
        counts.append(full if i % 2 == 0 else rng.randrange(1, 151))
    allocs: dict = {n.id: [] for n in nodes}
    if soa:
        a0 = mock.alloc(job_=standing, node_=nodes[0])
        idx = np.repeat(np.arange(NODES, dtype=np.int32), counts)
        ids = [f"row-{k}" for k in range(len(idx))]
        h.state.upsert_plan_results(h.next_index(), PlanResult(
            job=standing, alloc_batches=[PlacementBatch(
                namespace=standing.namespace, eval_id="ev-rows",
                job_id=standing.id, job=standing, task_group="web",
                resources=a0.resources, metrics=a0.metrics, ids=ids,
                names=[f"standing.web[{k}]" for k in range(len(idx))],
                node_idx_raw=idx.tobytes(),
                node_ids=[n.id for n in nodes],
                node_names=[n.name for n in nodes],
            )]))
        return World(h, standing, nodes, allocs)
    batch = []
    for node, count in zip(nodes, counts):
        for _ in range(count):
            a = mock.alloc(job_=standing, node_=node, index=len(batch))
            allocs[node.id].append(a)
            batch.append(a)
    h.state.upsert_allocs(h.next_index(), batch)
    return World(h, standing, nodes, allocs)


def service(cpu: int = ALLOC_CPU, memory: int = ALLOC_MEM, priority: int = 50):
    job = mock.job(id="under-test", priority=priority)
    tg = job.task_groups[0]
    tg.tasks[0].resources.cpu = cpu
    tg.tasks[0].resources.memory_mb = memory
    tg.tasks[0].resources.networks = []
    return job


# -- the cases ------------------------------------------------------------------

class Case(NamedTuple):
    ctx: EvalContext
    job: object
    nodes: list
    evict: bool = False
    touched: frozenset = frozenset()  # nodes a plan of the context writes
    # nodes whose ranking must end on the usage total, and nodes on
    # which the ask must fit
    by_usage: frozenset = frozenset()
    fits: frozenset = frozenset()


def _ctx(w: World, job, plan=None, extra=(), held=None) -> EvalContext:
    return EvalContext(w.h.snapshot(), plan or Plan(job=job), None,
                       SchedulerConfig(), extra_plans=list(extra),
                       extra_usage=held)


def _ids(nodes) -> frozenset:
    return frozenset(n.id for n in nodes)


def case_full_untouched(seed):
    w = world(seed)
    job = service()
    return Case(_ctx(w, job), job, w.nodes,
                by_usage=_ids(w.nodes[0::2]), fits=_ids(w.nodes[1::2]))


def case_part_full_untouched(seed):
    # an ask of 1,500 MHz fits a node holding up to 125 allocs
    w = world(seed)
    job = service(cpu=1_500)
    part = w.nodes[1::2]
    fits = [n for n in part if len(w.allocs[n.id]) <= 125]
    return Case(_ctx(w, job), job, w.nodes,
                by_usage=_ids(w.nodes) - _ids(fits), fits=_ids(fits))


def case_terminal_allocs(seed):
    # half of node 0's allocs and all of node 2's have completed: the
    # usage total holds only the live ones
    w = world(seed)
    done = []
    for node, k in ((w.nodes[0], 100), (w.nodes[2], 200)):
        for a in w.allocs[node.id][:k]:
            c = a.copy()
            c.client_status = "complete"
            done.append(c)
    w.h.state.upsert_allocs(w.h.next_index(), done)
    job = service(cpu=1_000)
    return Case(_ctx(w, job), job, w.nodes, by_usage=_ids(w.nodes[4:5]),
                fits=_ids(w.nodes[0:3:2]) | _ids(w.nodes[1::2]))


def case_soa_rows(seed):
    w = world(seed, soa=True)
    rows = w.h.state.snapshot()._tables[IDX_ALLOCS_NODE][w.nodes[0].id]
    assert {r.__class__ for r in rows.values()} == {AllocRow}
    job = service()
    return Case(_ctx(w, job), job, w.nodes,
                by_usage=_ids(w.nodes[0::2]), fits=_ids(w.nodes[1::2]))


def case_own_fresh_placement(seed):
    # the plan placed on a full node and on a part-full one
    w = world(seed)
    job = service()
    plan = Plan(job=job)
    for i, node in enumerate((w.nodes[0], w.nodes[1])):
        plan.append_fresh_alloc(mock.alloc(job_=job, node_=node, index=i), job)
    touched = _ids(w.nodes[:2])
    return Case(_ctx(w, job, plan), job, w.nodes, touched=touched,
                by_usage=_ids(w.nodes[2::2]), fits=_ids(w.nodes[1::2]))


def case_other_plan_stop(seed):
    # another plan of the batch stops one alloc of full node 0: the
    # usage total still says full, the ask fits in what the stop frees
    w = world(seed)
    other = Plan(job=w.standing)
    other.append_stopped_alloc(w.allocs[w.nodes[0].id][0], "stop")
    job = service()
    return Case(_ctx(w, job, extra=[other]), job, w.nodes,
                touched=_ids(w.nodes[:1]), by_usage=_ids(w.nodes[2::2]),
                fits=_ids(w.nodes[:1]) | _ids(w.nodes[1::2]))


def case_preemption(seed):
    w = world(seed)
    job = service()
    plan = Plan(job=job)
    plan.append_preempted_alloc(w.allocs[w.nodes[2].id][5], "preemptor")
    return Case(_ctx(w, job, plan), job, w.nodes,
                touched=_ids(w.nodes[2:3]),
                by_usage=_ids([w.nodes[0], w.nodes[4]]),
                fits=_ids(w.nodes[2:3]) | _ids(w.nodes[1::2]))


def case_in_place_update(seed):
    # an in-place update of one of full node 4's allocs that now asks
    # for nothing: the plan's copy replaces the stored one
    w = world(seed)
    job = service()
    plan = Plan(job=job)
    updated = w.allocs[w.nodes[4].id][7].copy()
    updated.resources.tasks["web"].cpu = 0
    plan.append_alloc(updated, w.standing)
    return Case(_ctx(w, job, plan), job, w.nodes,
                touched=_ids(w.nodes[4:5]), by_usage=_ids(w.nodes[0:3:2]),
                fits=_ids(w.nodes[4:5]) | _ids(w.nodes[1::2]))


def case_extra_usage_held(seed):
    # a batch in flight holds node 1 exactly to the ask's room and node
    # 3 one MHz past it
    w = world(seed)
    job = service()
    held = {}
    for node, over in ((w.nodes[1], 0), (w.nodes[3], 1)):
        used = len(w.allocs[node.id]) * ALLOC_CPU
        room = node.available_resources().cpu - used - ALLOC_CPU
        held[node.id] = (room + over, 0, 0)
    return Case(_ctx(w, job, held=held), job, w.nodes,
                by_usage=_ids(w.nodes[0::2]) | _ids(w.nodes[3:4]),
                fits=_ids([w.nodes[1], w.nodes[5]]))


def case_evict_exhausted(seed):
    # the evict pass keeps the list: the Preemptor picks from it
    w = world(seed, priority=20)
    job = service(cpu=200, priority=70)
    return Case(_ctx(w, job), job, w.nodes, evict=True,
                fits=_ids(w.nodes))


def case_port_ask(seed):
    w = world(seed)
    job = service()
    job.task_groups[0].tasks[0].resources.networks = [
        NetworkResource(mbits=10, dynamic_ports=[Port(label="http")])]
    return Case(_ctx(w, job), job, w.nodes,
                by_usage=_ids(w.nodes[0::2]), fits=_ids(w.nodes[1::2]))


def case_device_ask(seed):
    w = world(seed, tpu=True)
    job = service()
    job.task_groups[0].tasks[0].resources.devices = [
        RequestedDevice(name="tpu", count=1)]
    return Case(_ctx(w, job), job, w.nodes,
                by_usage=_ids(w.nodes[0::2]), fits=_ids(w.nodes[1::2]))


def case_core_ask(seed):
    # a core derives 1,000 MHz (4,000 over 4 cores); a part-full node
    # holds at most 150 allocs, 3,000 MHz, so the core fits there
    w = world(seed)
    job = service()
    job.task_groups[0].tasks[0].resources.cores = 1
    return Case(_ctx(w, job), job, w.nodes,
                by_usage=_ids(w.nodes[0::2]), fits=_ids(w.nodes[1::2]))


CASES: dict[str, Callable[[int], Case]] = {
    "full-untouched": case_full_untouched,
    "part-full-untouched": case_part_full_untouched,
    "terminal-allocs": case_terminal_allocs,
    "soa-rows": case_soa_rows,
    "own-fresh-placement": case_own_fresh_placement,
    "other-plan-stop": case_other_plan_stop,
    "preemption": case_preemption,
    "in-place-update": case_in_place_update,
    "extra-usage-held": case_extra_usage_held,
    "evict-exhausted": case_evict_exhausted,
    "port-ask": case_port_ask,
    "device-ask": case_device_ask,
    "core-ask": case_core_ask,
}


def _rank_each(rank, case: Case, seed: int) -> dict:
    tg = case.job.task_groups[0]
    out = {}
    for node in case.nodes:
        random.seed(seed)  # a port offer draws from `random`
        out[node.id] = rank(case.ctx, node, tg, "binpack", case.evict,
                            case.job)
    return out


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("case", sorted(CASES))
def test_binpack_node_gives_what_the_list_rule_gives(case, seed):
    c = CASES[case](seed)
    want = _rank_each(list_rank, c, seed)
    got = _rank_each(usage_rank, c, seed)
    assert got == want
    fits = {nid for nid, (opt, _) in got.items() if opt is not None}
    assert fits == set(c.fits)
    # the usage total decided exactly the untouched nodes that fail
    assert c.ctx.exhausted_by_usage == len(c.by_usage)
    assert not c.by_usage & (c.touched | c.fits)
    if case == "evict-exhausted":
        preempted = [opt[4] for opt, _ in got.values() if opt is not None]
        assert sum(1 for p in preempted if p) == NODES // 2


def _walk(case: Case, memo: Optional[RankMemo], seed: int):
    random.seed(seed)
    metric = AllocMetric()
    opts = list(binpack_rank(case.ctx, iter(case.nodes),
                             case.job.task_groups[0], metric,
                             evict=case.evict, job=case.job, memo=memo))
    m = dataclasses.asdict(metric)
    m.pop("allocation_time_ns")
    return [_option(o) for o in opts], m


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("case", sorted(CASES))
def test_binpack_rank_gives_the_same_with_and_without_a_memo(case, seed):
    c = CASES[case](seed)
    bare = _walk(c, None, seed)
    memo = RankMemo()
    first = _walk(c, memo, seed)
    again = _walk(c, memo, seed)  # replayed where the group allows
    assert first == bare and again == bare
    assert len(bare[0]) == len(c.fits)
    assert bare[1]["nodes_exhausted"] == NODES - len(c.fits)
