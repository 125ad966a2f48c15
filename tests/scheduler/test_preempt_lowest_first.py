"""The preempt path against a plain reference, by tier.

The reference is arithmetic over plain numbers (no jax, no solver): with
equal asks a node is a row of slots, a placement takes a free slot or,
where none is left, the slot of ONE victim; victims are at least 10
priorities under the preemptor and go lowest priority first over every
node the job admits. It says how many instances are placed and how many
victims each priority gives; WHICH nodes is not compared (ties).

The program's answer is `solve_placement_preempt` + `_materialize`
through the solver's own entry (`solve_eval_batch`, the plan it builds),
single chip and node-sharded over the CPU's virtual devices.
"""

import random
from collections import Counter

import numpy as np
import pytest

from nomad_tpu import metrics, mock, trace
from nomad_tpu.metrics import Registry
from nomad_tpu.scheduler.context import SchedulerConfig
from nomad_tpu.testing import Harness

SLOTS = 8          # 4,000 MHz a node, 500 MHz an alloc
ASK = (500, 256)   # cpu MHz, memory MB: every alloc of every tier
DELTA = 10


# -- the plain reference --------------------------------------------------

def reference(cluster: list[dict], k: int, priority: int,
              datacenters: list[str]) -> dict:
    """`cluster`: one {"dc", "tiers": {priority: allocs}} a node. Place
    `k` at `priority` over the nodes in `datacenters`."""
    admitted = [n for n in cluster if n["dc"] in datacenters]
    free = sum(SLOTS - sum(n["tiers"].values()) for n in admitted)
    left = k - min(k, free)
    victims = {}
    standing = Counter()
    for n in admitted:
        standing.update(n["tiers"])
    for p in sorted(standing):
        if priority - p < DELTA or left == 0:
            continue
        victims[p] = min(left, standing[p])
        left -= victims[p]
    return {"placed": k - left, "victims": victims}


# -- seeded clusters ------------------------------------------------------

def uneven(seed: int, n: int = 16, dcs=("dc1", "dc2")) -> list[dict]:
    """Every node full, its slots split between priority 20 and 50 as
    the seed deals them."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        low = rng.randint(0, SLOTS)
        out.append({"dc": dcs[i % len(dcs)],
                    "tiers": {20: low, 50: SLOTS - low}})
    return out


def a_datacenter_without_the_low_tier(seed: int) -> list[dict]:
    cluster = uneven(seed)
    for node in cluster:
        if node["dc"] == "dc2":
            node["tiers"] = {20: 0, 50: SLOTS}
    return cluster


def with_a_tier_too_close(seed: int) -> list[dict]:
    """A third of the nodes hold priority 65: not 10 under 70."""
    cluster = uneven(seed, n=18)
    for node in cluster[::3]:
        node["tiers"] = {65: SLOTS}
    return cluster


def with_free_slots(seed: int) -> list[dict]:
    cluster = uneven(seed)
    for node in cluster[:4]:
        node["tiers"] = {20: 2, 50: 3}  # three slots free
    return cluster


def total(cluster, priority, dcs=("dc1", "dc2")):
    return sum(n["tiers"].get(priority, 0) for n in cluster
               if n["dc"] in dcs)


CASES = {
    # name: (cluster, K as a function of it, the job's datacenters)
    "uneven_tiers": (uneven, lambda c: total(c, 20) // 2, ["dc1", "dc2"]),
    "a_dc_with_no_low_tier_left": (
        a_datacenter_without_the_low_tier,
        lambda c: total(c, 20) - 1, ["dc1", "dc2"]),
    "more_than_the_lowest_tier": (
        uneven, lambda c: total(c, 20) + 9, ["dc1", "dc2"]),
    "only_one_datacenter_admitted": (
        uneven, lambda c: total(c, 20, ("dc2",)) + 3, ["dc2"]),
    "more_than_everything_preemptible": (
        with_a_tier_too_close,
        lambda c: total(c, 20) + total(c, 50) + 5, ["dc1", "dc2"]),
    "free_slots_first": (
        with_free_slots, lambda c: 12 + total(c, 20) // 2,
        ["dc1", "dc2"]),
}


# -- the program ----------------------------------------------------------

def build(cluster: list[dict]):
    """The cluster in a store: full-size mock nodes, one job a priority,
    its allocs upserted where the cluster says."""
    h = Harness()
    jobs = {}
    for i, shape in enumerate(cluster):
        node = mock.node(datacenter=shape["dc"])
        node.reserved.cpu = 0
        node.reserved.memory_mb = 0
        h.state.upsert_node(h.next_index(), node)
        allocs = []
        for prio, count in shape["tiers"].items():
            job = jobs.get(prio)
            if job is None:
                job = jobs[prio] = mock.job(id=f"tier-{prio}", priority=prio)
                job.datacenters = ["dc1", "dc2"]
                h.state.upsert_job(h.next_index(), job)
            for _ in range(count):
                a = mock.alloc(job_=job, node_=node)
                a.resources.tasks["web"].cpu = ASK[0]
                a.resources.tasks["web"].memory_mb = ASK[1]
                a.resources.tasks["web"].networks = []
                a.client_status = "running"
                allocs.append(a)
        if allocs:
            h.state.upsert_allocs(h.next_index(), allocs)
    return h


def solve(h, k: int, datacenters: list[str], sharded: bool):
    from nomad_tpu.scheduler.tpu import solve_eval_batch

    job = mock.job(id="production", priority=70)
    job.datacenters = list(datacenters)
    tg = job.task_groups[0]
    tg.count = k
    tg.tasks[0].resources.cpu = ASK[0]
    tg.tasks[0].resources.memory_mb = ASK[1]
    tg.tasks[0].resources.networks = []
    h.state.upsert_job(h.next_index(), job)
    kw = {}
    if sharded:
        import jax
        from jax.sharding import Mesh

        from nomad_tpu.scheduler.tpu.kernels import (
            make_sharded_solver, make_sharded_solver_preempt)

        devs = np.array(jax.devices()[:8])
        if len(devs) < 8:
            pytest.skip("needs 8 virtual devices (conftest sets them up)")
        mesh = Mesh(devs, axis_names=("nodes",))
        kw = {"solve_fn": make_sharded_solver(mesh),
              "solve_preempt_fn": make_sharded_solver_preempt(mesh)}
    ev = mock.eval_for_job(job)
    plans = solve_eval_batch(
        h.snapshot(), h, [ev],
        SchedulerConfig(backend="tpu", small_batch_threshold=0), **kw)
    return job, plans[ev.id]


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one_chip", "sharded"])
@pytest.mark.parametrize("seed", [7, 3_000_000_019])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_preempt_path_agrees_with_the_plain_reference_by_tier(
        case, seed, sharded):
    make, k_of, datacenters = CASES[case]
    cluster = make(seed)
    k = k_of(cluster)
    want = reference(cluster, k, 70, datacenters)
    assert want["placed"] > 0 and sum(want["victims"].values()) > 0

    h = build(cluster)
    old = metrics._install_registry(Registry())
    try:
        job, plan = solve(h, k, datacenters, sharded)
        counters = metrics.snapshot()["counters"]
    finally:
        metrics._install_registry(old)

    placed = [a for allocs in plan.node_allocation.values() for a in allocs]
    victims = [a for allocs in plan.node_preemptions.values()
               for a in allocs]
    prio_of = {j.id: j.priority for j in h.state.jobs()}
    # the same number placed, the same number of victims BY TIER
    assert len(placed) == want["placed"]
    got = Counter(prio_of[v.job_id] for v in victims)
    assert dict(got) == {p: c for p, c in want["victims"].items() if c}
    # one victim a placement that needed one, and the plan says whose
    assert all(len(a.preempted_allocations) <= 1 for a in placed)
    assert sorted(v.id for v in victims) == sorted(
        vid for a in placed for vid in a.preempted_allocations)
    assert len({v.id for v in victims}) == len(victims)
    assert all(v.desired_status == "evict" for v in victims)
    # every placement and every victim on a node the job admits
    dc_of = {n.id: n.datacenter for n in h.state.nodes()}
    assert {dc_of[a.node_id] for a in placed} <= set(datacenters)
    # no node over capacity once the plan lands
    gone = {v.id for v in victims}
    for node in h.state.nodes():
        stay = [a for a in h.state.allocs_by_node_terminal(node.id, False)
                if a.id not in gone]
        cpu = sum(a.comparable_resources().cpu
                  for a in stay + plan.node_allocation.get(node.id, []))
        assert cpu <= node.resources.cpu, node.id
    # nothing evicted above the lowest tier standing: where a priority
    # gave victims, every lower preemptible one is spent on the nodes
    # the job admits
    for p in got:
        for lower in (q for q in want["victims"] if q < p):
            standing = sum(
                1 for a in h.state.allocs()
                if not a.terminal_status() and a.id not in gone
                and prio_of[a.job_id] == lower
                and dc_of[a.node_id] in datacenters)
            assert standing == 0, (p, lower, standing)
    # and the program's own account says the same
    needing = sum(1 for a in placed if a.preempted_allocations)
    assert counters["nomad.tpu.preempt.placed"] == needing
    assert counters["nomad.tpu.preempt.evicted"] == len(victims)
    assert counters.get("nomad.tpu.preempt.evicted_above_lowest", 0) == 0


def test_the_reference_itself_by_hand():
    cluster = [{"dc": "dc1", "tiers": {20: 3, 50: 5}},
               {"dc": "dc1", "tiers": {20: 1, 50: 4}},   # three free
               {"dc": "dc2", "tiers": {20: 8}},          # not admitted
               {"dc": "dc1", "tiers": {65: 8}}]          # too close
    assert reference(cluster, 2, 70, ["dc1"]) == {
        "placed": 2, "victims": {}}
    assert reference(cluster, 6, 70, ["dc1"]) == {
        "placed": 6, "victims": {20: 3}}
    assert reference(cluster, 9, 70, ["dc1"]) == {
        "placed": 9, "victims": {20: 4, 50: 2}}
    assert reference(cluster, 99, 70, ["dc1"]) == {
        "placed": 16, "victims": {20: 4, 50: 9}}
    # priority 55 may take 20 only
    assert reference(cluster, 99, 55, ["dc1"]) == {
        "placed": 7, "victims": {20: 4}}


def test_no_more_victims_than_the_shortage_needs():
    """Unequal asks: the greedy walk (lowest priority first, closest
    size within a priority) may pass a small alloc before the one that
    covers the shortage alone; the small one stays."""
    from nomad_tpu.scheduler.tpu import solve_eval_batch

    h = Harness()
    node = mock.node()
    node.reserved.cpu = 0
    node.reserved.memory_mb = 0
    h.state.upsert_node(h.next_index(), node)
    low = mock.job(id="low", priority=20)
    h.state.upsert_job(h.next_index(), low)
    allocs = []
    for cpu in (100, 1900, 2000):
        a = mock.alloc(job_=low, node_=node)
        a.resources.tasks["web"].cpu = cpu
        a.resources.tasks["web"].memory_mb = 64
        a.resources.tasks["web"].networks = []
        allocs.append(a)
    h.state.upsert_allocs(h.next_index(), allocs)
    job = mock.job(id="hi", priority=70)
    tg = job.task_groups[0]
    tg.count = 1
    tg.tasks[0].resources.cpu = 1950
    tg.tasks[0].resources.memory_mb = 64
    tg.tasks[0].resources.networks = []
    h.state.upsert_job(h.next_index(), job)
    ev = mock.eval_for_job(job)
    plan = solve_eval_batch(
        h.snapshot(), h, [ev],
        SchedulerConfig(backend="tpu", small_batch_threshold=0))[ev.id]
    victims = [a for v in plan.node_preemptions.values() for a in v]
    assert len(victims) == 1
    assert victims[0].comparable_resources().cpu >= 1950


@pytest.mark.parametrize("spread", [False, True],
                         ids=["one_group", "spread_sub_groups"])
def test_a_full_cluster_solve_that_cannot_preempt_does_not_visit_the_device(
        spread):
    """Priority 20 on a cluster full of priority 50: by the exact host
    arrays nothing can be placed, so the batch's owner fails it before
    the compact kernel is dispatched (what its batch would compile for
    is its own: PERF.md § 6, PR 27); the eval fails to place whole, as
    the kernel would have said — a spread's sub-groups as ONE task group
    judged over every node, as the relaxation retry leaves it."""
    from nomad_tpu import solverobs
    from nomad_tpu.scheduler.tpu import solve_eval_batch
    from nomad_tpu.structs import Spread

    cluster = [{"dc": dc, "tiers": {50: SLOTS}}
               for dc in ("dc1", "dc2") for _ in range(3)]
    h = build(cluster)
    job = mock.job(id="batch", priority=20)
    job.datacenters = ["dc1", "dc2"]
    if spread:
        job.spreads = [Spread(attribute="${node.datacenter}", weight=50)]
    job.task_groups[0].count = 60
    job.task_groups[0].tasks[0].resources.cpu = ASK[0]
    job.task_groups[0].tasks[0].resources.memory_mb = ASK[1]
    job.task_groups[0].tasks[0].resources.networks = []
    h.state.upsert_job(h.next_index(), job)
    ev = mock.eval_for_job(job)
    old = metrics._install_registry(Registry())
    calls0 = solverobs.snapshot()["ledger"]["kernels"].get(
        "solve_placement_compact", {}).get("calls", 0)
    try:
        plan = solve_eval_batch(
            h.snapshot(), h, [ev],
            SchedulerConfig(backend="tpu", small_batch_threshold=0))[ev.id]
        counters = metrics.snapshot()["counters"]
    finally:
        metrics._install_registry(old)
    assert not plan.node_allocation and not plan.alloc_batches
    # what the worker queues as blocked
    (tg_name, metric), = ev.failed_tg_allocs.items()
    assert tg_name == job.task_groups[0].name
    assert metric.coalesced_failures == 59
    assert (metric.nodes_evaluated, metric.nodes_filtered) == (6, 0)
    assert counters["nomad.tpu.full_cluster_solves"] >= 1
    assert solverobs.snapshot()["ledger"]["kernels"].get(
        "solve_placement_compact", {}).get("calls", 0) == calls0


def test_the_preempt_solve_names_its_own_stages():
    """`preempt.prefix` and `preempt.victims` on the solve's trace, and
    the dense readback as the compact path's two stages (`device.wait`,
    `readback`)."""
    cluster = uneven(5)
    h = build(cluster)
    trace.set_enabled(True)
    try:
        ctx = trace.start_trace("test.preempt")
        with trace.use(ctx):
            solve(h, total(cluster, 20) + 2, ["dc1", "dc2"], sharded=False)
        ctx.finish("ok")
    finally:
        trace.set_enabled(False)
    by_name = {}
    for s in ctx.spans:
        by_name.setdefault(s.name, []).append(s)
    for name in ("preempt.prefix", "device.wait", "readback",
                 "preempt.victims", "materialize"):
        assert len(by_name.get(name, ())) == 1, (name, sorted(by_name))
    assert "device.readback" not in by_name
    victims = by_name["preempt.victims"][0]
    assert victims.attrs["evicted"] == victims.attrs["placed"] > 0
    assert victims.attrs["above_lowest"] == 0
    assert set(victims.attrs["by_priority"]) == {"20", "50"}
    assert by_name["preempt.prefix"][0].attrs["tiers"] == 2
