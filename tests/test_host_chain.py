"""The host paths chain on a batch in flight, and offer their own.

The pipelined worker solves batch N+1 while batch N commits. N+1 chains
on what N offers (`PendingEvalBatch.chain`, a `solver.UsageChain`): the
kernel's used' tensor, the microsolve's used' rows, or a host-stack
batch's rows: the chain's it read, or the committed usage of its own
snapshot, with its placements added. So two small deploys solved beside
each other place as if the first had committed before the second was
solved — and a third beside the second, after the first committed,
counts the first once.

Held here on the CPU at small size:

- against `benchmarks/reference/serial_place.py`, a plain serial placer
  written without the solver: a microsolve or kernel batch chained on a
  microsolve or kernel batch in flight gives exactly what placing the
  two asks one after the other gives, on seeded random capacity and
  usage;
- a host-stack batch, whose rule is the iterator stack's and not the
  reference's, gives beside the batch in flight exactly what it gives
  after that batch's commit (the same seed draws the same sample), and
  every batch chained on a host-stack batch does too;
- three hops, two host-stack batches and a child, the first committed
  before the child's snapshot: a microsolve or kernel child places as
  the reference does on the usage the first two left, a host-stack
  child as it does after both commits;
- the worker: a kernel batch behind a microsolve batch in flight chains
  and never enters `chain.wait`; a chained host-path child of a trimmed
  parent takes the cascade (nacked, `nomad.tpu.chain_parent_failed`).
"""

import random
import threading

import numpy as np
import pytest

from nomad_tpu import metrics, mock
from nomad_tpu.metrics import Registry
from nomad_tpu.scheduler.context import SchedulerConfig
from nomad_tpu.scheduler.tpu import ResidentClusterState, solve_eval_batch_begin
from nomad_tpu.scheduler.tpu.solver import UsageChain
from nomad_tpu.server.worker import TPUBatchWorker
from nomad_tpu.structs import AllocatedResources, AllocatedTaskResources
from nomad_tpu.testing import Harness

from benchmarks.reference import serial_place

N_NODES = 24
CONFIGS = {
    "micro": SchedulerConfig(),
    "kernel": SchedulerConfig(small_batch_threshold=0),
    "host": SchedulerConfig(micro_solve_threshold=0),
}
SEEDS = [3, 11, 2**31 + 7]


@pytest.fixture
def registry():
    """A fresh registry, capturing raw observations: (registry, capture)."""
    old = metrics._install_registry(Registry())
    reg = metrics.registry()
    capture = reg.enable_timing_capture(cap=1 << 12)
    yield reg, capture
    reg.disable_timing_capture(capture)
    metrics._install_registry(old)


def cluster(seed: int, dcs=("dc1",)):
    """N_NODES nodes of seeded random capacity, dealt over `dcs`, each
    holding 0-3 standing allocs of seeded random size. Returns the
    harness and, by node id in table order, the capacity and the usage
    the allocs add up to."""
    rng = random.Random(seed)
    h = Harness()
    standing = mock.job(id=f"standing-{seed}", datacenters=list(dcs))
    h.state.upsert_job(h.next_index(), standing)
    cap, used = {}, {}
    for i in range(N_NODES):
        node = mock.node(datacenter=dcs[i % len(dcs)])
        node.id = node.name = f"hc-{seed}-{i:03d}"
        node.resources.cpu = rng.randrange(2_000, 8_001)
        node.resources.memory_mb = rng.randrange(2_048, 16_385)
        h.state.upsert_node(h.next_index(), node)
        avail = node.available_resources()
        cap[node.id] = [avail.cpu, avail.memory_mb, avail.disk_mb]
        u = [0, 0, 0]
        allocs = []
        for k in range(rng.randrange(0, 4)):
            cpu = rng.randrange(100, avail.cpu // 4)
            mem = rng.randrange(64, avail.memory_mb // 4)
            a = mock.alloc(standing, node, index=i * 4 + k)
            a.resources = AllocatedResources(
                tasks={"web": AllocatedTaskResources(cpu=cpu, memory_mb=mem)},
                shared_disk_mb=300,
            )
            a.client_status = "running"
            allocs.append(a)
            u = [u[0] + cpu, u[1] + mem, u[2] + 300]
        if allocs:
            h.state.upsert_allocs(h.next_index(), allocs)
        used[node.id] = u
    return h, cap, used


def deploy(h: Harness, name: str, count: int, cpu: int, mem: int,
           dcs=("dc1",)):
    job = mock.job(id=name, datacenters=list(dcs))
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.cpu = cpu
    tg.tasks[0].resources.memory_mb = mem
    tg.tasks[0].resources.networks = []
    h.state.upsert_job(h.next_index(), job)
    r = tg.combined_resources()
    return mock.eval_for_job(job), (r.cpu, r.memory_mb, r.disk_mb), count


def asks(seed: int, n: int = 2):
    """`n` deploys of seeded size, each within the small-batch
    threshold: which path takes one is its configuration's choice."""
    rng = random.Random(seed ^ 0x5EED)
    out = []
    for k in range(n):
        count = rng.randrange(6, 13)
        out.append((f"d{k}-{seed}", count, rng.randrange(200, 900),
                    rng.randrange(128, 1_024)))
    return out


def nodes_of(plan) -> list[str]:
    got = [a.node_id for allocs in plan.node_allocation.values()
           for a in allocs]
    for b in plan.alloc_batches:
        got += [a.node_id for a in b.materialize()]
    return sorted(got)


def run(seed: int, parent: str, child: str, chained: bool):
    """Solve the two deploys on `parent`'s and `child`'s path: the child
    beside the parent in flight (chained), or after its commit."""
    h, cap, used = cluster(seed)
    resident = ResidentClusterState()
    evs = [deploy(h, *a) for a in asks(seed)]
    random.seed(seed)  # the host stack's sample
    pa = solve_eval_batch_begin(h.snapshot(), h, [evs[0][0]],
                                CONFIGS[parent], resident=resident)
    if chained:
        pb = solve_eval_batch_begin(h.snapshot(), h, [evs[1][0]],
                                    CONFIGS[child], resident=resident,
                                    used_chain=pa.chain)
        assert pb.chain_accepted, (parent, child)
        plan_a = pa.finish()[evs[0][0].id]
        h.submit_plan(plan_a)
    else:
        plan_a = pa.finish()[evs[0][0].id]
        h.submit_plan(plan_a)
        pb = solve_eval_batch_begin(h.snapshot(), h, [evs[1][0]],
                                    CONFIGS[child], resident=resident)
        assert not pb.chain_accepted
    plan_b = pb.finish()[evs[1][0].id]
    h.submit_plan(plan_b)
    # every node within its capacity, read from the store
    for nid, c in cap.items():
        u = [0, 0, 0]
        for a in h.state.allocs_by_node_terminal(nid, False):
            r = a.comparable_resources()
            u = [u[0] + r.cpu, u[1] + r.memory_mb, u[2] + r.disk_mb]
        assert all(u[r] <= c[r] for r in range(3)), (nid, u, c)
    return (nodes_of(plan_a), nodes_of(plan_b)), (cap, used, evs)


RULE_PATHS = [(p, c) for p in ("micro", "kernel") for c in ("micro", "kernel")]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("parent, child", RULE_PATHS)
def test_a_chained_child_places_as_the_serial_reference_does(
        seed, parent, child):
    (got_a, got_b), (cap, used, evs) = run(seed, parent, child, True)
    ids = list(cap)
    want, _ = serial_place.place(
        [cap[i] for i in ids], [used[i] for i in ids],
        [(ask, count) for _, ask, count in evs])
    assert got_a == sorted(ids[i] for i in want[0])
    assert got_b == sorted(ids[i] for i in want[1])
    # every instance placed: the cluster has the room
    assert (len(got_a), len(got_b)) == (evs[0][2], evs[1][2])


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("parent, child", [
    ("micro", "host"), ("kernel", "host"), ("host", "host"),
    ("host", "micro"), ("host", "kernel"),
])
def test_a_host_stack_batch_beside_one_in_flight_places_as_after_its_commit(
        seed, parent, child):
    chained, _ = run(seed, parent, child, True)
    serial, _ = run(seed, parent, child, False)
    assert chained == serial


def run3(seed: int, paths, chained: bool, dcs=(("dc1",),) * 3):
    """Three deploys, on `paths` and over the datacenter sets `dcs`.
    Chained: the second beside the first in flight; the first commits;
    the third beside the second in flight, so its snapshot holds the
    first's placements and its chain holds them too, under the second's.
    Serial: each after the commit of the one before."""
    h, cap, used = cluster(seed, tuple(sorted({d for ds in dcs for d in ds})))
    resident = ResidentClusterState()
    evs = [deploy(h, *a, dcs=d) for a, d in zip(asks(seed, 3), dcs)]
    random.seed(seed)  # the host stack's sample

    def begin(k, chain=None):
        return solve_eval_batch_begin(h.snapshot(), h, [evs[k][0]],
                                      CONFIGS[paths[k]], resident=resident,
                                      used_chain=chain)

    def commit(k, pending):
        plan = pending.finish()[evs[k][0].id]
        h.submit_plan(plan)
        return nodes_of(plan)

    p1 = begin(0)
    if chained:
        p2 = begin(1, p1.chain)
        got1 = commit(0, p1)
        p3 = begin(2, p2.chain)
        assert p2.chain_accepted and p3.chain_accepted
        got2 = commit(1, p2)
    else:
        got1 = commit(0, p1)
        got2 = commit(1, begin(1))
        p3 = begin(2)
    return (got1, got2, commit(2, p3)), (cap, used, evs)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("child", ["micro", "kernel", "host"])
def test_a_grandparent_committed_under_the_chain_is_counted_once(
        seed, child):
    paths = ("host", "host", child)
    chained, (cap, used, evs) = run3(seed, paths, True)
    assert chained == run3(seed, paths, False)[0]
    if child == "host":
        return
    # the reference, on the usage the two host-stack batches left
    ids = list(cap)
    at = {nid: i for i, nid in enumerate(ids)}
    left = [list(used[nid]) for nid in ids]
    for k in (0, 1):
        ask = evs[k][1]
        for nid in chained[k]:
            left[at[nid]] = [left[at[nid]][r] + ask[r] for r in range(3)]
    want, _ = serial_place.place([cap[i] for i in ids], left,
                                 [(evs[2][1], evs[2][2])])
    assert chained[2] == sorted(ids[i] for i in want[0])


@pytest.mark.parametrize("seed", SEEDS)
def test_a_host_stack_batch_over_a_wider_universe_carries_the_chain_over(
        seed):
    """A microsolve batch over dc1, a host-stack batch over dc1 and dc2
    beside it, then a kernel batch over both beside that: the host
    stack's rows hold the first batch's row on dc1 and its own snapshot's
    committed usage on dc2, each with what it placed."""
    paths = ("micro", "host", "kernel")
    dcs = (("dc1",), ("dc1", "dc2"), ("dc1", "dc2"))
    chained, _ = run3(seed, paths, True, dcs)
    assert chained == run3(seed, paths, False, dcs)[0]
    # the host stack placed in dc2 too (nodes are dealt dc1, dc2, ...)
    assert any(int(nid[-3:]) % 2 for nid in chained[1])


def test_a_blind_child_would_have_collided():
    """The control: without the chain the deterministic bin-pack tops up
    the node the parent just filled, and the two overlap."""
    for seed in SEEDS:
        h, cap, used = cluster(seed)
        evs = [deploy(h, *a) for a in asks(seed)]
        pa = solve_eval_batch_begin(h.snapshot(), h, [evs[0][0]],
                                    CONFIGS["micro"])
        pb = solve_eval_batch_begin(h.snapshot(), h, [evs[1][0]],
                                    CONFIGS["micro"])
        a = set(nodes_of(pa.finish()[evs[0][0].id]))
        b = set(nodes_of(pb.finish()[evs[1][0].id]))
        if a & b:
            return
    pytest.fail("no seed makes the blind solves share a node")


def test_what_each_path_offers(registry):
    h, _, _ = cluster(5)
    evs = [deploy(h, f"o{k}", 4, 300, 256) for k in range(3)]
    micro = solve_eval_batch_begin(h.snapshot(), h, [evs[0][0]],
                                   CONFIGS["micro"])
    assert micro.used_micro and isinstance(micro.chain, UsageChain)
    assert isinstance(micro.chain.used, np.ndarray)  # rows on the host
    host = solve_eval_batch_begin(h.snapshot(), h, [evs[1][0]],
                                  CONFIGS["host"], used_chain=micro.chain)
    # the host stack offers the rows it read with its placements added
    placed = host.finish()[evs[1][0].id]
    assert host.chain.node_ids == micro.chain.node_ids
    want = micro.chain.used.astype(np.int64)
    for nid in nodes_of(placed):
        want[micro.chain.index_of[nid]] += evs[1][1]
    assert (host.chain.used == want).all()
    # with nothing in flight: the committed usage of its own snapshot
    alone = solve_eval_batch_begin(h.snapshot(), h, [evs[2][0]],
                                   CONFIGS["host"])
    snap = h.snapshot()
    placed = alone.finish()[evs[2][0].id]
    rows = alone.chain.used
    for nid, i in alone.chain.index_of.items():
        u = snap.node_usage(nid)[:3]
        u = [u[r] + evs[2][1][r] * nodes_of(placed).count(nid)
             for r in range(3)]
        assert list(rows[i]) == u, nid
    reg, capture = registry
    timings = reg.drain_timings(capture)
    assert len(timings["nomad.tpu.host_chain_offered"]) == 3
    assert len(timings["nomad.tpu.host_chain_consumed"]) == 1


# -- the worker ---------------------------------------------------------------

class _NoLane:
    def __init__(self):
        self.nacked = []

    def dequeue_ready(self, schedulers, timeout_s=None, min_priority=0):
        return None, "", 0

    def nack(self, eval_id, token):
        self.nacked.append(eval_id)


class _PlanQueue:
    def depth(self):
        return 0


class _Srv:
    def __init__(self, state):
        self.state = state
        self.eval_broker = _NoLane()
        self.plan_queue = _PlanQueue()


def test_a_kernel_batch_behind_a_microsolve_batch_chains_and_never_waits(
        registry):
    h, cap, used = cluster(17)
    ids = list(cap)
    w = TPUBatchWorker(_Srv(h.state), pipeline=True, lane_priority=0)
    ev_a, ask_a, n_a = deploy(h, "small", 10, 400, 512)
    ev_b, ask_b, n_b = deploy(h, "rollout", 60, 150, 128)
    pend_a, snap_a, on_a = w._solve_batch([ev_a])
    assert pend_a.used_micro and on_a is None
    committed = threading.Event()  # the micro batch's commit is pending
    w._prev = (pend_a, committed, {"ok": None}, snap_a.index)
    pend_b, _, on_b = w._solve_batch([ev_b])
    assert not pend_b.used_micro and pend_b.chain_accepted
    assert on_b is not None and on_b[1] == snap_a.index
    counters = metrics.snapshot()["counters"]
    assert counters.get("nomad.worker.chain.waited", 0) == 0
    got_a = nodes_of(pend_a.finish()[ev_a.id])
    got_b = nodes_of(pend_b.finish()[ev_b.id])
    want, _ = serial_place.place(
        [cap[i] for i in ids], [used[i] for i in ids],
        [(ask_a, n_a), (ask_b, n_b)])
    assert got_a == sorted(ids[i] for i in want[0])
    assert got_b == sorted(ids[i] for i in want[1])


@pytest.mark.parametrize("child", ["micro", "host"])
def test_a_chained_host_path_child_of_a_trimmed_parent_is_nacked(
        registry, child):
    h, _, _ = cluster(23)
    w = TPUBatchWorker(_Srv(h.state), pipeline=True, lane_priority=0,
                       config=SchedulerConfig(
                           backend="tpu",
                           micro_solve_threshold=(
                               0 if child == "host" else 8192)))
    ev_a, _, _ = deploy(h, "parent", 8, 400, 512)
    ev_b, _, _ = deploy(h, "child", 8, 400, 512)
    pend_a, snap_a, _ = w._solve_batch([ev_a])
    parent_outcome = {"ok": None}
    w._prev = (pend_a, threading.Event(), parent_outcome, snap_a.index)
    pend_b, snap_b, on_b = w._solve_batch([ev_b])
    assert on_b is not None and on_b[0] is parent_outcome
    parent_outcome["ok"] = False  # the applier trimmed the parent
    outcome, done = {"ok": None}, threading.Event()
    w._commit([(ev_b, "tok")], pend_b, snap_b, done, outcome, on_b)
    assert outcome["ok"] is False and done.is_set()
    assert w.server.eval_broker.nacked == [ev_b.id]
    counters = metrics.snapshot()["counters"]
    assert counters["nomad.tpu.chain_parent_failed"] == 1
