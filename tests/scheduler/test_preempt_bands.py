"""The preempt solve as a member of the batch pipeline (PR 35): several
evals a solve, groups that may preempt beside groups that may not, three
priority bands with several to a node, six unequal asks on four machine
shapes.

Three references, none of them the solver. A plain numpy waterfill with
tiers (pass 0 into free room, then one pass a band, lowest first, each
over the whole cluster before the next opens) says how many instances of
every group are placed. The host oracle (the iterator stack with
`scheduler/preemption.py`'s Preemptor, one eval after another on
committed state) says the same where nothing is scarce. And the
benchmark's band rule (`benchmarks/reference/rules/preemption_bands.py`,
read off the store alone) holds the committed result to: no node over
its capacity, no victim above the lowest band that could have made the
room, no victim the others made unnecessary.

Then the pipeline: two batches that may preempt, one behind the other,
place what the same evals place solved one batch after another with
nothing in flight — the second waits for the first's commit
(`chain.wait`), no plan is trimmed and no follower nacked.
"""

import random
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from nomad_tpu import metrics, mock
from nomad_tpu.metrics import Registry
from nomad_tpu.scheduler.context import SchedulerConfig
from nomad_tpu.structs import Constraint
from nomad_tpu.structs.node_class import compute_node_class
from nomad_tpu.testing import Harness

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.harness import spec  # noqa: E402
from benchmarks.reference.snapshot import snapshot  # noqa: E402

bands_rule = spec.load_module(spec.RULES, "preemption_bands")
RULE_CONFIG = {"preemption": {"priority_delta": 10}}
DELTA = 10

# four of Table 1's shapes: (platform, MHz, MB)
SHAPES = [("B", 16000, 32768), ("B", 16000, 16384), ("B", 16000, 49152),
          ("C", 32000, 65536)]
ASKS = {"sand": (400, 512), "small": (800, 1024), "medium": (2000, 2048),
        "mem-heavy": (1000, 8192), "boulder": (8000, 16384),
        "platform-c": (4000, 4096)}
BANDS = {"gratis": ("batch", 10), "other": ("batch", 30),
         "production": ("service", 50)}
TPU = SchedulerConfig(backend="tpu", small_batch_threshold=0)


# -- a seeded cell of bands -------------------------------------------------

def make_job(job_id, band, ask, count):
    type_, priority = BANDS[band]
    job = mock.job(id=job_id, priority=priority)
    job.type = type_
    job.datacenters = ["dc1", "dc2"]
    tg = job.task_groups[0]
    tg.count = count
    res = tg.tasks[0].resources
    res.cpu, res.memory_mb = ASKS[ask]
    res.networks = []
    job.constraints = [Constraint("${attr.kernel.name}", "linux", "=")]
    if ask == "platform-c":
        job.constraints.append(
            Constraint("${attr.platform.family}", "C", "="))
    return job


def build(seed: int, n: int = 40, fill=(0.85, 1.0)):
    """`n` nodes of the four shapes, each filled to 85-100 % of its CPU
    with allocs of the three bands as the seed deals them: several bands
    and several asks to a node."""
    rng = random.Random(seed)
    h = Harness()
    jobs = {}
    for i in range(n):
        family, cpu, mem = SHAPES[i % len(SHAPES)]
        node = mock.node(datacenter=f"dc{1 + (i // len(SHAPES)) % 2}")
        node.resources.cpu, node.resources.memory_mb = cpu, mem
        node.resources.disk_mb = 204_800
        node.reserved.cpu = node.reserved.memory_mb = 0
        node.reserved.disk_mb = 0
        node.attributes["kernel.name"] = "linux"
        node.attributes["platform.family"] = family
        node.computed_class = compute_node_class(node)
        h.state.upsert_node(h.next_index(), node)
        free = [cpu, mem]
        stop_at = cpu * (1 - rng.uniform(*fill))
        allocs = []
        while free[0] > stop_at:
            band = rng.choice(["gratis", "gratis", "other", "production"])
            fits = [a for a, (c, m) in ASKS.items()
                    if c <= free[0] and m <= free[1]
                    and (a != "platform-c" or family == "C")]
            if not fits:
                break
            ask = rng.choice(fits)
            job = jobs.get((band, ask))
            if job is None:
                job = jobs[band, ask] = make_job(
                    f"standing-{band}-{ask}", band, ask, 0)
                h.state.upsert_job(h.next_index(), job)
            a = mock.alloc(job_=job, node_=node)
            a.name = f"{job.id}.web[{job.task_groups[0].count}]"
            job.task_groups[0].count += 1
            a.resources.tasks["web"].cpu = ASKS[ask][0]
            a.resources.tasks["web"].memory_mb = ASKS[ask][1]
            a.resources.tasks["web"].networks = []
            a.resources.shared_disk_mb = 0
            a.client_status = "running"
            allocs.append(a)
            free[0] -= ASKS[ask][0]
            free[1] -= ASKS[ask][1]
        h.state.upsert_allocs(h.next_index(), allocs)
    return h


# a batch: production services that may preempt beside batch work of the
# two lower bands that may not (an evicted job's follow-up eval is one)
BATCH = [("production", "sand", 60), ("gratis", "sand", 12),
         ("production", "boulder", 3), ("production", "small", 25),
         ("other", "medium", 4), ("production", "platform-c", 6),
         ("production", "mem-heavy", 4), ("gratis", "small", 9),
         ("production", "medium", 10)]


def register(h, batch, tag=""):
    out = []
    for i, (band, ask, count) in enumerate(batch):
        job = make_job(f"window{tag}-{i}-{band}-{ask}", band, ask, count)
        h.state.upsert_job(h.next_index(), job)
        out.append((job, mock.eval_for_job(job)))
    return out


# -- reference 1: a plain numpy waterfill with tiers -----------------------

def tables(h):
    nodes = sorted(h.state.nodes(), key=lambda n: n.id)
    cap = np.array([[n.resources.cpu, n.resources.memory_mb] for n in nodes],
                   dtype=np.int64)
    prios = sorted({j.priority for j in h.state.jobs()})
    tier = {p: np.zeros_like(cap) for p in prios}
    prio_of = {j.id: j.priority for j in h.state.jobs()}
    for i, n in enumerate(nodes):
        for a in h.state.allocs_by_node_terminal(n.id, False):
            r = a.comparable_resources()
            tier[prio_of[a.job_id]][i] += (r.cpu, r.memory_mb)
    return nodes, cap, tier


def binpack_order(cap, used, ask):
    """Best fit first, ties to the lower index: ScoreFitBinPack on cpu
    and memory after one more instance, in f32 as the program scores."""
    fr = np.float32(1) - (used + ask).astype(np.float32) / np.maximum(
        cap.astype(np.float32), np.float32(1))
    total = np.exp(fr[:, 0] * np.float32(np.log(10.0))) + np.exp(
        fr[:, 1] * np.float32(np.log(10.0)))
    score = np.clip(np.float32(20) - total, 0, 18) / np.float32(18)
    return np.argsort(-score, kind="stable")


def waterfill_with_tiers(h, batch):
    """How many instances of each job of `batch` are placed, and how
    many of them on evicted room: groups in priority order, each in
    passes — free room, then one band more a pass, lowest first."""
    nodes, cap, tier = tables(h)
    used = sum(tier.values())
    family = np.array([n.attributes["platform.family"] for n in nodes])
    prios = sorted(tier)
    freed = np.zeros_like(cap)   # evicted room already claimed, lowest first
    placed = {}
    order = sorted(range(len(batch)), key=lambda i: -BANDS[batch[i][0]][1])
    for i in order:
        band, ask_name, count = batch[i]
        type_, priority = BANDS[band]
        ask = np.array(ASKS[ask_name], dtype=np.int64)
        feas = family == "C" if ask_name == "platform-c" \
            else np.ones(len(nodes), bool)
        may = [p for p in prios
               if type_ == "service" and priority - p >= DELTA]
        left, got, evicting = count, 0, 0
        taken = np.zeros(len(nodes), dtype=np.int64)
        for k in range(len(may) + 1):
            prefix = sum((tier[p] for p in may[:k]), np.zeros_like(cap))
            room = np.maximum(prefix - freed, 0)
            free = cap - used
            units = np.where(feas, ((free + room) // ask).min(axis=1), 0)
            units = np.clip(units, 0, left)
            nodes_by_fit = binpack_order(
                cap, np.maximum(used - room, 0), ask)
            nodes_by_fit = [j for j in nodes_by_fit if units[j] > 0]
            for j in nodes_by_fit:
                take = int(min(units[j], left))
                if take <= 0:
                    break
                claimed = take * ask
                over = np.maximum(claimed - np.maximum(cap[j] - used[j], 0), 0)
                hit = np.minimum(over, room[j])
                freed[j] += hit
                used[j] += claimed - hit
                left -= take
                got += take
                evicting += take if k else 0
        placed[i] = (got, evicting)
    return placed


# -- the program ------------------------------------------------------------

def solve_and_commit(h, pairs, config=TPU):
    from nomad_tpu.scheduler.tpu import solve_eval_batch

    plans = solve_eval_batch(
        h.snapshot(), h, [ev for _, ev in pairs], config)
    for _, ev in pairs:
        if not plans[ev.id].is_no_op():
            h.submit_plan(plans[ev.id])
    return plans


def count_placed(plan) -> int:
    return sum(len(a) for a in plan.node_allocation.values()) + sum(
        len(b) for b in plan.alloc_batches)


def expected_of(h) -> dict:
    out = {}
    for j in h.state.jobs():
        res = j.task_groups[0].tasks[0].resources
        out[j.id] = (j.task_groups[0].count, {
            "cpu_mhz": res.cpu, "memory_mb": res.memory_mb,
            "disk_mb": j.task_groups[0].ephemeral_disk.size_mb})
    return out


@pytest.mark.parametrize("seed", [7, 11, 3_000_000_019])
def test_a_mixed_batch_on_banded_shapes_agrees_with_the_plain_waterfill(seed):
    h = build(seed)
    want = waterfill_with_tiers(h, BATCH)
    pairs = register(h, BATCH)
    before = {a.id for a in h.state.allocs() if not a.terminal_status()}
    old = metrics._install_registry(Registry())
    try:
        plans = solve_and_commit(h, pairs)
        counters = metrics.snapshot()["counters"]
    finally:
        metrics._install_registry(old)

    # several evals in ONE preempt solve, groups of both kinds in it
    assert counters["nomad.tpu.preempt.chain_offered"] == 1
    for i, (job, ev) in enumerate(pairs):
        plan = plans[ev.id]
        got = count_placed(plan)
        assert got == want[i][0], (BATCH[i], got, want[i])
        victims = [v for vs in plan.node_preemptions.values() for v in vs]
        if BATCH[i][0] != "production":
            assert not victims, BATCH[i]  # batch work evicts nothing
    # the reference had something to say: room ran out and bands went
    assert sum(e for _, e in want.values()) > 0
    placed = counters["nomad.tpu.preempt.placements"]
    assert placed == sum(g for g, _ in want.values())
    assert 0 < counters["nomad.tpu.preempt.placed"] <= sum(
        e for _, e in want.values())

    # the committed result, read off the store alone
    snap = snapshot(h.state)
    by_node = Counter()
    for a in snap["allocs"]:
        by_node[a["node"], "cpu"] += a["cpu"]
        by_node[a["node"], "mem"] += a["mem"]
    for n in snap["nodes"]:
        assert by_node[n["id"], "cpu"] <= n["cpu"], n["id"]
        assert by_node[n["id"], "mem"] <= n["mem"], n["id"]
    assert bands_rule.check(snap, expected_of(h), RULE_CONFIG) == []
    # lowest band first, by count too: nothing of band `other` went
    # while the gratis band could still give a whole ask somewhere
    prio_of = {jid: j["priority"] for jid, j in snap["jobs"].items()}
    gone = [t for t in snap["terminal_allocs"]
            if t["desired_status"] == "evict"]
    assert gone and all(t["id"] in before for t in gone)
    assert {prio_of[t["job"]] for t in gone} <= {10, 30}
    # every victim names a live preemptor that names it back
    live = {a.id: a for a in h.state.allocs() if not a.terminal_status()}
    for t in gone:
        assert t["id"] in live[
            t["preempted_by_allocation"]].preempted_allocations


@pytest.mark.parametrize("seed", [7, 11, 3_000_000_019])
def test_an_eviction_stands_in_its_preemptors_plan_alone(seed):
    """Every eval of a solve is a plan of its own, and a placement may
    stand on room ANOTHER eval's whole victim left over. The victim is
    listed in the plan that holds its preemptor and in no other — a plan
    of batch work lists none — so nothing is evicted for a placement
    that was refused; what lets the other plan fit is the applier's
    order (tests/test_plan_apply_batch.py)."""
    from nomad_tpu.scheduler.tpu import solve_eval_batch

    h = build(seed, fill=(0.93, 1.0))
    pairs = register(h, BATCH)
    plans = solve_eval_batch(
        h.snapshot(), h, [ev for _, ev in pairs], TPU)
    listed = Counter()
    for (band, _, _), (_, ev) in zip(BATCH, pairs):
        plan = plans[ev.id]
        mine = {a.id for allocs in plan.node_allocation.values()
                for a in allocs}
        for node_id, victims in plan.node_preemptions.items():
            assert band == "production"
            for v in victims:
                listed[v.id] += 1
                assert v.preempted_by_allocation in mine
                by = next(a for a in plan.node_allocation[node_id]
                          if a.id == v.preempted_by_allocation)
                assert v.id in by.preempted_allocations
    assert listed and set(listed.values()) == {1}
    # some plan does stand on another's leftover: on the store as it is,
    # each by itself, the plans of this batch do not all fit ...
    from nomad_tpu.server.plan_apply import PlanApplier, evaluate_plan
    from nomad_tpu.server.plan_queue import PlanQueue
    from nomad_tpu.server.raft import FSM, InmemLog

    order = [plans[ev.id] for _, ev in sorted(
        pairs, key=lambda p: -p[0].priority) if not plans[ev.id].is_no_op()]
    snap = h.snapshot()
    assert not all(evaluate_plan(snap, p).full_commit(p)[0] for p in order)
    # ... and in the order the worker submits them, each on the results
    # of those before it, they all do, in one raft entry
    log = InmemLog(FSM(h.state), start_index=h.state.latest_index())
    results = PlanApplier(
        PlanQueue(), h.state, log.apply, log.apply_async).apply_batch(order)
    assert all(r.full_commit(p)[0] for p, r in zip(order, results))
    assert len({r.alloc_index for r in results}) == 1
    for n in h.state.nodes():
        used = h.state.node_usage(n.id)
        assert used[0] <= n.resources.cpu and used[1] <= n.resources.memory_mb


def test_the_waterfill_reference_by_hand():
    """One B machine full of 40 gratis sands, one full of 20 `other`
    smalls. Production asks 3 boulders: two from the gratis machine (20
    sands each), the third opens the `other` band (10 smalls)."""
    h = Harness()
    for k, (band, ask, count) in enumerate(
            [("gratis", "sand", 40), ("other", "small", 20)]):
        node = mock.node(datacenter="dc1")
        node.resources.cpu, node.resources.memory_mb = 16000, 32768
        node.reserved.cpu = node.reserved.memory_mb = 0
        node.attributes["platform.family"] = "B"
        h.state.upsert_node(h.next_index(), node)
        job = make_job(f"s-{band}", band, ask, count)
        h.state.upsert_job(h.next_index(), job)
        allocs = []
        for _ in range(count):
            a = mock.alloc(job_=job, node_=node)
            a.resources.tasks["web"].cpu = ASKS[ask][0]
            a.resources.tasks["web"].memory_mb = ASKS[ask][1]
            allocs.append(a)
        h.state.upsert_allocs(h.next_index(), allocs)
    assert waterfill_with_tiers(h, [("production", "boulder", 2)]) == {
        0: (2, 2)}
    assert waterfill_with_tiers(h, [("production", "boulder", 3)]) == {
        0: (3, 3)}
    # two machines hold four boulders whatever is evicted
    assert waterfill_with_tiers(h, [("production", "boulder", 9)]) == {
        0: (4, 4)}
    assert waterfill_with_tiers(h, [("production", "medium", 12)]) == {
        0: (12, 12)}
    assert waterfill_with_tiers(h, [("gratis", "sand", 5)]) == {0: (0, 0)}
    assert waterfill_with_tiers(h, [("other", "sand", 5)]) == {0: (0, 0)}


# -- reference 2: the host oracle, one eval after another -------------------

@pytest.mark.parametrize("seed", [7, 3_000_000_019])
def test_where_nothing_is_scarce_the_host_oracle_places_the_same(seed):
    """A batch the cell can hold whole, by evicting gratis work alone:
    the kernel batch and the host iterator stack (Preemptor), which
    solves each eval on the state the last one committed, place every
    instance, evict nothing of their own band, and leave no node over."""
    batch = [("production", "sand", 30), ("production", "small", 10),
             ("production", "boulder", 1), ("production", "medium", 5),
             ("production", "platform-c", 2)]
    results = {}
    for path in ("kernel", "host"):
        h = build(seed, fill=(0.9, 0.97))
        pairs = register(h, batch)
        if path == "kernel":
            plans = solve_and_commit(h, pairs)
            placed = [count_placed(plans[ev.id]) for _, ev in pairs]
        else:
            placed = []
            order = sorted(pairs, key=lambda p: -p[0].priority)
            for job, ev in order:
                n0 = len(h.plans)
                h.process(job.type, ev, SchedulerConfig())
                placed.append(sum(count_placed(p) for p in h.plans[n0:]))
            placed = [placed[order.index(p)] for p in pairs]
        snap = snapshot(h.state)
        prio_of = {jid: j["priority"] for jid, j in snap["jobs"].items()}
        results[path] = (placed, {
            prio_of[t["job"]] for t in snap["terminal_allocs"]
            if t["desired_status"] == "evict"})
        used = Counter()
        for a in snap["allocs"]:
            used[a["node"]] += a["cpu"]
        assert all(used[n["id"]] <= n["cpu"] for n in snap["nodes"]), path
    assert results["kernel"][0] == results["host"][0] == [
        count for _, _, count in batch]
    # the kernel takes the lowest band cluster-wide; the oracle scores a
    # shuffled sample of nodes (upstream's limit iterator) and may meet a
    # node whose lowest band is `other`
    assert results["kernel"][1] == {10} and results["host"][1] <= {10, 30}


# -- the pipeline -----------------------------------------------------------

def run_two_batches(pipeline: bool, seed: int = 5):
    """A served cell — real broker, worker, applier — that drains six
    production jobs as two batches of three, one behind the other."""
    from nomad_tpu.server import Server

    s = Server(use_tpu_batch_worker=True, scheduler_config=SchedulerConfig(
        backend="tpu", small_batch_threshold=0))
    s.establish_leadership()
    try:
        w = s.tpu_worker
        w.stop()
        src = build(seed, n=24, fill=(0.93, 1.0))
        for node in sorted(src.state.nodes(), key=lambda n: n.id):
            s.node_register(node)
        # the standing bands, written to the store as the source has them
        for job in src.state.jobs():
            s.state.upsert_job(src.next_index(), job)
        s.state.upsert_allocs(src.next_index(), list(src.state.allocs()))
        wave = [("production", "sand", 40), ("production", "small", 15),
                ("production", "medium", 6), ("production", "sand", 30),
                ("production", "boulder", 2), ("production", "small", 12)]
        jobs = []
        for i, (band, ask, count) in enumerate(wave):
            job = make_job(f"wave-{i}-{ask}", band, ask, count)
            s.job_register(job)
            jobs.append(job)
        assert s.eval_broker.ready_count() == len(wave)
        w.batch_size = 3
        w.pipeline = pipeline
        w.start()
        assert s.wait_for_evals(60)
        held = {}
        for job in jobs:
            held[job.id] = sum(
                1 for a in s.state.allocs_by_job(job.namespace, job.id)
                if not a.terminal_status())
        evicted = sum(1 for a in s.state.allocs()
                      if a.desired_status == "evict")
        return held, evicted
    finally:
        s.shutdown()


def test_two_preempt_batches_one_behind_the_other_place_what_a_sequence_does():
    reg = Registry()
    old = metrics._install_registry(reg)
    try:
        piped, piped_evicted = run_two_batches(pipeline=True)
        counters = metrics.snapshot()["counters"]
        trimmed = reg.histogram_raw("nomad.worker.batch.trimmed")
        batches = reg.histogram_raw("nomad.tpu.batch_evals")
    finally:
        metrics._install_registry(old)
    old = metrics._install_registry(Registry())
    try:
        serial, serial_evicted = run_two_batches(pipeline=False)
    finally:
        metrics._install_registry(old)
    # (which allocs go is a tie among equals that alloc ids break, and
    # the two cells' ids differ: the counts of victims are not compared)
    assert piped == serial and piped_evicted > 0 and serial_evicted > 0
    assert all(n > 0 for n in piped.values())
    # two batches of three, the second made to wait for the first
    assert batches["count"] >= 2 and batches["max"] == 3
    assert counters["nomad.worker.chain.waited"] >= 1
    assert trimmed is None or trimmed["count"] == 0
    assert counters.get("nomad.tpu.chain_parent_failed", 0) == 0
    assert counters["nomad.tpu.preempt.chain_offered"] >= 2


# -- the host stack on a full cell ------------------------------------------

def test_a_task_group_that_failed_once_is_not_walked_again(monkeypatch):
    """An evicted job's follow-up eval that asks a few allocs of a full
    cell takes the host stack, and every walk of a full cell visits
    every node: after the first failure the group's other requests are
    coalesced, not walked (48 walks of 12,583 machines held the solve
    thread 18 s after a window, PERF.md section 6, PR 35)."""
    from nomad_tpu.scheduler import stack as stack_mod
    from nomad_tpu.scheduler.tpu import solve_eval_batch

    h = Harness()
    job = make_job("full", "production", "medium", 8)
    h.state.upsert_job(h.next_index(), job)
    for _ in range(6):
        node = mock.node(datacenter="dc1")
        node.resources.cpu, node.resources.memory_mb = 16000, 16384
        node.reserved.cpu = node.reserved.memory_mb = 0
        node.attributes["kernel.name"] = "linux"
        h.state.upsert_node(h.next_index(), node)
        allocs = []
        for _ in range(8):  # eight medium fill it in both dimensions
            a = mock.alloc(job_=job, node_=node)
            a.resources.tasks["web"].cpu = 2000
            a.resources.tasks["web"].memory_mb = 2048
            allocs.append(a)
        h.state.upsert_allocs(h.next_index(), allocs)
    walks = []
    select = stack_mod.GenericStack.select

    def counted(self, tg, **kw):
        walks.append(kw.get("evict", False))
        return select(self, tg, **kw)

    monkeypatch.setattr(stack_mod.GenericStack, "select", counted)
    again = make_job("follow-up", "gratis", "sand", 12)
    h.state.upsert_job(h.next_index(), again)
    ev = mock.eval_for_job(again)
    plan = solve_eval_batch(
        h.snapshot(), h, [ev], SchedulerConfig(micro_solve_threshold=0))[ev.id]
    assert count_placed(plan) == 0
    assert walks == [False]  # one walk for twelve requests; batch: no evict
