"""A property set counts its job's allocs through the job's index
(scheduler/propertyset.py `_compute_existing`; reference propertyset.go
populateExisting → AllocsByJob), not by a walk of every alloc the store
holds: a spread eval's first `select` cost 6 ms on a store of ~9,000
allocs and 16 ms on one of ~26,000 (PERF.md section 6, PR 36). The
counts are those of the walk it replaces. No sleeps, no clock.
"""

import random

import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.context import EvalContext, SchedulerConfig
from nomad_tpu.scheduler.propertyset import PropertySet
from nomad_tpu.scheduler.tpu import solve_eval_batch
from nomad_tpu.structs import Spread
from nomad_tpu.testing import Harness

DCS = ["dc1", "dc2", "dc3"]


def store_of_three_jobs(seed: int):
    """Nodes over three datacenters; the job under test with two task
    groups' worth of allocs, some terminal; a neighbour job and a job of
    the same id in another namespace beside it."""
    rng = random.Random(seed)
    h = Harness()
    nodes = []
    for i in range(30):
        node = mock.node(datacenter=DCS[i % 3])
        h.state.upsert_node(h.next_index(), node)
        nodes.append(node)
    job = mock.job(id="under-test")
    neighbour = mock.job(id="neighbour")
    twin = mock.job(id="under-test", namespace="other")
    allocs = []
    for j, n_allocs in ((job, 40), (neighbour, 60), (twin, 25)):
        h.state.upsert_job(h.next_index(), j)
        for i in range(n_allocs):
            a = mock.alloc(job_=j, node_=rng.choice(nodes), index=i)
            if rng.random() < 0.3:
                a.task_group = "second"
            if rng.random() < 0.2:
                a.desired_status = "stop"
                a.client_status = "complete"
            allocs.append(a)
    h.state.upsert_allocs(h.next_index(), allocs)
    return h, job, twin


def by_walk(state, job, tg_name: str) -> dict:
    """The counts as the walk of every alloc gave them."""
    counts: dict = {}
    for a in state.allocs():
        if a.terminal_status() or a.job_id != job.id \
                or a.namespace != job.namespace:
            continue
        if tg_name and a.task_group != tg_name:
            continue
        dc = state.node_by_id(a.node_id).datacenter
        counts[dc] = counts.get(dc, 0) + 1
    return counts


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("tg_name", ["", "web", "second"])
@pytest.mark.parametrize("which", ["job", "twin"])
def test_counts_equal_the_walk_of_every_alloc(seed, tg_name, which):
    h, job, twin = store_of_three_jobs(seed)
    subject = job if which == "job" else twin
    snap = h.snapshot()
    pset = PropertySet(EvalContext(snap, None, None, SchedulerConfig()),
                       subject)
    pset.set_target_attribute("${node.datacenter}", tg_name)
    want = by_walk(snap, subject, tg_name)
    assert pset.used_counts() == want
    assert sum(want.values()) > 0


def test_a_spread_deploy_never_reads_the_whole_allocs_table(monkeypatch):
    h, _, _ = store_of_three_jobs(7)
    job = mock.job(datacenters=list(DCS))
    tg = job.task_groups[0]
    tg.count = 6
    tg.tasks[0].resources.networks = []
    tg.spreads = [Spread(attribute="${node.datacenter}", weight=50)]
    h.state.upsert_job(h.next_index(), job)
    ev = mock.eval_for_job(job)
    snap = h.snapshot()

    def no_walk():
        raise AssertionError("the whole allocs table was read")

    monkeypatch.setattr(snap, "allocs", no_walk)
    random.seed(36)
    plan = solve_eval_batch(
        snap, h, [ev], SchedulerConfig(micro_solve_threshold=0))[ev.id]
    placed = [a for allocs in plan.node_allocation.values() for a in allocs]
    per_dc = {}
    for a in placed:
        dc = snap.node_by_id(a.node_id).datacenter
        per_dc[dc] = per_dc.get(dc, 0) + 1
    # the spread scored (a boost, not a rule: a walk of five nodes may
    # miss a datacenter), and all six were placed
    assert sum(per_dc.values()) == 6 and len(per_dc) >= 2
