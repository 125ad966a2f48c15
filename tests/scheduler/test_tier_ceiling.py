"""A solve carries only the tiers it may preempt (solver.py
`_lower_table`'s ceiling; lower.py `TierSlabs.read`).

The tiers of a preempt solve are the priorities at or under the highest
preemptor's priority less PRIORITY_DELTA. A band above that ceiling —
the monitoring band (70) beside a production batch (50) — is in `used`
like any held alloc and in no tier, so the batch keeps the three-tier
program of `kernels.preempt_programs()` and places and evicts exactly
what it would with that band absent.
"""

import pytest

from nomad_tpu import metrics, mock, trace
from nomad_tpu.metrics import Registry
from nomad_tpu.scheduler.tpu import solve_eval_batch, solver as solver_mod
from nomad_tpu.scheduler.tpu.kernels import pad_t, preempt_programs
from nomad_tpu.scheduler.tpu.scheduler import _reconcile_eval_batch
from nomad_tpu.scheduler.tpu.solver import BatchSolver, SolveOutcome
from nomad_tpu.structs.node_class import compute_node_class

import test_preempt_bands as bands


def with_a_monitoring_alloc(h):
    """A node the window's jobs do not admit (another kernel), in their
    datacenter, holding one priority-70 service alloc: in the node table,
    out of every placement's reach."""
    node = mock.node(datacenter="dc1")
    node.resources.cpu, node.resources.memory_mb = 16000, 32768
    node.reserved.cpu = node.reserved.memory_mb = 0
    node.attributes["kernel.name"] = "windows"
    node.attributes["platform.family"] = "B"
    node.computed_class = compute_node_class(node)
    h.state.upsert_node(h.next_index(), node)
    job = mock.job(id="monitoring", priority=70)
    job.datacenters = ["dc1", "dc2"]
    job.task_groups[0].tasks[0].resources.networks = []
    h.state.upsert_job(h.next_index(), job)
    a = mock.alloc(job_=job, node_=node)
    a.resources.tasks["web"].networks = []
    h.state.upsert_allocs(h.next_index(), [a])
    return node, a


def solved(h, pairs):
    """(plans, [(kernel signature, tiers, tiers_above)] of the solve)."""
    calls = []
    orig = solver_mod.solverobs.timed_call_verdict

    def record(name, sig, *args, **kw):
        calls.append(sig)
        return orig(name, sig, *args, **kw)

    solver_mod.solverobs.timed_call_verdict = record
    old = metrics._install_registry(Registry())
    trace.set_enabled(True)
    try:
        ctx = trace.start_trace("test.ceiling")
        with trace.use(ctx):
            plans = solve_eval_batch(
                h.snapshot(), h, [ev for _, ev in pairs], bands.TPU)
        ctx.finish("ok")
    finally:
        trace.set_enabled(False)
        metrics._install_registry(old)
        solver_mod.solverobs.timed_call_verdict = orig
    prefix = [(s.attrs["tiers"], s.attrs["tiers_above"])
              for s in ctx.spans if s.name == "preempt.prefix"]
    return plans, calls, prefix


def placed_and_evicted(plans, pairs):
    out = []
    for _, ev in pairs:
        plan = plans[ev.id]
        nodes = sorted(a.node_id for allocs in plan.node_allocation.values()
                       for a in allocs)
        for b in plan.alloc_batches:
            nodes += [nid for nid, _ti, cnt in b.touched_nodes()
                      for _ in range(cnt)]
        victims = sorted(v.id for vs in plan.node_preemptions.values()
                         for v in vs)
        out.append((sorted(nodes), victims))
    return out


def test_the_ladder_has_no_tier_eight_rung():
    assert preempt_programs() == [(8, 4), (32, 4), (128, 4), (256, 4)]
    assert pad_t(3) == 4 and pad_t(4) == 8


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
def test_a_production_batch_beside_the_monitoring_band_keeps_its_program(
        seed):
    h = bands.build(seed)
    pairs = bands.register(h, bands.BATCH)
    node, _ = with_a_monitoring_alloc(h)
    # the same batch on the same cluster, the monitoring alloc absent
    h.state.delete_evals(h.next_index(), [], [
        a.id for a in h.state.allocs_by_node_terminal(node.id, False)])
    want, _, want_prefix = solved(h, pairs)
    _, a = with_a_monitoring_alloc(h)
    got, calls, prefix = solved(h, pairs)

    # two tiers (10, 30): the batch's own band and the one above it are
    # left out, and the program is the three-tier bucket's
    assert prefix == [(2, 2)] and want_prefix == [(2, 1)]
    (sig,) = calls
    assert sig[3] == 4 and (sig[2], sig[3]) in preempt_programs()
    assert placed_and_evicted(got, pairs) == placed_and_evicted(want, pairs)
    assert any(victims for _, victims in placed_and_evicted(got, pairs))
    assert a.id not in {v.id for p in got.values()
                        for vs in p.node_preemptions.values() for v in vs}


def lowered(h, evals):
    snap = h.snapshot()
    _plans, asks = _reconcile_eval_batch(snap, h, evals, bands.TPU)
    solver = BatchSolver(snap, bands.TPU)
    kind, low = solver._lower_batch(asks, SolveOutcome())
    assert kind == "dense"
    return low


def test_the_lane_carries_the_production_band():
    h = bands.build(11)
    with_a_monitoring_alloc(h)
    job = mock.job(id="lane", priority=70)
    job.datacenters = ["dc1", "dc2"]
    job.task_groups[0].count = 12
    job.task_groups[0].tasks[0].resources.networks = []
    h.state.upsert_job(h.next_index(), job)
    low = lowered(h, [mock.eval_for_job(job)])
    assert low.table.tier_prios == [10, 30, 50]
    assert low.table.tiers_above == 1  # its own band
    assert low.tier_limit.tolist() == [3]
    assert pad_t(len(low.table.tier_prios)) == 4


def test_a_follow_up_eval_beside_production_keeps_tier_limit_zero():
    """An evicted batch job's follow-up eval (batch, 10) rides a
    production batch: it may evict nothing. A service at 30 in the same
    batch takes the prefix under it, which the ceiling kept."""
    h = bands.build(3)
    with_a_monitoring_alloc(h)
    pairs = bands.register(h, [("production", "sand", 5),
                               ("gratis", "sand", 5), ("other", "small", 5)])
    other = pairs[2][0]
    other.type = "service"
    h.state.upsert_job(h.next_index(), other)
    low = lowered(h, [ev for _, ev in pairs])
    assert low.table.tier_prios == [10, 30]
    limit = {g.job.id: int(k) for g, k in zip(low.groups, low.tier_limit)}
    assert limit == {pairs[0][0].id: 2, pairs[1][0].id: 0,
                     pairs[2][0].id: 1}
