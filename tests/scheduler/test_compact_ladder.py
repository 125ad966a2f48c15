"""The compact solve's closed set of programs (PR 32), on a small Borg
cell: 96 machines of the ten shapes of Reiss et al.'s Table 1 in its
proportions (every shape at least once), part full, taking seeded
batches of jobs that differ — six asks, one constrained to a platform,
one spread over datacenters, counts from 1 to 600.

(i)   every compact solve lands in a program of `compact_programs()`,
      and after a warm pass of single-class batches a mixed stream meets
      no new one (`nomad.tpu.compact.programs_new` stays silent);
(ii)  the laddered program places what the parent's exact-shape program
      placed, element for element;
(iii) the kernel path against the host iterator stack on the same
      snapshot: feasible by shape, ask and platform, nothing over any
      node's own capacity, the same number placed.
"""

import random
from collections import Counter

import numpy as np
import pytest

from nomad_tpu import metrics, mock, solverobs
from nomad_tpu.metrics import Registry
from nomad_tpu.scheduler.context import SchedulerConfig
from nomad_tpu.scheduler.tpu import kernels, solve_eval_batch
from nomad_tpu.scheduler.tpu import solver as solver_mod
from nomad_tpu.structs import Constraint, Spread
from nomad_tpu.structs.node_class import compute_node_class
from nomad_tpu.testing import Harness

DCS = ["dc1", "dc2", "dc3", "dc4"]
# Reiss et al., SoCC 2012, Table 1: machines, platform, CPU, memory
TABLE_1 = [(6732, "B", 0.50, 0.50), (3863, "B", 0.50, 0.25),
           (1001, "B", 0.50, 0.75), (795, "C", 1.00, 1.00),
           (126, "A", 0.25, 0.25), (52, "B", 0.50, 0.12),
           (5, "B", 0.50, 0.03), (5, "B", 0.50, 0.97),
           (3, "C", 1.00, 0.50), (1, "B", 0.50, 0.06)]
ASKS = {  # class: (cpu MHz, memory MB)
    "sand": (400, 512), "small": (800, 1024), "medium": (2000, 2048),
    "mem-heavy": (1000, 8192), "boulder": (8000, 16384),
    "platform-c": (4000, 4096)}
DISK = 300
CONFIG = SchedulerConfig(backend="tpu", small_batch_threshold=0,
                         micro_solve_threshold=0)


# -- the cluster and the jobs ---------------------------------------------

def shapes_of(n: int) -> list[tuple]:
    """`n` machines in Table 1's proportions by largest remainder, every
    shape at least once."""
    total = sum(row[0] for row in TABLE_1)
    spare = n - len(TABLE_1)
    exact = [row[0] * spare / total for row in TABLE_1]
    counts = [1 + int(x) for x in exact]
    by_rest = sorted(range(len(TABLE_1)), key=lambda i: int(exact[i]) - exact[i])
    for i in by_rest[:n - sum(counts)]:
        counts[i] += 1
    return [row[1:] for row, c in zip(TABLE_1, counts) for _ in range(c)]


def borg_cell(n: int = 96, seed: int = 7) -> Harness:
    h = Harness()
    shapes = shapes_of(n)
    random.Random(seed).shuffle(shapes)
    for i, (platform, cpu, mem) in enumerate(shapes):
        node = mock.node(datacenter=DCS[i % len(DCS)])
        node.resources.cpu = round(cpu * 32_000)
        node.resources.memory_mb = round(mem * 65_536)
        node.resources.disk_mb = 204_800
        node.reserved.cpu = node.reserved.memory_mb = 0
        node.reserved.disk_mb = 0
        node.attributes["platform.family"] = platform
        node.computed_class = compute_node_class(node)
        h.state.upsert_node(h.next_index(), node)
    return h


def make_job(job_id: str, job_class: str, count: int):
    job = mock.job(id=job_id)
    job.type = "batch"
    job.datacenters = list(DCS)
    tg = job.task_groups[0]
    tg.count = count
    tg.ephemeral_disk.size_mb = DISK
    res = tg.tasks[0].resources
    res.cpu, res.memory_mb = ASKS[job_class]
    res.networks = []
    job.constraints = [Constraint("${attr.kernel.name}", "linux", "=")]
    if job_class == "platform-c":
        job.constraints.append(
            Constraint("${attr.platform.family}", "C", "="))
    if job_class == "small":
        job.spreads = [Spread(attribute="${node.datacenter}", weight=50)]
    return job


def solve(h: Harness, batch: list[tuple], tag: str, commit: bool = True):
    """One batch of (class, count) through the solver's own entry; the
    plans are committed unless the solve is a dry one. Returns the jobs."""
    jobs = [make_job(f"{tag}-{i}", jc, count)
            for i, (jc, count) in enumerate(batch)]
    for job in jobs:
        h.state.upsert_job(h.next_index(), job)
    evals = [mock.eval_for_job(job) for job in jobs]
    plans = solve_eval_batch(h.snapshot(), h, evals, CONFIG)
    if commit:
        for ev in evals:
            h.submit_plan(plans[ev.id])
    return jobs


def part_full(h: Harness) -> None:
    """The standing load: medium, small and mem-heavy, a third of the
    cell's CPU."""
    solve(h, [("medium", 60), ("small", 90), ("mem-heavy", 24)], "standing")


PERIOD = [("sand", 1)] * 4 + [("small", 1)] * 2 + [
    ("medium", 1), ("mem-heavy", 1), ("platform-c", 1), ("boulder", 1),
    ("sand", 3), ("small", 6), ("medium", 4), ("platform-c", 5),
    ("sand", 40), ("small", 60), ("sand", 300)]


def mixed_batches(seed: int, n: int = 6) -> list[list[tuple]]:
    """`n` batches cut at random lengths from shuffled periods."""
    rng = random.Random(seed)
    stream = []
    for _ in range(n):
        period = list(PERIOD)
        rng.shuffle(period)
        stream += period
    out = []
    while stream and len(out) < n:
        k = rng.randint(3, 16)
        out.append(stream[:k])
        stream = stream[k:]
    return out


def live(h: Harness, job) -> list:
    return [a for a in h.state.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()]


# -- watching the dispatches ----------------------------------------------

class Dispatches:
    """Every compact dispatch of a block: its signature, and (where
    asked) the laddered program's readback beside the exact-shape
    program's on the same lowered batch."""

    def __init__(self, monkeypatch, compare: bool = False) -> None:
        self.sigs: list[tuple] = []
        self.real_groups: list[int] = []
        self.compared = 0
        call = solver_mod.BatchSolver._call_compact
        dispatch = solver_mod.BatchSolver._compact_dispatch
        seen = self

        def noting(sig, span, g, gp, maxc, fn, *args, **kwargs):
            seen.sigs.append(sig)
            seen.real_groups.append(g)
            return call(sig, span, g, gp, maxc, fn, *args, **kwargs)

        def comparing(self, table, groups, used_n, dev_state, span):
            pending = dispatch(self, table, groups, used_n, dev_state, span)
            assert dev_state is None  # the host arrays are what it solved
            inst_l, over_l, used_l = pending[:3]
            inst_x, over_x, used_x = exact_shape(self, table, groups, used_n)
            g = len(groups)
            got, want = np.asarray(inst_l)[:g], np.asarray(inst_x)[:g]
            w = min(got.shape[1], want.shape[1])
            np.testing.assert_array_equal(got[:, :w], want[:, :w])
            assert (got[:, w:] == -1).all() and (want[:, w:] == -1).all()
            # padded groups place nothing
            assert (np.asarray(inst_l)[g:] == -1).all()
            n = table.n
            np.testing.assert_array_equal(
                np.asarray(used_l)[:n], np.asarray(used_x)[:n])
            assert not np.asarray(over_l).any()
            assert not np.asarray(over_x).any()
            seen.compared += 1
            return pending

        monkeypatch.setattr(solver_mod.BatchSolver, "_call_compact",
                            staticmethod(noting))
        if compare:
            monkeypatch.setattr(solver_mod.BatchSolver, "_compact_dispatch",
                                comparing)


def exact_shape(solver, table, groups, used_n):
    """The parent's program on the same lowered batch: the group axis
    padded to a multiple of 8, each row table to its distinct rows' next
    multiple of 8, the readback to the next power of two from 16."""
    n, g = table.n, len(groups)
    np_ = kernels.pad_n(n)
    gp = max(8, -(-g // 8) * 8)
    cap = np.zeros((np_, 3), dtype=np.int32)
    used = np.zeros((np_, 3), dtype=np.int32)
    cap[:n] = table.cap
    used[:n] = used_n[:n]
    asks = np.zeros((gp, 3), dtype=np.int32)
    counts = np.zeros(gp, dtype=np.int32)
    for i, grp in enumerate(groups):
        asks[i], counts[i] = grp.ask, grp.count

    def rows(arrays, dtype):
        distinct, idx = [], np.zeros(gp, dtype=np.int32)
        for i, a in enumerate(arrays):
            key = np.asarray(a, dtype=dtype).tobytes()
            if key not in distinct:
                distinct.append(key)
            idx[i] = distinct.index(key)
        out = np.zeros((max(8, -(-len(distinct) // 8) * 8), np_), dtype=dtype)
        for j, key in enumerate(distinct):
            out[j, :n] = np.frombuffer(key, dtype=dtype)
        return out, idx

    feas, fi = rows([grp.feasible for grp in groups], np.bool_)
    bias, bi = rows([grp.bias for grp in groups], np.float32)
    ucap, ui = rows([grp.units_cap for grp in groups], np.int64)
    ucap = np.clip(ucap, 0, 2**15 - 1).astype(np.int16)
    bound = max(1, solver._readback_bound(cap, used, groups, n))
    maxc = 16
    while maxc < bound:
        maxc *= 2
    return kernels.solve_placement_compact(
        cap, used, asks, counts, np.packbits(feas, axis=1), fi, bias, bi,
        ucap, ui, max_count=maxc)


def warm_shapes(groups: int, largest: int) -> list[list[tuple]]:
    """As the benchmark's generator lists them: for every program of
    compact_programs() a mix of up to `groups` groups and `largest` a
    group can reach, one single-class batch that lands in it."""
    programs = kernels.compact_programs()
    out = []
    for gp, maxc in programs:
        under_g = max((g for g, _ in programs if g < gp), default=0)
        under_c = max((c for _, c in programs if c < maxc), default=0)
        if groups <= under_g or largest <= under_c:
            continue
        out.append([("sand", under_c + 1)] * (under_g + 1))
    return out


# -- (i) the set is closed -------------------------------------------------

def test_the_ladder_lists_what_pad_g_and_pad_c_return():
    programs = kernels.compact_programs()
    assert programs == sorted(programs) and len(set(programs)) == len(programs)
    gps = sorted({g for g, _ in programs})
    cs = sorted({c for _, c in programs})
    assert gps == list(kernels.G_LADDER) == [8, 32, 128, 256]
    assert cs == list(kernels.C_LADDER) == [64, 256, 1024, 4096]
    assert set(programs) == {(g, c) for g in gps for c in cs}
    for g in range(1, gps[-1] + 1):
        assert kernels.pad_g(g) == min(r for r in gps if r >= g)
    for c in (1, 15, 16, 17, 64, 65, 250, 256, 257, 1000, 1024, 2000, 4096):
        assert kernels.pad_c(c) == min(r for r in cs if r >= c)
    # past the top the ladder goes on, coarse, and holds what it is given
    assert [kernels.pad_g(g) for g in (257, 512, 513)] == [512, 512, 768]
    assert [kernels.pad_c(c) for c in (4097, 16384, 16385)] == [
        16384, 16384, 65536]


@pytest.mark.parametrize("seed", [3, 11, 3_000_000_019])
def test_a_mixed_stream_lands_in_listed_programs_and_meets_none_unwarmed(
        seed, monkeypatch):
    h = borg_cell()
    part_full(h)
    batches = mixed_batches(seed)
    groups = max(sum(min(c, 4) if jc == "small" else 1 for jc, c in b)
                 for b in batches)
    largest = max(c for b in batches for _, c in b)
    # a compile ledger of this test's own: first sight is the ledger's
    old_obs = solverobs._install(solverobs.SolverObservatory())
    old = metrics._install_registry(Registry())
    try:
        warm = Dispatches(monkeypatch)
        for k, batch in enumerate(warm_shapes(groups, largest)):
            solve(h, batch, f"warm-{k}", commit=False)
        warmed = set(warm.sigs)
        n_new = metrics.snapshot()["samples"][
            "nomad.tpu.compact.programs_new"]["count"]
        assert n_new == len(warmed) > 0
        metrics._install_registry(Registry())
        mixed = Dispatches(monkeypatch)
        for k, batch in enumerate(batches):
            solve(h, batch, f"mixed-{seed}-{k}")
        samples = metrics.snapshot()["samples"]
    finally:
        metrics._install_registry(old)
        solverobs._install(old_obs)
    programs = set(kernels.compact_programs())
    n_pad = kernels.pad_n(96)
    assert len(mixed.sigs) >= len(batches)  # spread retries add some
    for name, np_, gp, maxc in mixed.sigs:
        assert name == "solve_placement_compact" and np_ == n_pad
        assert (gp, maxc) in programs
    assert set(mixed.sigs) <= warmed
    assert "nomad.tpu.compact.programs_new" not in samples
    assert samples["nomad.tpu.compact.groups"]["count"] == len(mixed.sigs)
    assert samples["nomad.tpu.compact.distinct_rows"]["max"] >= 6
    padded = samples["nomad.tpu.compact.groups_padded"]["sum"]
    assert padded == sum(gp for _, _, gp, _ in mixed.sigs)
    assert padded == samples["nomad.tpu.compact.groups"]["sum"] \
        + samples["nomad.tpu.compact.groups_pad"]["sum"]


def test_the_rung_is_on_the_host_prep_span():
    from nomad_tpu import trace

    h = borg_cell()
    trace.configure(max_traces=64, enabled_=True)
    try:
        ctx = trace.start_trace("tpu.batch")
        with trace.use(ctx):
            solve(h, [("sand", 70), ("small", 9)], "span")
        spans = [s for s in ctx.spans if s.name == "host_prep"]
    finally:
        trace.set_enabled(False)
    assert [(s.attrs["gp"], s.attrs["maxc"]) for s in spans] == [(8, 256)]


# -- (ii) the same answers -------------------------------------------------

@pytest.mark.parametrize("seed", [5, 17, 3_000_000_023])
def test_the_laddered_program_places_what_the_exact_shape_program_placed(
        seed, monkeypatch):
    h = borg_cell(seed=seed)
    part_full(h)
    seen = Dispatches(monkeypatch, compare=True)
    for k, batch in enumerate(mixed_batches(seed)):
        solve(h, batch, f"same-{seed}-{k}")
    assert seen.compared >= 6
    # the comparison was not of one shape with itself: some batch's
    # rung is wider than its exact bucket
    assert any(gp > max(8, -(-g // 8) * 8)
               for (_, _, gp, _), g in zip(seen.sigs, seen.real_groups))


# -- (iii) against the host iterator stack ---------------------------------

def usage_by_node(h: Harness) -> dict:
    out = Counter()
    for node in h.state.nodes():
        for a in h.state.allocs_by_node(node.id):
            if a.terminal_status():
                continue
            cpu = sum(t.cpu for t in a.resources.tasks.values())
            mem = sum(t.memory_mb for t in a.resources.tasks.values())
            out[node.id, "cpu"] += cpu
            out[node.id, "mem"] += mem
            out[node.id, "disk"] += a.resources.shared_disk_mb
    return out


@pytest.mark.parametrize("seed", [2, 3_000_000_029])
def test_the_kernel_path_against_the_host_stack_on_the_same_snapshot(seed):
    batches = mixed_batches(seed, n=3)
    kernel, host = borg_cell(seed=seed), borg_cell(seed=seed)
    part_full(kernel)
    part_full(host)  # the same standing load, by the same path
    placed = {}
    for name, h in (("kernel", kernel), ("host", host)):
        jobs = []
        for k, batch in enumerate(batches):
            if name == "kernel":
                jobs += solve(h, batch, f"vs-{k}")
            else:
                for i, (jc, count) in enumerate(batch):
                    job = make_job(f"vs-{k}-{i}", jc, count)
                    h.state.upsert_job(h.next_index(), job)
                    h.process("batch", mock.eval_for_job(job))
                    jobs.append(job)
        placed[name] = {job.id: len(live(h, job)) for job in jobs}
        by_id = {n.id: n for n in h.state.nodes()}
        for job in jobs:
            jc = next(c for c, ask in ASKS.items() if ask == (
                job.task_groups[0].tasks[0].resources.cpu,
                job.task_groups[0].tasks[0].resources.memory_mb))
            for a in live(h, job):
                node = by_id[a.node_id]
                assert node.attributes["kernel.name"] == "linux"
                if jc == "platform-c":
                    assert node.attributes["platform.family"] == "C"
                task = next(iter(a.resources.tasks.values()))
                assert (task.cpu, task.memory_mb) == ASKS[jc]
                # the ask fits the machine's own shape at all
                assert node.resources.memory_mb >= ASKS[jc][1]
        used = usage_by_node(h)
        for node in h.state.nodes():
            assert used[node.id, "cpu"] <= node.resources.cpu
            assert used[node.id, "mem"] <= node.resources.memory_mb
            assert used[node.id, "disk"] <= node.resources.disk_mb
    assert sum(placed["kernel"].values()) == sum(placed["host"].values())
    # nothing of this backlog is left over on a cell a third full
    asked = {f"vs-{k}-{i}": c for k, b in enumerate(batches)
             for i, (_, c) in enumerate(b)}
    assert placed["kernel"] == asked == placed["host"]
