"""The Borg cell as published (`borg2011-12k`) and the cell PR 32 adds on
it, `mixed-backlog`: Table 1's ten counts exact and the fleet of a seed
pinned, the fill's arithmetic, the multiset of a period the same for
every seed, the shapes set-up warms read from the program's own list,
the per-layer entries and their files, the two faults of this
deployment's own kind planted under the timed path — and the one test
that PR 27's pin on the END of three lists now breaks, run whole on the
lists as PR 27 left them. The cell is also rehearsed end to end by
test_bench_cells_rehearsal.py::test_cell_rehearsal_prints_the_contracts_line.
"""

import hashlib
import json
import time
from collections import Counter
from pathlib import Path

import pytest

from bench_helpers import plant as plant_every
from bench_helpers_mixed import FAULTS, plant
from benchmarks import run as bench_run
from benchmarks.harness import cluster, kernel_cost, spec

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "benchmarks"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
BORG = json.loads((BENCH_DIR / "configs" / "borg2011-12k.json").read_text())
MIX = json.loads((BENCH_DIR / "traffic" / "mixed-backlog.json").read_text())
CELL = "borg2011-12k.mixed-backlog"
FILL = "c2m-10k-tiers.preempt-fill"
mixed_backlog = spec.load_module("generators", "mixed_backlog")
WINDOW_CLASSES = set(BORG["job_classes"])
PERIODS = 32  # the largest of 24-32 that drains within 15 s (PERF.md section 4)

# Reiss et al., SoCC 2012, Table 1: machines, platform, CPU, memory
TABLE_1 = [(6732, "B", 0.50, 0.50), (3863, "B", 0.50, 0.25),
           (1001, "B", 0.50, 0.75), (795, "C", 1.00, 1.00),
           (126, "A", 0.25, 0.25), (52, "B", 0.50, 0.12),
           (5, "B", 0.50, 0.03), (5, "B", 0.50, 0.97),
           (3, "C", 1.00, 0.50), (1, "B", 0.50, 0.06)]
FLEETS = {  # seed: digest of the nodes
    7: "7ff94b1436f4b4865389d9cc8f81c29952794a04e8d52af598bf87d4b206e189",
    3000000019:
        "1eb035eb15ba52c59f5e41655b66c9851dd698ec359cc51f0b1682f72755b6fe",
}


def checks_of(line: dict) -> dict:
    return {k: v["value"] for k, v in line["checks"].items()}


def rehearse(capsys, trace: int, seed: int, seconds: float = 1.0):
    rc = bench_run.main(
        ["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--rehearsal"], time.monotonic())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    report = json.loads(
        (BENCH_DIR / "out" / f"{CELL}.{seed}.json").read_text())
    return json.loads(out[-1]), report


# -- the deployment's file -------------------------------------------------

def test_the_machines_are_table_1_uncut():
    assert sum(row[0] for row in TABLE_1) == 12_583 == BORG["nodes"]
    got = [(c["count"], c["attributes"]["platform.family"], c["cpu_mhz"],
            c["memory_mb"], c["name"]) for c in BORG["node_classes"]]
    assert got == [(n, p, round(cpu * 32_000), round(mem * 65_536),
                    f"{p}-{cpu:.2f}-{mem:.2f}")
                   for n, p, cpu, mem in TABLE_1]
    for c in BORG["node_classes"]:
        assert c["attributes"]["kernel.name"] == "linux"
        assert c["disk_mb"] == 204_800
    entry = next(c for c in BENCH["configs"] if c["name"] == "borg2011-12k")
    assert entry["source"] == BORG["source"] and len(entry["source"]) <= 200
    assert "nodes" not in entry["reduced"]
    assert sorted(BORG["reduced_why"]) == sorted(BORG["reduced"])
    for key in ("units", "disk_mb", "datacenters", "job_classes", "fill",
                "constrained_share"):
        assert key in BORG["assumed"], key
    assert [g["rule"] for g in BORG["guarantees"]] == [
        "acked_jobs_held", "unique_allocs", "node_capacity",
        "job_feasibility", "asks_carried", "watch_visibility"]
    assert BORG["may_remain"] == []
    assert "allocs_per_node" not in BORG and "packing_share" not in BORG


def test_the_six_asks_and_who_may_go_where():
    classes = BORG["job_classes"]
    assert {n: (c["ask"]["cpu_mhz"], c["ask"]["memory_mb"])
            for n, c in classes.items()} == {
        "sand": (400, 512), "small": (800, 1024), "medium": (2000, 2048),
        "mem-heavy": (1000, 8192), "boulder": (8000, 16384),
        "platform-c": (4000, 4096)}
    linux = {"attribute": "kernel.name", "operand": "=", "value": "linux"}
    for name, c in classes.items():
        # batch-type: a service's deployment never completes in this
        # harness (assumed.job_type says what that cost)
        assert c["type"] == "batch" and c["priority"] == 50
        assert c["ask"]["disk_mb"] == 300 and c["constraints"][0] == linux
        assert ("spread" in c) == (name == "small")
    assert classes["platform-c"]["constraints"][1] == {
        "attribute": "platform.family", "operand": "=", "value": "C"}
    c_machines = sum(n for n, p, _, _ in TABLE_1 if p == "C")
    assert c_machines == 798 and round(100 * c_machines / 12_583, 1) == 6.3
    assert len(classes) == 6 and "job_type" in BORG["assumed"]
    # mem-heavy fits no machine of 0.12 memory or less
    small_mem = [c for c in BORG["node_classes"] if c["memory_mb"] < 8192]
    assert sum(c["count"] for c in small_mem) == 52 + 5 + 1


def test_the_fill_is_69_jobs_of_1000_in_alternating_entries():
    from benchmarks.reference import density

    entries = BORG["standing"]
    assert [e["job_class"] for e in entries] == [
        "medium", "small", "mem-heavy"] * 2
    jobs, cpu, mem = [], 0, 0
    for e in entries:
        ask = spec.job_class(BORG, e["job_class"])["ask"]
        room = sum(c["count"] * density.allocs_per_node(c, ask)
                   for c in BORG["node_classes"])
        n = int(e["fill_share"] * room) // e["count"]
        jobs.append(n)
        cpu += n * e["count"] * ask["cpu_mhz"]
        mem += n * e["count"] * ask["memory_mb"]
    assert jobs == [11, 18, 6, 11, 18, 5] and sum(jobs) == 69
    total_cpu = sum(c["count"] * c["cpu_mhz"] for c in BORG["node_classes"])
    total_mem = sum(c["count"] * c["memory_mb"] for c in BORG["node_classes"])
    assert (total_cpu, total_mem) == (213_088_000, 388_091_068)
    assert (cpu, mem) == (83_800_000, 172_032_000)
    assert 0.38 <= cpu / total_cpu <= 0.42 and 0.43 <= mem / total_mem <= 0.47
    # the file says its own arithmetic
    for said in ("69 jobs", "83,800,000", "172,032,000", "39.3 %", "44.3 %"):
        assert said in BORG["assumed"]["fill"], said


def test_the_backlog_fits_what_the_fill_leaves():
    """`periods` x the period on top of the fill: inside the range the
    issue gives, and under the cell's capacity in both dimensions with
    room to spare (nothing may end blocked: `may_remain` is empty)."""
    asks = {n: c["ask"] for n, c in BORG["job_classes"].items()}
    cpu = sum(n * asks[jc]["cpu_mhz"] for jc, n in MIX["period"])
    mem = sum(n * asks[jc]["memory_mb"] for jc, n in MIX["period"])
    assert (cpu, mem) == (1_716_800, 2_221_568)
    assert 24 <= MIX["periods"] <= 32
    assert 0.19 <= MIX["periods"] * cpu / 213_088_000 <= 0.26
    assert (83_800_000 + MIX["periods"] * cpu) / 213_088_000 < 0.66
    assert (172_032_000 + MIX["periods"] * mem) / 388_091_068 < 0.63


@pytest.mark.parametrize("seed", sorted(FLEETS))
def test_the_fleet_of_a_seed_is_pinned_with_table_1s_counts_exact(seed):
    fleet = cluster.Fleet(None, BORG, BORG["nodes"], seed)
    rows = [(n.id, n.name, n.datacenter, n.node_class, n.resources.cpu,
             n.resources.memory_mb, n.resources.disk_mb,
             sorted(n.attributes.items())) for n in fleet.nodes]
    assert hashlib.sha256(json.dumps(rows).encode()).hexdigest() == \
        FLEETS[seed]
    assert Counter(n.node_class for n in fleet.nodes) == {
        f"{p}-{cpu:.2f}-{mem:.2f}": n for n, p, cpu, mem in TABLE_1}
    # every shape is dealt over the four datacenters in turn
    by_dc = Counter((n.node_class, n.datacenter) for n in fleet.nodes)
    assert by_dc["B-0.50-0.50", "dc1"] == 6732 // 4
    assert {dc for (c, dc) in by_dc if c == "C-1.00-0.50"} == {
        "dc1", "dc2", "dc3"}
    # what a node offers: ten shapes in four datacenters, less the
    # pairs too few machines leave empty
    assert len({n.computed_class for n in fleet.nodes}) == len(by_dc) == 36


# -- the mix ---------------------------------------------------------------

def test_a_period_is_the_issues_forty_jobs():
    period = [(jc, n) for jc, n in MIX["period"]]
    assert len(period) == 40 and MIX["periods"] == PERIODS
    assert sum(n for _, n in period) == 3_292
    ones = Counter(jc for jc, n in period if n == 1)
    assert ones == {"sand": 10, "small": 6, "medium": 4, "mem-heavy": 2,
                    "platform-c": 1, "boulder": 1}
    assert sorted(n for _, n in period if 1 < n <= 10) == [
        2, 3, 4, 5, 6, 8, 10, 10]
    assert Counter(jc for jc, n in period if 1 < n <= 10) == {
        "sand": 3, "small": 3, "medium": 1, "mem-heavy": 1}
    assert sorted((n, jc) for jc, n in period if n > 10) == [
        (20, "platform-c"), (20, "small"), (30, "sand"), (50, "medium"),
        (100, "sand"), (500, "sand"), (500, "small"), (2000, "sand")]
    assert sum(n == 1 for _, n in period) / 40 == 0.6
    assert round(100 * (2000 + 500 + 500) / 3_292) == 91
    assert (MIX["submitters"], MIX["priority"]) == (4, 50)
    assert set(jc for jc, _ in period) == WINDOW_CLASSES


@pytest.mark.parametrize("params", [MIX, {**MIX, **MIX["rehearsal"]}],
                         ids=["cell", "rehearsal"])
def test_every_seed_deals_the_same_multiset_in_another_order(params):
    want = Counter((jc, n) for jc, n in params["period"])
    orders = []
    for seed in (0, 11, 2_999_999_999, 3_000_000_031):
        dealt = mixed_backlog.deal(seed, params)
        assert len(dealt) == params["periods"] * len(params["period"])
        for k in range(params["periods"]):  # period by period
            n = len(params["period"])
            assert Counter(dealt[k * n:(k + 1) * n]) == want
        assert dealt == mixed_backlog.deal(seed, params)
        orders.append(dealt)
    assert len({tuple(o) for o in orders}) == len(orders)


def test_the_rehearsals_mix_holds_every_class_and_passes_two_buckets():
    from nomad_tpu.scheduler.tpu.kernels import C_LADDER

    period = MIX["rehearsal"]["period"]
    assert {jc for jc, _ in period} == WINDOW_CLASSES
    assert max(n for _, n in period) > C_LADDER[1]
    assert any(jc == "platform-c" for jc, _ in period)


# -- what set-up warms -------------------------------------------------------

def bucket_of(shape: dict) -> tuple:
    """The program a single-class dry batch lands in, by the program's
    own buckets: a spread job is one group a datacenter."""
    from nomad_tpu.scheduler.tpu.kernels import pad_c, pad_g

    dcs = len(BORG["datacenters"])
    spread = "spread" in BORG["job_classes"][shape["job_class"]]
    groups = shape["evals"] * (dcs if spread else 1)
    largest = shape["count"] // dcs if spread else shape["count"]
    return pad_g(groups), pad_c(largest)


@pytest.mark.parametrize("params, reach", [
    # 11 jobs of a period spread: four groups each, and a batch holds
    # at most 64 evals
    (MIX, (64, 64 + 3 * min(64, 11 * MIX["periods"]), 2000)),
    ({**MIX, **MIX["rehearsal"]}, (15, 24, 300)),
], ids=["cell", "rehearsal"])
def test_shapes_lists_one_single_class_batch_for_every_program_in_reach(
        params, reach):
    from nomad_tpu.scheduler.tpu.kernels import compact_programs

    assert mixed_backlog._reach(params, BORG) == reach
    evals, groups, largest = reach
    shapes = mixed_backlog.shapes(params, BORG)
    programs = compact_programs()
    gps = sorted({g for g, _ in programs})
    cs = sorted({c for _, c in programs})
    # a program is in reach when the mix passes the rungs under it
    want = {(g, c) for g, c in programs
            if groups > max([x for x in gps if x < g], default=0)
            and largest > max([x for x in cs if x < c], default=0)}
    assert [bucket_of(s) for s in shapes] == sorted(want)
    assert len(shapes) <= 24
    for s in shapes:
        assert s["evals"] <= evals  # a batch the worker can drain
        # past the small-batch route: the batch reaches the kernel
        assert s["evals"] * s["count"] > 48
        assert set(s) == {"evals", "count", "job_class"}
    assert len(shapes) == (16 if params is MIX else 6)


def test_warm_jobs_are_one_real_deploy_of_each_class():
    warm = mixed_backlog.warm_jobs(MIX)
    assert {jc for _, jc, _ in warm} == WINDOW_CLASSES
    assert all(p == 50 for _, _, p in warm)
    assert [c for c, jc, _ in warm if jc == "sand"][0] > 48  # the kernel


# -- the entries -------------------------------------------------------------

NAMES = [
    "compiles_in_window", "device_idle_share", "programs_new",
    "kernel_ms_per_batch", "solve_placement_compact_mixed_roofline",
    "padded_groups_share", "distinct_rows_mean", "lower_ms_per_batch",
    "lower_groups_ms_per_batch", "host_prep_ms_per_batch",
    "batch_evals_mean", "kernel_path_share", "host_stack_path_share",
    "materialize_ms_per_batch", "plan_submit_p50_ms", "raft_apply_p50_ms",
    "register_p50_ms", "broker_wait_p50_ms", "watch_fanout_p95_ms"]


def metric_file(name: str) -> dict:
    return json.loads(
        (BENCH_DIR / "layer_metrics" / f"{name}.json").read_text())


def test_the_cell_and_its_metrics_are_appended_and_nothing_else_moved():
    assert BENCH["workloads"][-1] == {
        "name": CELL, "config": "borg2011-12k", "traffic": "mixed-backlog",
        "chips": 1, "why": BENCH["workloads"][-1]["why"]}
    assert BENCH["configs"][-1]["name"] == "borg2011-12k"
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["placements_per_s"]["workloads"][-1] == CELL
    assert CELL not in e2e["packing_share"]["workloads"]
    assert CELL not in e2e["e2e_p50_ms"]["workloads"]
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    assert BENCH["per_layer"][-len(mine):] == mine
    assert [m["name"] for m in mine] == [
        n if n.endswith("_roofline") else f"{n}.mixed-backlog"
        for n in NAMES]
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "placements_per_s"
        f = metric_file(m["name"])
        assert f["traffic"] == ["mixed-backlog"]
        twin = BENCH_DIR / "layer_metrics" / m["name"].replace(
            ".mixed-backlog", ".bulk.json")
        if twin.exists():  # the shared path reads as it does on `bulk`
            theirs = json.loads(twin.read_text())
            assert all(f[k] == v for k, v in theirs.items()
                       if k not in ("name", "traffic")), m["name"]
    # no other accepted metric lists the cell
    assert {m["name"] for m in mine} == {
        m["name"] for m in BENCH["per_layer"] if "mixed" in m["name"]}


def test_the_mixed_roofline_counts_the_real_groups_of_every_dispatch():
    f = metric_file("solve_placement_compact_mixed_roofline")
    assert f["reads"] == {"timings": ["nomad.tpu.compact.groups"]}
    ctx = {"config": BORG, "device_kind": "TPU v5 lite"}
    samples = {
        "device": {"modules": {"solve_placement_compact": [0.02, 0.3]}},
        "timings": {"nomad.tpu.compact.groups": [9, 140],
                    # padded groups are the program's waste: not counted
                    "nomad.tpu.compact.groups_padded": [32, 512]},
    }
    need = kernel_cost.compact_solve_bytes(12_583, 9) \
        + kernel_cost.compact_solve_bytes(12_583, 140)
    assert need == 51 * 12_583 * 149
    got = spec.load_module("reducers", f["reducer"]).reduce(samples, f, ctx)
    assert got == pytest.approx(100.0 * (need / 819e9) / 0.32)
    assert got < 100.0
    # the parent has no such series: nothing is read, nothing raises
    assert spec.load_module("reducers", f["reducer"]).reduce(
        {**samples, "timings": {}}, f, ctx) is None


@pytest.mark.parametrize("name, timings, want", [
    ("padded_groups_share.mixed-backlog",
     {"nomad.tpu.compact.groups_pad": [23, 372],
      "nomad.tpu.compact.groups_padded": [32, 512]}, 100 * 395 / 544),
    ("padded_groups_share.mixed-backlog", {}, None),
    ("distinct_rows_mean.mixed-backlog",
     {"nomad.tpu.compact.distinct_rows": [1, 2, 6]}, 3.0),
    ("distinct_rows_mean.mixed-backlog", {}, None),
    ("programs_new.mixed-backlog",
     {"nomad.tpu.compact.programs_new": [1.0, 1.0]}, 2.0),
    # no program met for the first time: 0 is a reading
    ("programs_new.mixed-backlog", {}, 0.0),
])
def test_the_new_series_are_read_by_reducers_that_were_there(
        name, timings, want):
    f = metric_file(name)
    got = spec.load_module("reducers", f["reducer"]).reduce(
        {"timings": timings}, f, {})
    assert got == (pytest.approx(want) if want is not None else None)


# -- the rehearsal, and the controls ----------------------------------------

def test_the_rehearsed_window_is_mixed_and_meets_no_new_program(capsys):
    line, report = rehearse(capsys, 1, seed=3_000_000_035)
    assert line["correct"] is True and line["failed"] == 0, report
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["compiles_in_window.mixed-backlog"] == 0.0
    assert got["programs_new.mixed-backlog"] == 0.0
    assert got["distinct_rows_mean.mixed-backlog"] > 1.0
    assert 0.0 <= got["padded_groups_share.mixed-backlog"] < 100.0
    # 256 nodes admit the microsolve, which 12,583 do not: here the
    # two shares leave room for it
    assert got["kernel_path_share.mixed-backlog"] > 0.0
    assert got["kernel_path_share.mixed-backlog"] \
        + got["host_stack_path_share.mixed-backlog"] <= 100.0
    assert report["packing"] is None  # ten shapes, six asks: no ideal
    assert report["ops"]["by_kind"] == {
        "job": len(MIX["rehearsal"]["period"])}
    assert line["attempted"] == sum(n for _, n in MIX["rehearsal"]["period"])
    assert report["setup"]["dry_solves"] == len(
        mixed_backlog.shapes({**MIX, **MIX["rehearsal"]}, BORG))
    # a sound run reads 0 on every check
    assert set(checks_of(line).values()) == {0}, line["checks"]


@pytest.mark.parametrize("fault, rule, seed", [
    ("constraint_dropped", "job_feasibility", 3_000_000_036),
    ("shape_overcommitted", "node_capacity", 3_000_000_038)])
def test_a_fault_of_this_deployments_kind_is_found_by_its_rule_alone(
        capsys, fault, rule, seed):
    """The controls of this deployment's own kind. The store takes a
    platform-c alloc on a B machine where the plan applier verified a C
    one: of the run's six rules `job_feasibility` alone says so. It
    takes a boulder on an A machine that is at work: every constraint
    holds, and `node_capacity` alone says so, by that machine's own
    shape."""
    assert sorted(FAULTS) == ["constraint_dropped", "shape_overcommitted"]
    undo, planted = plant(fault)
    try:
        line, report = rehearse(capsys, 0, seed=seed)
    finally:
        undo()
    assert planted()
    assert line["correct"] is False and line["failed"] >= 1
    checks = checks_of(line)
    assert checks.pop(f"faults.{rule}") >= 1, report
    assert checks.pop("failed") >= 1
    assert set(checks.values()) == {0}, checks


@pytest.mark.parametrize("fault, rule", [
    ("ask_altered", "asks_carried"), ("all_on_one_node", "node_capacity")])
def test_the_accepted_controls_read_false_on_this_configuration(
        capsys, fault, rule):
    undo = plant_every(fault)
    try:
        line, report = rehearse(capsys, 0, seed=3_000_000_037)
    finally:
        undo()
    assert line["correct"] is False and line["failed"] >= 1
    assert checks_of(line)[f"faults.{rule}"] >= 1, report
    assert checks_of(line)["compiles_in_window"] == 0


# -- PR 27's pin, on the lists as PR 27 left them ----------------------------

def test_every_line_of_pr_27s_pinned_test_holds_of_the_lists_it_left(
        monkeypatch):
    """`test_bench_tiers.py::test_the_cell_is_an_entry_appended_with_the_
    metrics_the_issue_names` wants `preempt-fill` LAST in `workloads`, in
    `placements_per_s`' list and in `per_layer`. PR 28 appended a metric
    and marked it (tests/conftest.py); PR 32 appends a cell, which also
    breaks the copy of that test in test_bench_lower_skipped.py (cut in
    `per_layer` alone; marked beside it). So the test is run here,
    whole, on the three lists cut after the preempt cell's last entry:
    what was appended since is not looked at, and the next entry needs
    no mark and no edit here."""
    import test_bench_tiers as tiers

    bench = json.loads(json.dumps(tiers.BENCH))

    def cut(rows, is_fill):
        last = max(i for i, r in enumerate(rows) if is_fill(r))
        assert rows[last + 1:]  # else the marks have nothing to excuse
        return rows[:last + 1]

    bench["workloads"] = cut(bench["workloads"],
                             lambda w: w["name"] == FILL)
    bench["per_layer"] = cut(bench["per_layer"],
                             lambda m: FILL in m.get("workloads", ()))
    for m in bench["end_to_end"]:
        if m["name"] == "placements_per_s":
            m["workloads"] = cut(m["workloads"], lambda name: name == FILL)
    monkeypatch.setattr(tiers, "BENCH", bench)
    tiers.test_the_cell_is_an_entry_appended_with_the_metrics_the_issue_names()
