"""The tiered deployment (`c2m-10k-tiers`) and the cell PR 27 adds on
it, `preempt-fill`: its two reference rules on clusters made by hand,
the faults planted under the timed path, the bytes of the preempt
kernel's roofline and the reducers the new metrics read with. The cell
is rehearsed end to end by
test_bench_cells_rehearsal.py::test_cell_rehearsal_prints_the_contracts_line.
(`priority-lanes` was built, measured and left out: PERF.md section 7.)
"""

import json
import time
from pathlib import Path

import pytest

from bench_helpers_preempt import FAULTS, plant
from benchmarks import run as bench_run
from benchmarks.harness import kernel_cost_preempt, spec

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "benchmarks"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
TIERS = json.loads((BENCH_DIR / "configs" / "c2m-10k-tiers.json").read_text())
FILL = "c2m-10k-tiers.preempt-fill"
lowest_first = spec.load_module(spec.RULES, "preemption_lowest_first")
held_or_evicted = spec.load_module(spec.RULES, "standing_held_or_evicted")
preempt_backlog = spec.load_module("generators", "preempt_backlog")


# -- a cluster by hand ----------------------------------------------------

def node(i: int, dc: str = "dc1") -> dict:
    return {"id": f"n{i}", "datacenter": dc, "class": "", "cpu": 1000,
            "mem": 1000, "disk": 1000,
            "attributes": {"kernel.name": "linux"}, "devices": []}


def alloc(aid: str, job: str, node_id: str, cpu: int = 250) -> dict:
    return {"id": aid, "name": aid, "job": job, "node": node_id,
            "cpu": cpu, "mem": 100, "disk": 100}


def victim(aid: str, job: str, node_id: str, by: str) -> dict:
    return {"id": aid, "name": aid, "job": job, "node": node_id,
            "desired_status": "evict", "client_status": "running",
            "preempted_by_allocation": by}


def job(priority: int, dcs=("dc1",)) -> dict:
    return {"datacenters": list(dcs), "priority": priority,
            "type": "service",
            "constraints": [("${attr.kernel.name}", "=", "linux")]}


def sound() -> dict:
    """Two nodes, each full: n0 held four of `batch` (20), n1 four of
    `web` (50). `prod` (70) placed two, each on n0 in place of one
    `batch` alloc."""
    return {
        "nodes": [node(0), node(1)],
        "jobs": {"batch": job(20), "web": job(50), "prod": job(70)},
        "allocs": [alloc("b2", "batch", "n0"), alloc("b3", "batch", "n0"),
                   alloc("p0", "prod", "n0"), alloc("p1", "prod", "n0"),
                   *(alloc(f"w{i}", "web", "n1") for i in range(4))],
        "terminal_allocs": [victim("b0", "batch", "n0", "p0"),
                            victim("b1", "batch", "n0", "p1")],
        "observed": {},
    }


ASK = {"cpu_mhz": 250, "memory_mb": 100, "disk_mb": 100}
EXPECTED = {"batch": (4, ASK), "web": (4, ASK), "prod": (2, ASK)}


def too_close(s):  # (a): the preemptor is 5 over its victims
    s["jobs"]["prod"] = job(25)


def higher_on_the_node(s):  # (b): a `web` alloc went, `batch` stays
    s["allocs"] = [a for a in s["allocs"] if a["id"] != "b2"]
    s["allocs"].append(alloc("w9", "web", "n0"))
    s["allocs"].append(alloc("b0", "batch", "n0"))
    s["terminal_allocs"][0] = victim("w8", "web", "n0", "p0")


def higher_in_the_cluster(s):  # (c): p1 sits on n1 for a `web` alloc
    s["allocs"] = [a for a in s["allocs"] if a["id"] not in ("p1", "w0")]
    s["allocs"] += [alloc("p1", "prod", "n1"), alloc("b1", "batch", "n0")]
    s["terminal_allocs"][1] = victim("w0", "web", "n1", "p1")


def one_too_many(s):  # (d): p0 took b2 as well
    s["allocs"] = [a for a in s["allocs"] if a["id"] != "b2"]
    s["terminal_allocs"].append(victim("b2", "batch", "n0", "p0"))


def one_too_many_of_a_job_evicted_whole(s):
    """(d) where the victims' job has no live alloc left to tell their
    size (on the chip the first batch jobs go whole): the ask the job
    was sent with tells it."""
    one_too_many(s)
    s["allocs"] = [a for a in s["allocs"] if a["id"] != "b3"]
    s["terminal_allocs"].append(victim("b3", "batch", "n0", "p1"))


def preemptor_gone(s):  # (e): p1 is not live
    s["allocs"] = [a for a in s["allocs"] if a["id"] != "p1"]


def outside_the_jobs_datacenters(s):
    """Priority 20 stands in dc2 only, which `prod` does not admit: a
    `web` victim in dc1 is then the lowest tier standing."""
    s["nodes"].append(node(2, "dc2"))
    s["allocs"] = [a for a in s["allocs"] if a["job"] != "batch"]
    s["allocs"] += [alloc("b9", "batch", "n2"), alloc("w8", "web", "n0"),
                    alloc("w9", "web", "n0")]
    s["terminal_allocs"] = [victim("w6", "web", "n0", "p0"),
                            victim("w7", "web", "n0", "p1")]


@pytest.mark.parametrize("alter, says", [
    (None, None),
    (outside_the_jobs_datacenters, None),
    (too_close, "not 10 priorities under"),
    (higher_on_the_node, "stood on the same node"),
    (higher_in_the_cluster, "above the lowest tier standing"),
    (one_too_many, "more victims than their shortage needs"),
    (one_too_many_of_a_job_evicted_whole,
     "more victims than their shortage needs"),
    (preemptor_gone, "their preemptor is not live"),
])
def test_preemption_lowest_first_finds_each_fault_and_only_it(alter, says):
    snap = sound()
    if alter is not None:
        alter(snap)
    faults = lowest_first.check(snap, EXPECTED, TIERS)
    if says is None:
        assert faults == []
        return
    assert any(says in f for f in faults), faults
    # (b) is (c) seen on one node: both speak there, nothing else does
    also = {"stood on the same node": "above the lowest tier standing"}
    assert all(says in f or also.get(says, says) in f for f in faults), faults


def lost_without_a_preemptor(s):
    s["terminal_allocs"].pop()          # b1 is gone and nothing says why
    s["allocs"] = [a for a in s["allocs"] if a["id"] != "p1"]


def holds_more_than_asked(s):
    s["allocs"].append(alloc("w9", "web", "n1"))


def a_production_alloc_evicted(s):
    s["allocs"] = [a for a in s["allocs"] if a["id"] != "p1"]
    s["terminal_allocs"].append(victim("p1", "prod", "n0", "p0"))


def evicted_and_placed_again(s):
    s["nodes"].append(node(2))
    s["allocs"].append(alloc("b4", "batch", "n2"))   # b0's replacement


def not_in_the_store(s):
    del s["jobs"]["web"]


@pytest.mark.parametrize("alter, says", [
    (None, None),
    (evicted_and_placed_again, None),
    (lost_without_a_preemptor, "hold another number of allocs"),
    (holds_more_than_asked, "hold another number of allocs"),
    (a_production_alloc_evicted, "hold another number of allocs"),
    (not_in_the_store, "are not in the store"),
])
def test_standing_held_or_evicted(alter, says):
    snap = sound()
    if alter is not None:
        alter(snap)
    faults = held_or_evicted.check(snap, EXPECTED, TIERS)
    if says is None:
        assert faults == []
    else:
        assert len(faults) == 1 and says in faults[0], faults


# -- the cells ------------------------------------------------------------

def rehearse(capsys, cell: str, trace: int, seed: int):
    rc = bench_run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", "1.5",
         "--trace", str(trace), "--rehearsal"], time.monotonic())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    report = json.loads(
        (BENCH_DIR / "out" / f"{cell}.{seed}.json").read_text())
    return json.loads(out[-1]), report


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_eviction_fault_is_found_by_lowest_first_alone(
        capsys, fault):
    """The control: the store takes another victim than the plan applier
    verified — a higher tier for a lower one, or one more than needed —
    and of the run's rules `preemption_lowest_first` alone says so."""
    undo, planted = plant(fault)
    try:
        line, report = rehearse(capsys, FILL, 0, seed=3_000_000_033)
    finally:
        undo()
    assert planted()
    assert line["correct"] is False and line["failed"] >= 1
    checks = {k: v["value"] for k, v in line["checks"].items()}
    assert checks.pop("faults.preemption_lowest_first") >= 1, report
    assert checks.pop("failed") >= 1
    assert set(checks.values()) == {0}, checks


def test_the_rehearsal_of_preempt_fill_preempts_on_the_kernel(capsys):
    line, report = rehearse(capsys, FILL, 1, seed=3_000_000_034)
    assert line["correct"] is True, report
    # every eval of the window was solved alone, on the preempt kernel
    assert report["batch_evals"] and set(report["batch_evals"]) == {1}
    assert report["path_counts"]["kernel"] == report["ops"]["by_kind"]["job"]
    assert report["path_counts"]["host_stack"] == 0
    assert line["metrics"]["evictions_per_placement.preempt-fill"] == {
        "value": 1.0, "unit": "evictions"}
    assert report["packing"] is None  # three job classes: no ideal
    assert report["setup"]["standing_jobs"] == 4


# -- generators -----------------------------------------------------------

def test_the_shapes_to_warm_carry_the_class_and_the_priority():
    fill = json.loads(
        (BENCH_DIR / "traffic" / "preempt-fill.json").read_text())
    shapes = preempt_backlog.shapes(fill, TIERS)
    assert shapes == [{"evals": 1, "count": 1000, "priority": 70,
                       "job_class": "production"}]
    assert preempt_backlog.warm_jobs(fill) == [(1000, "production", 70)]


# -- the deployment's file and the entries --------------------------------

def test_the_tiered_deployment_keeps_c2m_10ks_shapes():
    base = json.loads((BENCH_DIR / "configs" / "c2m-10k.json").read_text())
    for key in ("nodes", "datacenters", "node", "allocs_per_node",
                "reduced", "servers", "raft", "task_execution"):
        assert TIERS[key] == base[key], key
    classes = TIERS["job_classes"]
    assert {n: c["priority"] for n, c in classes.items()} == {
        "batch": 20, "service": 50, "production": 70}
    for c in classes.values():
        assert c["ask"] == base["ask"]
        assert c["constraints"] == base["constraints"]
        assert c["spread"] == base["spread"]
    assert classes["batch"]["type"] == "batch"
    assert TIERS["architecture"] is None and "packing_share" not in TIERS
    assert [(s["job_class"], s["fill_share"], s["count"])
            for s in TIERS["standing"]] == [
        ("service", 0.5, 1000), ("batch", 0.5, 1000)]
    assert TIERS["may_remain"] == ["blocked_evals"]
    rules = [g["rule"] for g in TIERS["guarantees"]]
    assert "acked_jobs_held" not in rules
    assert set(rules) == {
        "standing_held_or_evicted", "preemption_lowest_first",
        "unique_allocs", "node_capacity", "job_feasibility",
        "asks_carried", "watch_visibility"}
    for key in ("priorities", "fill", "jobs_of", "cross_node_lowest_first",
                "preemption_enabled_for"):
        assert key in TIERS["assumed"], key


def test_the_six_rules_stand_and_every_rule_file_is_named():
    """What test_bench_failure_rules pinned by the directory's listing,
    without the listing: PR 26's six rules exist, `c1m-5k` and `c2m-10k`
    name exactly those, `store_check.py` is gone — and no rule file lies
    about that no configuration names."""
    six = ["acked_jobs_held", "unique_allocs", "node_capacity",
           "job_feasibility", "asks_carried", "watch_visibility"]
    on_disk = {p.stem for p in (BENCH_DIR / spec.RULES).glob("*.py")}
    assert set(six) <= on_disk
    assert not (BENCH_DIR / "reference" / "store_check.py").exists()
    named = set()
    for entry in BENCH["configs"]:
        config = json.loads((ROOT / entry["file"]).read_text())
        spec.check_config(config, BENCH_DIR)
        named |= {g["rule"] for g in config["guarantees"]}
        if entry["name"] in ("c1m-5k", "c2m-10k"):
            assert [g["rule"] for g in config["guarantees"]] == six
    assert on_disk == named


def test_the_cell_is_an_entry_appended_with_the_metrics_the_issue_names():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[FILL]["chips"] == 1
    assert (cells[FILL]["config"], cells[FILL]["traffic"]) == (
        "c2m-10k-tiers", "preempt-fill")
    assert [w["name"] for w in BENCH["workloads"]][-1] == FILL  # appended
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["placements_per_s"]["workloads"][-1] == FILL
    assert FILL not in e2e["packing_share"]["workloads"]
    assert FILL not in e2e["e2e_p50_ms"]["workloads"]
    mine = [m for m in BENCH["per_layer"] if FILL in m.get("workloads", ())]
    assert BENCH["per_layer"][-len(mine):] == mine  # appended, at the end
    assert all(m["workloads"] == [FILL] and m["moves"] == "placements_per_s"
               for m in mine)
    issue = {
        "kernel_ms_per_solve.preempt-fill",
        "solve_placement_preempt_roofline",
        "prefix_ms_per_solve.preempt-fill",
        "readback_ms_per_solve.preempt-fill",
        "victims_ms_per_solve.preempt-fill",
        "evictions_per_placement.preempt-fill",
        "lane_p50_ms.preempt-fill", "plan_submit_p50_ms.preempt-fill",
        "device_idle_share.preempt-fill",
        "compiles_in_window.preempt-fill"}
    # and the layers the issue's ten left dark on this cell: the two
    # largest of a solve (lower, materialize) and the shared path's
    review = {
        "lower_ms_per_solve.preempt-fill",
        "lower_table_ms_per_solve.preempt-fill",
        "materialize_ms_per_solve.preempt-fill",
        "register_p50_ms.preempt-fill", "broker_wait_p50_ms.preempt-fill",
        "raft_apply_p50_ms.preempt-fill", "watch_fanout_p95_ms.preempt-fill"}
    assert {m["name"] for m in mine} == issue | review
    metrics_dir = BENCH_DIR / "layer_metrics"
    for name in review:
        mine_file = json.loads((metrics_dir / f"{name}.json").read_text())
        assert mine_file["traffic"] == ["preempt-fill"]
        twin = metrics_dir / name.replace(".preempt-fill", ".bulk.json")
        if twin.exists():  # the shared path reads as it does on `bulk`
            theirs = json.loads(twin.read_text())
            assert (mine_file["reads"], mine_file["reducer"]) == (
                theirs["reads"], theirs["reducer"])
        else:  # a solve's own stages: a span's sum over the solves
            assert mine_file["per"] == {"spans": ["preempt.prefix"]}


# -- the roofline's bytes and the new reducers ----------------------------

def test_the_preempt_kernels_bytes_by_hand():
    # 57 read + 32 written a node a group, 12 more for each tier
    assert kernel_cost_preempt.preempt_solve_bytes(10, 1, 0) == 890
    assert kernel_cost_preempt.preempt_solve_bytes(10_000, 4, 2) == \
        (89 + 24) * 10_000 * 4
    assert kernel_cost_preempt.preemptible_tiers(TIERS) == 2
    assert kernel_cost_preempt.preemptible_tiers({"ask": {}}) == 0
    close = {"job_classes": {"a": {"priority": 65}, "b": {"priority": 70}}}
    assert kernel_cost_preempt.preemptible_tiers(close) == 0


def reducer(name: str):
    return spec.load_module("reducers", name)


def metric_file(name: str) -> dict:
    return json.loads(
        (BENCH_DIR / "layer_metrics" / f"{name}.json").read_text())


def test_the_preempt_roofline_reads_the_kernels_own_solves():
    f = metric_file("solve_placement_preempt_roofline")
    ctx = {"config": TIERS, "device_kind": "TPU v5 lite"}
    samples = {
        "device": {"modules": {"solve_placement_preempt": [0.001, 0.001]}},
        "timings": {"nomad.tpu.preempt.groups": [4, 4],
                    # the compact kernel's solves are not this kernel's
                    "nomad.tpu.solve_groups": [4, 4, 256]},
    }
    need = 2 * (89 + 24) * 10_000 * 4
    got = reducer(f["reducer"]).reduce(samples, f, ctx)
    assert got == pytest.approx(100.0 * (need / 819e9) / 0.002)
    assert got < 100.0
    # a program without the counter, or a trace without the module
    assert reducer(f["reducer"]).reduce(
        {**samples, "timings": {}}, f, ctx) is None
    assert reducer(f["reducer"]).reduce(
        {**samples, "device": {"modules": {}}}, f, ctx) is None
    assert reducer(f["reducer"]).reduce({"timings": {}}, f, ctx) is None


def test_counter_ratio_reads_what_moved_in_the_window():
    f = metric_file("evictions_per_placement.preempt-fill")
    red = reducer(f["reducer"])
    both = {"counters": {"nomad.tpu.preempt.evicted": 3000,
                         "nomad.tpu.preempt.placed": 3000}}
    assert red.reduce(both, f, {}) == 1.0
    assert red.reduce({"counters": {}}, f, {}) is None  # the parent
    # a numerator that did not move is a reading, 0; scale multiplies
    share = {"reads": "a", "over": "b", "scale": 100.0}
    assert red.reduce({"counters": {"b": 200}}, share, {}) == 0.0
    assert red.reduce({"counters": {"b": 200, "a": 5}}, share, {}) == 2.5
