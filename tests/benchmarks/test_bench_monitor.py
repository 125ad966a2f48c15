"""The Borg cell in all four of its bands (`borg2011-12k-monitor`) and its
cell `prod-lanes`: the fleet and the standing load are
`borg2011-12k-bands`' for every seed, the monitoring band's classes, the
room the background needs, the traffic's two streams, the shapes set-up
warms (the lane's program among them), the entries appended to
BENCHMARK.json, the cell rehearsed end to end, and two faults planted
under the timed path, each found by the rule that names it alone.
"""

import json
import time
from collections import Counter
from pathlib import Path

import pytest

from bench_helpers_monitor import plant
from benchmarks import run as bench_run
from benchmarks.harness import cluster, spec

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "benchmarks"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
BANDS = json.loads(
    (BENCH_DIR / "configs" / "borg2011-12k-bands.json").read_text())
MONITOR = json.loads(
    (BENCH_DIR / "configs" / "borg2011-12k-monitor.json").read_text())
PROD = json.loads((BENCH_DIR / "traffic" / "prod-backlog.json").read_text())
LANES = json.loads((BENCH_DIR / "traffic" / "prod-lanes.json").read_text())
CELL = "borg2011-12k-monitor.prod-lanes"
banded_lanes = spec.load_module("generators", "banded_lanes")


# -- the deployment's file -------------------------------------------------

def test_everything_but_the_monitoring_band_is_the_bands_cells():
    for key in ("nodes", "datacenters", "node_classes", "standing",
                "may_remain", "preemption", "chips", "servers", "raft",
                "task_execution", "heartbeats"):
        assert MONITOR[key] == BANDS[key], key
    assert [g["rule"] for g in MONITOR["guarantees"]] == [
        g["rule"] for g in BANDS["guarantees"]]
    added = {n: c for n, c in MONITOR["job_classes"].items()
             if n not in BANDS["job_classes"]}
    assert {n: c for n, c in MONITOR["job_classes"].items()
            if n in BANDS["job_classes"]} == BANDS["job_classes"]
    assert sorted(added) == ["monitoring-sand", "monitoring-small"]
    for name, ask, spread in (("monitoring-sand", (400, 512), None),
                              ("monitoring-small", (800, 1024),
                               {"attribute": "datacenter", "weight": 50})):
        c = added[name]
        assert (c["type"], c["priority"], c["band"]) == (
            "service", 70, "monitoring")
        assert (c["ask"]["cpu_mhz"], c["ask"]["memory_mb"],
                c["ask"]["disk_mb"]) == (*ask, 300)
        assert c["constraints"] == [{"attribute": "kernel.name",
                                     "operand": "=", "value": "linux"}]
        assert c.get("spread") == spread
    # two deltas over production, over the worker's lane priority
    assert 70 - 50 >= 2 * MONITOR["preemption"]["priority_delta"]
    assert 70 >= 60
    assert MONITOR["reduced"] == [
        r for r in BANDS["reduced"] if r != "monitoring_band"]
    assert sorted(MONITOR["reduced_why"]) == sorted(MONITOR["reduced"])
    for key in ("monitoring_band", "monitoring_fill", "monitoring_asks"):
        assert key in MONITOR["assumed"], key
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "borg2011-12k-monitor")
    assert entry == BENCH["configs"][-1]
    assert entry["source"] == MONITOR["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == MONITOR["reduced"]


@pytest.mark.parametrize("seed", [7, 11, 3_000_000_019, 2**31 + 39])
def test_the_fleet_and_the_fill_of_a_seed_are_the_bands_cells(seed):
    def rows(config):
        fleet = cluster.Fleet(None, config, config["nodes"], seed)
        return [(n.id, n.name, n.datacenter, n.node_class, n.resources.cpu,
                 n.resources.memory_mb, n.resources.disk_mb,
                 sorted(n.attributes.items()), n.computed_class)
                for n in fleet.nodes]

    assert rows(MONITOR) == rows(BANDS)

    class _Ctx:  # what run.py's standing load reads of its context
        def __init__(self, config):
            self.seed = seed
            self.config = config

        def new_op(self, job_id, count, kind, job_class=None):
            return (job_id, count, kind, job_class)

    def fill(config):
        """(job, allocs, kind, class) of every standing job, and what the
        class asks: the bodies differ by the ids a job mints."""
        nodes = cluster.Fleet(None, config, config["nodes"], seed).nodes
        return [(op, config["job_classes"][op[3]])
                for op, _body in bench_run._standing_load(
                    config, nodes, _Ctx(config))]

    assert fill(MONITOR) == fill(BANDS)
    assert len(fill(MONITOR)) == 226


def test_the_room_and_the_gratis_band_hold_every_period():
    """The standing load packed as the program packs it (the bands
    cell's test): the usable room and the gratis band hold every period
    the background sends in the window."""
    import test_bench_bands as bands

    periods = int(-(-BENCH["run_seconds"] // LANES["period_s"]))
    assert periods == 20
    cap, used, by_band, family = bands.packed()
    per = Counter()
    for ask, count in LANES["period"]:
        per[ask] += count
    asks = {a: MONITOR["job_classes"][f"production-{a}"]["ask"] for a in per}
    cpu = periods * sum(n * asks[a]["cpu_mhz"] for a, n in per.items())
    assert cpu == 34_336_000
    free = cap - used
    sand = MONITOR["job_classes"]["production-sand"]["ask"]
    units = (free // [sand["cpu_mhz"], sand["memory_mb"], sand["disk_mb"]]
             ).min(axis=1)
    usable = int(units.sum()) * sand["cpu_mhz"]
    gratis = int(by_band["gratis"][:, 0].sum())
    assert usable + gratis >= cpu
    assert 0.25 <= usable / cpu <= 0.35
    assert gratis >= 1.75 * (cpu - usable)
    assert "34,336,000 MHz" in MONITOR["assumed"]["free_room"]
    assert "34,336,000 MHz" in LANES["assumed"]["period_s"]
    # the usable room is spent in the window's first third or so
    per_s = cpu / periods / LANES["period_s"]
    assert 0.25 < usable / per_s / BENCH["run_seconds"] < 0.4


# -- the traffic -------------------------------------------------------------

def test_the_background_is_prod_backlogs_period_paced():
    assert LANES["generator"] == "banded_lanes"
    assert LANES["period"] == PROD["period"] and LANES["submitters"] == 4
    assert LANES["band"] == "production" and LANES["period_s"] == 1.5
    assert round(sum(n for _, n in LANES["period"]) / LANES["period_s"]) \
        == 2195
    jobs = banded_lanes.background(11, LANES, 20)
    assert len(jobs) == 800
    for k in range(20):
        assert Counter(jobs[k * 40:(k + 1) * 40]) == Counter(
            (f"production-{a}", n) for a, n in PROD["period"])
    cell = BENCH["workloads"][-1]
    assert cell["name"] == CELL and "2,195 allocs/s" in cell["why"]


@pytest.mark.parametrize("params", [LANES, {**LANES, **LANES["rehearsal"]}],
                         ids=["cell", "rehearsal"])
def test_every_seed_deals_the_lane_the_same_multiset(params):
    a, b = (banded_lanes.lane(s, params, 90) for s in (5, 3_000_000_019))
    assert Counter(a) == Counter(b) and a != b
    assert Counter(jc for jc, _ in a) == {"monitoring-sand": 45,
                                          "monitoring-small": 45}
    assert {n for _, n in a} == set(range(2, 13))
    assert LANES["lane_per_s"] == 3


def test_shapes_covers_the_lanes_program():
    from nomad_tpu.scheduler.tpu.kernels import pad_g, preempt_programs

    shapes = banded_lanes.shapes(LANES, MONITOR)
    lane = [s for s in shapes if s["job_class"].startswith("monitoring-")]
    assert [s["job_class"] for s in lane] == LANES["lane_classes"]
    for s in lane:
        c = MONITOR["job_classes"][s["job_class"]]
        groups = len(MONITOR["datacenters"]) if c.get("spread") else 1
        assert s["evals"] == 1 and s["priority"] == 70
        assert s["count"] == LANES["lane_counts"][1]
        # gp 8 at the three-tier bucket: the lane's 10, 30, 50
        assert (pad_g(groups), 4) in preempt_programs()
    # and the background's, as prod-backlog warms them
    banded_backlog = spec.load_module("generators", "banded_backlog")
    assert shapes[:-len(lane)] == banded_backlog.shapes(
        {**PROD, **{k: LANES[k] for k in ("band", "period")}}, MONITOR)
    warm = banded_lanes.warm_jobs(LANES)
    assert warm == [(12, "monitoring-sand", 70), (12, "monitoring-small", 70)]


# -- BENCHMARK.json ----------------------------------------------------------

NAMES = ["lane_queue_p50_ms", "lane_p50_ms", "lane_commit_p50_ms",
         "lane_in_wait_share", "lane_trimmed_share",
         "chain_wait_ms_per_batch", "kernel_ms_per_batch",
         "higher_band_victims_share", "programs_new", "compiles_in_window",
         "device_idle_share"]


def test_the_cell_and_its_metrics_are_appended():
    assert BENCH["workloads"][-1] == {
        "name": CELL, "config": "borg2011-12k-monitor",
        "traffic": "prod-lanes", "chips": 1,
        "why": BENCH["workloads"][-1]["why"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["e2e_p50_ms"]["workloads"][-1] == CELL
    assert CELL not in e2e["placements_per_s"]["workloads"]
    assert CELL not in e2e["packing_share"]["workloads"]
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    assert BENCH["per_layer"][-len(mine):] == mine
    assert [m["name"] for m in mine] == [f"{n}.prod-lanes" for n in NAMES]
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "e2e_p50_ms"
        f = json.loads((BENCH_DIR / "layer_metrics"
                        / f"{m['name']}.json").read_text())
        assert f["traffic"] == ["prod-lanes"]
        twin = BENCH_DIR / "layer_metrics" / m["name"].replace(
            ".prod-lanes", ".prod-backlog.json")
        if twin.exists():  # as prod-backlog's reads it
            theirs = json.loads(twin.read_text())
            assert {k: v for k, v in f.items()
                    if k not in ("name", "traffic", "moves")} == {
                k: v for k, v in theirs.items()
                if k not in ("name", "traffic", "moves")}, m["name"]
            entry = next(e for e in BENCH["per_layer"]
                         if e["name"] == twin.stem)
            assert (m["layer"], m["unit"], m["source"], m["better"]) == (
                entry["layer"], entry["unit"], entry["source"],
                entry["better"])


# -- the cell, rehearsed -----------------------------------------------------

def rehearse(capsys, trace: int, seed: int, seconds: float = 5.0):
    rc = bench_run.main(
        ["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--rehearsal"], time.monotonic())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    report = json.loads(
        (BENCH_DIR / "out" / f"{CELL}.{seed}.json").read_text())
    return json.loads(out[-1]), report


def checks_of(line: dict) -> dict:
    return {k: v["value"] for k, v in line["checks"].items()}


def test_the_rehearsed_window_is_correct(capsys):
    line, report = rehearse(capsys, 1, 3_000_000_039)
    assert line["correct"] is True, report["store_faults"]
    assert all(v == 0 for v in checks_of(line).values())
    assert set(checks_of(line)) == {
        "faults.standing_held_or_evicted", "faults.preemption_bands",
        "faults.unique_allocs", "faults.node_capacity",
        "faults.job_feasibility", "faults.asks_carried",
        "faults.watch_visibility", "failed", "compiles_in_window",
        "left_in_flight"}
    assert report["ops"]["by_kind"]["lane"] == 15  # 3 a second for 5 s
    assert report["ops"]["by_kind"]["job"] == len(
        LANES["rehearsal"]["period"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["compiles_in_window.prod-lanes"] == 0
    assert m["programs_new.prod-lanes"] == 0
    assert 0 <= m["lane_trimmed_share.prod-lanes"] <= 100
    # the lane's deploys of a tiny fleet are the host stack's
    assert report["path_counts"]["host_stack"] >= 15


@pytest.mark.parametrize("fault, rule", [
    ("monitoring_alloc_lost", "standing_held_or_evicted"),
    ("lane_evicts_production", "preemption_bands"),
])
def test_a_planted_fault_is_found_by_its_rule_alone(capsys, fault, rule):
    undo, planted = plant(fault)
    try:
        line, report = rehearse(capsys, 0, 3_000_000_040)
    finally:
        undo()
    assert planted()
    assert line["correct"] is False
    got = checks_of(line)
    assert {k: v for k, v in got.items() if k.startswith("faults.") and v} \
        == {f"faults.{rule}": got[f"faults.{rule}"]}, report["store_faults"]
    assert got["compiles_in_window"] == 0 and got["left_in_flight"] == 0
    if fault == "lane_evicts_production":
        assert any("above the lowest band" in f
                   for f in report["store_faults"])
