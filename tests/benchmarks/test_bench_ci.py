"""The C1M cluster half full, deployed to by a CI system's four pipelines
(`c1m-5k-ci`), and its cell `deploys-c4`: the fleet is `c1m-5k`'s for
every seed, the standing half is 500 jobs of 1,000, the traffic is
`deploys`' with four operators, the shapes set-up warms cover every
program a batch of the operators' deploys or a retried partial count can
reach, the entries are appended to BENCHMARK.json (found by name), the
cell rehearses end to end, and a planted fault — the host paths offering
no chain — is seen by `trimmed_plans_share`.
"""

import json
import shutil
import time
from pathlib import Path

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import cluster, spec

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "benchmarks"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
C1M = json.loads((BENCH_DIR / "configs" / "c1m-5k.json").read_text())
CI = json.loads((BENCH_DIR / "configs" / "c1m-5k-ci.json").read_text())
DEPLOYS = json.loads((BENCH_DIR / "traffic" / "deploys.json").read_text())
C4 = json.loads((BENCH_DIR / "traffic" / "deploys-c4.json").read_text())
CELL = "c1m-5k-ci.deploys-c4"
mixed = spec.load_module("generators", "closed_loop_mixed")
NAMES = ["batch_evals_mean", "drain_p50_ms", "host_chain_share",
         "trimmed_plans_share", "small_solve_p50_ms", "compiles_in_window"]


def by_name(rows: list, name: str) -> dict:
    return next(r for r in rows if r["name"] == name)


# -- the deployment's file ---------------------------------------------------

def test_the_cluster_is_c1m_5ks_letter_for_letter_and_half_full():
    for key in ("nodes", "datacenters", "node", "ask", "allocs_per_node",
                "constraints", "spread", "guarantees", "chips", "servers",
                "raft", "task_execution", "heartbeats", "reduced"):
        assert CI[key] == C1M[key], key
    assert [g["rule"] for g in CI["guarantees"]] == [
        "acked_jobs_held", "unique_allocs", "node_capacity",
        "job_feasibility", "asks_carried", "watch_visibility"]
    assert CI["standing"] == [{"fill_share": 0.5, "count": 1000,
                               "priority": 50}]
    assert sorted(CI["reduced_why"]) == sorted(CI["reduced"])
    for key in ("fill", "pipelines"):
        assert key in CI["assumed"], key
    assert "hashicorp.com/c1m" in CI["source"]
    entry = by_name(BENCH["configs"], "c1m-5k-ci")
    assert entry["file"] == "benchmarks/configs/c1m-5k-ci.json"
    assert entry["source"] == CI["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == CI["reduced"]


@pytest.mark.parametrize("seed", [7, 11, 3_000_000_019, 2**31 + 39])
def test_the_fleet_of_a_seed_is_c1m_5ks(seed):
    def rows(config):
        fleet = cluster.Fleet(None, config, config["nodes"], seed)
        return [(n.id, n.name, n.datacenter, n.node_class, n.resources.cpu,
                 n.resources.memory_mb, n.resources.disk_mb,
                 sorted(n.attributes.items()), n.computed_class)
                for n in fleet.nodes]

    assert rows(CI) == rows(C1M)


def test_the_standing_half_is_500_jobs_of_1000():
    class _Ctx:  # what run.py's standing load reads of its context
        seed = 5
        config = CI

        def new_op(self, job_id, count, kind, job_class=None):
            return (job_id, count, kind, job_class)

    nodes = cluster.Fleet(None, CI, CI["nodes"], 5).nodes
    ops = [op for op, _body in bench_run._standing_load(CI, nodes, _Ctx())]
    assert len(ops) == 500
    assert {(count, kind) for _, count, kind, _ in ops} == {
        (1000, "standing")}
    assert 500 * 1000 == CI["nodes"] * CI["allocs_per_node"] // 2


# -- the traffic and what set-up warms ---------------------------------------

def test_the_traffic_is_deploys_with_four_operators():
    for key in ("priority", "period", "small_counts", "rollouts_per_period",
                "rollout_counts", "rehearsal"):
        assert C4[key] == DEPLOYS[key], key
    assert C4["operators"] == 4 and DEPLOYS["operators"] == 1
    assert C4["generator"] == "closed_loop_mixed"
    assert not any("rate" in k for k in C4)  # a closed loop sets its own
    closed_loop = spec.load_module("generators", "closed_loop")
    for name in ("run", "sizes", "warm_jobs"):  # closed_loop's own
        fn = getattr(mixed, name)
        assert (fn.__name__, fn.__code__.co_filename) == (
            name, closed_loop.__file__)
    assert mixed.warm_jobs(C4) == closed_loop.warm_jobs(C4)


def _program(counts: list) -> tuple:
    """The compact program a batch of one-group deploys of `counts`
    lands in, as the solver picks it: the group rung, and the instance
    rung of the largest (the readback bound with room to spare)."""
    from nomad_tpu.scheduler.tpu.kernels import pad_c, pad_g

    return pad_g(len(counts)), pad_c(max(counts))


@pytest.mark.parametrize("params", [C4, {**C4, **C4["rehearsal"]}],
                         ids=["cell", "rehearsal"])
def test_shapes_cover_every_program_a_batch_or_a_retry_reaches(params):
    from nomad_tpu.scheduler.context import SchedulerConfig

    threshold = SchedulerConfig().small_batch_threshold
    shapes = mixed.shapes(params, CI)
    warmed = {_program([s["count"]] * s["evals"]) for s in shapes
              if s["evals"] * s["count"] > threshold}
    largest = max(params["rollout_counts"])
    # a batch of k evals whose largest is c (a retried partial count is
    # any count up to the largest deploy) is the kernel's when it can
    # ask more than the small-batch threshold
    reached = {_program([c] * k)
               for k in range(1, params["operators"] + 1)
               for c in range(1, largest + 1) if k * c > threshold}
    assert reached <= warmed, reached - warmed
    # and one batch of a small deploy from every operator
    assert {"evals": params["operators"],
            "count": min(params["small_counts"])} in shapes


# -- BENCHMARK.json ----------------------------------------------------------

def test_the_cell_and_its_metrics_are_appended():
    cell = by_name(BENCH["workloads"], CELL)
    assert cell == {"name": CELL, "config": "c1m-5k-ci",
                    "traffic": "deploys-c4", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["e2e_p50_ms"]["workloads"]
    assert CELL not in e2e["placements_per_s"]["workloads"]
    assert CELL not in e2e["packing_share"]["workloads"]
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    assert [m["name"] for m in mine] == [f"{n}.deploys-c4" for n in NAMES]
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "e2e_p50_ms"
        f = json.loads((BENCH_DIR / "layer_metrics"
                        / f"{m['name']}.json").read_text())
        assert f["traffic"] == ["deploys-c4"]
        assert (f["name"], f["layer"], f["unit"], f["moves"]) == (
            m["name"], m["layer"], m["unit"], m["moves"])
        for twin in ("deploys", "bulk", "prod-lanes"):
            path = BENCH_DIR / "layer_metrics" / m["name"].replace(
                ".deploys-c4", f".{twin}.json")
            if path.exists():  # read as its twin reads it
                theirs = json.loads(path.read_text())
                keep = ("reducer", "reads", "among", "per", "scale", "what")
                assert {k: f.get(k) for k in keep} == {
                    k: theirs.get(k) for k in keep}, m["name"]
                break
        else:
            assert m["name"] in ("host_chain_share.deploys-c4",
                                 "trimmed_plans_share.deploys-c4")


@pytest.mark.parametrize("name", NAMES)
def test_each_metric_is_read_by_a_reducer_the_benchmark_has(name):
    f = json.loads((BENCH_DIR / "layer_metrics"
                    / f"{name}.deploys-c4.json").read_text())
    assert (BENCH_DIR / "reducers" / f"{f['reducer']}.py").is_file()
    assert callable(spec.load_module("reducers", f["reducer"]).reduce)


def test_the_per_layer_list_stays_within_its_limits():
    assert 1 <= len(BENCH["per_layer"]) <= 128
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


# -- the cell, rehearsed -----------------------------------------------------

def rehearse(capsys, seed: int, seconds: float, bench_dir=BENCH_DIR):
    rc = bench_run.main(
        ["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", "1", "--rehearsal", "--bench-dir", str(bench_dir)],
        time.monotonic())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    report = json.loads(
        (Path(bench_dir) / "out" / f"{CELL}.{seed}.json").read_text())
    return json.loads(out[-1]), report


def metrics_of(line: dict) -> dict:
    return {k: v["value"] for k, v in line["metrics"].items()}


def test_the_rehearsed_window_is_correct(capsys):
    line, report = rehearse(capsys, 3_000_000_041, 5.0)
    assert line["correct"] is True, report["store_faults"]
    assert all(c["value"] == c["limit"] for c in line["checks"].values())
    m = metrics_of(line)
    assert m["compiles_in_window.deploys-c4"] == 0
    assert m["trimmed_plans_share.deploys-c4"] == 0
    assert 1 < m["batch_evals_mean.deploys-c4"] <= 4  # the drain coalesces
    assert 0 <= m["host_chain_share.deploys-c4"] <= 100
    assert report["setup"]["standing_jobs"] == 25  # half of 256 nodes
    assert report["ops"]["by_kind"]["small"] > 0
    assert report["ops"]["by_kind"]["rollout"] > 0
    # the paths' counts, which the cell carries in its report rather
    # than as metrics of its own
    assert set(report["path_counts"]) == {"kernel", "micro", "host_stack"}
    assert sum(report["path_counts"].values()) > 0


@pytest.fixture
def tight_tree(tmp_path):
    """A copy of benchmarks/ whose rehearsal sends small deploys of 40-48
    allocs: four fill a 256-node rehearsal's node, so two small batches
    that place blind onto the same node overflow it."""
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    (tmp_path / "benchmarks" / "out").mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    path = tmp_path / "benchmarks" / "traffic" / "deploys-c4.json"
    traffic = json.loads(path.read_text())
    traffic["rehearsal"] = {**traffic["rehearsal"],
                            "small_counts": [40, 44, 48]}
    path.write_text(json.dumps(traffic))
    return tmp_path / "benchmarks"


@pytest.mark.parametrize("fault", [False, True],
                         ids=["chained", "host_paths_offer_no_chain"])
def test_a_host_path_batch_blind_to_the_one_in_flight_is_seen_trimmed(
        capsys, monkeypatch, tight_tree, fault):
    """Without the drain's straggler wait every batch is solved beside
    the one before it. Chained, nothing collides; with the host paths
    offering no chain, a small batch tops up the node the batch in
    flight just filled, and the applier trims it."""
    from nomad_tpu.scheduler.tpu import solver
    from nomad_tpu.server import worker

    monkeypatch.setattr(worker, "STRAGGLER_WAIT_S", 0)
    if fault:
        monkeypatch.setattr(solver.BatchSolver, "_offer_rows",
                            lambda self, *args: None)
        monkeypatch.setattr(solver.BatchSolver, "_publish_host",
                            lambda self, out: out)
    line, report = rehearse(capsys, 5, 5.0, tight_tree)
    m = metrics_of(line)
    assert m["batch_evals_mean.deploys-c4"] < 2  # batches overlap
    if fault:
        assert m["trimmed_plans_share.deploys-c4"] > 0
        assert report["plans_trimmed"] > 0
    else:
        assert line["correct"] is True, report["store_faults"]
        assert m["trimmed_plans_share.deploys-c4"] == 0
        assert m["host_chain_share.deploys-c4"] > 50
