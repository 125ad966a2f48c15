"""Every cell of BENCHMARK.json runs end to end in its rehearsal (tiny,
XLA:CPU, in this process: no child that loads jax) and ends its stdout
with the one line the driver reads — exactly the contract's keys."""

import json
import time
from pathlib import Path

import pytest

from bench_helpers import plant, with_candidates
from benchmarks import run as bench_run

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


MERGED = with_candidates(BENCH)
CANDIDATES = [w["name"] for w in MERGED["workloads"] if w["name"] not in CELLS]


@pytest.fixture(scope="module")
def candidate_tree(tmp_path_factory):
    """A copy of benchmarks/ beside a BENCHMARK.json that has the
    candidate cells in it."""
    import shutil

    root = tmp_path_factory.mktemp("with-candidates")
    shutil.copytree(ROOT / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    (root / "benchmarks" / "out").mkdir()
    (root / "BENCHMARK.json").write_text(json.dumps(MERGED))
    return root / "benchmarks"


def rehearse(capsys, cell: str, trace: int, seed: int = 11,
             seconds: float = 1.0, bench_dir=None):
    argv = ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--rehearsal"]
    if bench_dir is not None:
        argv += ["--bench-dir", str(bench_dir)]
    rc = bench_run.main(argv, time.monotonic())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(out[-1])
    report = json.loads(
        (Path(bench_dir or ROOT / "benchmarks") / "out"
         / f"{cell}.{seed}.json").read_text())
    return line, report


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS + CANDIDATES)
def test_cell_rehearsal_prints_the_contracts_line(capsys, cell, trace,
                                                  candidate_tree):
    line, report = rehearse(
        capsys, cell, trace,
        bench_dir=candidate_tree if cell in CANDIDATES else None)
    # `breakdown` only with a device trace; what was compared comes last
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    for name, c in line["checks"].items():
        assert set(c) == {"value", "limit"}
        assert c["value"] == c["limit"], name  # also packing_share: 100
    guarantees = json.loads((ROOT / "benchmarks" / "configs" / (
        cell.rsplit(".", 1)[0] + ".json")).read_text())["guarantees"]
    assert {n for n in line["checks"] if n.startswith("faults.")} == {
        f"faults.{g['rule']}" for g in guarantees}
    assert line["correct"] is True, report
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    assert report["compiles_in_window"] == []
    assert report["failures_by_cause"] == {
        "front_door": 0, "eval_failed": 0, "not_visible": 0}
    names = {m["name"]: m for m in MERGED["end_to_end"] + MERGED["per_layer"]}
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert m["unit"] == names[name]["unit"]
        assert isinstance(m["value"], float)
        # an XLA:CPU second is printed under no name, and nothing that
        # only a device trace can give is reported from the CPU
        assert m["unit"] not in ("ms", "s", "allocs/s")
        assert names[name]["source"] != "device_trace"
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in MERGED[kind]
               if cell in m.get("workloads", CELLS + CANDIDATES)}
    assert set(line["metrics"]) <= allowed
    if trace:
        assert f"compiles_in_window.{cell.rsplit('.', 1)[1]}" in line["metrics"]
    elif cell.endswith(".bulk"):
        assert 0 < line["metrics"]["packing_share"]["value"] <= 100.0


@pytest.mark.parametrize("fault, cell, rule", [
    ("ask_altered", "c1m-5k.deploys", "asks_carried"),
    ("ask_altered", "c2m-10k.bulk", "asks_carried"),
    ("all_on_one_node", "c2m-10k.bulk", "node_capacity"),
    ("all_on_one_node", "c2m-10k.deploys", "node_capacity"),
])
def test_a_fault_planted_under_the_timed_path_reads_correct_false(
        capsys, fault, cell, rule):
    """The control: the program commits an answer altered where it is
    produced (the store takes another grant, or another node, than the
    plan applier verified), the rest of the run is driven as it is, and
    the run's own reference rules find it."""
    undo = plant(fault)
    try:
        line, report = rehearse(capsys, cell, 0, seed=3_000_000_031)
    finally:
        undo()
    assert line["correct"] is False and line["failed"] >= 1
    assert line["checks"][f"faults.{rule}"]["value"] >= 1, report
    assert line["checks"]["compiles_in_window"]["value"] == 0
    # with no fault planted the same cells read `correct` true: the
    # rehearsals above


def test_one_operator_puts_one_eval_in_every_batch(capsys):
    _, report = rehearse(capsys, "c1m-5k.deploys", 1, seed=2_999_999_999)
    assert report["max_batch_evals"] == 1
    assert report["ops"]["by_kind"]["small"] > report["ops"]["by_kind"].get(
        "rollout", 0) > 0
    # the path follows from the job's size: every rollout took the
    # kernel, every small deploy the microsolve
    assert report["path_counts"]["kernel"] == report["ops"]["by_kind"]["rollout"]
    assert report["path_counts"]["micro"] == report["ops"]["by_kind"]["small"]
    assert report["path_counts"]["host_stack"] == 0


def test_without_a_chip_it_refuses_and_prints_no_result(capsys, monkeypatch):
    """Off the rehearsal the CPU is no device to measure on."""
    rc = bench_run.main(["--workload", CELLS[0], "--seconds", "1"],
                        time.monotonic())
    captured = capsys.readouterr()
    assert rc != 0
    assert captured.out.strip() == ""
    assert "no TPU" in captured.err


def test_an_unknown_cell_is_refused(capsys):
    rc = bench_run.main(["--workload", "no-such.cell", "--rehearsal"],
                        time.monotonic())
    captured = capsys.readouterr()
    assert rc != 0 and captured.out.strip() == ""
    assert "no workload" in captured.err
