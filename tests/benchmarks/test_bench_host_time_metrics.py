"""The per-layer metrics that read the program's own account of a
deploy's host time (PR 24): the `ratio_of_sums` reducer on hand-made
samples, each new metric's file reduced from samples shaped as the probe
shapes them, and the rehearsal of a deploy cell, which reports the ones
that are not times."""

import json
import time
from pathlib import Path

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DEPLOY_CELLS = ["c1m-5k.deploys", "c2m-10k.deploys"]
ADDED = [
    "http_handle_p50_ms.deploys", "lower_p50_ms.deploys",
    "worker_idle_ms_per_deploy.deploys", "watch_route_p95_ms.deploys",
    "deploywatch_ms_per_deploy.deploys", "deploywatch_scanned_mean.deploys",
    "solve_offcpu_share.deploys", "apply_offcpu_share.deploys",
]


def metric_file(name: str) -> dict:
    return json.loads(
        (ROOT / "benchmarks" / "layer_metrics" / f"{name}.json").read_text())


def reduce(name: str, samples: dict):
    f = metric_file(name)
    return spec.load_module("reducers", f["reducer"]).reduce(samples, f, {})


RATIO = spec.load_module("reducers", "ratio_of_sums")
OFF, WALL = {"timings": ["off.a", "off.b"]}, {"timings": ["wall.a", "wall.b"]}


@pytest.mark.parametrize("timings, want", [
    # 1 + 2 of 4 + 8 seconds were spent off the processor
    ({"off.a": [1.0], "off.b": [2.0], "wall.a": [4.0], "wall.b": [8.0]},
     25.0),
    # sums, not a mean of ratios: the long span decides
    ({"off.a": [0.0, 9.0], "wall.a": [1.0, 9.0]}, 90.0),
    # one of the names never observed: the others still count
    ({"off.b": [0.5], "wall.b": [2.0]}, 25.0),
    ({"off.a": [0.0], "wall.a": [3.0]}, 0.0),
    ({"off.a": [3.0], "wall.a": [3.0]}, 100.0),
])
def test_ratio_of_sums_is_100_times_sum_over_sum(timings, want):
    got = RATIO.reduce({"timings": timings}, {"reads": OFF, "over": WALL}, {})
    assert isinstance(got, float) and got == pytest.approx(want)


@pytest.mark.parametrize("timings", [
    {},  # nothing observed: the parent commit, or tracing off
    {"off.a": [1.0]},  # no denominator
    {"wall.a": [1.0]},  # no numerator
    {"off.a": [0.0], "wall.a": [0.0]},  # spans of no length
])
def test_ratio_of_sums_with_nothing_to_read_leaves_the_metric_out(timings):
    assert RATIO.reduce({"timings": timings},
                        {"reads": OFF, "over": WALL}, {}) is None


def test_the_offcpu_shares_read_the_cpu_spans_of_their_own_thread_group():
    solve, apply_ = (metric_file(n) for n in ADDED[-2:])
    for f, spans in (
            (solve, {"reconcile", "lower", "host_prep", "micro_solve",
                     "host_solve", "materialize", "plan.assemble"}),
            (apply_, {"plan.verify", "fsm.apply", "raft.encode"})):
        assert {n.removeprefix("nomad.trace.offcpu_seconds.")
                for n in f["reads"]["timings"]} == spans
        assert {n.removeprefix("nomad.trace.wall_seconds.")
                for n in f["over"]["timings"]} == spans
    # a 0.75 ms microsolve that took 15 ms of wall time waited for 95 %
    samples = {"timings": {
        "nomad.trace.offcpu_seconds.micro_solve": [0.01425],
        "nomad.trace.wall_seconds.micro_solve": [0.015],
        "nomad.trace.offcpu_seconds.fsm.apply": [0.0],
        "nomad.trace.wall_seconds.fsm.apply": [0.002]}}
    assert reduce("solve_offcpu_share.deploys", samples) == pytest.approx(95.0)
    assert reduce("apply_offcpu_share.deploys", samples) == 0.0


def test_span_and_timing_metrics_reduce_from_the_probes_samples():
    ms = 1_000_000
    samples = {
        "spans": {
            "http.handle": [(0, 3 * ms), (0, 5 * ms), (0, 4 * ms)],
            "lower": [(10, 10 + 20 * ms), (10, 10 + 22 * ms)],
            "worker.idle": [(0, 6 * ms), (0, 10 * ms)],
            "deploywatch.pass": [(0, 30 * ms)] * 4,
        },
        "timings": {
            "nomad.watch.route_seconds": [0.001] * 19 + [0.5],
            "nomad.deploywatch.scanned": [100, 300],
        },
        "client": {"e2e_s": [0.02, 0.03]},  # two deploys in the window
    }
    assert reduce("http_handle_p50_ms.deploys", samples) == pytest.approx(4.0)
    assert reduce("lower_p50_ms.deploys", samples) == pytest.approx(21.0)
    # per deploy: what the window spent there over the deploys it landed
    assert reduce("worker_idle_ms_per_deploy.deploys", samples) == \
        pytest.approx(8.0)
    assert reduce("deploywatch_ms_per_deploy.deploys", samples) == \
        pytest.approx(60.0)
    assert reduce("deploywatch_scanned_mean.deploys", samples) == \
        pytest.approx(200.0)
    assert 1.0 <= reduce("watch_route_p95_ms.deploys", samples) < 500.0


@pytest.mark.parametrize("name", ADDED)
def test_a_program_without_the_span_or_counter_leaves_the_metric_out(name):
    """The parent commit under this PR's benchmark files: nothing to
    read is no result and no error."""
    assert reduce(name, {"spans": {}, "timings": {},
                         "client": {"e2e_s": [0.02]}}) is None


@pytest.mark.parametrize("name", ADDED)
def test_added_entries_report_in_both_deploy_cells_and_nowhere_else(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == DEPLOY_CELLS
    assert entry["moves"] == "e2e_p50_ms" and entry["better"] == "lower"
    assert entry["source"] in ("program_span", "program_counter")
    # appended: the fourteen accepted entries stand where they stood
    assert BENCH["per_layer"].index(entry) >= 14


@pytest.mark.parametrize("cell", DEPLOY_CELLS)
def test_rehearsal_reports_the_added_metrics_that_are_not_times(capsys, cell):
    rc = bench_run.main(
        ["--workload", cell, "--seed", "24", "--seconds", "1.5",
         "--trace", "1", "--rehearsal"], time.monotonic())
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    for name in ("solve_offcpu_share.deploys", "apply_offcpu_share.deploys"):
        assert got[name]["unit"] == "%"
        assert 0.0 <= got[name]["value"] <= 100.0
    assert got["deploywatch_scanned_mean.deploys"]["unit"] == "deployments"
    # every deploy of the window leaves a deployment the watcher judges
    assert got["deploywatch_scanned_mean.deploys"]["value"] >= 1.0
    assert not set(got) & {n for n in ADDED if "_ms" in n}
