"""The harness takes additions as data, and BENCHMARK.json keeps to what
the driver admits.

A later PR adds a cell, a configuration, a traffic mix or a per-layer
metric by adding files and entries; it may edit no file that is there.
The first test does exactly that in a temporary copy and runs the new
cell."""

import hashlib
import json
import re
import shutil
import time
from pathlib import Path

import pytest

from bench_helpers import with_candidates
from benchmarks import run as bench_run

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
# the candidate cells' entries are held to the same rules as the file's
MERGED = with_candidates(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_cell_is_files_and_entries_and_no_edit(tmp_path, capsys):
    bench_dir = tmp_path / "benchmarks"
    shutil.copytree(ROOT / "benchmarks", bench_dir, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "*.pb"))
    (bench_dir / "out").mkdir()
    before = digest(bench_dir)

    config = json.loads((bench_dir / "configs" / "c1m-5k.json").read_text())
    config.update(name="two-dc", datacenters=["east", "west"], nodes=64)
    (bench_dir / "configs" / "two-dc.json").write_text(json.dumps(config))
    traffic = json.loads((bench_dir / "traffic" / "deploys.json").read_text())
    traffic.update(operators=2)
    (bench_dir / "traffic" / "deploys-c2.json").write_text(json.dumps(traffic))
    (bench_dir / "layer_metrics" / "batch_evals_mean.deploys-c2.json").write_text(
        json.dumps({"name": "batch_evals_mean.deploys-c2", "layer": "worker",
                    "unit": "evals", "moves": "e2e_p50_ms",
                    "traffic": ["deploys-c2"], "reducer": "mean",
                    "reads": {"timings": ["nomad.tpu.batch_evals"]}}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "two-dc", "source": "a test's own", "reduced": [],
        "file": "benchmarks/configs/two-dc.json", "why": "added as data"})
    bench["workloads"].append({
        "name": "two-dc.deploys-c2", "config": "two-dc",
        "traffic": "deploys-c2", "chips": 1, "why": "added as data"})
    bench["per_layer"].append({
        "name": "batch_evals_mean.deploys-c2", "unit": "evals",
        "better": "higher", "source": "program_counter", "layer": "worker",
        "moves": "e2e_p50_ms", "workloads": ["two-dc.deploys-c2"]})
    for m in bench["end_to_end"]:
        if m["name"].startswith("e2e_"):
            m["workloads"].append("two-dc.deploys-c2")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    rc = bench_run.main(
        ["--workload", "two-dc.deploys-c2", "--seed", "4", "--seconds", "1.5",
         "--trace", "1", "--rehearsal", "--bench-dir", str(bench_dir)],
        time.monotonic())
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"batch_evals_mean.deploys-c2"}
    assert 1.0 <= line["metrics"]["batch_evals_mean.deploys-c2"]["value"] <= 2.0
    report = json.loads(
        (bench_dir / "out" / "two-dc.deploys-c2.4.json").read_text())
    assert report["ops"]["sent"] > 2
    after = digest(bench_dir)
    assert {k: after[k] for k in before} == before  # nothing there was edited


CLASS_LOOP = '''"""A closed loop of one operator that deals the mix's `deploys` in turn:
(job class, count, priority) each."""
import time

from benchmarks.harness import jobs


def shapes(params, config):
    return [{"evals": 1, "count": c, "job_class": jc, "priority": p}
            for jc, c, p in params["deploys"]]


def warm_jobs(params):
    return [(c, jc, p) for jc, c, p in params["deploys"]]


def run(ctx):
    deploys = ctx.params["deploys"]
    ctx.open_window()
    i = 0
    while time.monotonic() < ctx.t_end:
        jc, count, priority = deploys[i % len(deploys)]
        job = jobs.make_job(ctx.config, f"d-{ctx.seed}-{i}", count, priority,
                            jc)
        op = ctx.new_op(job.id, count, "small", jc)
        ctx.send(op, jobs.encode(job))
        if op.acked:
            ctx.await_visible(op)
        i += 1
'''

CLASS_CAP_RULE = '''"""A node of a class that states `max_allocs` holds at most that many
live allocs."""
from collections import Counter


def check(snap, expected, config):
    cap = {c["name"]: c["max_allocs"] for c in config["node_classes"]
           if "max_allocs" in c}
    held = Counter(a["node"] for a in snap["allocs"])
    over = [n["id"] for n in snap["nodes"]
            if held[n["id"]] > cap.get(n["class"], held[n["id"]])]
    return [f"{len(over)} nodes hold more allocs than their class admits, "
            f"e.g. {over[0]}"] if over else []
'''

WEB = {"cpu_mhz": 250, "memory_mb": 128, "disk_mb": 300}
BATCH = {"cpu_mhz": 1000, "memory_mb": 4096, "disk_mb": 300}


def two_class_deployment(small_cap: int) -> dict:
    linux = {"kernel.name": "linux"}
    base = json.loads((ROOT / "benchmarks" / "configs" / "c2m-10k.json")
                      .read_text())
    return {
        "name": "two-class", "source": "a test's own", "chips": 1,
        "nodes": 256, "datacenters": ["east", "west"],
        "node_classes": [
            {"name": "small", "share": 0.75, "cpu_mhz": 4000,
             "memory_mb": 8192, "disk_mb": 102400, "attributes": linux,
             "max_allocs": small_cap},
            {"name": "large", "share": 0.25, "cpu_mhz": 16000,
             "memory_mb": 65536, "disk_mb": 204800, "attributes": linux,
             "datacenters": ["east"]}],
        "job_classes": {
            "web": {"ask": WEB, "spread": base["spread"],
                    "constraints": base["constraints"]},
            "batch": {"ask": BATCH, "type": "batch", "priority": 30}},
        "standing": [
            {"job_class": "batch", "fill_share": 0.25, "count": 16},
            {"job_class": "web", "jobs": 2, "count": 12, "priority": 60}],
        "may_remain": ["blocked_evals"],
        "guarantees": base["guarantees"] + [
            {"rule": "class_alloc_cap",
             "says": "a small node holds at most its class's max_allocs"}],
        "reduced": [], "assumed": {},
    }


@pytest.mark.parametrize("small_cap, sound", [(16, True), (0, False)])
def test_a_new_deployment_is_files_and_entries_and_no_edit(
        tmp_path, capsys, small_cap, sound):
    """Two node classes, two job classes of different asks, standing
    load, a further rule and `may_remain`: a deployment that is not C1M
    at other numbers is still files and entries. With the added rule's
    limit set where the placement must break it, `correct` is false."""
    bench_dir = tmp_path / "benchmarks"
    shutil.copytree(ROOT / "benchmarks", bench_dir, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "*.pb"))
    (bench_dir / "out").mkdir()
    before = digest(bench_dir)

    config = two_class_deployment(small_cap)
    (bench_dir / "configs" / "two-class.json").write_text(json.dumps(config))
    (bench_dir / "generators" / "class_loop.py").write_text(CLASS_LOOP)
    (bench_dir / "reference" / "rules" / "class_alloc_cap.py").write_text(
        CLASS_CAP_RULE)
    (bench_dir / "traffic" / "mixed.json").write_text(json.dumps({
        "generator": "class_loop", "priority": 50,
        "deploys": [["web", 8, 50], ["batch", 3, None], ["web", 40, 70]]}))
    (bench_dir / "layer_metrics" / "batch_evals_mean.mixed.json").write_text(
        json.dumps({"name": "batch_evals_mean.mixed", "layer": "worker",
                    "unit": "evals", "moves": "e2e_p50_ms",
                    "traffic": ["mixed"], "reducer": "mean",
                    "reads": {"timings": ["nomad.tpu.batch_evals"]}}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "two-class", "source": "a test's own", "reduced": [],
        "file": "benchmarks/configs/two-class.json", "why": "added as data"})
    bench["workloads"].append({
        "name": "two-class.mixed", "config": "two-class", "traffic": "mixed",
        "chips": 1, "why": "added as data"})
    bench["per_layer"].append({
        "name": "batch_evals_mean.mixed", "unit": "evals",
        "better": "higher", "source": "program_counter", "layer": "worker",
        "moves": "e2e_p50_ms", "workloads": ["two-class.mixed"]})
    for m in bench["end_to_end"]:
        if m["name"].startswith("e2e_"):
            m["workloads"].append("two-class.mixed")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    rc = bench_run.main(
        ["--workload", "two-class.mixed", "--seed", "3000000021", "--seconds",
         "1.5", "--trace", "1", "--rehearsal", "--bench-dir", str(bench_dir)],
        time.monotonic())
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    report = json.loads(
        (bench_dir / "out" / "two-class.mixed.3000000021.json").read_text())
    assert rc == 0
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {
        f"faults.{g['rule']}" for g in config["guarantees"]} | {
        "failed", "compiles_in_window", "left_in_flight"}
    if sound:
        assert line["correct"] is True and line["failed"] == 0, report
        assert all(c["value"] == c["limit"] for c in line["checks"].values())
    else:
        assert line["correct"] is False and line["failed"] >= 1
        assert line["checks"]["faults.class_alloc_cap"] == {
            "value": 1, "limit": 0}
        assert any("more allocs than their class admits" in f
                   for f in report["store_faults"])
        # the rule that was made to fail is the only one that speaks
        assert len(report["store_faults"]) == 1
    assert set(line["metrics"]) == {"batch_evals_mean.mixed"}
    # the standing load went through the front door during set-up: 192
    # small nodes hold 2 batch asks (by memory) and 64 large ones 16, a
    # quarter of 1,408 is 352 allocs, 22 jobs of 16; and two web jobs
    assert report["setup"]["standing_jobs"] == 24
    assert report["setup"]["registered_s"] <= report["setup"]["standing_s"] \
        <= report["setup"]["warmed_s"]
    assert report["ops"]["by_kind"]["small"] >= 3
    # no arithmetic ideal where nodes or asks differ: no packing_share
    assert report["packing"] is None
    after = digest(bench_dir)
    assert {k: after[k] for k in before} == before  # nothing there was edited


def test_benchmark_json_has_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["command"]) <= 32
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        on_file = json.loads((ROOT / c["file"]).read_text())
        assert sorted(on_file["reduced"]) == sorted(c["reduced"])
        for key in ("source", "reduced", "assumed", "guarantees", "chips"):
            assert key in on_file, (c["name"], key)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    # four chips only where one chip's runs spread too widely to be
    # admitted (PERF.md, section 2): the backlog of the north-star shape
    assert [w["name"] for w in BENCH["workloads"] if w["chips"] == 4] == [
        "c2m-10k.bulk"]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES


def test_every_name_and_unit_holds_only_what_the_driver_admits():
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MERGED[kind]:
            names.append(entry["name"])
            assert NAME.match(entry["name"]), entry["name"]
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200
                    assert "\n" not in entry[key] and "\t" not in entry[key]
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry
                assert entry["better"] in ("lower", "higher")
    for w in MERGED["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    metrics = [m["name"] for m in MERGED["end_to_end"] + MERGED["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for p in MERGED["paths"]:
        for f in (ROOT / p).rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts and \
                    f.parent.name != "out":
                assert PATH.match(str(f.relative_to(ROOT))), f
    # a roofline share is named <kernel>_roofline, in %
    for m in MERGED["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


def test_every_cell_reports_what_it_must_and_every_arrow_lands():
    cells = [w["name"] for w in MERGED["workloads"]]
    configs = {c["name"] for c in MERGED["configs"]}
    assert {w["config"] for w in MERGED["workloads"]} == configs
    pairs = [(w["config"], w["traffic"]) for w in MERGED["workloads"]]
    assert len(set(pairs)) == len(pairs)

    def reported_in(m):
        got = m.get("workloads", cells)
        assert set(got) <= set(cells), m["name"]
        return set(got)

    e2e = {m["name"]: reported_in(m) for m in MERGED["end_to_end"]}
    assert e2e["setup_s"] == set(cells)
    traffic_of = {w["name"]: w["traffic"] for w in MERGED["workloads"]}
    layers = set()
    for m in MERGED["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert reported_in(m) <= e2e[m["moves"]], m["name"]
        # the metric's own file says the same: layer, unit, arrow, and
        # the traffic whose cells report it
        on_file = json.loads((ROOT / "benchmarks" / "layer_metrics"
                              / f"{m['name']}.json").read_text())
        for key in ("name", "layer", "unit", "moves"):
            assert on_file[key] == m[key], (m["name"], key)
        assert {traffic_of[c] for c in reported_in(m)} == set(on_file["traffic"])
        assert (ROOT / "benchmarks" / "reducers"
                / f"{on_file['reducer']}.py").is_file()
        layers.add(m["layer"])
    for cell in cells:
        assert sum(cell in got for n, got in e2e.items() if n != "setup_s") >= 1
        assert any(cell in reported_in(m) for m in MERGED["per_layer"])
    # the layers are the ones PERF.md lists, letter for letter
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert f"| {layer} |" in perf, layer
