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
        assert w["chips"] == 1
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
