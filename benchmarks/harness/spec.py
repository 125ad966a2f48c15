"""Finding a cell's parts by name.

`BENCHMARK.json` names the cells, configurations and metrics; everything
that belongs to one of them is a file of its own under `benchmarks/`,
found by that name. Adding a cell, a configuration, a traffic mix, a
generator, a per-layer metric or a reducer is adding files and one
entry — nothing here, and no file that exists, is edited for it.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class SpecError(Exception):
    """`BENCHMARK.json` or a file it names is missing or malformed."""


def load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"{path}: {e}") from e


def load_module(kind: str, name: str, bench_dir: Path = BENCH_DIR):
    """The module `benchmarks/<kind>/<name>.py` (a generator or a
    reducer), loaded from its file so that a new one needs no import
    line anywhere."""
    if not NAME_RE.match(name):
        raise SpecError(f"bad {kind} name {name!r}")
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no {kind} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('-', '_').replace('.', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict  # the mix's parameters, the cell's own overrides applied
    end_to_end: list[dict]  # BENCHMARK.json entries this cell reports
    per_layer: list[dict]  # the same, each with its file under "file"
    bench_dir: Path = field(default=BENCH_DIR)

    def generator(self):
        return load_module("generators", self.traffic["generator"],
                           self.bench_dir)


def _reports(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def load_cell(name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell `name` of `<bench_dir>/../BENCHMARK.json` with its
    configuration, traffic mix and metrics."""
    bench = load_json(bench_dir.parent / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(
            f"no workload {name!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in bench['workloads']]}"
        )
    cfg_entry = next(
        (c for c in bench["configs"] if c["name"] == entry["config"]), None
    )
    if cfg_entry is None:
        raise SpecError(f"workload {name!r} names no known config")
    config = load_json(bench_dir.parent / cfg_entry["file"])
    traffic = load_json(bench_dir / "traffic" / f"{entry['traffic']}.json")
    # a cell's own parameters (say, the number of jobs of its backlog)
    # override the mix's defaults: benchmarks/cells/<cell>.json, optional
    own = bench_dir / "cells" / f"{name}.json"
    if own.is_file():
        traffic = {**traffic, **load_json(own)}
    per_layer = []
    for m in bench["per_layer"]:
        if not _reports(m, name):
            continue
        path = bench_dir / "layer_metrics" / f"{m['name']}.json"
        per_layer.append({**m, "file": load_json(path)})
    return Cell(
        name=name, chips=int(entry["chips"]),
        config_name=entry["config"], config=config,
        traffic_name=entry["traffic"], traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=per_layer, bench_dir=bench_dir,
    )
