"""Finding a cell's parts by name, and reading a deployment's file.

`BENCHMARK.json` names the cells, configurations and metrics; everything
that belongs to one of them is a file of its own under `benchmarks/`,
found by that name. Adding a cell, a configuration, a traffic mix, a
generator, a per-layer metric, a reducer or a reference rule is adding
files and one entry — nothing here, and no file that exists, is edited
for it.

A configuration says what its cluster is made of (`node`, or
`node_classes`), what its jobs ask (`ask` + `constraints` + `spread`, or
`job_classes`), what runs on it before the window (`standing`), which
rules hold a run to its `guarantees`, and what `may_remain` after the
drain. The short forms mean one class; the readers below give every
caller the long form.
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
    """The module `benchmarks/<kind>/<name>.py` (a generator, a reducer
    or a reference rule), loaded from its file so that a new one needs
    no import line anywhere."""
    if not NAME_RE.match(name):
        raise SpecError(f"bad {kind} name {name!r}")
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no {kind} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_" + re.sub(r"\W", "_", f"{kind}_{name}"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RULES = "reference/rules"
MAY_REMAIN = ("blocked_evals",)


def node_classes(config: dict) -> list[dict]:
    """The fleet's machine classes. One `node` is one class that every
    node belongs to."""
    if "node_classes" in config:
        return config["node_classes"]
    return [config["node"]]


def job_classes(config: dict) -> dict[str, dict]:
    """What the deployment's jobs ask, by class. One `ask` with the
    configuration's `constraints` and `spread` is one class."""
    if "job_classes" in config:
        return config["job_classes"]
    return {"default": {"ask": config["ask"],
                        "constraints": config.get("constraints", []),
                        "spread": config.get("spread")}}


def job_class(config: dict, name: str | None = None) -> dict:
    """The class `name`; None is the first (for one `ask`, the only)."""
    classes = job_classes(config)
    if name is None:
        return next(iter(classes.values()))
    if name not in classes:
        raise SpecError(f"no job class {name!r}; the configuration has "
                        f"{sorted(classes)}")
    return classes[name]


def check_config(config: dict, bench_dir: Path) -> None:
    """A configuration cannot state what nothing checks: every guarantee
    names a rule file, and only what the harness knows may remain."""
    for g in config.get("guarantees", ()):
        if not isinstance(g, dict) or not g.get("rule") or not g.get("says"):
            raise SpecError(
                f"guarantee {g!r} of {config.get('name')!r} names no rule: "
                'write {"rule": <file under reference/rules>, "says": ...}')
        if not NAME_RE.match(g["rule"]) or not (
                bench_dir / RULES / f"{g['rule']}.py").is_file():
            raise SpecError(
                f"guarantee {g['says']!r} names the rule {g['rule']!r} and "
                f"{RULES}/{g['rule']}.py is missing")
    for key in config.get("may_remain", ()):
        if key not in MAY_REMAIN:
            raise SpecError(f"may_remain lists {key!r}; the harness knows "
                            f"{list(MAY_REMAIN)}")
    for entry in config.get("standing", ()):
        job_class(config, entry.get("job_class"))


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

    def rules(self) -> list:
        """(name, module) of every rule the configuration's guarantees
        name, in their order, each once."""
        names = dict.fromkeys(g["rule"] for g in self.config["guarantees"])
        return [(n, load_module(RULES, n, self.bench_dir)) for n in names]


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
    check_config(config, bench_dir)
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
