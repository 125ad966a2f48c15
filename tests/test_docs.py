"""What the repo says of itself, held to what is in the tree: the README
and PERF.md name the benchmark's cells as `BENCHMARK.json` lists them,
the README sends nobody to a harness that is gone, and every path its
Layout table and "Running it" section name exists. Also the cluster
builder the four overhead gates import (`nomad_tpu.testing.build_cluster`):
node count, datacenter split and ask are what it was called with.
"""

import json
import re
from collections import Counter
from pathlib import Path

import pytest

from nomad_tpu.testing import build_cluster

ROOT = Path(__file__).resolve().parents[1]
README = (ROOT / "README.md").read_text()
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
# a backticked word is a path when it ends in `/` or in a file suffix;
# `/v1/...` and `/ui` are routes, not paths
PATH = re.compile(r"`([\w.-][\w./-]*(?:/|\.(?:py|md|json|jsonl|sh|c|cc)))`")


def section(text: str, heading: str) -> str:
    """The body of the `## heading` section (heading by its first words)."""
    start = text.index(f"\n## {heading}")
    end = text.find("\n## ", start + 1)
    return text[start:end if end != -1 else len(text)]


def test_readme_and_perf_name_every_cell_and_no_other_benchmark():
    cells = section((ROOT / "PERF.md").read_text(), "4. Cells")
    for cell in CELLS:
        assert f"`{cell}`" in README, cell
        assert f"`{cell}`" in cells, cell
    assert "`BENCHMARK.json`" in README
    assert "benchmarks/run.py --workload" in README
    assert not re.search(r"\bbench\.py", README)
    assert "not measured on the chip" not in README


@pytest.mark.parametrize("heading", ["Layout", "Running it"])
def test_every_path_the_readme_names_exists(heading):
    ignored = {line.strip() for line in
               (ROOT / ".gitignore").read_text().splitlines()}
    named = set(PATH.findall(section(README, heading)))
    assert len(named) >= 10, named
    # what a run leaves behind is named in .gitignore, not in the tree
    missing = sorted(p for p in named - ignored if not (ROOT / p).exists())
    assert not missing, missing


@pytest.mark.parametrize("n_nodes, n_jobs, count, constrained, ask", [
    (10, 1, 10, False, {}),
    (203, 3, 30, True, {"cpu": 500, "mem": 256}),
])
def test_build_cluster_gives_what_it_is_called_with(
        n_nodes, n_jobs, count, constrained, ask):
    h, jobs = build_cluster(n_nodes, n_jobs, count, constrained,
                            job_prefix="doc", **ask)
    nodes = list(h.snapshot().nodes())
    assert len(nodes) == n_nodes
    split = Counter(n.datacenter for n in nodes)
    assert set(split) == {"dc1", "dc2", "dc3", "dc4"}
    assert max(split.values()) - min(split.values()) <= 1
    assert {(n.resources.cpu, n.resources.memory_mb) for n in nodes} \
        == {(4000, 8192)}
    assert all(n.computed_class for n in nodes)
    assert [j.id for j in jobs] == [f"doc-{i}" for i in range(n_jobs)]
    for job in jobs:
        assert h.snapshot().job_by_id(job.namespace, job.id) is not None
        assert job.datacenters == ["dc1", "dc2", "dc3", "dc4"]
        (tg,) = job.task_groups
        res = tg.tasks[0].resources
        assert tg.count == count
        assert (res.cpu, res.memory_mb, res.networks) \
            == (ask.get("cpu", 250), ask.get("mem", 128), [])
        # mock.job brings the kernel-name constraint; `constrained` adds
        # the c2m job's own beside it, and the spread
        assert len(job.constraints) == 1 + constrained
        assert [s.attribute for s in job.spreads] \
            == ["${node.datacenter}"] * constrained
