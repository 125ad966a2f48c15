"""What the tests of the benchmark share."""

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def with_candidates(bench: dict) -> dict:
    """BENCHMARK.json with the entries of benchmarks/candidates.json
    moved in, as the PR that measures those cells will move them: cells
    that are built and rehearsed but not yet bounded."""
    path = ROOT / "benchmarks" / "candidates.json"
    merged = json.loads(json.dumps(bench))
    if not path.is_file():
        return merged
    cand = json.loads(path.read_text())
    merged["workloads"] += cand["workloads"]
    for kind in ("end_to_end", "per_layer"):
        have = {m["name"]: m for m in merged[kind]}
        for m in cand[kind]:
            if m["name"] in have:
                have[m["name"]]["workloads"] += m["workloads"]
            else:
                merged[kind].append(m)
    return merged
