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


# -- faults planted under the timed path --------------------------------
# Each alters what the program commits, where the store takes it from
# the plan applier: the run's own reference rules have to find it.

def ask_altered(result) -> None:
    """Every alloc of the plan is granted 1 MHz more than its job asked."""
    grants = [b.resources for b in result.alloc_batches]
    grants += [a.resources for allocs in result.node_allocation.values()
               for a in allocs]
    for g in {id(g): g for g in grants if g is not None}.values():
        for task in g.tasks.values():
            task.cpu += 1


def all_on_one_node(result) -> None:
    """Every alloc of a placement batch lands on the batch's first node
    (and every eagerly minted alloc on the plan's first node)."""
    import numpy as np

    for b in result.alloc_batches:
        if len(b):
            b.node_idx_raw = np.full(len(b), b.node_idx[0],
                                     dtype=np.int32).tobytes()
            b._idx_arr = None
    if result.node_allocation:
        first = next(iter(result.node_allocation))
        for allocs in result.node_allocation.values():
            for a in allocs:
                a.node_id = first


FAULTS = {"ask_altered": ask_altered, "all_on_one_node": all_on_one_node}


def plant(fault: str):
    """Patch the store so that every committed plan result carries the
    fault; returns the undo."""
    from nomad_tpu.state.store import StateStore

    orig = StateStore.upsert_plan_results_batch
    alter = FAULTS[fault]

    def broken(self, index, results):
        for r in results:
            alter(r)
        return orig(self, index, results)

    StateStore.upsert_plan_results_batch = broken
    return lambda: setattr(StateStore, "upsert_plan_results_batch", orig)
