"""Faults of a mixed deployment's own kind, planted under the timed
path.

As `bench_helpers.plant`: what the program commits is altered where the
store takes a plan result from the applier, and the rest of the run is
driven as it is, so the run's own reference rules have to find it. Both
faults change WHERE one alloc lands, once a run, and nothing else —
counts, asks and visibility read as in a sound run:

`constraint_dropped`   an alloc of a job constrained to a platform goes
                       to a machine of another platform that stands
                       empty: capacity holds, `job_feasibility` alone
                       finds it.
`shape_overcommitted`  an alloc of the largest ask (`boulder`, 8,000 MHz
                       / 16,384 MB: all of an A machine) goes to an A
                       machine that already holds work: every
                       constraint holds (A is linux), the machine's own
                       shape does not — `node_capacity` alone finds it,
                       and only because it reads each node's OWN
                       capacity.
"""

import numpy as np

ATTR = "${attr.platform.family}"
BOULDER_MB = 16_384


def _platform_wanted(job):
    """The platform a job's constraints name, or None."""
    return next((c.rtarget for c in (job.constraints if job else ())
                 if c.ltarget == ATTR and c.operand == "="), None)


def _is_boulder(job) -> bool:
    return bool(job) and any(
        t.resources.memory_mb == BOULDER_MB
        for tg in job.task_groups for t in tg.tasks)


def _touched(result) -> set:
    out = set(result.node_allocation)
    for b in result.alloc_batches:
        out |= {b.node_ids[i] for i in set(b.node_idx.tolist())}
    return out


def _another_platform_empty(store, result, job):
    """A ready machine of ANOTHER platform than the job names, in the
    job's datacenters, that holds nothing and that this plan does not
    touch."""
    wanted = _platform_wanted(job)
    if wanted is None:
        return None
    touched = _touched(result)
    for node in store.nodes():
        if (node.attributes.get("platform.family") not in (None, wanted)
                and node.datacenter in job.datacenters
                and node.id not in touched
                and not store.allocs_by_node_terminal(node.id, False)):
            return node
    return None


def _an_a_machine_at_work(store, result, job):
    """An A machine (a quarter of the CPU, a quarter of the memory) in
    the job's datacenters that this plan does not touch and whose live
    allocs leave no room for a boulder's memory."""
    if not _is_boulder(job):
        return None
    touched = _touched(result)
    for node in store.nodes():
        if (node.attributes.get("platform.family") != "A"
                or node.datacenter not in job.datacenters
                or node.id in touched):
            continue
        held = sum(
            sum(t.memory_mb for t in a.resources.tasks.values())
            for a in store.allocs_by_node_terminal(node.id, False))
        if 0 < held and held + BOULDER_MB > node.resources.memory_mb:
            return node
    return None


def _move_one(store, result, target_of) -> bool:
    """Move the first alloc of the plan result for whose job `target_of`
    names a machine onto that machine, in either form a result carries
    its placements."""
    for b in result.alloc_batches:
        job = b.job or store.job_by_id(b.namespace, b.job_id)
        if not len(b):
            continue
        node = target_of(store, result, job)
        if node is None or node.id not in b.node_ids:
            continue
        idx = np.array(b.node_idx, dtype=np.int32)
        idx[0] = b.node_ids.index(node.id)
        b.node_idx_raw = idx.tobytes()
        b._idx_arr = b._touched = None  # what the columns cached
        return True
    for node_id, allocs in result.node_allocation.items():
        for a in allocs:
            job = a.job or store.job_by_id(a.namespace, a.job_id)
            node = target_of(store, result, job)
            if node is None:
                continue
            allocs.remove(a)
            a.node_id, a.node_name = node.id, node.name
            result.node_allocation.setdefault(node.id, []).append(a)
            return True
    return False


def constraint_dropped(store, result) -> bool:
    return _move_one(store, result, _another_platform_empty)


def shape_overcommitted(store, result) -> bool:
    return _move_one(store, result, _an_a_machine_at_work)


FAULTS = {"constraint_dropped": constraint_dropped,
          "shape_overcommitted": shape_overcommitted}


def plant(fault: str):
    """Patch the store so that the first committed plan result that
    gives the chance carries the fault; returns (undo, planted) where
    `planted()` says whether it found one."""
    from nomad_tpu.state.store import StateStore

    orig = StateStore.upsert_plan_results_batch
    alter = FAULTS[fault]
    done = []

    def broken(self, index, results):
        if not done:
            for r in results:
                if alter(self, r):
                    done.append(index)
                    break
        return orig(self, index, results)

    StateStore.upsert_plan_results_batch = broken
    return (lambda: setattr(StateStore, "upsert_plan_results_batch", orig),
            lambda: bool(done))
