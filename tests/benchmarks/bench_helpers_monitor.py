"""Faults planted under the timed path of `borg2011-12k-monitor`.

As `bench_helpers.plant`: what the program commits is altered where the
store takes a plan result from the applier, once a run (the first result
of the monitoring band's lane that gives the chance), and the rest of
the run is driven as it is, so the run's own reference rules have to
find it.

`monitoring_alloc_lost` — one placed monitoring alloc is committed as
lost by its client: its job, which nothing may preempt, holds one alloc
fewer than it asked (`standing_held_or_evicted`). The alloc still
reaches its node's watch, so the deploy completes.

`lane_evicts_production` — the lane's placement moves to a machine that
holds production work and no lower band, and evicts a production alloc
there no smaller than itself, while gratis work that stood all the while
holds its whole ask elsewhere (`preemption_bands` (c)). Its node holds
no more than before: the victim frees at least what the mover takes.
"""

import bench_helpers_preempt as base

MONITORING = 70
PRODUCTION = 50


def _monitoring_rows(store, result) -> list:
    return [a for allocs in result.node_allocation.values() for a in allocs
            if base._priority(store, a) == MONITORING]


def monitoring_alloc_lost(store, result) -> bool:
    rows = _monitoring_rows(store, result)
    if not rows:
        return False
    rows[0].client_status = "lost"
    return True


def lane_evicts_production(store, result) -> bool:
    rows = _monitoring_rows(store, result)
    if not rows:
        return False
    mover = rows[0]
    need = mover.comparable_resources()
    touched = set(result.node_preemptions) | set(result.node_allocation)
    for node in store.nodes():
        if node.id in touched:
            continue
        live = store.allocs_by_node_terminal(node.id, False)
        if any(base._priority(store, a) < PRODUCTION for a in live):
            continue  # a lower band here: that would be fault (b) too
        for a in live:
            r = a.comparable_resources()
            if base._priority(store, a) == PRODUCTION and \
                    r.cpu >= need.cpu and r.memory_mb >= need.memory_mb:
                result.node_allocation[mover.node_id].remove(mover)
                if not result.node_allocation[mover.node_id]:
                    del result.node_allocation[mover.node_id]
                mover.node_id, mover.node_name = node.id, node.name
                mover.preempted_allocations = [a.id]
                result.node_allocation.setdefault(node.id, []).append(mover)
                result.node_preemptions.setdefault(node.id, []).append(
                    base._evicted_by(a, mover.id))
                return True
    return False


FAULTS = {"monitoring_alloc_lost": monitoring_alloc_lost,
          "lane_evicts_production": lane_evicts_production}


def plant(fault: str):
    """Patch the store so that the first committed plan result of the
    monitoring band that gives the chance carries the fault; returns
    (undo, planted)."""
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
