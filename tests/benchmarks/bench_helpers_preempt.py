"""Faults planted under the timed path of a run that preempts.

As `bench_helpers.plant`: what the program commits is altered where the
store takes a plan result from the applier, and the rest of the run is
driven as it is, so the run's own reference rules have to find it. Both
faults here change WHO is evicted, once a run (the first plan result
that gives the chance), and nothing else: capacity, counts of placed
allocs and every other rule read as in a sound run, so the control shows
`preemption_lowest_first` alone finding them.
"""


def _priority(store, alloc) -> int:
    job = alloc.job or store.job_by_id(alloc.namespace, alloc.job_id)
    return int(job.priority) if job is not None else 50


def _evicted_by(alloc, preemptor_id: str):
    """`alloc` as a plan marks a victim (structs.Plan.append_preempted_alloc)."""
    row = alloc.copy()
    row.job = None
    row.desired_status = "evict"
    row.preempted_by_allocation = preemptor_id
    row.desired_description = f"Preempted by alloc ID {preemptor_id}"
    return row


def _preemptor(result, node_id: str, victim):
    """The placed alloc that takes `victim`'s room: on the same node."""
    return next((a for a in result.node_allocation.get(node_id, ())
                 if a.id == victim.preempted_by_allocation), None)


def _standing(store, node_id: str, victims: list) -> list:
    taken = {v.id for v in victims}
    return [a for a in store.allocs_by_node_terminal(node_id, False)
            if a.id not in taken]


def evict_higher_tier(store, result) -> bool:
    """One victim is swapped for an alloc of a HIGHER priority tier
    (still 10 under the preemptor) and the lower one stays: on the same
    node where it holds one, else — a cluster whose nodes each hold one
    tier — the preemptor moves to another node its plan does not touch
    and takes its victim there. Every node holds what it held."""
    for node_id, victims in result.node_preemptions.items():
        standing = _standing(store, node_id, victims)
        for i, v in enumerate(victims):
            mover = _preemptor(result, node_id, v)
            if mover is None:
                continue
            top, low = _priority(store, mover) - 10, _priority(store, v)
            for a in standing:
                if low < _priority(store, a) <= top:
                    victims[i] = _evicted_by(a, v.preempted_by_allocation)
                    return True
    touched = set(result.node_preemptions) | set(result.node_allocation)
    for node_id, victims in result.node_preemptions.items():
        for i, v in enumerate(victims):
            mover = _preemptor(result, node_id, v)
            if mover is None:
                continue
            top, low = _priority(store, mover) - 10, _priority(store, v)
            here = store.node_by_id(node_id)
            for node in store.nodes():
                if node.id in touched or node.datacenter != here.datacenter:
                    continue
                for a in store.allocs_by_node_terminal(node.id, False):
                    if low < _priority(store, a) <= top:
                        del victims[i]
                        result.node_allocation[node_id].remove(mover)
                        mover.node_id, mover.node_name = node.id, node.name
                        result.node_allocation.setdefault(
                            node.id, []).append(mover)
                        result.node_preemptions.setdefault(
                            node.id, []).append(_evicted_by(a, mover.id))
                        return True
    return False


def evict_without_need(store, result) -> bool:
    """One preemptor takes a second victim of the same tier on its node:
    more than its shortage needs."""
    for node_id, victims in result.node_preemptions.items():
        standing = _standing(store, node_id, victims)
        for v in victims:
            low = _priority(store, v)
            for a in standing:
                if _priority(store, a) == low:
                    victims.append(
                        _evicted_by(a, v.preempted_by_allocation))
                    return True
    return False


FAULTS = {"evict_higher_tier": evict_higher_tier,
          "evict_without_need": evict_without_need}


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
                if r.node_preemptions and alter(self, r):
                    done.append(index)
                    break
        return orig(self, index, results)

    StateStore.upsert_plan_results_batch = broken
    return (lambda: setattr(StateStore, "upsert_plan_results_batch", orig),
            lambda: bool(done))
