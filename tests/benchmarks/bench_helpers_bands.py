"""`evict_without_need` where the store can show it.

`bench_helpers_preempt.evict_without_need` gives one preemptor a second
victim of the same tier on its node, at the first chance. With equal
asks on a full cluster (its own cell) that is always one victim more
than the whole ask needs. With unequal asks on a cell that has free
fragments it may not be: a `small` that found 400 MHz free and took one
gratis `sand` is given a second, and two sands are no more than its ask
— the second went for nothing only because of room that was free at the
time, which no store remembers. `preemption_bands` says so of itself
(fault (d)); this control therefore plants the SAME fault on the first
preemptor whose own victims already hold its whole ask, where the extra
one is unnecessary whatever was free.

And `plant` here knows that a commit carries MANY plans, and that a
later commit may undo what was planted: see its docstring.
"""

import bench_helpers_preempt as base
from bench_helpers_preempt import (
    FAULTS, _evicted_by, _preemptor, _priority)

__all__ = ["FAULTS", "plant"]


def _covers(allocs, ask) -> bool:
    got = [0, 0, 0]
    for a in allocs:
        r = a.comparable_resources()
        got = [got[0] + r.cpu, got[1] + r.memory_mb, got[2] + r.disk_mb]
    return (got[0] >= ask.cpu and got[1] >= ask.memory_mb
            and got[2] >= ask.disk_mb)


def evict_without_need_shown(store, result) -> bool:
    for node_id, victims in result.node_preemptions.items():
        standing = base._standing(store, node_id, victims)
        for v in victims:
            mover = _preemptor(result, node_id, v)
            if mover is None:
                continue
            mine = [w for w in victims
                    if w.preempted_by_allocation == mover.id]
            if not _covers(mine, mover.comparable_resources()):
                continue  # the store could not show one more as needless
            low = _priority(store, v)
            # the LAST of the tier as the store lists it: the batch's
            # later plans take their victims on this node from the front
            for a in reversed(standing):
                if _priority(store, a) == low:
                    victims.append(_evicted_by(a, mover.id))
                    return True
    return False


FAULTS["evict_without_need_shown"] = evict_without_need_shown
AT_EVERY_CHANCE = {"evict_without_need_shown"}


def plant(fault: str):
    """As `bench_helpers_preempt.plant`, over commits of many plans: the
    extra victim is planted at EVERY commit that gives the chance (a
    later batch may take the planted victim for a preemptor of its own:
    that fault has vanished), the accepted `evict_higher_tier` once, as it was; and
    `planted()` says whether one stands in the store at the end as it
    was planted."""
    from nomad_tpu.state.store import StateStore

    orig = StateStore.upsert_plan_results_batch
    alter = FAULTS[fault]
    marks = []  # (store, victim id, the preemptor the fault named)

    def victims_of(results) -> dict:
        return {v.id: v.preempted_by_allocation for r in results
                for vs in r.node_preemptions.values() for v in vs}

    def broken(self, index, results):
        if marks and fault not in AT_EVERY_CHANCE:
            return orig(self, index, results)
        before = victims_of(results)
        standing = base._standing
        base._standing = lambda store, node_id, victims: [
            a for a in standing(store, node_id, victims)
            if a.id not in before]
        try:
            for r in results:
                if r.node_preemptions and alter(self, r):
                    break
        finally:
            base._standing = standing
        marks.extend((self, vid, by) for vid, by in
                     victims_of(results).items() if before.get(vid) != by)
        return orig(self, index, results)

    def planted() -> bool:
        for store, vid, by in marks:
            a = store.alloc_by_id(vid)
            if a is not None and a.preempted_by_allocation == by:
                return True
        return False

    StateStore.upsert_plan_results_batch = broken
    return (lambda: setattr(StateStore, "upsert_plan_results_batch", orig),
            planted)
