"""No alloc is placed twice: every live alloc id is unique, and so is
every alloc name within a job."""

from collections import Counter


def check(snap: dict, expected: dict, config: dict) -> list[str]:
    faults = []
    allocs = snap["allocs"]
    ids = Counter(a["id"] for a in allocs)
    dup = [i for i, c in ids.items() if c > 1]
    if dup:
        faults.append(f"{len(dup)} alloc ids are held twice, e.g. {dup[0]}")
    names = Counter((a["job"], a["name"]) for a in allocs)
    dup = [k for k, c in names.items() if c > 1]
    if dup:
        faults.append(f"{len(dup)} (job, alloc name) pairs are placed twice,"
                      f" e.g. {dup[0]}")
    return faults
