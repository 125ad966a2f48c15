"""Every acked job is held by the store, and holds exactly the allocs
it asked for once its operation completed (`expected`: job -> (allocs
asked, ask))."""

from collections import Counter


def check(snap: dict, expected: dict, config: dict) -> list[str]:
    faults = []
    placed = Counter(a["job"] for a in snap["allocs"])
    missing = [j for j in expected if j not in snap["jobs"]]
    if missing:
        faults.append(f"{len(missing)} acked jobs are not in the store, "
                      f"e.g. {missing[0]}")
    wrong = [(j, placed.get(j, 0), n) for j, (n, _) in expected.items()
             if placed.get(j, 0) != n]
    if wrong:
        j, got, n = wrong[0]
        faults.append(f"{len(wrong)} completed jobs hold another number of "
                      f"allocs than asked, e.g. {j}: {got} of {n}")
    return faults
