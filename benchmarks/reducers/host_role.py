"""Seconds of CPU that the thread roles of `roles` used in the window,
from the program's own account of its threads' CPU clocks (the traced
report's `host_threads.busy_s_by_thread_role`), times `scale`. A role
without an entry reads 0.0: a role without a thread used no CPU. With
`per` (a source, as in `per_batch`), divided by its count of samples:
CPU for each completed deploy. With `"over": "all"`, 100 x the share of
the sum over every role, `(unaccounted)` included. None where the run
has no such block (an untraced run), or nothing to divide by."""

from benchmarks.harness.series import series


def reduce(samples: dict, spec: dict, ctx: dict):
    by_role = (samples.get("host") or {}).get("busy_s_by_thread_role")
    if by_role is None:
        return None
    cpu = sum(by_role.get(role, 0.0) for role in spec["roles"])
    if spec.get("over") == "all":
        total = sum(by_role.values())
        return 100.0 * cpu / total if total else None
    if "per" in spec:
        n = len(series(samples, spec["per"]))
        if not n:
            return None
        cpu /= n
    return cpu * spec.get("scale", 1.0)
