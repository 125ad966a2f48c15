"""One of the program's counters over another, as they moved in the
window, times the file's `scale`: `reads` and `over` are counter names.
None when `over` did not move (nothing to take a share of); a `reads`
that did not move reads 0."""


def reduce(samples: dict, spec: dict, ctx: dict):
    counters = samples.get("counters", {})
    over = counters.get(spec["over"], 0)
    if not over:
        return None
    return float(counters.get(spec["reads"], 0)) / over \
        * spec.get("scale", 1.0)
