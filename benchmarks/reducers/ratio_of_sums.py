"""100 x the sum of `reads` over the sum of `over`, as a float: which
share of one total another is (say, the off-CPU seconds of a set of
spans over their wall seconds). None when either series is empty or
`over` sums to nothing."""

from benchmarks.harness.series import series


def reduce(samples: dict, spec: dict, ctx: dict):
    xs = series(samples, spec["reads"])
    over = series(samples, spec["over"])
    if not xs or not over or not sum(over):
        return None
    return 100.0 * sum(xs) / sum(over)
