"""100 x the samples of `reads` over the samples of all of `among`
(a list of sources): which share of the solves took this path."""

from benchmarks.harness.series import series


def reduce(samples: dict, spec: dict, ctx: dict):
    total = sum(len(series(samples, r)) for r in spec["among"])
    if not total:
        return None
    return 100.0 * len(series(samples, spec["reads"])) / total
