"""The 95th percentile of a series, times the file's `scale`."""

from benchmarks.harness.series import quantile, series


def reduce(samples: dict, spec: dict, ctx: dict):
    xs = series(samples, spec["reads"])
    if not xs:
        return None
    return quantile(xs, 0.95) * spec.get("scale", 1.0)
