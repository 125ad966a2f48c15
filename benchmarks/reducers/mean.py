"""The mean of a series, times the file's `scale`."""

from benchmarks.harness.series import series


def reduce(samples: dict, spec: dict, ctx: dict):
    xs = series(samples, spec["reads"])
    if not xs:
        return None
    return sum(xs) / len(xs) * spec.get("scale", 1.0)
