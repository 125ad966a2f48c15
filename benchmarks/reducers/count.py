"""How many samples a series holds — 0 is a reading, not an absence."""

from benchmarks.harness.series import series


def reduce(samples: dict, spec: dict, ctx: dict):
    return float(len(series(samples, spec["reads"])))
