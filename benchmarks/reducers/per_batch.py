"""The sum of a series over the number of samples of `per` (the solved
batches), times `scale`: a stage's time for each batch, whether or not
every batch went through the stage."""

from benchmarks.harness.series import series


def reduce(samples: dict, spec: dict, ctx: dict):
    xs = series(samples, spec["reads"])
    n = len(series(samples, spec["per"]))
    if not xs or not n:
        return None
    return sum(xs) / n * spec.get("scale", 1.0)
