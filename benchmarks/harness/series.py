"""What a per-layer metric's file may read, and how a reducer gets it.

A layer-metric file names its source under `reads`, one of:

  {"timings": [names]}  raw observations of the program's histograms in
                        the window (seconds, or counts for size ones)
  {"spans": [names]}    durations (s) of the program's spans and stages
  {"client": name}      the benchmark's own host-clock samples
  {"derived": name}     a series the harness derives from batch traces
  {"compiles": true}    jax's compile events inside the window

A source that is absent or empty reads as an empty list, and a reducer
that gets nothing returns None: the metric is left out of the line.
"""

from __future__ import annotations


def series(samples: dict, reads: dict) -> list[float]:
    if "timings" in reads:
        return [v for n in reads["timings"]
                for v in samples.get("timings", {}).get(n, ())]
    if "spans" in reads:
        return [(e - s) / 1e9 for n in reads["spans"]
                for s, e in samples.get("spans", {}).get(n, ())]
    if "client" in reads:
        return list(samples.get("client", {}).get(reads["client"], ()))
    if "derived" in reads:
        return list(samples.get("derived", {}).get(reads["derived"], ()))
    if "compiles" in reads:
        return [secs for _, secs in samples.get("compiles", ())]
    return []


def quantile(values: list[float], q: float) -> float:
    """The q-quantile by linear interpolation between order statistics
    (q in [0, 1]); the median of an even count is the mean of the two
    middle values, as `statistics.median` gives it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
