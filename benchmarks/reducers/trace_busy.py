"""Device time from the profiler's trace (harness/xplane.py).

`what`: "idle_share" — 100 x (1 - seconds in which an operation ran /
window), averaged over the devices used; a window in which nothing ran
reads 100. "module_ms" — the mean device time of one execution of the
modules whose name contains `module`, in ms.
"""


def reduce(samples: dict, spec: dict, ctx: dict):
    device = samples.get("device")
    if not device:
        return None
    if spec["what"] == "idle_share":
        return device["idle_share"]
    durs = device["modules"].get(spec["module"], ())
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
