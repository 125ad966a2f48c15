"""A kernel's share of its roofline, in %.

The least time the chip could take for the window's solves is the bytes
the algorithm has to move (`harness/kernel_cost.py`, from the shapes of
the batches the program reports) over the device's peak memory
bandwidth (`harness/peaks.py`, by `device_kind`; a device that is not in
the table is an error). The share is that over the device time of the
kernel's module in the trace. Bytes bound: the kernel is integer
compares, adds and a top-k over the node axis, no matrix unit work.
Not clipped: a reading over 100 means the bytes are counted too high
or the module's time leaves out part of the work.
"""

from benchmarks.harness import kernel_cost, peaks
from benchmarks.harness.series import series


def reduce(samples: dict, spec: dict, ctx: dict):
    device = samples.get("device")
    if not device:
        return None
    durs = device["modules"].get(spec["module"], ())
    groups = series(samples, spec["reads"])
    if not durs or not groups:
        return None
    need = sum(
        getattr(kernel_cost, spec["bytes_fn"])(ctx["config"]["nodes"], int(g))
        for g in groups
    )
    peak = peaks.peak(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / sum(durs)
