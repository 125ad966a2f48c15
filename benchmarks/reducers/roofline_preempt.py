"""The preemption kernel's share of its roofline, in %.

As `roofline`: the least time the chip could take for the window's
preempt solves — the bytes the algorithm has to move
(`harness/kernel_cost_preempt.py`, from the group count of every preempt
solve the program reports and the tiers the deployment's top class may
take) over the device's peak memory bandwidth (`harness/peaks.py`) —
over the device time of the kernel's module in the trace. Bytes bound:
integer compares, adds and a sort over the node axis, no matrix unit
work. Not clipped. None where the program reports no preempt solve or
the trace holds no execution of the module.
"""

from benchmarks.harness import kernel_cost_preempt, peaks
from benchmarks.harness.series import series


def reduce(samples: dict, spec: dict, ctx: dict):
    device = samples.get("device")
    if not device:
        return None
    durs = device["modules"].get(spec["module"], ())
    groups = series(samples, spec["reads"])
    if not durs or not groups:
        return None
    tiers = kernel_cost_preempt.preemptible_tiers(ctx["config"])
    need = sum(
        kernel_cost_preempt.preempt_solve_bytes(
            ctx["config"]["nodes"], int(g), tiers)
        for g in groups
    )
    peak = peaks.peak(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / sum(durs)
