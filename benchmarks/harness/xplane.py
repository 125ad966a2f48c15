"""From a profiler trace to device numbers.

`jax.profiler` writes an `.xplane.pb`; `jax.profiler.ProfileData` reads
it with nothing but jax. This file is the whole reduction: which planes
are devices, when an operation ran on them (the union of the intervals
of the `XLA Ops` line), how long each operation and each module took,
and — with the program's host spans moved onto the trace's clock — what
the host was doing in each gap in which the device ran nothing.

The trace's clock starts at its own zero. `SYNC_NAME` is a
`TraceAnnotation` the harness records while it reads `time.monotonic_ns`;
the difference moves the program's spans (monotonic ns) onto that clock.
"""

from __future__ import annotations

from pathlib import Path

SYNC_NAME = "bench.sync"
DEVICE_PLANE_PREFIXES = ("/device:TPU:",)
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
# lines of a device plane that restate work counted on another line
NOT_OPS_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                 "Framework Name Scope", "Source code", "Sparse Core Steps")


def find_trace(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read(path: Path, device_prefixes=DEVICE_PLANE_PREFIXES) -> dict:
    """{"devices": {plane: {"ops": [(name, start, end)], "modules": [...]}},
    "sync_ns": start of SYNC_NAME on the trace's clock or None}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: dict = {}
    sync_ns = None
    for plane in data.planes:
        is_device = plane.name.startswith(tuple(device_prefixes))
        if is_device:
            lines = {line.name: line for line in plane.lines}
            ops_lines = [lines[n] for n in OPS_LINES if n in lines] or [
                ln for n, ln in lines.items() if n not in NOT_OPS_LINES
            ]
            mod_lines = [lines[n] for n in MODULE_LINES if n in lines]
            devices[plane.name] = {
                "ops": [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for ln in ops_lines for e in ln.events],
                "modules": [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for ln in mod_lines for e in ln.events],
                "lines": sorted(lines),
            }
        elif sync_ns is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == SYNC_NAME:
                        sync_ns = e.start_ns
                        break
                if sync_ns is not None:
                    break
    return {"devices": devices, "sync_ns": sync_ns,
            "planes": [p.name for p in data.planes]}


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def busy(trace: dict, t0: float, t1: float) -> dict:
    """Seconds in which an operation ran, per device and averaged over
    the devices, inside [t0, t1) of the trace's clock. A window in which
    nothing ran reads busy 0 and idle 100, whatever its length."""
    window_s = max(t1 - t0, 0.0) / 1e9
    per_dev = {}
    for name, dev in trace["devices"].items():
        ivs = union(clip([(s, e) for _, s, e in dev["ops"]], t0, t1))
        per_dev[name] = sum(e - s for s, e in ivs) / 1e9
    n = len(per_dev)
    busy_s = sum(per_dev.values()) / n if n else 0.0
    idle = 100.0 if window_s <= 0 else 100.0 * (1.0 - busy_s / window_s)
    return {"busy_s": busy_s, "window_s": window_s, "idle_share": idle,
            "per_device": per_dev}


def by_name(rows, t0: float, t1: float) -> dict[str, list[float]]:
    """name -> durations (s) of the events that start inside [t0, t1)."""
    out: dict[str, list[float]] = {}
    for name, s, e in rows:
        if t0 <= s < t1:
            out.setdefault(name, []).append((e - s) / 1e9)
    return out


def short_name(op: str) -> str:
    """`%while.6 = (s32[] ...) while(...)` -> `while.6`: the trace names
    an op by its whole HLO line."""
    return op.split(" = ", 1)[0].lstrip("%")


def self_seconds(rows, t0: float, t1: float) -> dict[str, float]:
    """name -> seconds spent in the op itself, its nested ops taken out
    (a `while` holds the ops of its body on the same line), for the
    events that start inside [t0, t1)."""
    total: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self_ns]

    def close(entry) -> None:
        total[entry[0]] = total.get(entry[0], 0.0) + entry[2] / 1e9

    for name, s, e in sorted(rows, key=lambda r: (r[1], -r[2])):
        if not t0 <= s < t1:
            continue
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= e - s
        stack.append([short_name(name), e, e - s])
    while stack:
        close(stack.pop())
    return total


def top_ops(trace: dict, t0: float, t1: float, k: int = 10):
    """The device operations that took most time in themselves, over all
    devices."""
    total: dict[str, float] = {}
    for dev in trace["devices"].values():
        for name, secs in self_seconds(dev["ops"], t0, t1).items():
            total[name] = total.get(name, 0.0) + secs
    return sorted(total.items(), key=lambda kv: -kv[1])[:k]


def module_seconds(trace: dict, t0: float, t1: float,
                   needle: str) -> list[float]:
    """Durations of the executions of the modules whose name contains
    `needle`, on the first device that ran any."""
    for dev in trace["devices"].values():
        durs = [d for name, ds in by_name(dev["modules"], t0, t1).items()
                if needle in name for d in ds]
        if durs:
            return durs
    return []


def idle_gaps(trace: dict, t0: float, t1: float,
              host_spans: list[tuple[str, float, float]], k: int = 10):
    """The time in which no device ran an operation, by what the host
    was doing: every instant of a gap goes to the host span that covers
    it and started last (the innermost), or to `unattributed`. The `k`
    longest, `unattributed` always among them.
    host_spans — (name, start, end) on the trace's clock."""
    busy_ivs = union(clip(
        [(s, e) for dev in trace["devices"].values()
         for _, s, e in dev["ops"]], t0, t1))
    gaps, cur = [], t0
    for s, e in busy_ivs:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    spans = sorted((s, e, name) for name, s, e in host_spans
                   if e > t0 and s < t1)
    # sweep: at every boundary the covering span that started last wins
    points = sorted({p for s, e, _ in spans for p in (s, e)}
                    | {p for g in gaps for p in g})
    total: dict[str, float] = {}
    gi = 0
    active: list[tuple[float, float, str]] = []
    si = 0
    for a, b in zip(points, points[1:]):
        while gi < len(gaps) and gaps[gi][1] <= a:
            gi += 1
        if gi >= len(gaps):
            break
        if not (gaps[gi][0] <= a and b <= gaps[gi][1]):
            continue
        while si < len(spans) and spans[si][0] <= a:
            active.append(spans[si])
            si += 1
        active = [sp for sp in active if sp[1] > a]
        name = max(active)[2] if active else "unattributed"
        total[name] = total.get(name, 0.0) + (b - a) / 1e9
    ranked = sorted(total.items(), key=lambda kv: -kv[1])
    # what no span covers is always shown: where it is not among the
    # longest it takes the last place (the line admits `k` entries)
    if "unattributed" in dict(ranked[k:]):
        ranked[k - 1:] = [("unattributed", total["unattributed"])]
    return ranked[:k]
