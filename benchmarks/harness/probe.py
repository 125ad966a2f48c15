"""What the program says about itself during the window.

From the program the benchmark takes its counters, its raw histogram
observations (the registry's timing capture), its spans (the trace
recorder) and jax's own compile events — nothing else, and nothing in
the program is changed for it. Spans and timing capture are switched on
only in a traced run; the end-to-end run pays for the compile listener
and two counter snapshots.
"""

from __future__ import annotations

import threading

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# counters whose movement explains a failure, written out with every run
WATCHED_COUNTERS = (
    "nomad.worker.invoke.failed", "nomad.worker.device_failover",
    "nomad.broker.shed", "nomad.heartbeat.expired",
    "nomad.plan_apply.dup_mint_trimmed", "nomad.tpu.chain_parent_failed",
    "nomad.http.throttled", "nomad.rpc.throttled",
    "nomad.worker.backpressure_throttled",
)

_compiles: list[tuple[str, float]] = []
_installed = threading.Event()


def install_compile_listener() -> None:
    """Once per process: jax keeps listeners for good."""
    if _installed.is_set():
        return
    _installed.set()
    from jax import monitoring

    def on_duration(event: str, secs: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            _compiles.append((str(kw.get("fun_name", "?")), secs))

    monitoring.register_event_duration_secs_listener(on_duration)


def compiles_so_far() -> int:
    return len(_compiles)


def compiles_since(mark: int) -> list[tuple[str, float]]:
    return list(_compiles[mark:])


def _host_threads() -> dict:
    from nomad_tpu import hostobs

    snap = hostobs.profiler().snapshot(top=12)
    return {"threads": {r: v["busy_seconds"]
                        for r, v in snap["threads"].items()},
            "top_sites": snap["top_sites"]}


def _batches(reg) -> dict:
    raw = reg.histogram_raw("nomad.tpu.batch_evals") or {}
    return {"count": raw.get("count", 0), "sum": raw.get("sum", 0)}


class Probe:
    """Open at the window's start, close at its end; `samples` is then
    what the reducers read."""

    def __init__(self, traced: bool) -> None:
        self.traced = traced
        self._capture = None
        self._c0: dict = {}
        self._mark = 0
        self._b0: dict = {}
        self._host0: dict = {}
        self.samples: dict = {}

    def open(self) -> None:
        from nomad_tpu import metrics, trace

        self._mark = compiles_so_far()
        reg = metrics.registry()
        self._c0 = dict(reg.snapshot()["counters"])
        self._b0 = _batches(reg)
        if self.traced:
            trace.configure(max_traces=65536, enabled_=True)
            trace.recorder().clear()
            self._capture = reg.enable_timing_capture(cap=1 << 20)
            self._host0 = _host_threads()

    def close(self) -> None:
        from nomad_tpu import metrics, trace

        reg = metrics.registry()
        c1 = reg.snapshot()["counters"]
        counters = {k: v - self._c0.get(k, 0) for k, v in c1.items()
                    if v != self._c0.get(k, 0)}
        b1 = _batches(reg)
        timings: dict = {}
        spans: dict = {}
        batches: list = []
        if self.traced:
            timings = reg.drain_timings(self._capture)
            reg.disable_timing_capture(self._capture)
            trace.set_enabled(False)
            rec = trace.recorder()
            for summary in rec.list(limit=1 << 20):
                full = rec.get(summary["id"])
                if full is None:
                    continue
                rows = [(s["name"], s["start"], s["end"])
                        for s in full["spans"]]
                for name, start, end in rows:
                    spans.setdefault(name, []).append((start, end))
                if full["name"] in ("tpu.batch", "tpu.interactive"):
                    batches.append({
                        "status": full["attrs"].get("status", ""),
                        "evals": full["attrs"].get("evals", 1),
                        "spans": rows,
                    })
        host = {}
        if self.traced:
            # what each of the host's thread roles was busy with in the
            # window (the program's sampling profiler): where the time
            # that no span covers went
            h1 = _host_threads()
            host = {"busy_s_by_thread_role": {
                role: round(h1["threads"][role] - self._host0["threads"].get(
                    role, 0.0), 4) for role in h1["threads"]},
                "top_sites_since_start": h1["top_sites"]}
        self.samples = {
            "host": host,
            "counters": counters,
            "timings": timings,
            "spans": spans,
            "batches": batches,
            "compiles": compiles_since(self._mark),
            # how the worker split the window's evals into solves: read
            # in untraced runs too, it explains a backlog's rate
            "batches_in_window": {k: b1[k] - self._b0[k]
                                  for k in ("count", "sum")},
        }
