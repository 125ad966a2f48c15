"""Solver device observability: compile ledger, batch occupancy, and
host<->device transfer / device-memory accounting.

The batched solver's design claim — "compiles once per bucket"
(scheduler/tpu/kernels.py pad_n/pad_g) — was previously unmeasured: a
bucket recompile, padding waste, and host<->device transfer cost all
look identical from the outside (a slow solve). This module is the
always-on attribution layer that separates them:

  * compile ledger — every jit entry-point call records its padded-shape
    signature; a new signature is a TRACE/COMPILE event (with the call's
    wall time, split first-compile vs steady-state recompile), a repeat
    is a cache hit. The ledger is bounded (per-kernel signature FIFO) so
    a pathological shape storm can't grow it without bound — an evicted
    signature re-counts as a compile, which is exactly the pessimistic
    direction a regression guard wants.
  * batch occupancy — real rows/cols vs the padded bucket shapes
    (pad_n/pad_g): occupancy fraction, padding-waste fraction, and
    asks-per-batch, per solve.
  * transfer accounting — host->device bytes from the numpy arrays
    actually uploaded per dispatch (device-resident inputs excluded) and
    device->host bytes read back, from array ``nbytes``.
  * device memory — ``device.memory_stats()`` where the backend provides
    it (TPU/GPU; the CPU backend tier-1 uses returns None — kept as an
    explicit null, never fabricated) plus a live-array byte census and
    its high-water mark.

Deliberately a stdlib-only leaf (like faultplane.py): the control plane
imports it for the ``/v1/solver/status`` surface without paying the jax
import; jax is touched only inside :func:`sample_device_memory`, and only
when jax is already loaded in this process.

Everything is published through the established machinery: the
``nomad.solver.*`` metric names below are catalogued in docs/metrics.md
(the source-walk test enforces the names), ``solver.compile`` /
``solver.transfer`` spans land on the live trace, and ``snapshot()``
feeds ``GET /v1/solver/status``, ``operator solver status|top``, the
``operator debug`` bundle, and the bench's ``solver_observability``
block.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Optional

from . import metrics, trace

# Bounds: kernels are a closed set (the jit entry points in
# scheduler/tpu); signatures per kernel are the shape buckets, a handful
# in practice. The FIFO bound only matters under a shape storm — the
# very condition the ledger exists to surface.
MAX_KERNELS = 64
MAX_SIGNATURES = 256

_enabled = True


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool) -> None:
    """The e2e overhead comparator's off switch (tests); production
    leaves this on — the whole point is always-on attribution."""
    global _enabled
    _enabled = bool(on)


class _Kernel:
    __slots__ = (
        "sigs", "compiles", "cache_hits", "steady_recompiles",
        "first_compile_ns", "steady_compile_ns", "last_sig", "evicted",
        "platforms",
    )

    def __init__(self) -> None:
        # sig -> hit count; insertion-ordered dict IS the FIFO bound
        self.sigs: dict = {}
        self.compiles = 0
        self.cache_hits = 0
        self.steady_recompiles = 0
        self.first_compile_ns = 0
        self.steady_compile_ns = 0
        self.last_sig: Optional[tuple] = None
        self.evicted = 0
        # platforms the outputs of this kernel's compiled programs were
        # committed to (sampled at compile events): the proof a solve
        # ran where the operator thinks it ran
        self.platforms: set = set()

    def to_wire(self) -> dict:
        return {
            "compiles": self.compiles,
            "cache_hits": self.cache_hits,
            "steady_recompiles": self.steady_recompiles,
            "first_compile_ms": round(self.first_compile_ns / 1e6, 3),
            "steady_compile_ms": round(self.steady_compile_ns / 1e6, 3),
            "signatures": len(self.sigs),
            "signatures_evicted": self.evicted,
            "last_signature": (
                list(self.last_sig) if self.last_sig is not None else None
            ),
            "platforms": sorted(self.platforms),
        }


class SolverObservatory:
    """One process-wide instance (module functions below delegate);
    tests may install a fresh one via _install()."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._kernels: dict[str, _Kernel] = {}
        # occupancy over batches
        self.batches = 0
        self.occupancy_sum = 0.0
        self.last_batch: Optional[dict] = None
        # asks-per-batch (recorded at the eval-batch layer, scheduler.py)
        self.last_asks: Optional[dict] = None
        # lowered node-table shape (lower.py build_node_table)
        self.last_table: Optional[dict] = None
        # transfer totals (bytes); allgather = modeled ICI traffic of
        # node-sharded solves, scatter = delta-sync rows landing in
        # their owning resident shard (scheduler/tpu/sharding.py)
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.allgather_bytes = 0
        self.scatter_bytes = 0
        # sharding: device count + per-shard occupancy of the last
        # node-sharded dispatch (bounded: a mesh is <= 64 devices here)
        self.mesh_devices = 0
        self.last_shards: Optional[list] = None
        # device memory
        self.device_memory: Optional[dict] = None
        self.live_array_bytes = 0
        self.live_array_highwater = 0
        self._last_mem_sample = 0.0

    # -- compile ledger -------------------------------------------------

    def record_call(self, kernel: str, signature: tuple, wall_ns: int) -> bool:
        """One jit entry-point call: True when it was a trace/compile
        event (new padded-shape signature), False on a cache hit. Emits
        the nomad.solver.* compile metrics and a solver.compile span on
        the live trace for compile events."""
        if not _enabled:
            return False
        with self._lock:
            k = self._kernels.get(kernel)
            if k is None:
                if len(self._kernels) >= MAX_KERNELS:
                    return False  # closed set in practice; never grow past
                k = self._kernels[kernel] = _Kernel()
            k.last_sig = signature
            if signature in k.sigs:
                k.sigs[signature] += 1
                k.cache_hits += 1
                hit = True
            else:
                while len(k.sigs) >= MAX_SIGNATURES:
                    k.sigs.pop(next(iter(k.sigs)))
                    k.evicted += 1
                k.sigs[signature] = 0
                k.compiles += 1
                # steady-state recompile = the kernel had already
                # settled into serving cache hits, then compiled again.
                # Warm-up compiles (a multi-bucket cluster filling its
                # buckets before any repeat traffic) are NOT steady
                # recompiles — a healthy server reads ~0 here, and a
                # CLIMBING count is the recompile storm (operations.md).
                if k.cache_hits > 0:
                    k.steady_recompiles += 1
                    k.steady_compile_ns += wall_ns
                else:
                    k.first_compile_ns += wall_ns
                hit = False
        if hit:
            metrics.incr("nomad.solver.cache_hits")
            return False
        metrics.incr("nomad.solver.compiles")
        metrics.observe("nomad.solver.compile_seconds", wall_ns / 1e9)
        trace.stage_attrs(
            "solver.compile", wall_ns, kernel=kernel,
            signature=str(signature),
        )
        return True

    def record_outputs(self, kernel: str, out) -> None:
        """Where a freshly compiled program put its results: the
        platform of every device holding an output array (`out` is the
        jit call's array or tuple of arrays). Called on compile events
        only — an executable is bound to its devices."""
        platforms = set()
        for arr in out if isinstance(out, (tuple, list)) else (out,):
            devices = getattr(arr, "devices", None)
            if devices is not None:  # a custom solve_fn may return numpy
                platforms.update(d.platform for d in devices())
        with self._lock:
            k = self._kernels.get(kernel)
            if k is not None:
                k.platforms |= platforms

    def signatures(self) -> dict[str, list]:
        """Every live ledger signature per kernel, in first-seen order
        (the wire form carries only their count and the last one)."""
        with self._lock:
            return {
                name: list(k.sigs) for name, k in self._kernels.items()
            }

    def compiles(self, prefix: str = "") -> int:
        with self._lock:
            return sum(
                k.compiles
                for name, k in self._kernels.items()
                if name.startswith(prefix)
            )

    def steady_recompiles(self, prefix: str = "") -> int:
        with self._lock:
            return sum(
                k.steady_recompiles
                for name, k in self._kernels.items()
                if name.startswith(prefix)
            )

    # -- batch occupancy ------------------------------------------------

    def record_batch(self, n: int, g: int, pad_n: int, pad_g: int) -> None:
        """One kernel dispatch's real vs padded shape."""
        if not _enabled:
            return
        denom = max(1, pad_n * pad_g)
        occ = (n * g) / denom
        waste = 1.0 - occ
        with self._lock:
            self.batches += 1
            self.occupancy_sum += occ
            self.last_batch = {
                "n": n, "g": g, "pad_n": pad_n, "pad_g": pad_g,
                "occupancy": round(occ, 4), "pad_waste": round(waste, 4),
            }
        metrics.observe("nomad.solver.occupancy", occ)
        metrics.observe("nomad.solver.pad_waste", waste)

    def note_asks(self, groups: int, requests: int) -> None:
        """Asks-per-batch at the eval-batch layer (scheduler.py)."""
        if not _enabled:
            return
        with self._lock:
            self.last_asks = {"groups": groups, "requests": requests}
        metrics.observe("nomad.solver.batch_asks", float(groups))
        metrics.observe("nomad.solver.batch_requests", float(requests))

    def note_table(self, n: int, nbytes: int) -> None:
        """The lowered node table's host-side tensor footprint
        (lower.py build_node_table)."""
        if not _enabled:
            return
        with self._lock:
            self.last_table = {"nodes": n, "host_bytes": int(nbytes)}

    def record_shards(self, n_dev: int, shards: list) -> None:
        """Per-shard occupancy of one node-sharded dispatch
        (sharding.SolverMesh.shard_occupancy rows). Bounded: a mesh
        larger than 64 devices keeps its first 64 rows plus the count —
        enough to read an imbalance, never an unbounded payload."""
        if not _enabled:
            return
        shards = list(shards[:64])
        with self._lock:
            self.mesh_devices = int(n_dev)
            self.last_shards = shards
        for s in shards:
            metrics.observe(
                "nomad.solver.shard_occupancy", float(s.get("occupancy", 0.0))
            )

    # -- transfers ------------------------------------------------------

    def record_transfer(
        self, direction: str, nbytes: int, dur_ns: int = 0, span: bool = False
    ) -> None:
        """direction: 'h2d' | 'd2h' | 'allgather' | 'scatter'. span=True
        also lands a solver.transfer span of dur_ns on the live trace."""
        if not _enabled or nbytes <= 0:
            return
        with self._lock:
            if direction == "h2d":
                self.h2d_bytes += nbytes
            elif direction == "allgather":
                self.allgather_bytes += nbytes
            elif direction == "scatter":
                self.scatter_bytes += nbytes
            else:
                self.d2h_bytes += nbytes
        metrics.incr(f"nomad.solver.transfer_bytes.{direction}", nbytes)
        # per-dispatch size distribution in MEGABYTES: the registry's
        # fixed exponential bounds (1e-4 .. ~1677, tuned for seconds)
        # then cover 100B .. ~1.6GB per dispatch — byte-unit values
        # would all land in the +Inf bucket and make the percentiles
        # meaningless
        metrics.observe(f"nomad.solver.{direction}_mb", nbytes / 1e6)
        if span:
            trace.stage_attrs(
                "solver.transfer", dur_ns, direction=direction, bytes=nbytes
            )

    # -- device memory --------------------------------------------------

    def sample_device_memory(self, force: bool = False) -> None:
        """Sample backend memory stats + live-array census. Only touches
        jax when it is already imported (never drags the backend into a
        control-plane process); memory_stats() is None on backends that
        don't report (the CPU tier-1 backend) and stays an explicit
        null. Rate-limited to ~1/s on the solve path (live_arrays()
        walks every live array — per-batch cost that matters at
        millisecond solve sizes); force=True (the /v1/solver/status
        read) always samples fresh."""
        if not _enabled or "jax" not in sys.modules:
            return
        now = time.monotonic()
        if not force and now - self._last_mem_sample < 1.0:
            return
        self._last_mem_sample = now
        try:
            import jax

            dev = jax.devices()[0]
            stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
            live = 0
            for arr in jax.live_arrays():
                live += getattr(arr, "nbytes", 0) or 0
        except Exception:  # device introspection must never break a solve
            return
        with self._lock:
            self.device_memory = dict(stats) if stats else None
            self.live_array_bytes = live
            if live > self.live_array_highwater:
                self.live_array_highwater = live
        metrics.set_gauge("nomad.solver.live_array_bytes", float(live))
        metrics.set_gauge(
            "nomad.solver.live_array_highwater_bytes",
            float(self.live_array_highwater),
        )
        if stats and "bytes_in_use" in stats:
            metrics.set_gauge(
                "nomad.solver.device_bytes_in_use",
                float(stats["bytes_in_use"]),
            )

    # -- read side ------------------------------------------------------

    def snapshot(self, sample: bool = True) -> dict:
        """The /v1/solver/status payload. sample=True refreshes the
        device-memory census first (no-op unless jax is loaded)."""
        if sample:
            self.sample_device_memory(force=True)
        with self._lock:
            kernels = {
                name: k.to_wire() for name, k in self._kernels.items()
            }
            compiles = sum(k.compiles for k in self._kernels.values())
            hits = sum(k.cache_hits for k in self._kernels.values())
            steady = sum(
                k.steady_recompiles for k in self._kernels.values()
            )
            batches = self.batches
            occ_mean = (
                self.occupancy_sum / batches if batches else None
            )
            return {
                "enabled": _enabled,
                "ledger": {
                    "kernels": kernels,
                    "compiles": compiles,
                    "cache_hits": hits,
                    "steady_recompiles": steady,
                },
                "occupancy": {
                    "batches": batches,
                    "mean": round(occ_mean, 4) if occ_mean is not None else None,
                    "last_batch": dict(self.last_batch)
                    if self.last_batch else None,
                    "last_asks": dict(self.last_asks)
                    if self.last_asks else None,
                    "last_table": dict(self.last_table)
                    if self.last_table else None,
                },
                "transfers": {
                    "h2d_bytes": self.h2d_bytes,
                    "d2h_bytes": self.d2h_bytes,
                    "allgather_bytes": self.allgather_bytes,
                    "scatter_bytes": self.scatter_bytes,
                },
                "sharding": {
                    "devices": self.mesh_devices,
                    "last_shards": (
                        [dict(s) for s in self.last_shards]
                        if self.last_shards else None
                    ),
                },
                "device_memory": dict(self.device_memory)
                if self.device_memory else None,
                "live_array_bytes": self.live_array_bytes,
                "live_array_highwater_bytes": self.live_array_highwater,
            }


_global = SolverObservatory()


def observatory() -> SolverObservatory:
    return _global


def _install(obs: SolverObservatory) -> SolverObservatory:
    """Swap the process-global observatory (returns the previous one) —
    the test/bench isolation hook, mirroring metrics._install_registry."""
    global _global, record_call, record_batch, note_asks, note_table
    global record_transfer, record_shards, sample_device_memory, snapshot
    global compiles, steady_recompiles, record_outputs, signatures
    old = _global
    _global = obs
    record_call = obs.record_call
    record_batch = obs.record_batch
    note_asks = obs.note_asks
    note_table = obs.note_table
    record_transfer = obs.record_transfer
    record_shards = obs.record_shards
    sample_device_memory = obs.sample_device_memory
    snapshot = obs.snapshot
    compiles = obs.compiles
    steady_recompiles = obs.steady_recompiles
    record_outputs = obs.record_outputs
    signatures = obs.signatures
    return old


# Module-level conveniences, rebindable via _install (call sites read
# `solverobs.<fn>` through the module at call time).
record_call = _global.record_call
record_batch = _global.record_batch
note_asks = _global.note_asks
note_table = _global.note_table
record_transfer = _global.record_transfer
record_shards = _global.record_shards
sample_device_memory = _global.sample_device_memory
snapshot = _global.snapshot
compiles = _global.compiles
steady_recompiles = _global.steady_recompiles
record_outputs = _global.record_outputs
signatures = _global.signatures


def timed_call_verdict(kernel: str, signature: tuple, fn, *args, **kwargs):
    """Run a jit entry point under the compile ledger: times the call
    (tracing + compilation happen synchronously at dispatch; execution
    is async and NOT awaited here) and records compile-vs-hit. Returns
    (the call's result, the ledger's verdict: True for a compile)."""
    t0 = time.monotonic_ns()
    out = fn(*args, **kwargs)
    compiled = record_call(kernel, signature, time.monotonic_ns() - t0)
    if compiled:
        record_outputs(kernel, out)
    return out, compiled


def timed_call(kernel: str, signature: tuple, fn, *args, **kwargs):
    """timed_call_verdict for a caller that has no use for the verdict."""
    return timed_call_verdict(kernel, signature, fn, *args, **kwargs)[0]
