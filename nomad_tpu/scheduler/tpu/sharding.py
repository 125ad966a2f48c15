"""Node-axis mesh sharding for the batched solver.

A real TPU is a *mesh*, not one chip (SNIPPETS.md's t5x mesh/pjit
partitioning is the pattern). This module owns everything the solver
needs to split the node axis over a `jax.sharding.Mesh`:

  * `SolverMesh` — the mesh itself plus the per-mesh jit cache: the
    distributed-top-k solver (kernels.make_sharded_solver, one jit per
    readback-width bucket so group-count drift never recompiles), the
    preemption variant, and the `NamedSharding` the resident tensors are
    placed with.
  * node-axis padding — `pad_nodes()` extends the pad_n bucket to a
    multiple of the mesh size, so every device owns an equal [N/D, R]
    shard regardless of the cluster's real node count (the shard-padding
    edge: n not divisible by the mesh is absorbed by the bucket, and the
    pad rows carry zero capacity so they can never place).
  * shard accounting — per-shard real-row occupancy for solverobs and
    the modeled ICI bytes an all-gather solve moves (the transfer ledger
    records them under the ``allgather`` direction; they are computed
    from shapes, not measured on the interconnect, and documented as
    such in docs/sharding.md).

Layering: this module lives under scheduler/tpu, the one package allowed
to import jax eagerly (nomad-vet NV-layering); the control plane reaches
sharding state only through solverobs snapshots.
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .kernels import (
    _pad_to,
    make_sharded_solver,
    make_sharded_solver_preempt,
    pad_c,
    pad_n,
)


class SolverMesh:
    """One device mesh with the node axis sharded, plus its jit cache.

    Build once per (device count) and reuse — the factory jits compile
    per mesh, and a fresh SolverMesh per solve would recompile every
    batch (the ledger would show the storm). `solver_mesh()` below is
    the process-global cache production paths go through.
    """

    def __init__(
        self,
        n_devices: Optional[int] = None,
        axis: str = "nodes",
        devices=None,
    ) -> None:
        if devices is None:
            devices = jax.devices()
            if n_devices is not None:
                if len(devices) < n_devices:
                    raise RuntimeError(
                        f"mesh wants {n_devices} devices, the "
                        f"{devices[0].platform} backend has "
                        f"{len(devices)}: fix NOMAD_TPU_MESH_DEVICES "
                        "(mesh_devices) or the backend's device count"
                    )
                devices = devices[:n_devices]
        self.axis = axis
        self.mesh = Mesh(np.asarray(devices), axis_names=(axis,))
        self.n_dev = int(self.mesh.shape[axis])
        self._lock = threading.Lock()
        self._solvers: dict = {}  # k bucket (or None) -> jit
        self._preempt = None

    # -- kernels --------------------------------------------------------

    def solver(self, max_count: Optional[int] = None,
               compact: bool = False):
        """(jit, k_bucket) for the node-sharded solve. max_count bounds
        every group's count in the batch; it is bucketed (pad_c) so the
        jit signature — and the compile ledger — stay stable while the
        batch's biggest group drifts. None = the always-exact full
        argsort waterfill (tests, tiny meshes). compact=True returns
        the [G, maxC] instance-list readback (requires max_count)."""
        k = None if max_count is None else pad_c(max(1, int(max_count)))
        key = (k, compact)
        with self._lock:
            fn = self._solvers.get(key)
            if fn is None:
                fn = self._solvers[key] = make_sharded_solver(
                    self.mesh, self.axis, max_count=k, compact=compact
                )
            return fn, k

    def preempt_solver(self):
        with self._lock:
            if self._preempt is None:
                self._preempt = make_sharded_solver_preempt(
                    self.mesh, self.axis
                )
            return self._preempt

    # -- placement of resident tensors ----------------------------------

    def node_sharding(self) -> NamedSharding:
        """Row-sharded [N, R]: each device owns its node rows once;
        delta syncs scatter into the owning shard (solver.py
        ResidentClusterState)."""
        return NamedSharding(self.mesh, P(self.axis, None))

    def pad_nodes(self, n: int) -> int:
        """pad_n extended to a multiple of the mesh size. pad_n buckets
        (powers of two >= 256, then 2048-multiples) already divide any
        power-of-two mesh <= 256; the round-up only moves for odd mesh
        sizes, and stays a stable bucket either way."""
        return _pad_to(pad_n(n), self.n_dev)

    # -- shard accounting ----------------------------------------------

    def shard_occupancy(self, n: int, np_: int) -> list[dict]:
        """Per-shard real-row occupancy of one dispatch: shard d owns
        rows [d*w, (d+1)*w); rows past the cluster's real n are pad."""
        w = np_ // self.n_dev
        out = []
        for d in range(self.n_dev):
            real = min(max(n - d * w, 0), w)
            out.append({
                "shard": d,
                "rows": w,
                "real_rows": real,
                "occupancy": round(real / w, 4) if w else 0.0,
            })
        return out

    def allgather_bytes(self, g: int, np_: int, k: Optional[int]) -> int:
        """Modeled ICI bytes one solve's all-gathers move (the transfer
        ledger's ``allgather`` direction). Per scan step each device
        receives the other shards' contribution:

          top-k path: (D-1) * k candidate triples (score f32 + units
          i32 + index i32 = 12B) per device, D devices;
          argsort path: the full remote score+units vectors,
          (N - N/D) * 8B per device, D devices.
        """
        d = self.n_dev
        if k is not None:
            per_step = d * (d - 1) * k * 12
        else:
            per_step = d * (np_ - np_ // d) * 8
        return g * per_step


_MESHES: dict[int, SolverMesh] = {}
_MESHES_LOCK = threading.Lock()


def solver_mesh(n_devices: int) -> SolverMesh:
    """Process-global per-device-count cache: every worker/bench caller
    sharing a mesh size shares its compiled kernels."""
    with _MESHES_LOCK:
        m = _MESHES.get(n_devices)
        if m is None:
            m = _MESHES[n_devices] = SolverMesh(n_devices)
        return m
