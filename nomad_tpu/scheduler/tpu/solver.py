"""The batched TPU placement solver.

Orchestration (reference analog: the per-eval loop in
scheduler/generic_sched.go computePlacements :472, batched here across all
pending evaluations — SURVEY.md north star):

  1. host: reconcile each eval (unchanged AllocReconciler) → placement asks
  2. host: lower nodes + groups to tensors (lower.py)
  3. device: solve_placement kernel — score + waterfill every group
  4. host: read back [G, N] assignment counts, pick ports (NetworkIndex),
     mint Allocations, split into per-eval Plans, and *verify* every node
     with the exact host-oracle AllocsFit — any overflow is repaired by
     dropping that node's placements back to the failed list.

The plans then feed the standard plan-queue/applier path unchanged; partial
rejection and RefreshIndex semantics are untouched.
"""

from __future__ import annotations

import logging
import math
import time
from bisect import bisect_right
from dataclasses import dataclass, field, fields as dataclass_fields
from typing import NamedTuple, Optional

import numpy as np

from ...structs import (
    AllocMetric,
    AllocatedResources,
    AllocatedTaskResources,
    Allocation,
    Evaluation,
    Job,
    NetworkIndex,
    Plan,
    generate_uuid,
    generate_uuids,
    now_ns,
)
from ... import solverobs, trace
from ...gctune import paused_gc
from ..context import EvalContext, SchedulerConfig
from ..reconcile import PlacementRequest
from ..util import ready_nodes_in_dcs
from ...structs.structs import AllocDeploymentStatus
from ...structs.placement_batch import PlacementBatch
from ..preemption import PRIORITY_DELTA
from .lower import (
    LoweredGroup, TierSlabs, UsageRows, alloc_priority, build_node_table,
    lower_group,
)
from .kernels import (
    pad_c,
    pad_g,
    pad_n,
    pad_t,
    solve_placement,
    solve_placement_compact,
    solve_placement_preempt,
)

logger = logging.getLogger("nomad_tpu.scheduler.tpu")


@dataclass
class GroupAsk:
    eval_obj: Evaluation
    job: Job
    tg_name: str
    requests: list[PlacementRequest]
    # The eval's plan-so-far (stops/updates appended by the reconciler pass):
    # distinct_hosts / distinct_property / capacity must see vacated slots.
    plan: Optional[Plan] = None


@dataclass
class SolveOutcome:
    # eval_id -> plan additions
    placements: dict[str, list[Allocation]] = field(default_factory=dict)
    # eval_id -> SoA PlacementBatches (the fast-mint path's columns;
    # structs/placement_batch.py) — plan assembly appends these whole,
    # never as per-row Allocations
    batch_placements: dict[str, list] = field(default_factory=dict)
    # eval_id -> {tg_name: AllocMetric} for failed asks
    failures: dict[str, dict[str, AllocMetric]] = field(default_factory=dict)
    # eval_id -> [(victim alloc, preempting alloc id)] — the caller turns
    # these into plan.node_preemptions entries
    preemptions: dict[str, list[tuple[Allocation, str]]] = field(
        default_factory=dict
    )
    groups: int = 0
    solve_ns: int = 0
    # ids of allocs the solver already appended to their ask's plan
    # (the host fast path accumulates into the plan so later selects see
    # earlier placements); the caller must not append those again.
    # Per-ALLOC because one eval can mix host-path asks (sticky groups)
    # with dense-kernel asks in the same batch.
    pre_appended: set = field(default_factory=set)
    # the host stack's walk: nodes its stacks were given, and how many
    # positions of their permutations were ever drawn (stack.ShuffledNodes)
    stack_nodes: int = 0
    stack_nodes_drawn: int = 0
    # binpack rankings its walks computed, and replayed (rank.RankMemo)
    stack_ranked: int = 0
    stack_reused: int = 0
    # of those computed, the ones that found the node exhausted from the
    # store's usage total alone (rank.binpack_node)
    stack_by_usage: int = 0


def may_preempt(state, config: SchedulerConfig, jobs, extra_tiers=()) -> bool:
    """Can a batch of these jobs — (scheduler type, priority) each —
    take a victim at all? Only when a job whose type the operator lets
    preempt sits PRIORITY_DELTA above some committed alloc's priority:
    the store's priority-count aggregate proves absence in O(1), without
    walking allocs (the common all-priority-50 cluster). `extra_tiers`:
    priorities standing beside the store's (a batch's host partition).
    The solver picks its kernel by it, and the worker decides by it
    whether a batch may solve beside an uncommitted one."""
    prios = [p for t, p in jobs if config.preemption_enabled(t)]
    if not prios:
        return False
    tiers = getattr(state, "alloc_priority_tiers", None)
    if tiers is None:
        return True  # a state that keeps no aggregate proves nothing
    top = max(prios)
    return any(top - p >= PRIORITY_DELTA for p in (*tiers(), *extra_tiers))


class _Lowered(NamedTuple):
    """BatchSolver._lower_batch's payload for the dense paths."""

    nodes: list
    table: object
    groups: list
    base_of: dict  # group idx -> unrestricted base (spread retry)
    usage_of: object  # None off the usage-aggregate path
    adj: dict
    total_requests: int
    used: np.ndarray
    tier_limit: np.ndarray
    use_preempt: bool
    compact: bool
    micro: bool


def _merge_outcomes(a: SolveOutcome, b: SolveOutcome) -> SolveOutcome:
    """Union of two partial solves (host-path sticky asks + dense rest)."""
    out = SolveOutcome()
    for src in (a, b):
        for ev, allocs in src.placements.items():
            out.placements.setdefault(ev, []).extend(allocs)
        for ev, batches in src.batch_placements.items():
            out.batch_placements.setdefault(ev, []).extend(batches)
        for ev, fails in src.failures.items():
            out.failures.setdefault(ev, {}).update(fails)
        for ev, pre in src.preemptions.items():
            out.preemptions.setdefault(ev, []).extend(pre)
        out.pre_appended |= src.pre_appended
    out.groups = a.groups + b.groups
    out.solve_ns = a.solve_ns + b.solve_ns
    return out


def group_alloc_metric(grp: LoweredGroup, n: int) -> AllocMetric:
    """AllocMetric for a dense-path group: the lowered feasibility mask
    IS the evaluation record, so nodes_evaluated/nodes_filtered fall out
    of it directly and the per-screen attrition (lower_group's
    filtered_dims) maps onto the reference's per-checker counts —
    resource-shaped screens (cores, network capacity/ports) read as
    dimension_exhausted, membership screens (datacenter, driver,
    constraints, volumes) as constraint_filtered. `alloc status` and the
    blackbox timeline explain a fast-mint placement the same way the
    host GenericStack explains an iterator-path one."""
    metric = AllocMetric(nodes_evaluated=n)
    metric.nodes_filtered = n - int(np.sum(grp.feasible))
    for dim, dropped in grp.filtered_dims.items():
        if dim == "cores" or dim.startswith("network."):
            metric.dimension_exhausted[dim] = dropped
        else:
            metric.constraint_filtered[dim] = dropped
    return metric


class ResidentClusterState:
    """Device-resident capacity/usage tensors reused across solves.

    Re-uploading the full [N, 3] cap/used tensors every solve is
    redundant when the node universe is stable between batches: cap
    changes only on node register/update and usage changes only by the
    deltas of applied plans. This keeps both as DEVICE arrays at the
    padded bucket shape and, per solve, ships only the rows that changed
    since the last sync (diffed against the store's incremental per-node
    usage aggregate, state/store.py IDX_NODE_USED). Over the host-device
    link (PCIe/DCN) that turns the steady-state upload into the
    per-batch group tensors plus a usually-empty delta. Single-writer by
    design: the server's TPU worker
    owns one instance (the eval broker already serializes solves).

    mesh — an optional sharding.SolverMesh: the resident tensors are
    then placed ONCE with the node-axis NamedSharding (each device owns
    its [N/D, R] rows) and never re-upload whole; a delta sync's row
    scatter lands in the owning shard (XLA routes the replicated update
    rows to the shard that holds the index), recorded as ``scatter``
    bytes on the transfer ledger.
    """

    def __init__(self, mesh=None) -> None:
        self.mesh = mesh
        self._node_vers: Optional[tuple] = None
        self._usage: dict[str, tuple] = {}
        self._cap_dev = None
        self._used_dev = None
        self._np = 0
        # host-side NodeTable skeleton cached across solves (same
        # node-universe fingerprint as the device tensors): attribute /
        # driver interning and the capacity columns survive, only the
        # usage rows refresh per solve
        self._host_table = None
        self._host_vers: Optional[tuple] = None
        # the skeleton's preemption tiers as the store last gave them
        # (lower.TierSlabs), for the batches that may preempt
        self._host_tiers = None
        # ... and its usage rows (lower.UsageRows), for every batch
        self._host_usage = None
        # the node list last proven to be the skeleton's universe: the
        # same list object needs no fingerprint walk
        self._host_nodes: Optional[list] = None
        # the previous solve's returned table, held one solve gap so
        # the skeleton can harvest its lazily-built SoA columns
        self._last_table = None
        # warm eval-context caches (the interactive fast path): ready
        # node lists per dc set, keyed by the nodes-table index, and
        # lowered-group skeletons (feasibility/bias/unit-cap tensors)
        # keyed by (job identity, tg) against the host-table fingerprint
        # — a repeat-shaped eval skips both the node scan and the
        # lowering entirely.
        self._node_cache: dict[tuple, tuple] = {}
        self._lowered: dict[tuple, tuple] = {}
        # telemetry: how the last sync was satisfied
        self.last_sync = "cold"

    @classmethod
    def for_config(cls, config) -> "ResidentClusterState":
        """The resident state a SchedulerConfig asks for: sharded over
        the configured mesh when mesh_devices > 1, plain otherwise. A
        mesh wider than the backend raises (sharding.SolverMesh) — it is
        never narrowed to what the backend happens to have."""
        n = getattr(config, "mesh_devices", 0) or 0
        if n > 1:
            from .sharding import solver_mesh

            return cls(mesh=solver_mesh(n))
        return cls()

    def describe(self) -> dict:
        """Where the resident usage tensor lives — the worker publishes
        it (stats_snapshot → /v1/solver/status) so an operator can read
        the placement of a sharded resident state off a live server."""
        used = self._used_dev
        return {
            "last_sync": self.last_sync,
            "shape": None if used is None else list(used.shape),
            "sharding": None if used is None else str(used.sharding),
            "platforms": [] if used is None else sorted(
                {d.platform for d in used.devices()}
            ),
        }

    def ready_nodes(self, state, datacenters: tuple):
        """Cached ready_nodes_in_dcs keyed by (dc glob set, nodes-table
        index). The nodes-table index moves on any node register /
        status / drain write — exactly the events that change ready-node
        membership — and alloc/usage writes leave it alone, so a warm
        entry survives steady-state scheduling traffic untouched."""
        from ..util import ready_nodes_in_dcs

        idx_fn = getattr(state, "nodes_table_index", None)
        if idx_fn is None:
            return ready_nodes_in_dcs(state, list(datacenters))
        idx = idx_fn()
        entry = self._node_cache.get(datacenters)
        if entry is not None and entry[0] == idx:
            return entry[1], entry[2]
        nodes, counts = ready_nodes_in_dcs(state, list(datacenters))
        if len(self._node_cache) > 64:
            self._node_cache.clear()
        self._node_cache[datacenters] = (idx, nodes, counts)
        return nodes, counts

    def lowered_skeleton(self, vers, job, tg_name: str):
        """Cached (ask, feasible, bias, units_cap, filtered_dims) for
        one task group
        against the host-table fingerprint `vers` (identity compare:
        host_table interns one tuple per node-universe generation).
        Arrays are shared read-only — every consumer (dedupe, spread
        splits, the micro kernel) copies before mutating."""
        key = (job.namespace, job.id, job.version, job.modify_index,
               tg_name)
        entry = self._lowered.get(key)
        if entry is not None and entry[0] is vers:
            return entry[1]
        return None

    def store_lowered(self, vers, job, tg_name: str, tensors) -> None:
        if len(self._lowered) > 256:
            self._lowered.clear()
        self._lowered[
            (job.namespace, job.id, job.version, job.modify_index, tg_name)
        ] = (vers, tensors)

    def host_table(self, nodes: list, allocs_by_node, usage_of, usage,
                   tiers=None):
        """Cached build_node_table for the usage-aggregate path, with
        (tiers given: a batch that may preempt) or without the
        preemption tiers. `usage`: (the snapshot's bulk reader
        `node_usage_many`, the batch's per-node adjustments) — the rows
        come from lower.UsageRows, rewriting only the nodes whose entry
        changed; `usage_of` builds the rows of a rebuilt skeleton.

        Rebuilding the 100k-row host table every solve was the largest
        steady-state host cost of the sharded bench (~0.7s/solve at c2m
        scale, plus re-interning every constraint attribute). The
        skeleton (cap, index_of, dc codes, attr/driver interning) is
        valid as long as every node's (id, modify_index) is unchanged —
        proven without a walk when `nodes` is the very list last proven:
        ready_nodes hands out one cached list per datacenter set and
        nodes-table index, every node write moves the index, and the
        reference held here keeps the list's id from being reused.

        Every call returns a FRESH NodeTable object that shares only
        the immutable skeleton: pipelined batches overlap (batch N's
        finish runs while batch N+1's begin re-reads usage), so handing
        consecutive solves one mutated-in-place table would race batch
        N's overflow-repair reads against batch N+1's usage refresh.
        Per-solve state — the usage rows, the tier slabs, the snapshot
        accessor, the static-port masks — is this table's own; the
        shared attr/driver caches are append-only interning keyed by
        node attrs the fingerprint already pins."""
        from .lower import NodeTable

        def clone(src, used_arr, tiers, accessor):
            out = NodeTable(
                nodes=src.nodes,
                index_of=src.index_of,
                cap=src.cap,
                used=used_arr,
                datacenters=src.datacenters,
                dc_values=src.dc_values,
                tier_prios=tiers[0],
                tier_used=tiers[1],
                cores_free=src.cores_free,
                _attr_cache=src._attr_cache,
                _driver_cache=src._driver_cache,
            )
            out._allocs_by_node = accessor
            # SoA id/name columns are node-set-derived: share the
            # interned lists instead of rebuilding 100k-string columns
            for col in ("_node_id_col", "_node_name_col"):
                cached = getattr(src, col, None)
                if cached is not None:
                    setattr(out, col, cached)
            return out

        from ... import metrics

        n = len(nodes)
        no_tiers = ([], np.zeros((0, n, 3), dtype=np.int64))
        skel = self._host_table
        rebuild = False
        if skel is None or nodes is not self._host_nodes:
            metrics.incr("nomad.tpu.lower_fingerprint_walks")
            vers = tuple((node.id, node.modify_index) for node in nodes)
            rebuild = skel is None or self._host_vers != vers
        self._host_nodes = nodes
        if rebuild:
            t = build_node_table(nodes, allocs_by_node, usage_of=usage_of)
            self._host_tiers = None
            if tiers is not None:
                self._host_tiers = TierSlabs(t.index_of)
                t.tier_prios, t.tier_used = self._host_tiers.read(*tiers)
            # the usage rows start over too: the next read writes them all
            self._host_usage = None
            # The cached skeleton carries NO snapshot accessor: holding
            # this solve's allocs_by_node closure would pin its whole
            # state snapshot for as long as the node fingerprint stays
            # stable (hours on a quiet cluster). The live table keeps
            # its accessor; only the cache copy is stripped — and its
            # tiers, which are this solve's as the usage rows are.
            self._host_table = clone(t, t.used, no_tiers, None)
            self._host_vers = vers
            self._last_table = t
            return t
        # Harvest SoA columns lazily built on the previous solve's table
        # into the skeleton, then drop the reference — _last_table pins
        # at most one solve's snapshot, the same one the pipelined
        # overlap (finish(N) concurrent with begin(N+1)) keeps live
        # anyway.
        last = self._last_table
        self._last_table = None
        if last is not None:
            for col in ("_node_id_col", "_node_name_col"):
                if getattr(skel, col, None) is None:
                    cached = getattr(last, col, None)
                    if cached is not None:
                        setattr(skel, col, cached)
        if self._host_usage is None:
            self._host_usage = UsageRows(skel.index_of)
        used = self._host_usage.read(*usage)
        metrics.observe(
            "nomad.tpu.lower_usage_rewritten", self._host_usage.rewritten
        )
        slabs = no_tiers
        if tiers is not None:
            if self._host_tiers is None:
                self._host_tiers = TierSlabs(skel.index_of)
            slabs = self._host_tiers.read(*tiers)
        t2 = clone(skel, used, slabs, allocs_by_node)
        self._last_table = t2
        return t2

    def sync(self, snapshot, nodes: list) -> tuple:
        """Return (cap_dev, used_dev) current for `nodes` (table order).

        Full re-upload when the node universe/capacity changed
        (fingerprint: per-node (id, modify_index)); otherwise a
        scatter-update of just the usage rows whose committed aggregate
        moved since the last solve.
        """
        import jax
        import jax.numpy as jnp

        n = len(nodes)
        np_ = self.mesh.pad_nodes(n) if self.mesh is not None else pad_n(n)
        vers = tuple((node.id, node.modify_index) for node in nodes)
        usage = {
            node.id: snapshot.node_usage(node.id) for node in nodes
        }
        if (
            self._node_vers != vers
            or self._np != np_
            or self._cap_dev is None
        ):
            # identical clipping to _lower_small so the resident tensors
            # are bit-equal to what a fresh upload would carry
            cap = np.zeros((np_, 3), dtype=np.int32)
            used = np.zeros((np_, 3), dtype=np.int32)
            cap_rows = np.array(
                [
                    (a.cpu, a.memory_mb, a.disk_mb)
                    for a in (node.available_resources() for node in nodes)
                ],
                dtype=np.int64,
            ).reshape(n, 3)
            used_rows = np.array(
                [usage[node.id][:3] for node in nodes], dtype=np.int64
            ).reshape(n, 3)
            cap[:n] = np.clip(cap_rows, 0, 2**31 - 1)
            used[:n] = np.clip(used_rows, 0, 2**31 - 1)
            t_up0 = now_ns()
            if self.mesh is not None:
                # placed per-shard ONCE: each device gets its own node
                # rows and the full tensors never re-upload again
                sharding = self.mesh.node_sharding()
                self._cap_dev = jax.device_put(cap, sharding)
                self._used_dev = jax.device_put(used, sharding)
            else:
                self._cap_dev = jax.device_put(cap)
                self._used_dev = jax.device_put(used)
                # A chained solve adds its adjustments — a lane commit
                # the parent's used' never saw — onto that jit output
                # with the scatter-add (_chain_adj_add): its smallest
                # bucket is compiled here, once, with the resident
                # tensors, and not inside the first chained solve that
                # has any. Every index is out of range: nothing lands.
                idx = np.full(1, 1 << 30, dtype=np.int32)
                rows = np.zeros((1, 3), dtype=np.int32)
                _scatter_add_rows(_scatter_rows(
                    self._used_dev, idx, rows, donate=False), idx, rows)
            # block before timestamping: device_put only ENQUEUES on
            # async backends, and an un-awaited span would read ~0 on
            # exactly the slow-link deployments the span exists to
            # expose (the full sync is rare — node-universe changes)
            jax.block_until_ready(self._used_dev)
            solverobs.record_transfer(
                "h2d", cap.nbytes + used.nbytes,
                dur_ns=now_ns() - t_up0, span=True,
            )
            self._node_vers = vers
            self._np = np_
            self._usage = usage
            self.last_sync = "full"
            return self._cap_dev, self._used_dev
        prev = self._usage
        changed_idx = [
            i for i, node in enumerate(nodes)
            if usage[node.id] != prev.get(node.id, (0, 0, 0, 0))
        ]
        if changed_idx:
            rows = np.clip(
                np.array(
                    [usage[nodes[i].id][:3] for i in changed_idx],
                    dtype=np.int64,
                ),
                0,
                2**31 - 1,
            ).astype(np.int32)
            idx = np.asarray(changed_idx, dtype=np.int32)
            self._used_dev = _scatter_rows(
                self._used_dev, idx, rows,
                shard_tag=self.mesh.n_dev if self.mesh is not None else 0,
            )
            # bytes only, no span: the scatter call above is a jit
            # DISPATCH (a new idx shape trace/compiles synchronously —
            # timed_call ledgers that as solver.compile), so timing it
            # as a transfer would attribute compile cost to the link
            solverobs.record_transfer("h2d", rows.nbytes + idx.nbytes)
            if self.mesh is not None:
                # sharded resident: the delta rows land in their owning
                # shard — ledgered as scatter traffic so a delta storm
                # is visible next to the all-gather column
                solverobs.record_transfer("scatter", rows.nbytes)
            self._usage = usage
            self.last_sync = f"delta:{len(changed_idx)}"
        else:
            self.last_sync = "clean"
        return self._cap_dev, self._used_dev


def _pad_scatter_args(idx: np.ndarray, rows: np.ndarray):
    """Bucket a row-scatter's update shape (power of two, floor 1024)
    so the jit signature — and so the compile ledger — stays stable
    while the per-solve delta size drifts. Pad indices point past the
    array and the scatter jits run mode="drop", so pad rows never
    land."""
    n = idx.shape[0]
    b = 1024
    while b < n:
        b *= 2
    if b == n:
        return idx, rows
    pad_idx = np.full(b - n, 1 << 30, dtype=idx.dtype)
    pad_rows = np.zeros((b - n, rows.shape[1]), dtype=rows.dtype)
    return (
        np.concatenate([idx, pad_idx]),
        np.concatenate([rows, pad_rows]),
    )


def _scatter_rows(used_dev, idx, rows, donate: bool = True,
                  shard_tag: int = 0):
    """Row-scatter onto a resident device array. donate=True consumes
    the old buffer in place (sync updates — the resident array is
    replaced by its successor); donate=False leaves it intact (a
    per-batch adjusted view for vacated stops / partition placements).
    One jit per flavor, cached. shard_tag (the mesh size, 0 unsharded)
    keys the ledger signature: a sharded operand compiles its own SPMD
    executable even at equal shapes, and the ledger must count it."""
    import jax

    idx, rows = _pad_scatter_args(idx, rows)
    fn = _SCATTER_JITS.get(donate)
    if fn is None:

        def _scatter(used, idx, rows):
            return used.at[idx].set(rows, mode="drop")

        fn = _SCATTER_JITS[donate] = jax.jit(
            _scatter, donate_argnums=(0,) if donate else ()
        )
    return solverobs.timed_call(
        "scatter_rows",
        ("scatter_rows", donate, tuple(used_dev.shape), tuple(idx.shape),
         shard_tag),
        fn, used_dev, idx, rows,
    )


_SCATTER_JITS: dict = {}


def _scatter_add_rows(used_dev, idx, rows, shard_tag: int = 0):
    """Row-scatter-ADD (clamped at zero) onto a non-donated device usage
    array: applies a batch's vacated-stop deltas on top of a CHAINED
    used' tensor. A set-scatter of aggregate rows would clobber the
    chain's in-flight placements; the delta add preserves them."""
    import jax

    idx, rows = _pad_scatter_args(idx, rows)
    fn = _SCATTER_ADD_JIT.get("fn")
    if fn is None:
        import jax.numpy as jnp

        def _scatter_add(used, idx, rows):
            return jnp.maximum(used.at[idx].add(rows, mode="drop"), 0)

        fn = _SCATTER_ADD_JIT["fn"] = jax.jit(_scatter_add)
    return solverobs.timed_call(
        "scatter_add_rows",
        ("scatter_add_rows", tuple(used_dev.shape), tuple(idx.shape),
         shard_tag),
        fn, used_dev, idx, rows,
    )


_SCATTER_ADD_JIT: dict = {}


def _chain_adj_add(used_dev, table, adj, adj_in, shard_tag: int):
    """Apply the committed-gap usage DELTAS (`adj`) for the in-table
    node ids `adj_in` onto a CHAINED used' tensor — the one adj
    application both chain consumers (resident+chain and chain-only)
    share. Deltas, not aggregates: a set-scatter would clobber the
    parent's in-flight placements."""
    idx = np.asarray(
        [table.index_of[nid] for nid in adj_in], dtype=np.int32
    )
    rows = np.clip(
        np.asarray([adj[nid] for nid in adj_in], dtype=np.int64),
        -(2**31) + 1,
        2**31 - 1,
    ).astype(np.int32)
    return _scatter_add_rows(used_dev, idx, rows, shard_tag=shard_tag)


class UsageChain:
    """What a batch in flight offers the next solve (`chain_out`, the
    worker's `PendingEvalBatch.chain`): the usage it left behind, while
    its commit is pending, as [pad_n, 3] rows over a node universe. A
    kernel batch offers its device `used'`, a microsolve batch the same
    rows on the host, a host-stack batch the rows it read — the chain in
    flight's, or the committed usage of its own snapshot — with its
    placements added. Every form lies over the committed usage of one
    snapshot, the chain's basis, so a commit that lands after it is
    cancelled by the child's own committed usage (a child reads the
    rows in place of it). A host-stack batch's rows are built at their
    first read (`build`): a batch that nothing chains on builds none."""

    __slots__ = ("_node_ids", "_used", "_index_of", "_build")

    def __init__(self, node_ids=None, used=None, index_of=None,
                 build=None) -> None:
        self._node_ids = node_ids  # the node universe, in row order
        self._used = used  # [pad_n, 3]: a jit output or a host array
        self._index_of = index_of  # node id -> row of `used`
        self._build = build  # () -> (node_ids, used, index_of)

    def _resolve(self) -> None:
        if self._build is not None:
            self._node_ids, self._used, self._index_of = self._build()
            self._build = None

    @property
    def node_ids(self) -> list:
        self._resolve()
        return self._node_ids

    @property
    def used(self):
        self._resolve()
        return self._used

    @property
    def index_of(self) -> dict:
        self._resolve()
        return self._index_of

    def with_used(self, used) -> "UsageChain":
        return UsageChain(self.node_ids, used, self.index_of)


class _InFlightUsage:
    """What a host-stack solve counts on a node over its snapshot
    (EvalContext.extra_usage): the in-flight chain's row less the node's
    committed usage, and the interactive lane's ledger. Read per node
    as the stack visits it, so a sample of 17 nodes reads 17 rows of a
    5,000-node chain."""

    __slots__ = ("_usage", "rows", "_index_of", "_extra")

    def __init__(self, state, chain: UsageChain,
                 extra: Optional[dict]) -> None:
        self._usage = state.node_usage
        # a kernel parent's used' is read back here, once: the copy
        # waits out its kernel, not its commit
        self.rows = np.asarray(chain.used)
        self._index_of = chain.index_of
        self._extra = extra or {}

    def get(self, nid: str):
        d = None
        i = self._index_of.get(nid)
        if i is not None:
            row, u = self.rows[i], self._usage(nid)
            d = [int(row[0]) - u[0], int(row[1]) - u[1], int(row[2]) - u[2]]
        v = self._extra.get(nid)
        if v is not None:
            if d is None:
                d = [0, 0, 0]
            d[0] += v[0]
            d[1] += v[1]
            d[2] += v[2]
        return d


def _union_nodes(lists) -> list:
    """The union of ready-node lists, in first-seen order: the one list
    itself where there is one."""
    lists = list(lists)
    if len(lists) == 1:
        return lists[0]
    all_nodes = {}
    for nodes in lists:
        for node in nodes:
            all_nodes[node.id] = node
    return list(all_nodes.values())


_ALLOC_FIELD_NAMES = tuple(f.name for f in dataclass_fields(Allocation))


class _MintTemplate:
    """Interned per-(job, taskgroup) Allocation prototype for the bulk
    fast-mint path: fresh solver placements within one group differ only
    in (id, name, node), so cloning the prototype via __new__ + slot
    copy-and-patch skips the dataclass constructor and its per-alloc
    default-factory constructions (~4 objects each across 10^5 mints at
    c2m scale). Shared sub-objects — resources, metrics, the empty
    containers — ride the state store's copy-on-write discipline: every
    writer copies an alloc (Allocation.copy deep-copies the mutable
    fields) before mutating, the same rule the shared AllocatedResources
    fast-mint has always relied on.

    With soa_placements the same template seeds whole PlacementBatches
    (shared resources/metrics objects across a group's sub-batches, the
    identical sharing the eager mint had); per-row mint survives as the
    eager comparator and the overflow-repair/cores paths."""

    __slots__ = ("items", "proto")

    def __init__(self, proto: Allocation) -> None:
        self.proto = proto
        self.items = [(n, getattr(proto, n)) for n in _ALLOC_FIELD_NAMES]

    def mint(self, uid: str, name: str, node) -> Allocation:
        a = Allocation.__new__(Allocation)
        for n, v in self.items:
            setattr(a, n, v)
        a.id = uid
        a.name = name
        a.node_id = node.id
        a.node_name = node.name
        return a


class PendingSolve:
    """An in-flight batch solve between its two phases.

    Phase A (already run): host prep + async device dispatch. finish()
    runs phase B — block on the device, injected-RTT wait, readback,
    materialization, spread-relaxation retry — and returns the
    SolveOutcome. Single-shot; the generator is dropped after finish so
    a double finish() returns the cached outcome."""

    __slots__ = ("_gen", "_outcome", "solved_in_begin")

    def __init__(self, gen, outcome: Optional[SolveOutcome]) -> None:
        self._gen = gen
        self._outcome = outcome
        # the whole solve ran in phase A (host stack, microsolve, a
        # sticky partition, nothing to place): no kernel is in flight
        # and finish() has nothing to block on
        self.solved_in_begin = gen is None

    def finish(self) -> SolveOutcome:
        if self._gen is None:
            return self._outcome
        gen, self._gen = self._gen, None
        with paused_gc():
            try:
                next(gen)
            except StopIteration as s:
                self._outcome = s.value
                return self._outcome
        raise AssertionError("solver generator yielded more than once")


class BatchSolver:
    """Solves placement for a batch of evaluations against one snapshot."""

    def __init__(self, state, config: Optional[SchedulerConfig] = None,
                 solve_fn=None, solve_preempt_fn=None,
                 resident: Optional[ResidentClusterState] = None,
                 used_chain: Optional[tuple] = None,
                 mesh=None, extra_usage: Optional[dict] = None) -> None:
        self.state = state
        self.config = config or SchedulerConfig()
        # Multi-chip: a sharding.SolverMesh routes the dense solve
        # through the node-sharded kernels (distributed-top-k waterfill,
        # per-mesh jit cache) and places resident tensors per-shard.
        # The host fast paths (sticky partition, small batches) stay
        # live — the sharded kernel is bit-identical to solve_placement,
        # so the same routing rules hold.
        if mesh is not None and solve_fn is not None:
            raise ValueError("mesh and solve_fn are mutually exclusive")
        self.mesh = mesh
        # Device-resident cap/used tensors shared across solves (the
        # server's TPU worker owns one instance); None = upload per solve.
        self.resident = resident
        # Per-node (cpu, mem, disk) usage DELTAS external to this
        # snapshot that the aggregate fast path must still count — the
        # worker's interactive-lane ledger: placements a priority-lane
        # eval committed after the chain basis, which neither the
        # chained used' tensor nor (for in-flight ones) the committed
        # aggregate carries. Applied on the usage-aggregate path, and by
        # a host-stack solve that reads an in-flight chain.
        self.extra_usage = extra_usage
        # set when the solve ran the host microsolve kernel: zero device
        # involvement
        self.used_micro = False
        # host-table fingerprint token for the lowered-skeleton cache
        # (set when the resident host-table path produced this solve's
        # table; None disables the cache for the solve)
        self._lower_vers = None
        self._lower_cache_hits = 0  # lowered-skeleton hits, this solve
        # (node_ids tuple, used_dev) — the PREVIOUS batch's post-solve
        # usage tensor, still on device. While that batch's commit is in
        # flight, the committed aggregate hasn't caught up, so a
        # deterministic binpack would re-place the next batch onto the
        # same nodes and the applier would reject everything. Chaining
        # the kernel's own used' output as the next solve's used input
        # keeps consecutive in-flight batches conflict-free WITHOUT
        # blocking on the device (a pure device-graph dependency) —
        # this is what makes the worker's solve/commit overlap pay at
        # high fill (docs/pipeline.md). The host paths chain too: the
        # microsolve reads the rows back, the host stack counts them per
        # node it visits (UsageChain).
        self.used_chain: Optional[UsageChain] = used_chain
        # set during phase A: the UsageChain the NEXT batch may chain on
        # (every path that places offers one)
        self.chain_out: Optional[UsageChain] = None
        # did this solve actually CONSUME used_chain? False when the
        # solve took the preempt path or walked the allocs, or the chain
        # was rejected on a node-universe/shape mismatch — the worker's
        # chain-failure cascade only applies when this is True
        self.chain_accepted = False
        self.ctx = EvalContext(state, None, logger, self.config)
        self.solve_fn = solve_fn or solve_placement
        # Preemption kernel seam: defaults to the single-chip tier kernel
        # when the plain kernel is the default; a custom solve_fn (e.g. a
        # mesh-sharded solver) must bring its own preempt variant
        # (make_sharded_solver_preempt) or preemption is disabled for it.
        if solve_preempt_fn is not None:
            self.solve_preempt_fn = solve_preempt_fn
        elif mesh is not None:
            self.solve_preempt_fn = mesh.preempt_solver()
        elif solve_fn is None:
            self.solve_preempt_fn = solve_placement_preempt
        else:
            self.solve_preempt_fn = None
        # Port-accounting index per node, shared across the whole batch so
        # placements in this solve see each other's port reservations.
        self._net_cache: dict[str, NetworkIndex] = {}
        # Per-node device allocator, shared across the batch (like the
        # port index above) so placements see each other's reservations.
        self._dev_cache: dict[str, object] = {}
        # Per-node (free dedicated-core ids, MHz/core), shared across
        # the batch; the list is mutated in place as grants are cut.
        self._core_cache: dict[str, tuple] = {}
        # set by solve(): with no cores ask anywhere in the batch the
        # dense solve's declared-MHz accounting is exact and the ledger
        # (an O(allocs-per-node) state scan per node) is skipped
        self._batch_has_cores = False
        # allocs stopped by this batch's plans: vacated for seeding
        self._stopped_ids: set = set()
        # Per-node cpu MHz ledger. The dense solve packs the DECLARED
        # cpu ask; a `cores` task's granted cpu is DERIVED (cores x
        # MHz/core) and can exceed it, so cores placements re-screen
        # against real remaining MHz (rank.py does the same superset
        # re-check on the host path). _state_cpu is the committed-state
        # baseline; _batch_cpu tracks EVERY placement this solve makes
        # (fast path included) so the screen sees same-batch neighbors.
        self._state_cpu: dict[str, int] = {}
        self._batch_cpu: dict[str, int] = {}
        # Set while solving the dense remainder of a mixed batch: the
        # host partition's placements (capacity) and plans (cross-eval
        # accounting) that this solve must observe.
        self._partition_placed: list = []
        self._partition_plans: list = []
        # what the host stack read and visited, for the chain it offers
        # (_publish_host): the chain in flight, read once, and the ready
        # list of each datacenter set
        self._in_flight: Optional[_InFlightUsage] = None
        self._host_nodes: dict[tuple, list] = {}
        # (eval_id, id(job), tg_name) -> _MintTemplate, shared across a
        # batch's groups (spread sub-groups and the relaxation retry
        # re-hit it; keyed by eval so same-job evals never cross-stamp).
        self._mint_cache: dict[tuple, _MintTemplate] = {}

    def _pad_n(self, n: int) -> int:
        """Node-axis bucket: the mesh extends pad_n to a multiple of the
        device count so every shard is equal-width (pad rows carry zero
        capacity and can never place)."""
        if self.mesh is not None:
            return self.mesh.pad_nodes(n)
        return pad_n(n)

    def solve(self, asks: list[GroupAsk]) -> SolveOutcome:
        return self.solve_begin(asks).finish()

    def solve_begin(self, asks: list[GroupAsk]) -> "PendingSolve":
        """Phase A of a two-phase solve: reconcile-independent host prep
        (node table, lowering, ledgers) plus the ASYNC device dispatch.
        Returns a PendingSolve whose finish() blocks on the device, reads
        back, and materializes Allocations — the pipelined worker runs
        finish() on its commit stage so batch N's readback/materialization
        overlaps batch N+1's host prep and device round-trip."""
        # One batch is a bounded allocation burst (up to ~100k minted
        # allocs at c2m scale); young-gen GC passes during it cost more
        # than everything they could ever reclaim (gctune.py).
        gen = self._solve_gen(asks)
        with paused_gc():
            try:
                next(gen)
            except StopIteration as s:
                # host-only solve (small batch / empty / host partition):
                # finished without touching the device
                return PendingSolve(None, s.value)
        return PendingSolve(gen, None)

    def _solve_gen(self, asks: list[GroupAsk]):
        from ... import metrics

        out = SolveOutcome()
        self._outcome = out
        self._batch_has_cores = False
        kind, low = self._lower_batch(asks, out) if asks else ("done", None)
        if kind == "done":
            # nothing placed: the chain in flight is passed on whole, so
            # the batch behind this one still sees what is under it
            if self.used_chain is not None:
                self.chain_out = self.used_chain
                self.chain_accepted = True
            return out
        if kind == "host":
            return self._solve_host_timed(*low)
        if kind == "sticky":
            sticky_idx = low
            sticky_asks = [a for i, a in enumerate(asks) if i in sticky_idx]
            host_out = self._solve_host(sticky_asks)
            rest = [a for i, a in enumerate(asks) if i not in sticky_idx]
            if not rest:
                return self._publish_host(host_out)
            # the rest-solve must see the host partition's results:
            # its placements consume capacity; its plans feed the
            # host fast path's cross-eval accounting
            self._partition_placed = [
                a
                for allocs_ in host_out.placements.values()
                for a in allocs_
            ]
            self._partition_plans = [
                a.plan for a in sticky_asks if a.plan is not None
            ]
            try:
                dense_out = self.solve(rest)
            finally:
                self._partition_placed = []
                self._partition_plans = []
            return _merge_outcomes(host_out, dense_out)
        (nodes, table, groups, base_of, usage_of, adj, total_requests,
         used, tier_limit, use_preempt, compact, micro) = low
        n = table.n

        t0 = now_ns()
        # Resident device tensors: valid only when the usage-aggregate
        # path produced the table (the sync diffs against the same
        # aggregate) — the batch adjustments are scattered onto a
        # non-donated copy so the resident buffer stays committed-state.
        # On a mesh the resident tensors are placed per-shard
        # (ResidentClusterState.mesh). A micro solve skips all of it:
        # the table's host arrays already carry the aggregate + adj, and
        # an in-flight chain's rows are read back in their place.
        dev_state = None
        chain_used = None
        if compact and usage_of is not None and self.used_chain is not None:
            chain = self.used_chain
            if (
                chain.node_ids == self._node_id_col(table)
                and chain.used.shape == (self._pad_n(n), 3)
            ):
                chain_used = chain.used
        if compact and usage_of is not None and not micro:
            shard_tag = self.mesh.n_dev if self.mesh is not None else 0
            if isinstance(chain_used, np.ndarray):
                # a host-path parent's rows: onto the device as the
                # resident tensors are put there
                import jax

                chain_used = jax.device_put(
                    chain_used,
                    *([self.mesh.node_sharding()] if self.mesh else []),
                )
            if self.resident is not None:
                cap_dev, used_dev = self.resident.sync(self.state, nodes)
                if chain_used is not None:
                    # Compose resident + chain: the chained used' tensor
                    # IS the resident usage as of the in-flight parent's
                    # solve plus its placements (the parent consumed the
                    # resident tensors), so it supersedes the committed
                    # aggregate while the parent's commit is pending —
                    # without it, a pipelined resident solver would
                    # re-place onto the parent's nodes and lean on
                    # applier rejections. cap still rides the resident
                    # shard (node-capacity changes invalidate the chain
                    # via the fingerprint/node-id check above).
                    used_dev = chain_used
                    self.chain_accepted = True
                # stops can reference nodes outside this batch's dc
                # universe — those rows aren't in the table (or tensors)
                adj_in = [nid for nid in adj if nid in table.index_of]
                if adj_in:
                    if chain_used is not None:
                        used_dev = _chain_adj_add(
                            used_dev, table, adj, adj_in, shard_tag
                        )
                    else:
                        idx = np.array(
                            [table.index_of[nid] for nid in adj_in],
                            dtype=np.int32,
                        )
                        rows = np.clip(
                            np.array(
                                [usage_of(nid)[:3] for nid in adj_in],
                                dtype=np.int64,
                            ),
                            0,
                            2**31 - 1,
                        ).astype(np.int32)
                        used_dev = _scatter_rows(
                            used_dev, idx, rows, donate=False,
                            shard_tag=shard_tag,
                        )
                dev_state = (cap_dev, used_dev)
            elif chain_used is not None:
                # Chain the in-flight previous batch's post-solve usage
                # (device array, never blocked on) so this batch's
                # waterfill sees its placements and stays conflict-free.
                used_dev = chain_used
                adj_in = [nid for nid in adj if nid in table.index_of]
                if adj_in:
                    used_dev = _chain_adj_add(
                        used_dev, table, adj, adj_in, shard_tag
                    )
                dev_state = (None, used_dev)
                self.chain_accepted = True
        if (
            compact and not micro
            and self._readback_bound(table.cap, used, groups, n) == 0
        ):
            # A full cluster: by the exact host arrays no group can
            # place one instance (never the verdict of a solve that
            # consumed a chain, whose bound is the groups' counts), so
            # the batch is failed here and the device never sees it.
            return self._fail_full_cluster(groups, base_of, n, t0)
        if micro:
            inst, over, used_out = self._run_micro(
                table, groups, used, total_requests, chain_used, adj
            )
            if self.chain_accepted:
                metrics.observe("nomad.tpu.host_chain_consumed", 1)
        elif compact:
            pending = self._run_compact_async(
                table, groups, used, dev_state=dev_state
            )
            # expose this batch's post-solve usage for the NEXT batch's
            # chain (pending[2] is the kernel's used' device output)
            self.chain_out = UsageChain(
                self._node_id_col(table), pending[2], table.index_of
            )
        else:
            # Exact-repair ledger as plain Python ints: it is touched once
            # per PLACED INSTANCE where small-array numpy ops cost ~10x an
            # int compare.
            self._free = [
                [int(c) for c in row] for row in (table.cap - table.used)
            ]
            pending = self._run_kernel_async(
                table, groups, used, tier_limit=tier_limit,
                use_preempt=use_preempt,
            )
            if use_preempt and usage_of is not None:
                # The preempt solve's used' — placements added, what the
                # kernel counted as freed subtracted — is offered to the
                # NEXT batch as the compact solve's is. The host picks
                # whole victims, which free at least what the kernel
                # counted, so the tensor never shows room that is not
                # there: sound for a follower that places into free room
                # alone. One that may preempt would read its tiers from
                # a store that still holds this batch's victims and take
                # them again; the worker has it wait for this batch's
                # commit instead (worker._solve_batch, docs/pipeline.md).
                metrics.incr("nomad.tpu.preempt.chain_offered")
                self.chain_out = UsageChain(
                    self._node_id_col(table), pending[2], table.index_of
                )
        # -- phase boundary: the kernel is dispatched, nothing has read
        # it back. The pipelined worker parks here and resumes on its
        # commit stage, so the device round-trip (and everything below)
        # overlaps the NEXT batch's dequeue/reconcile/lower/dispatch.
        # A MICRO solve never parks: the result is already on the host,
        # so the whole solve completes in phase A and the worker's
        # commit stage has nothing to wait on (PendingSolve finishes
        # without a generator hop).
        phase_a_ns = now_ns() - t0
        if not micro:
            yield
        t0 = now_ns()
        if compact:
            if not micro:
                inst, over, used_out = self._run_compact_finish(pending)
            free_base = table.cap - table.used
            leftovers, mat_ns = self._timed_materialize(
                self._materialize_compact,
                table, groups, inst, over, free_base,
            )
        else:
            assign, assign_evict, used_out = self._run_kernel_finish(pending)
            leftovers, mat_ns = self._timed_materialize(
                self._materialize, table, groups, assign, assign_evict
            )

        # Fallback pass: spread is a soft preference — requests a
        # value-restricted sub-group could not place retry against the
        # unrestricted base feasibility with updated utilization.
        retry: list[LoweredGroup] = []
        final_unplaced: dict[tuple, tuple[LoweredGroup, list]] = {}
        for gi, reqs in leftovers.items():
            grp = groups[gi]
            if reqs and grp.restricted:
                import dataclasses

                retry.append(
                    dataclasses.replace(
                        base_of[gi],
                        count=len(reqs),
                        names=[r.name for r in reqs],
                        requests=reqs,
                        restricted=False,
                    )
                )
            elif reqs:
                key = (grp.key[0], grp.tg.name)
                prev = final_unplaced.get(key)
                final_unplaced[key] = (grp, (prev[1] if prev else []) + reqs)
        if retry:
            # Spread-relaxation retry runs WITHOUT preemption: the tier
            # prefix tensors describe pre-solve usage and a second
            # preemption pass could double-claim the same victims.
            used2 = np.asarray(used_out)[:n]
            if micro:
                inst2, over2, used_retry = self._run_micro(
                    table, retry, used2, sum(g.count for g in retry)
                )
                leftovers2, mat2_ns = self._timed_materialize(
                    self._materialize_compact,
                    table, retry, inst2, over2, table.cap - used2,
                )
            elif compact:
                inst2, over2, used_retry = self._run_compact(
                    table, retry, used2
                )
                # Refresh the chain with the retry's used': the next
                # chained batch must see BOTH passes' placements, not the
                # pre-retry tensor. (Host-only overflow repair in
                # _materialize_compact still isn't reflected — the
                # applier's optimistic verification catches that residual
                # over-placement direction.)
                self.chain_out = self.chain_out.with_used(used_retry)
                leftovers2, mat2_ns = self._timed_materialize(
                    self._materialize_compact,
                    table, retry, inst2, over2, table.cap - used2,
                )
            else:
                # the preempt solve retries on ITS kernel with every
                # tier limit 0 — the plain waterfill, and a program of
                # the closed set (kernels.preempt_programs) where the
                # dense kernel would be one more family to warm
                assign2, _, used_retry = self._run_kernel(
                    table, retry, used2,
                    tier_limit=np.zeros(len(retry), dtype=np.int32),
                    use_preempt=use_preempt,
                )
                if self.chain_out is not None:
                    self.chain_out = self.chain_out.with_used(used_retry)
                leftovers2, mat2_ns = self._timed_materialize(
                    self._materialize, table, retry, assign2, None
                )
            mat_ns += mat2_ns
            for gi, reqs in leftovers2.items():
                grp = retry[gi]
                key = (grp.key[0], grp.tg.name)
                prev = final_unplaced.get(key)
                final_unplaced[key] = (grp, (prev[1] if prev else []) + reqs)

        self._record_failures(final_unplaced, n)
        # solve_ns excludes any pipeline gap between the two phases
        out.solve_ns = phase_a_ns + (now_ns() - t0)
        metrics.time_ns("nomad.tpu.solve_seconds", out.solve_ns)
        # Alloc materialization joins the host_prep/device/readback stage
        # registry so the bench's breakdown covers the full commit half.
        metrics.time_ns("nomad.tpu.materialize_seconds", mat_ns)
        metrics.observe("nomad.tpu.solve_groups", out.groups)
        return out

    def _record_failures(self, final_unplaced: dict, n: int) -> None:
        """Failure metrics from the FINAL unplaced set (both passes)."""
        for (eval_id, tg_name), (grp, reqs) in final_unplaced.items():
            metric = group_alloc_metric(grp, n)
            metric.coalesced_failures = len(reqs) - 1
            self._outcome.failures.setdefault(eval_id, {})[tg_name] = metric

    def _fail_full_cluster(self, groups: list[LoweredGroup], base_of: dict,
                           n: int, t0: int) -> SolveOutcome:
        """The outcome of a compact solve on a cluster with no room for
        one instance of any group, without the kernel: every request
        unplaced, a value-restricted sub-group counted under its base as
        the spread-relaxation retry would have left it. What the device
        would have compiled for is worse than its round trip: a batch of
        evals that re-place evicted allocs has its own group count, row
        counts and readback width (PERF.md § 6, PR 27)."""
        from ... import metrics

        out = self._outcome
        final_unplaced: dict[tuple, tuple[LoweredGroup, list]] = {}
        for gi, grp in enumerate(groups):
            if not grp.requests:
                continue
            key = (grp.key[0], grp.tg.name)
            prev = final_unplaced.get(key)
            final_unplaced[key] = (
                base_of[gi] if grp.restricted else grp,
                (prev[1] if prev else []) + list(grp.requests),
            )
        self._record_failures(final_unplaced, n)
        out.solve_ns = now_ns() - t0
        metrics.incr("nomad.tpu.full_cluster_solves")
        metrics.time_ns("nomad.tpu.solve_seconds", out.solve_ns)
        metrics.observe("nomad.tpu.solve_groups", out.groups)
        return out

    @staticmethod
    def _timed_materialize(materialize, *args):
        """One materialization pass under its `materialize` span:
        (leftovers, the pass's ns for nomad.tpu.materialize_seconds)."""
        t0 = now_ns()
        with trace.span(trace.current(), "materialize", cpu=True):
            leftovers = materialize(*args)
        return leftovers, now_ns() - t0

    def _lower_batch(self, asks: list[GroupAsk], out: SolveOutcome):
        """Everything solve_begin does on the host BEFORE the batch's
        path is chosen, under one span, `lower`: the sticky/penalty
        scan, the small-batch verdict, then (children `lower.table` and
        `lower.groups`) the node universe and node table, built or
        refreshed from the resident cache, and every ask lowered to its
        group tensors; last the path verdict. Returns (kind, payload),
        which the caller acts on AFTER the span closed: ("dense",
        _Lowered); ("done", None) — nothing to solve, `out` is final;
        ("host", (asks, total_requests)) — the host iterator stack takes
        the batch: a small batch past the microsolve's bound leaves as
        soon as its node universe and its asks prove it past (nothing
        lowered, `nomad.tpu.lower_skipped`); only one whose spread
        splits carry it past is lowered first; ("sticky", indices of
        the asks that solve on the host, the rest dense)."""
        from ... import metrics

        tctx = trace.current()
        with trace.span(tctx, "lower", cpu=True):
            self._batch_has_cores = any(
                t.resources.cores > 0
                for ask in asks
                for tg in [ask.job.lookup_task_group(ask.tg_name)]
                if tg is not None
                for t in tg.tasks
            )
            # Asks needing per-request node preference — sticky-disk
            # replacements (prefer the previous node) and reschedules
            # with a node penalty (avoid it) — take the host path; the
            # dense kernel only expresses per-GROUP bias. The rest of
            # the batch solves dense, with the host partition's
            # placements counted against node capacity. A custom
            # solve_fn keeps the whole batch (its topology logic must
            # not be bypassed; preference degrades to none there).
            if self.solve_fn is solve_placement:
                sticky_idx = self._sticky_asks(asks)
                if sticky_idx:
                    return "sticky", sticky_idx
            total_requests = sum(len(a.requests) for a in asks)
            # A custom solve_fn (e.g. the mesh-sharded solver) must
            # never be silently bypassed — the fast path exists for the
            # default kernel's device round-trip only (same precedent
            # as the compact path).
            small = (
                total_requests <= self.config.small_batch_threshold
                and self.solve_fn is solve_placement
            )
            # Small batches prefer the MICROSOLVE: the dense pipeline
            # with the numpy kernel (microsolve.py) — zero device
            # round-trip, shared lowering/materialization semantics.
            # Ineligible shapes (cores asks, a preemption-capable
            # batch, a sharded mesh, or a node universe past the n·g
            # threshold) fall back to the host iterator stack exactly
            # as before.
            micro_wanted = (
                small
                and self.mesh is None
                and self.config.micro_solve_threshold > 0
                and not self._batch_has_cores
            )
            if small and not micro_wanted:
                return "host", (asks, total_requests)
            # Priority order: higher-priority jobs consume capacity
            # first (mirrors the eval broker's priority dequeue).
            asks = sorted(asks, key=lambda a: -a.job.priority)
            to_host = "host", (asks, total_requests)

            with trace.span(tctx, "lower.table") as tspan:
                # One node universe per batch. Union of the jobs'
                # datacenters, scanning the node table once per DISTINCT
                # dc set, not per ask — and skipping the union dict
                # entirely in the common one-dc-set case (it was a
                # million dict writes at c2m scale).
                dc_cache: dict[tuple, list] = {}
                for ask in asks:
                    key = tuple(ask.job.datacenters)
                    if key not in dc_cache:
                        dc_cache[key] = self._ready_nodes(key)[0]
                nodes = _union_nodes(dc_cache.values())
                if not nodes:
                    for ask in asks:
                        self._fail_all(out, ask, {})
                    return "done", None
                if micro_wanted and self._past_micro_bound(nodes, asks):
                    if self.solve_preempt_fn is not None and may_preempt(
                        self.state, self.config,
                        ((a.job.type, a.job.priority) for a in asks),
                    ):
                        # Past the bound a small batch that may preempt
                        # is the tier kernel's, not the host stack's: the
                        # stack walks every node for a placement there
                        # and draws its node from a shuffled sample
                        # (upstream's limit iterator), so it takes a
                        # higher band on the node it drew while a lower
                        # one stands elsewhere — the kernel opens a band
                        # only once every lower one is spent wherever the
                        # group can go (PERF.md section 6, PR 35). Within
                        # the bound the stack keeps it, as it did. O(1):
                        # the store's priority counts.
                        micro_wanted = False
                    else:
                        # the host stack takes it whatever the lowering
                        # would say, and uses none of it
                        metrics.observe(
                            "nomad.tpu.lower_skipped", total_requests
                        )
                        return to_host
                tab = self._lower_table(nodes, asks, micro_wanted)
                if tab is None:
                    return to_host
                table, usage_of, adj = tab
                tspan.set_attr("nodes", table.n)
                if usage_of is None:
                    tspan.set_attr(
                        "alloc_walk",
                        "cores" if self._batch_has_cores else "no_index",
                    )

            with trace.span(tctx, "lower.groups") as gspan:
                self._lower_cache_hits = 0
                groups: list[LoweredGroup] = []
                # group idx -> unrestricted base
                base_of: dict[int, LoweredGroup] = {}
                for ask in asks:
                    tg = ask.job.lookup_task_group(ask.tg_name)
                    if tg is None or not ask.requests:
                        continue
                    # plan-aware distinct/property masks
                    self.ctx.plan = ask.plan
                    grp = self._lower_group_cached(table, ask, tg)
                    for sub in self._split_for_spread(
                        table, ask.job, tg, grp
                    ):
                        base_of[len(groups)] = grp
                        groups.append(sub)
                    self.ctx.plan = None
                gspan.set_attr("groups", len(groups))
                gspan.set_attr("cache_hits", self._lower_cache_hits)
            if not groups:
                return "done", None
            out.groups = len(groups)

            # node index -> the node's allocs a placement may still evict
            self._victim_cands: dict[int, list] = {}
            used = np.clip(table.used, 0, 2**31 - 1).astype(np.int32)

            tier_limit = np.zeros(len(groups), dtype=np.int32)
            for i, grp in enumerate(groups):
                tier_limit[i] = self._tier_limit(table, grp)
            use_preempt = (
                bool(tier_limit.any()) and self.solve_preempt_fn is not None
            )
            # The compact readback contract covers the default single-
            # chip kernel AND the mesh path (the sharded compact kernel
            # emits the same [G, maxC] instance list); only the
            # preemption kernels and custom solve_fns return the dense
            # [G, N] assignment.
            compact = not use_preempt and self.solve_fn is solve_placement
            # Microsolve verdict (the interactive fast path): the numpy
            # kernel replaces the device dispatch when the problem is
            # tiny. Past the n·g bound the batch keeps its historical
            # host-stack route. What reaches this line past the bound
            # is a batch that only its spread splits carried there
            # (nodes × asks was within it, above): its lowering is
            # wasted once.
            micro = (
                micro_wanted
                and compact
                and table.n * len(groups) <= self.config.micro_solve_threshold
            )
            if micro_wanted and not micro:
                return to_host
            return "dense", _Lowered(
                nodes, table, groups, base_of, usage_of, adj,
                total_requests, used, tier_limit, use_preempt, compact,
                micro,
            )

    def _past_micro_bound(self, nodes: list, asks: list[GroupAsk]) -> bool:
        """The microsolve's n·g bound, as soon as it is decided: every
        ask with a task group and a request lowers to at least one
        group, so nodes × such asks is a lower bound of table.n ×
        len(groups) — True only where the verdict after lowering would
        be the host stack too."""
        return len(nodes) * sum(
            1 for ask in asks
            if ask.requests
            and ask.job.lookup_task_group(ask.tg_name) is not None
        ) > self.config.micro_solve_threshold

    def _ready_nodes(self, datacenters: tuple) -> tuple[list, dict]:
        """(ready nodes, per-datacenter counts) of one datacenter set,
        for the lowering and the host stack alike: the resident state's
        warm list (keyed by the nodes-table index) where there is one,
        a walk of the nodes table otherwise."""
        if self.resident is not None:
            return self.resident.ready_nodes(self.state, datacenters)
        return ready_nodes_in_dcs(self.state, list(datacenters))

    @staticmethod
    def _sticky_asks(asks: list[GroupAsk]) -> set:
        """Indices of the asks that need per-request node preference."""
        from ..reconcile import PlacementRun

        sticky_idx = set()
        for i, ask in enumerate(asks):
            if isinstance(ask.requests, PlacementRun):
                # shared-proto fresh fills carry no previous alloc
                # or penalty node by construction — and iterating
                # the run here would mint every row it exists to
                # avoid
                continue
            tg = ask.job.lookup_task_group(ask.tg_name)
            sticky = (
                tg is not None
                and tg.ephemeral_disk.sticky
                and any(r.previous_alloc is not None for r in ask.requests)
            )
            if sticky or any(r.penalty_node for r in ask.requests):
                sticky_idx.add(i)
        return sticky_idx

    def _lower_table(self, nodes: list, asks: list[GroupAsk],
                     micro_wanted: bool):
        """`lower.table` over the batch's node universe: (table,
        usage_of, adj) — or None: a small batch that may preempt, the
        host stack's. usage_of is None where the table had to walk the
        allocs."""
        from ... import metrics

        # Capacity freed by this batch's plans (stops/destructive updates)
        # is usable: plan application re-verifies, so optimistic batching
        # treats all batch stops as vacated (reference: the host oracle's
        # ProposedAllocs does the same per plan, context.go:120).
        stopped_ids: set[str] = set()
        for ask in asks:
            if ask.plan is not None:
                for allocs_ in ask.plan.node_update.values():
                    stopped_ids.update(a.id for a in allocs_)
        # the materializer's per-node seeds (ports/devices/cores/cpu)
        # must see the SAME vacated capacity as the dense table, or an
        # in-place replacement of a full node can never materialize
        self._stopped_ids = stopped_ids

        placed_by_node: dict[str, list] = {}
        for a in self._partition_placed:
            placed_by_node.setdefault(a.node_id, []).append(a)

        def live_allocs(nid: str):
            return [
                a
                for a in self.state.allocs_by_node_terminal(nid, False)
                if a.id not in stopped_ids
            ] + placed_by_node.get(nid, [])

        # Aggregate fast path: a batch that asks for no dedicated cores
        # (no core pools needed) takes per-node utilization straight
        # from the store's incremental aggregate — O(nodes), not
        # O(allocs) — with this batch's vacated stops and the host
        # partition's placements applied as per-node adjustments; one
        # that may preempt takes its tiers from the store's usage by
        # (node, priority) the same way, adjusted per tier.
        preempt_possible = self.solve_preempt_fn is not None and may_preempt(
            self.state, self.config,
            ((a.job.type, a.job.priority) for a in asks),
            # same-batch host-partition placements are preemptible too
            # (they're in the dense table's live view)
            [alloc_priority(a) for a in self._partition_placed],
        )
        if micro_wanted and preempt_possible:
            # preemption needs the tier kernel (or the host stack's
            # per-request evict pass) — keep the host path for it
            return None
        usage_of = None
        tiers = None
        adj: dict[str, list[int]] = {}
        if (
            not self._batch_has_cores
            and hasattr(
                self.state,
                "node_tier_usage" if preempt_possible else "node_usage",
            )
        ):
            # node -> {priority: [cpu, mem, disk, allocs]}: the same
            # adjustments by tier, for a batch that may preempt
            tier_adj: dict[str, dict[int, list[int]]] = {}

            def _adjust(a, sign: int) -> None:
                nid, r = a.node_id, a.comparable_resources()
                d = adj.get(nid)
                if d is None:
                    d = adj[nid] = [0, 0, 0]
                d[0] += sign * r.cpu
                d[1] += sign * r.memory_mb
                d[2] += sign * r.disk_mb
                if preempt_possible:
                    t = tier_adj.setdefault(nid, {}).setdefault(
                        alloc_priority(a), [0, 0, 0, 0]
                    )
                    for k, v in enumerate((r.cpu, r.memory_mb, r.disk_mb, 1)):
                        t[k] += sign * v

            for sid in stopped_ids:
                stored = self.state.alloc_by_id(sid)
                if stored is not None and not stored.terminal_status():
                    _adjust(stored, -1)
            for a in self._partition_placed:
                _adjust(a, +1)
            if self.extra_usage:
                # interactive-lane ledger (worker.py): placements the
                # priority lane committed past the chain basis — deltas,
                # so they compose with both the set-scatter and the
                # chained-add paths below. They carry no priority and
                # join no tier: held, not evictable.
                for nid, vec in self.extra_usage.items():
                    d = adj.get(nid)
                    if d is None:
                        d = adj[nid] = [0, 0, 0]
                    d[0] += vec[0]
                    d[1] += vec[1]
                    d[2] += vec[2]
            state_usage = self.state.node_usage
            if adj:

                def usage_of(nid: str):
                    u = state_usage(nid)
                    d = adj.get(nid)
                    if d is None:
                        return u
                    return (u[0] + d[0], u[1] + d[1], u[2] + d[2])

            else:
                usage_of = state_usage
            if preempt_possible:
                metrics.incr("nomad.tpu.lower_tiers_from_store")
                tiers = (self.state.node_tier_usage, tier_adj)
        else:
            # core pools are in no aggregate (or the state keeps none):
            # every live alloc is read
            metrics.incr("nomad.tpu.lower_alloc_walks")

        if self.resident is not None and usage_of is not None:
            # cross-solve host-table cache: same fingerprint discipline
            # as the resident device tensors (ResidentClusterState)
            table = self.resident.host_table(
                nodes, live_allocs, usage_of,
                (self.state.node_usage_many, adj), tiers,
            )
            # lowered-skeleton cache rides the same fingerprint: valid
            # only for tables produced by this generation's skeleton
            self._lower_vers = self.resident._host_vers
        else:
            table = build_node_table(nodes, live_allocs, usage_of=usage_of)
            if tiers is not None:
                table.tier_prios, table.tier_used = TierSlabs(
                    table.index_of
                ).read(*tiers)
        if preempt_possible:
            # The tier ceiling: the highest priority any group of the
            # batch may evict. A tier above it is no group's victim — its
            # usage is in `used` like any held alloc's — so the solve
            # carries the tiers at or under it: a production batch beside
            # a standing monitoring band keeps its three-tier program
            # instead of compiling the next tier bucket, and makes no
            # waterfill pass over a tier it may not take. The tiers are
            # in ascending priority, so what is kept is a prefix, and
            # every group's tier_limit counts within it (_tier_limit).
            ceiling = max(
                (a.job.priority for a in asks
                 if self.config.preemption_enabled(a.job.type)),
                default=0,
            ) - PRIORITY_DELTA
            k = bisect_right(table.tier_prios, ceiling)
            if k < len(table.tier_prios):
                table.tiers_above = len(table.tier_prios) - k
                table.tier_prios = table.tier_prios[:k]
                table.tier_used = table.tier_used[:k]
        return table, usage_of, adj

    def _solve_host_timed(self, asks: list[GroupAsk],
                          total_requests: int) -> SolveOutcome:
        """The host-stack fast path with its historical telemetry."""
        from ... import metrics

        t0 = now_ns()
        with trace.span(trace.current(), "host_solve", cpu=True) as span:
            out = self._publish_host(self._solve_host(asks))
            span.set_attr("nodes", out.stack_nodes)
            span.set_attr("nodes_drawn", out.stack_nodes_drawn)
            span.set_attr("ranked", out.stack_ranked)
            span.set_attr("reused", out.stack_reused)
            span.set_attr("by_usage", out.stack_by_usage)
            span.set_attr("chain", self.chain_accepted)
        out.solve_ns = now_ns() - t0
        metrics.time_ns("nomad.tpu.solve_seconds", out.solve_ns)
        metrics.observe("nomad.tpu.small_batch_requests", total_requests)
        if self.chain_accepted:
            metrics.observe("nomad.tpu.host_chain_consumed", 1)
        return out

    def _offer_rows(self, table, used_out) -> None:
        """A microsolve batch offers its used' rows as the NEXT batch's
        chain, padded to the node bucket as the kernel's are and left on
        the host: a microsolve child reads them as they are, a kernel
        child puts them on the device."""
        from ... import metrics

        n = table.n
        rows = np.zeros((self._pad_n(n), 3), dtype=np.int32)
        rows[:n] = np.clip(np.asarray(used_out)[:n], 0, 2**31 - 1)
        metrics.observe("nomad.tpu.host_chain_offered", 1)
        self.chain_out = UsageChain(
            self._node_id_col(table), rows, table.index_of
        )

    def _publish_host(self, out: SolveOutcome) -> SolveOutcome:
        """A host-stack batch offers what it placed as the NEXT batch's
        chain, as rows like every other path's: the rows it read from
        the chain in flight, or with nothing in flight the committed
        usage of its own snapshot, with its placements (and a sticky
        partition's) added. The rows are built at the child's first
        read. Its stops are left out: a follower that counts a stopped
        alloc as held under-fills, and never over-places."""
        from ... import metrics

        base = self.used_chain
        placed = [a for allocs in out.placements.values() for a in allocs]
        placed += self._partition_placed
        if not placed:
            # nothing placed: what is in flight is passed on whole
            self.chain_out = base
            return out
        metrics.observe("nomad.tpu.host_chain_offered", 1)
        adds = []
        for a in placed:
            r = a.comparable_resources()
            adds.append((a.node_id, (r.cpu, r.memory_mb, r.disk_mb)))
        base_rows = self._in_flight.rows if base is not None else None
        ready = list(self._host_nodes.values())

        def build():
            if base is not None and all(
                nid in base.index_of for nid, _ in adds
            ):
                ids, index_of = base.node_ids, base.index_of
                rows = np.array(base_rows, dtype=np.int64)
            else:
                ids = [node.id for node in _union_nodes(ready)]
                index_of = {nid: i for i, nid in enumerate(ids)}
                rows = np.zeros((self._pad_n(len(ids)), 3), dtype=np.int64)
                if ids:
                    rows[: len(ids)] = np.array(
                        self.state.node_usage_many(ids), dtype=np.int64
                    )[:, :3]
                if base is not None:
                    # the chain in flight over another universe: its
                    # rows where the two meet
                    for i, nid in enumerate(ids):
                        j = base.index_of.get(nid)
                        if j is not None:
                            rows[i] = base_rows[j]
            for nid, r in adds:
                i = index_of.get(nid)
                if i is not None:
                    rows[i] += r
            rows = np.clip(rows, 0, 2**31 - 1).astype(np.int32)
            return ids, rows, index_of

        self.chain_out = UsageChain(build=build)
        return out

    def _run_micro(self, table, groups: list[LoweredGroup], used_n,
                   total_requests: int, chain_used=None, adj=None):
        """Host microsolve dispatch: the numpy compact kernel over the
        UNPADDED table arrays — same readback contract as
        _run_compact_finish ((inst [G, maxC], over [N], used' [N, 3])),
        zero device involvement, zero jit signatures. The instance width
        is the groups' raw count bound (no pad_c bucketing: nothing is
        transferred, so width stability buys nothing). With `chain_used`
        (the batch in flight's used' rows) those rows and this batch's
        adjustments `adj` replace `used_n`; the result's rows are offered
        as the NEXT batch's chain."""
        from ... import metrics
        from .microsolve import solve_placement_compact_micro

        t0 = now_ns()
        self.used_micro = True
        n = table.n
        with trace.span(trace.current(), "micro_solve", cpu=True) as span:
            if chain_used is not None:
                # Chain the batch in flight as the kernel does: its used'
                # rows supersede the committed aggregate, this batch's
                # adjustments are added on top (_chain_adj_add's rule).
                # A kernel parent's rows are read back once — the copy
                # waits out its kernel, never its commit.
                used_n = np.asarray(chain_used)[:n].astype(np.int64)
                for nid, d in adj.items():
                    i = table.index_of.get(nid)
                    if i is not None:
                        used_n[i] += d
                np.maximum(used_n, 0, out=used_n)
                self.chain_accepted = True
            span.set_attr("chain", self.chain_accepted)
            maxc = max(1, max(int(grp.count) for grp in groups)) \
                if groups else 1
            inst, over, used_out = solve_placement_compact_micro(
                table.cap,
                np.asarray(used_n)[:n],
                [
                    (
                        np.asarray(grp.ask, dtype=np.int64),
                        int(grp.count),
                        grp.feasible,
                        grp.bias,
                        np.asarray(grp.units_cap, dtype=np.int64),
                    )
                    for grp in groups
                ],
                maxc,
            )
            self._offer_rows(table, used_out)
        micro_ns = now_ns() - t0
        metrics.time_ns("nomad.tpu.micro_seconds", micro_ns)
        metrics.observe("nomad.tpu.micro_batch_requests", total_requests)
        return inst, over, used_out

    def _lower_group_cached(self, table, ask: GroupAsk, tg) -> LoweredGroup:
        """lower_group through the warm lowered-skeleton cache: a
        repeat-shaped eval (same job version, same node universe) reuses
        the feasibility/bias/unit-cap tensors instead of re-lowering.
        The cache holds the STATIC part only (no spread addend) — groups
        qualify via lower.group_lower_static_cacheable (no distinct_*
        constraints, volumes, static ports, or cores, whose masks read
        live state beyond the fingerprint); spread-carrying groups reuse
        the static tensors and re-add lower.spread_bias per solve."""
        from .lower import group_lower_static_cacheable, spread_bias

        res = self.resident
        vers = self._lower_vers
        if res is None or vers is None:
            return lower_group(
                self.ctx, table, ask.job, tg, ask.requests, ask.eval_obj.id
            )
        cached = res.lowered_skeleton(vers, ask.job, tg.name)
        if cached is not None:
            from .lower import request_names

            self._lower_cache_hits += 1
            ask_vec, feas, bias, ucap, fdims = cached
            sb = spread_bias(self.ctx, table, ask.job, tg)
            if sb is not None:
                bias = bias + sb  # new array: the cached one is shared
            reqs = ask.requests
            return LoweredGroup(
                key=(ask.eval_obj.id, tg.name),
                job=ask.job,
                tg=tg,
                count=len(reqs),
                ask=ask_vec,
                feasible=feas,
                bias=bias,
                units_cap=ucap,
                priority=ask.job.priority,
                names=request_names(reqs),
                requests=reqs,
                filtered_dims=dict(fdims),
            )
        grp = lower_group(
            self.ctx, table, ask.job, tg, ask.requests, ask.eval_obj.id
        )
        if group_lower_static_cacheable(ask.job, tg):
            res.store_lowered(
                vers, ask.job, tg.name,
                (grp.ask, grp.feasible, grp.bias_static, grp.units_cap,
                 grp.filtered_dims),
            )
        return grp

    def _solve_host(self, asks: list[GroupAsk]) -> SolveOutcome:
        """Small-batch fast path (VERDICT r3 #3): below the threshold the
        device round-trip dominates any kernel win, so the asks run
        through the host GenericStack — the exact iterator chain the host
        oracle uses (reference stack.go:43) — with placements appended to
        each ask's plan as they land, so distinct/property/capacity
        checks see earlier placements exactly as generic.py's loop does
        (computePlacements, generic_sched.go:472)."""
        from ... import metrics
        from ..stack import GenericStack
        from ..util import annotate_previous_alloc

        out = SolveOutcome()
        asks = sorted(asks, key=lambda a: -a.job.priority)
        # A batch in flight: every node the stacks visit counts what it
        # published there (its row over the committed usage) and the
        # lane's ledger, as the kernel's chained solve does.
        in_flight = None
        if self.used_chain is not None:
            in_flight = self._in_flight = _InFlightUsage(
                self.state, self.used_chain, self.extra_usage
            )
            self.chain_accepted = True
        # Cross-eval accounting: every eval's stack must see every OTHER
        # plan in this batch (via ctx.extra_plans) or two evals would
        # double-book one node's capacity/ports — the dense path
        # coordinates through its shared lowered table instead.
        batch_plans: list = list(self._partition_plans)
        seen_plans: set[int] = {id(p) for p in batch_plans}
        for ask in asks:
            if ask.plan is not None and id(ask.plan) not in seen_plans:
                seen_plans.add(id(ask.plan))
                batch_plans.append(ask.plan)
        dc_cache: dict[tuple, tuple] = {}
        stacks: dict[tuple, GenericStack] = {}
        for ask in asks:
            tg = ask.job.lookup_task_group(ask.tg_name)
            if tg is None or not ask.requests:
                continue
            key = tuple(ask.job.datacenters)
            cached = dc_cache.get(key)
            if cached is None:
                cached = dc_cache[key] = self._ready_nodes(key)
                self._host_nodes[key] = cached[0]
            nodes, dc_counts = cached
            if not nodes:
                self._fail_all(out, ask, dc_counts)
                continue
            # keyed by version too: one eval can carry asks for two job
            # versions (canary-state downgrades), each needing its own
            # job-level constraint set
            skey = (ask.eval_obj.id, ask.job.id, ask.job.version)
            stack = stacks.get(skey)
            if stack is None:
                ctx = EvalContext(
                    self.state,
                    ask.plan,
                    logger,
                    self.config,
                    extra_plans=[p for p in batch_plans if p is not ask.plan],
                    extra_usage=in_flight,
                )
                stack = GenericStack(ask.eval_obj.type == "batch", ctx)
                stack.set_nodes(nodes)
                stack.set_job(ask.job)
                stacks[skey] = stack
            ctx = stack.ctx
            placements = out.placements.setdefault(ask.eval_obj.id, [])
            preemptions = out.preemptions.setdefault(ask.eval_obj.id, [])
            preempt_ok = self.config.preemption_enabled(ask.job.type)
            sticky = tg.ephemeral_disk.sticky
            for req in ask.requests:
                penalty = {req.penalty_node} if req.penalty_node else None
                metric = AllocMetric(nodes_available=dict(dc_counts))
                start = now_ns()
                option = None
                prev = req.previous_alloc
                if sticky and prev is not None and prev.node_id:
                    # sticky disk: try the previous node first (reference
                    # computePlacements -> SelectOptions.PreferredNodes);
                    # a tainted/drained previous node is never preferred
                    prev_node = self.state.node_by_id(prev.node_id)
                    if prev_node is not None and prev_node.ready():
                        option = stack.select(
                            tg, penalty_nodes=penalty, metrics=metric,
                            selected_nodes=[prev_node],
                        )
                # A task group that has failed to place once in this eval
                # is not walked again for its other requests: they ask
                # the same of a cluster that only got fuller, and each
                # walk of a FULL cluster visits every node (the limit
                # iterator never finds its candidates) — 48 requests of
                # one small follow-up eval held the solve thread 18 s on
                # 12,583 full machines (PERF.md section 6, PR 35). The
                # reference coalesces at the same place
                # (generic_sched.go computePlacements, failedTGAllocs).
                failed_before = out.failures.get(ask.eval_obj.id, {}).get(
                    ask.tg_name
                ) is not None
                if option is None and not failed_before:
                    option = stack.select(
                        tg, penalty_nodes=penalty, metrics=metric
                    )
                    if option is None and preempt_ok:
                        option = stack.select(
                            tg, penalty_nodes=penalty, metrics=metric,
                            evict=True,
                        )
                metric.allocation_time_ns = now_ns() - start
                metric.nodes_evaluated = ctx.metrics_nodes_evaluated
                if option is None:
                    existing = out.failures.get(ask.eval_obj.id, {}).get(
                        ask.tg_name
                    )
                    if existing is not None:
                        existing.coalesced_failures += 1
                    else:
                        out.failures.setdefault(ask.eval_obj.id, {})[
                            ask.tg_name
                        ] = metric
                    continue
                alloc = Allocation(
                    id=generate_uuid(),
                    namespace=ask.eval_obj.namespace,
                    eval_id=ask.eval_obj.id,
                    name=req.name,
                    node_id=option.node.id,
                    node_name=option.node.name,
                    job_id=ask.job.id,
                    job=ask.job,
                    task_group=tg.name,
                    resources=option.alloc_resources,
                    metrics=metric,
                    desired_status="run",
                    client_status="pending",
                )
                if req.canary:
                    alloc.deployment_status = AllocDeploymentStatus(canary=True)
                if option.preempted_allocs:
                    alloc.preempted_allocations = [
                        p.id for p in option.preempted_allocs
                    ]
                    for p in option.preempted_allocs:
                        ask.plan.append_preempted_alloc(p, alloc.id)
                        preemptions.append((p, alloc.id))
                annotate_previous_alloc(alloc, req)
                ask.plan.append_fresh_alloc(alloc, ask.job)
                out.pre_appended.add(alloc.id)
                placements.append(alloc)
        for stack in stacks.values():
            drawn = stack.nodes.drawn
            metrics.observe("nomad.sched.stack.nodes_drawn", drawn)
            if stack.nodes.eager:
                metrics.incr("nomad.sched.stack.eager_finishes")
            ranks = stack.ranks
            metrics.observe("nomad.sched.stack.ranked", ranks.ranked)
            metrics.observe("nomad.sched.stack.rank_reused", ranks.reused)
            by_usage = stack.ctx.exhausted_by_usage
            metrics.observe("nomad.sched.stack.exhausted_by_usage", by_usage)
            out.stack_nodes += len(stack.nodes)
            out.stack_nodes_drawn += drawn
            out.stack_ranked += ranks.ranked
            out.stack_reused += ranks.reused
            out.stack_by_usage += by_usage
        return out

    def _tier_limit(self, table, grp: LoweredGroup) -> int:
        """How many of the node table's (ascending) priority tiers this
        group may preempt: tiers more than PRIORITY_DELTA below the
        job's priority, when the operator enabled preemption for the
        job's scheduler type."""
        if not self.config.preemption_enabled(grp.job.type):
            return 0
        k = 0
        for p in table.tier_prios:
            if grp.priority - p >= PRIORITY_DELTA:
                k += 1
            else:
                break  # ascending order: no later tier qualifies
        return k

    def _lower_small(self, table, groups: list[LoweredGroup]):
        """The per-batch small tensors shared by both kernel paths:
        (np_, gp, cap [np_,3], used-zeros [np_,3], asks [gp,3], counts [gp])."""
        n, g = table.n, len(groups)
        np_, gp = self._pad_n(n), pad_g(g)
        cap = np.zeros((np_, 3), dtype=np.int32)
        used = np.zeros((np_, 3), dtype=np.int32)
        cap[:n] = np.clip(table.cap, 0, 2**31 - 1)
        asks_arr = np.zeros((gp, 3), dtype=np.int32)
        counts = np.zeros(gp, dtype=np.int32)
        for i, grp in enumerate(groups):
            asks_arr[i] = grp.ask
            counts[i] = grp.count
        return np_, gp, cap, used, asks_arr, counts

    @staticmethod
    def _dense_group_rows(n: int, np_: int, gp: int,
                          groups: list[LoweredGroup]):
        """Densify per-group feasibility/bias/unit-cap rows to the
        padded [gp, np_] bucket (shared by the preempt / custom-solve_fn
        lowering and the mesh compact dispatch)."""
        feas = np.zeros((gp, np_), dtype=bool)
        bias = np.zeros((gp, np_), dtype=np.float32)
        ucap = np.zeros((gp, np_), dtype=np.int32)
        for i, grp in enumerate(groups):
            feas[i, :n] = grp.feasible
            bias[i, :n] = grp.bias
            ucap[i, :n] = np.clip(grp.units_cap, 0, 2**31 - 1)
        return feas, bias, ucap

    def _lower_arrays(self, table, groups: list[LoweredGroup]):
        """Pad + stack the groups' tensors to the jit bucket shapes
        (dense [G, N] form, used by the preempt / custom-solve_fn path)."""
        n = table.n
        np_, gp, cap, used, asks_arr, counts = self._lower_small(table, groups)
        feas, bias, ucap = self._dense_group_rows(n, np_, gp, groups)
        return cap, used, asks_arr, counts, feas, bias, ucap

    @staticmethod
    def _dedupe_rows(
        arrays: list[np.ndarray], gp: int, np_: int, dtype
    ) -> tuple[np.ndarray, np.ndarray, int]:
        """Row table + per-group index for host->device compression,
        and how many of the table's rows are distinct ones.

        Groups lowered from one job share bias/ucap array OBJECTS (spread
        splits keep the parent's references) and unconstrained jobs have
        value-identical rows, so dedupe is first by identity then by
        content; a row cast to an integer `dtype` is clipped to what it
        holds, from 0. The table always has `gp` rows — the group
        bucket's, not the distinct rows': how many rows of a kind a
        batch holds is the mix's business (one for a backlog of one job,
        six or more for a Borg mix), and a table sized by it puts three
        more numbers into the program's signature, so that a
        single-class warm-up can never reach the programs a mixed batch
        compiles. `gp` rows hold any batch (no group can bring more than
        one row of a kind); rows past the distinct ones are zero and no
        index names them."""
        by_id: dict[int, int] = {}
        by_content: dict[bytes, int] = {}
        top = np.iinfo(dtype).max if np.issubdtype(dtype, np.integer) \
            else None
        out = np.zeros((gp, np_), dtype=dtype)
        idx = np.zeros(gp, dtype=np.int32)
        for i, arr in enumerate(arrays):
            j = by_id.get(id(arr))
            if j is None:
                a = np.asarray(arr)
                key = a.tobytes()
                j = by_content.get(key)
                if j is None:
                    j = by_content[key] = len(by_content)
                    if top is not None:
                        a = np.clip(a, 0, top)
                    out[j, : a.shape[0]] = a
                by_id[id(arr)] = j
            idx[i] = j
        return out, idx, len(by_content)

    def _readback_bound(self, cap, used, groups: list[LoweredGroup],
                        n: int) -> int:
        """Bound any group's receiving node set. Guards the compact
        readback width ([G, maxC] vs the dense [G, N] transfer) and
        sizes the sharded solver's top-k (kernels._topk_fill stays
        exact because free only shrinks as groups place).

        Two regimes: normally the free-capacity refinement — a group
        can never place more instances than sum over nodes of
        free // ask. When this solve CONSUMED a chain, the kernel's
        usage tensor is the in-flight parent's view, which can hold
        MORE free capacity than the committed `used` here whenever the
        parent's plan vacated stops — a host-derived refinement could
        then under-bound the device's receiving set and silently
        truncate placements, so the bound falls back to the groups'
        raw counts (always sufficient: a group never receives on more
        than `count` nodes)."""
        if self.chain_accepted:
            return max(int(grp.count) for grp in groups)
        free = np.maximum(cap[:n].astype(np.int64) - used[:n], 0)
        units_by_ask: dict[bytes, np.ndarray] = {}
        placeable_cap = 0
        for grp in groups:
            ask = np.asarray(grp.ask, dtype=np.int64)
            key = ask.tobytes()
            per_node = units_by_ask.get(key)
            if per_node is None:
                per_res = np.where(
                    ask[None, :] > 0,
                    free // np.maximum(ask[None, :], 1),
                    np.int64(1 << 30),
                )
                per_node = units_by_ask[key] = per_res.min(axis=1)
            count = int(grp.count)
            placeable = min(count, int(np.minimum(per_node, count).sum()))
            if placeable > placeable_cap:
                placeable_cap = placeable
        return placeable_cap

    def _run_compact(
        self, table, groups: list[LoweredGroup], used_n, dev_state=None
    ):
        """Synchronous form: async dispatch + finish in one call (the
        spread-relaxation retry and direct callers use this)."""
        return self._run_compact_finish(
            self._run_compact_async(table, groups, used_n, dev_state)
        )

    def _run_compact_async(
        self, table, groups: list[LoweredGroup], used_n, dev_state=None
    ):
        """Default kernel with deduped/bit-packed uploads and device-side
        compaction, DISPATCH HALF: lowers, uploads, and queues the kernel
        without blocking. Returns a pending tuple for
        _run_compact_finish, which blocks, reads back, and returns
        (inst_node [G, maxC], over [N] bool, used' device array).

        dev_state — optional (cap_dev, used_dev) resident device tensors
        at this table's padded shape; when given, the [N, 3] host arrays
        are used only for the readback-width bound and the upload ships
        just the per-batch group tensors. Phase timings land in the
        telemetry registry (nomad.tpu.{host_prep,device,readback}_seconds)
        so the bench can publish the device/transfer/host split.
        """
        from ... import metrics

        t_prep0 = now_ns()
        with trace.span(trace.current(), "host_prep", cpu=True) as span:
            pending = self._compact_dispatch(
                table, groups, used_n, dev_state, span
            )
        metrics.time_ns("nomad.tpu.host_prep_seconds", now_ns() - t_prep0)
        return pending

    @staticmethod
    def _call_compact(sig: tuple, span, g: int, gp: int, maxc: int,
                      fn, *args, **kwargs):
        """Queue a compact program under the compile ledger, and say
        what the dispatch is: the rung on the `host_prep` span, the real
        and the padded groups (the roofline counts the real ones:
        padding is the program's waste), and one `programs_new` event
        where the ledger (solverobs.record_call) meets the signature
        for the first time — one event a compile."""
        from ... import metrics

        span.set_attr("gp", gp)
        span.set_attr("maxc", maxc)
        metrics.observe("nomad.tpu.compact.groups", g)
        metrics.observe("nomad.tpu.compact.groups_pad", gp - g)
        metrics.observe("nomad.tpu.compact.groups_padded", gp)
        out, compiled = solverobs.timed_call_verdict(
            sig[0], sig, fn, *args, **kwargs
        )
        if compiled:
            metrics.observe("nomad.tpu.compact.programs_new", 1)
        return out

    def _compact_dispatch(self, table, groups, used_n, dev_state, span):
        """_run_compact_async's body: pack, dedupe, upload, queue. The
        program it queues follows from (np_, gp, maxc) alone — the node
        bucket and the two rungs of the ladder (kernels.pad_g / pad_c):
        the row tables have gp rows each and the unit caps' dtype
        follows from maxc, so nothing a mix of jobs varies is in the
        signature."""
        from ... import metrics

        n, g = table.n, len(groups)
        np_, gp, cap, used, asks_arr, counts = self._lower_small(table, groups)
        used[:n] = used_n[:n]
        maxc = pad_c(max(1, self._readback_bound(cap, used, groups, n)))
        if self.mesh is not None:
            return self._dispatch_mesh_compact(
                table, groups, np_, gp, maxc, cap, used, asks_arr, counts,
                dev_state, span,
            )
        feas_rows, feas_idx, n_feas = self._dedupe_rows(
            [grp.feasible for grp in groups], gp, np_, np.bool_
        )
        feas_packed = np.packbits(feas_rows, axis=1)
        bias_rows, bias_idx, n_bias = self._dedupe_rows(
            [grp.bias for grp in groups], gp, np_, np.float32
        )
        # Dedupe on the ORIGINAL arrays (spread sub-groups share the
        # parent's reference — the identity fast path); only the distinct
        # rows are clipped and cast. A cap beyond a group's count is
        # equivalent to the count (the kernel clips units to it), so i16
        # loses nothing unless a count AND a node's room for it pass
        # 32,767 — and then the readback bound does too. So the dtype
        # follows from the rung and stays out of the signature.
        ucap_rows, ucap_idx, n_ucap = self._dedupe_rows(
            [grp.units_cap for grp in groups], gp, np_,
            np.int16 if maxc < 2**15 else np.int32,
        )
        metrics.observe(
            "nomad.tpu.compact.distinct_rows", n_feas + n_bias + n_ucap
        )
        # resident/chained device tensors replace the cap and/or used
        # upload when their padded shape matches this table's bucket
        cap_in, used_in = cap, used
        if dev_state is not None:
            dcap, dused = dev_state
            if dcap is not None and dcap.shape == (np_, 3):
                cap_in = dcap
            if dused is not None and dused.shape == (np_, 3):
                used_in = dused
        solverobs.record_batch(n, g, np_, gp)
        # host->device bytes: exactly the numpy arguments this dispatch
        # uploads (a device-resident cap/used input ships nothing)
        solverobs.record_transfer("h2d", sum(
            a.nbytes
            for a in (
                cap_in, used_in, asks_arr, counts, feas_packed, feas_idx,
                bias_rows, bias_idx, ucap_rows, ucap_idx,
            )
            if isinstance(a, np.ndarray)
        ))
        inst, over, used_out = self._call_compact(
            ("solve_placement_compact", np_, gp, maxc), span, g, gp, maxc,
            solve_placement_compact,
            cap_in,
            used_in,
            asks_arr,
            counts,
            feas_packed,
            feas_idx,
            bias_rows,
            bias_idx,
            ucap_rows,
            ucap_idx,
            max_count=maxc,
        )
        return inst, over, used_out, g, n, time.perf_counter()

    def _dispatch_mesh_compact(
        self, table, groups, np_, gp, maxc, cap, used, asks_arr, counts,
        dev_state, span,
    ):
        """Node-sharded dispatch with the compact readback contract:
        the mesh's top-k compact kernel returns the same
        (inst [G, maxC], over [N], used') as solve_placement_compact, so
        everything downstream (_run_compact_finish, _materialize_compact,
        the SoA fast-mint, the chain) is shared with the single-chip
        path. Group tensors upload dense (the node axis is what shards;
        the input-dedupe trick stays single-chip-only — with resident
        cap/used the group tensors ARE the whole upload); per-shard
        occupancy and the modeled all-gather bytes land on the ledger.
        """
        mesh = self.mesh
        n, g = table.n, len(groups)
        feas, bias, ucap = self._dense_group_rows(n, np_, gp, groups)
        fn, k = mesh.solver(maxc, compact=True)
        cap_in, used_in = cap, used
        if dev_state is not None:
            dcap, dused = dev_state
            if dcap is not None and dcap.shape == (np_, 3):
                cap_in = dcap
            if dused is not None and dused.shape == (np_, 3):
                used_in = dused
        solverobs.record_batch(n, g, np_, gp)
        # host->device bytes: only what this dispatch actually uploads
        # (resident/chained device inputs ship nothing)
        solverobs.record_transfer("h2d", sum(
            a.nbytes
            for a in (cap_in, used_in, asks_arr, counts, feas, bias, ucap)
            if isinstance(a, np.ndarray)
        ))
        solverobs.record_shards(mesh.n_dev, mesh.shard_occupancy(n, np_))
        solverobs.record_transfer(
            # gp, not g: the kernel's scan runs over the PADDED group
            # axis, and each step all-gathers its candidates (matching
            # the preempt path's accounting below)
            "allgather", mesh.allgather_bytes(gp, np_, k)
        )
        kname = getattr(fn, "__name__", "sharded_solver_compact")
        inst, over, used_out = self._call_compact(
            (kname, np_, gp, k), span, g, gp, k, fn,
            cap_in, used_in, asks_arr, counts, feas, bias, ucap,
        )
        return inst, over, used_out, g, n, time.perf_counter()

    def _run_compact_finish(self, pending):
        """Block on the dispatched compact kernel and read back."""
        import jax

        from ... import metrics

        inst, over, used_out, g, n, t_disp = pending
        # device compute vs readback split: block on the async dispatch
        # first, then transfer — so the bench's breakdown distinguishes
        # chip time from link time
        t_dev0 = now_ns()
        jax.block_until_ready(used_out)
        self._inject_rtt(t_disp)
        dev_ns = now_ns() - t_dev0
        metrics.time_ns("nomad.tpu.device_seconds", dev_ns)
        trace.stage("device.wait", dev_ns)
        t_rb0 = now_ns()
        # the padded arrays cross the link whole and are cut on the
        # host: an on-device slice is an eager program of its own for
        # every distinct group count (as _run_kernel_finish), and the
        # pad is a few hundred KB where the link moves GB/s
        result = np.asarray(inst)[:g], np.asarray(over)[:n], used_out
        rb_ns = now_ns() - t_rb0
        metrics.time_ns("nomad.tpu.readback_seconds", rb_ns)
        trace.stage("readback", rb_ns)
        # device->host bytes actually moved (used_out stays on device
        # for the chain); plus a post-solve device-memory census
        solverobs.record_transfer(
            "d2h", result[0].nbytes + result[1].nbytes,
            dur_ns=rb_ns, span=True,
        )
        solverobs.sample_device_memory()
        return result

    def _run_kernel(
        self,
        table,
        groups: list[LoweredGroup],
        used_n: np.ndarray,
        tier_limit: Optional[np.ndarray] = None,
        use_preempt: bool = False,
    ):
        """Synchronous form: async dispatch + finish in one call."""
        return self._run_kernel_finish(
            self._run_kernel_async(
                table, groups, used_n, tier_limit=tier_limit,
                use_preempt=use_preempt,
            )
        )

    def _run_kernel_async(
        self,
        table,
        groups: list[LoweredGroup],
        used_n: np.ndarray,
        tier_limit: Optional[np.ndarray] = None,
        use_preempt: bool = False,
    ):
        n, g = table.n, len(groups)
        np_, gp = self._pad_n(n), pad_g(g)
        cap, used, asks_arr, counts, feas, bias, ucap = self._lower_arrays(
            table, groups
        )
        used[:n] = used_n[:n]
        solverobs.record_batch(n, g, np_, gp)
        if self.mesh is not None and use_preempt:
            # shard accounting for the preempt mesh dispatch (the
            # non-preempt mesh path rides _run_compact_async)
            solverobs.record_shards(
                self.mesh.n_dev, self.mesh.shard_occupancy(n, np_)
            )
            solverobs.record_transfer(
                "allgather",
                # two all-gather phases per preempt scan step
                2 * self.mesh.allgather_bytes(gp, np_, None),
            )
        solverobs.record_transfer("h2d", sum(
            a.nbytes for a in (cap, used, asks_arr, counts, feas, bias, ucap)
        ))
        if use_preempt:
            import jax

            from ... import metrics

            tl = np.zeros(gp, dtype=np.int32)
            tl[:g] = tier_limit[:g]
            tier_limit = tl
            t = len(table.tier_prios)
            # the tier axis has its bucket as the node and group axes
            # have theirs: the program is one of kernels.preempt_programs
            tp = pad_t(t)
            tctx = trace.current()
            with trace.span(tctx, "preempt.prefix", cpu=True, tiers=t,
                            tiers_above=table.tiers_above):
                prefix = np.zeros((tp, np_, 3), dtype=np.int32)
                if t:
                    cum = np.cumsum(
                        np.clip(table.tier_used, 0, 2**31 - 1), axis=0
                    )
                    prefix[1 : t + 1, :n] = cum.astype(np.int32)
                    # padded tail repeats the full sum so any (unused)
                    # out-of-range index still reads a valid prefix
                    prefix[t + 1 :, :n] = cum[-1].astype(np.int32)
                    # nodes on which more than one tier stands: where
                    # the prefix has more than one step
                    stands = (np.asarray(table.tier_used)[:, :n] > 0).any(
                        axis=2)
                    metrics.observe(
                        "nomad.tpu.preempt.multi_tier_nodes",
                        int((stands.sum(axis=0) > 1).sum()),
                    )
                solverobs.record_transfer(
                    "h2d", prefix.nbytes + tier_limit.nbytes
                )
                if tctx is not None:
                    # a traced solve uploads HERE and awaits it, so that
                    # the span is the whole cost of the prefix; untraced
                    # it rides the dispatch like every other input
                    prefix = jax.block_until_ready(jax.device_put(prefix))
            # factory-built preempt variants (mesh-sharded) ledger under
            # their own name so per-mesh recompiles are attributable
            kname = getattr(
                self.solve_preempt_fn, "__name__", "solve_placement_preempt"
            )
            metrics.observe("nomad.tpu.preempt.groups", g)
            (assign, assign_evict, used_out), compiled = \
                solverobs.timed_call_verdict(
                    kname, (kname, np_, gp, tp), self.solve_preempt_fn,
                    cap, used, prefix, asks_arr, counts, feas, bias, ucap,
                    tier_limit,
                )
            if compiled:
                # the compile ledger met the signature for the first
                # time: one event a compile, as the compact solve's
                metrics.observe("nomad.tpu.preempt.programs_new", 1)
            return assign, assign_evict, used_out, g, n, time.perf_counter()
        kname = getattr(self.solve_fn, "__name__", "solve_placement")
        assign, used_out = solverobs.timed_call(
            kname, (kname, np_, gp), self.solve_fn,
            cap, used, asks_arr, counts, feas, bias, ucap
        )
        return assign, None, used_out, g, n, time.perf_counter()

    def _run_kernel_finish(self, pending):
        """Block on the dispatched dense kernel and read back: the same
        two stages as the compact path (`device.wait`, then `readback`
        over BOTH dense arrays of the preempt kernel). The padded
        [gp, np_] arrays cross the link whole and are cut on the host:
        an on-device slice is a program of its own for every distinct
        group count."""
        import jax

        from ... import metrics

        assign, assign_evict, used_out, g, n, t_disp = pending
        t_dev0 = now_ns()
        jax.block_until_ready(used_out)
        self._inject_rtt(t_disp)
        dev_ns = now_ns() - t_dev0
        metrics.time_ns("nomad.tpu.device_seconds", dev_ns)
        trace.stage("device.wait", dev_ns)
        t_rb0 = now_ns()
        result = (
            np.asarray(assign)[:g, :n],
            None if assign_evict is None
            else np.asarray(assign_evict)[:g, :n],
            used_out,
        )
        rb_ns = now_ns() - t_rb0
        metrics.time_ns("nomad.tpu.readback_seconds", rb_ns)
        trace.stage("readback", rb_ns)
        nbytes = assign.nbytes + (
            assign_evict.nbytes if assign_evict is not None else 0
        )
        if assign_evict is not None:
            metrics.incr("nomad.tpu.preempt.readback_bytes", nbytes)
        solverobs.record_transfer("d2h", nbytes, dur_ns=rb_ns, span=True)
        solverobs.sample_device_memory()
        return result

    def _inject_rtt(self, t_disp: float) -> None:
        """Simulated chip round-trip (docs/pipeline.md): results become
        available inject_device_latency_s AFTER DISPATCH, the way a real
        async device computes while the host works — NOT a fixed sleep at
        readback, which would model a device that only starts when asked
        for results and would serialize the simulated RTT behind the
        commit stage's own host work. Lets the worker's solve/commit
        overlap be exercised on XLA:CPU.

        The modeled device is a serially-busy queue: a dispatch that
        lands while an earlier batch's window is still open starts AFTER
        it (`_device_free_at` rides the shared SchedulerConfig, the one
        object that spans a worker's batches). Without this, two
        in-flight batches' windows overlapped and the model behaved like
        a second chip — overstating pipeline overlap and sharded
        scaling alike."""
        lat = self.config.inject_device_latency_s
        if lat > 0:
            start = max(
                getattr(self.config, "_device_free_at", 0.0), t_disp
            )
            ready = start + lat
            self.config._device_free_at = ready
            remain = ready - time.perf_counter()
            if remain > 0:
                time.sleep(remain)

    # ------------------------------------------------------------------

    def _split_for_spread(
        self, table, job: Job, tg, grp: LoweredGroup
    ) -> list[LoweredGroup]:
        """Spread stanzas become per-value sub-groups with quota counts.

        The waterfill scan is greedy per group, so a within-batch spread
        can't be expressed as a static score bias — instead the group is
        split: one sub-group per attribute value, count = that value's
        remaining desired share, feasibility ANDed with value membership.
        Leftover instances become an unrestricted remainder sub-group.
        (Multiple spread stanzas: the highest-weight one drives the split;
        the rest stay score bias.)
        """
        import dataclasses

        from .lower import _property_counts, _spread_desired

        spreads = list(tg.spreads) + [
            s
            for s in job.spreads
            if s.attribute not in {t.attribute for t in tg.spreads}
        ]
        if not spreads:
            return [grp]
        s = max(spreads, key=lambda x: x.weight)
        from .lower import request_names

        codes, values, exists = table.attr_codes(s.attribute)
        counts_v = _property_counts(self.ctx, table, job, s.attribute, tg.name)
        desired = _spread_desired(s, values, tg.count)
        quotas = np.maximum(0, desired - counts_v).astype(np.int64)
        # slicing (not list()-ing) keeps PlacementRun fills as runs —
        # the sub-groups' rows never materialize on the fast path
        reqs = grp.requests
        out: list[LoweredGroup] = []
        order = np.argsort(-(quotas / np.maximum(desired, 1)))
        for vi in order:
            if not len(reqs):
                break
            take = min(int(quotas[vi]), len(reqs))
            if take <= 0:
                continue
            sub_reqs, reqs = reqs[:take], reqs[take:]
            out.append(
                dataclasses.replace(
                    grp,
                    count=take,
                    feasible=grp.feasible & (codes == vi) & exists,
                    names=request_names(sub_reqs),
                    requests=sub_reqs,
                    restricted=True,
                )
            )
        if len(reqs):
            out.append(
                dataclasses.replace(
                    grp,
                    count=len(reqs),
                    names=request_names(reqs),
                    requests=reqs,
                )
            )
        return out

    @staticmethod
    def _node_id_col(table) -> list:
        """Node-id column for PlacementBatches, built once per table and
        shared by every batch of the solve (string references, no copies)."""
        col = getattr(table, "_node_id_col", None)
        if col is None:
            col = table._node_id_col = [n.id for n in table.nodes]
        return col

    @staticmethod
    def _node_name_col(table) -> list:
        col = getattr(table, "_node_name_col", None)
        if col is None:
            col = table._node_name_col = [n.name for n in table.nodes]
        return col

    def _materialize_compact(
        self,
        table,
        groups: list[LoweredGroup],
        inst: np.ndarray,
        over: np.ndarray,
        free_base: np.ndarray,
    ) -> dict[int, list]:
        """Mint Allocations from the compact per-instance node list.

        inst[gi] holds the node index of each placed instance of group gi
        (-1 padded past the placed total); `over` flags nodes where the
        device ledger detected capacity overflow. The integer kernel never
        overflows by construction, so `over` is a defensive invariant
        check (kernel regressions, bad `used` inputs): placements on
        flagged nodes are re-verified host-side with exact integer math
        against `free_base`, the node free vector at the start of this
        pass, instead of being committed blindly.

        Fast-mint groups (no network asks, no previous-alloc rewiring)
        share ONE AllocatedResources and ONE AllocMetric across all their
        instances: the state store's copy-on-write discipline — every
        writer copies an alloc before mutating — makes stored sub-object
        sharing safe, and it removes ~100k object constructions per c2m
        solve (VERDICT r2 weak #2).
        """
        out = self._outcome
        nodes = table.nodes
        n = table.n
        leftovers: dict[int, list] = {}
        over_set = (
            set(np.nonzero(over)[0].tolist()) if over.any() else None
        )
        over_free: dict[int, list[int]] = {}
        for gi, grp in enumerate(groups):
            eval_id = grp.key[0]
            placements = out.placements.setdefault(eval_id, [])
            row = inst[gi]
            placed = int((row != -1).sum())
            reqs = grp.requests
            placed = min(placed, len(reqs))
            row_placed = row[:placed]
            node_idx = None  # listified lazily — the SoA path never does
            unplaced: list = []
            tg = grp.tg
            a0, a1, a2 = (int(grp.ask[0]), int(grp.ask[1]), int(grp.ask[2]))

            def _check_over(ni: int) -> bool:
                """Exact replay on an overflow-flagged node; True = fits."""
                fr = over_free.get(ni)
                if fr is None:
                    fr = over_free[ni] = [int(c) for c in free_base[ni]]
                if fr[0] < a0 or fr[1] < a1 or fr[2] < a2:
                    return False
                fr[0] -= a0
                fr[1] -= a1
                fr[2] -= a2
                return True

            if self._needs_build(grp):
                node_idx = row_placed.tolist()
                for i, ni in enumerate(node_idx):
                    req = reqs[i]
                    if over_set is not None and ni in over_set:
                        if not _check_over(ni):
                            unplaced.append(req)
                            continue
                    alloc = self._build_alloc(table, grp, nodes[ni], req)
                    if alloc is None:
                        unplaced.append(req)  # port assignment failed
                        continue
                    placements.append(alloc)
            else:
                tmpl = self._mint_template(grp, n)
                uuids = generate_uuids(placed) if placed else []
                group_cpu = sum(t.resources.cpu for t in tg.tasks)
                ap = placements.append
                mint = tmpl.mint
                if over_set is None and not self._batch_has_cores:
                    if self.config.soa_placements and placed:
                        # the array-native case: the kernel's node-index
                        # readback BECOMES the placement column — no
                        # per-row Python objects exist until an API/
                        # client boundary materializes them lazily
                        # (structs/placement_batch.py)
                        proto = tmpl.proto
                        batch = PlacementBatch(
                            namespace=proto.namespace,
                            eval_id=eval_id,
                            job_id=proto.job_id,
                            job=proto.job,
                            task_group=proto.task_group,
                            resources=proto.resources,
                            metrics=proto.metrics,
                            ids=uuids,
                            names=(
                                grp.names[:placed]
                                if len(grp.names) == len(reqs)
                                else [r.name for r in reqs[:placed]]
                            ),
                            node_idx_raw=np.ascontiguousarray(
                                row_placed, dtype=np.int32
                            ).tobytes(),
                            node_ids=self._node_id_col(table),
                            node_names=self._node_name_col(table),
                        )
                        out.batch_placements.setdefault(
                            eval_id, []
                        ).append(batch)
                    else:
                        # the eager bulk case (the SoA comparator): one
                        # tight mint loop, ~100k iterations/solve
                        node_idx = row_placed.tolist()
                        for uid, ni, req in zip(uuids, node_idx, reqs):
                            ap(mint(uid, req.name, nodes[ni]))
                    node_idx = ()
                elif node_idx is None:
                    node_idx = row_placed.tolist()
                for i, ni in enumerate(node_idx):
                    if over_set is not None and ni in over_set:
                        if not _check_over(ni):
                            unplaced.append(reqs[i])
                            continue
                    node = nodes[ni]
                    if self._batch_has_cores:
                        # the dense solve can't see the derived-MHz
                        # excess of cores groups materialized earlier
                        # in this batch — the shared ledger can
                        if group_cpu > self._remaining_cpu(node):
                            unplaced.append(reqs[i])
                            continue
                        self._batch_cpu[node.id] = (
                            self._batch_cpu.get(node.id, 0) + group_cpu
                        )
                    ap(mint(uuids[i], reqs[i].name, node))
            unplaced.extend(reqs[placed:])
            if unplaced:
                leftovers[gi] = unplaced
        return leftovers

    @staticmethod
    def _needs_build(grp: LoweredGroup) -> bool:
        """Does every alloc of the group need its own assembly
        (_build_alloc: ports, device instances, core ids, a previous
        alloc, a canary's status), or do they differ by id, name and
        node alone and mint off one template? A PlacementRun answers
        from its shared proto: iterating the run here would mint ~10^5
        request rows (dataclasses.replace each) per c2m solve — the
        exact cost the run exists to avoid, and the single hottest host
        site of the r10 profile when it regressed."""
        tg, reqs = grp.tg, grp.requests
        run_proto = getattr(reqs, "proto", None)
        return (
            bool(tg.networks)
            or any(t.resources.networks for t in tg.tasks)
            or any(t.resources.devices for t in tg.tasks)
            # dedicated cores need per-placement id assignment
            or any(t.resources.cores > 0 for t in tg.tasks)
            # canaries carry a per-alloc deployment status
            or (
                (run_proto.previous_alloc is not None or run_proto.canary)
                if run_proto is not None
                else any(
                    r.previous_alloc is not None or r.canary for r in reqs
                )
            )
        )

    def _mint_template(self, grp: LoweredGroup, n: int) -> _MintTemplate:
        """The group's interned alloc prototype. Keyed by eval too: the
        broker serializes evals per job, but solve_eval_batch is public
        API — two evals of one job in a batch must not stamp each
        other's eval_id (the intended reuse — spread sub-groups, the
        relaxation retry — is all within one eval)."""
        eval_id, tg = grp.key[0], grp.tg
        tmpl_key = (eval_id, id(grp.job), tg.name)
        tmpl = self._mint_cache.get(tmpl_key)
        if tmpl is None:
            shared_res = AllocatedResources(
                tasks={
                    t.name: AllocatedTaskResources(
                        cpu=t.resources.cpu,
                        memory_mb=t.resources.memory_mb,
                    )
                    for t in tg.tasks
                },
                shared_disk_mb=tg.ephemeral_disk.size_mb,
            )
            tmpl = self._mint_cache[tmpl_key] = _MintTemplate(
                Allocation(
                    namespace=grp.job.namespace,
                    eval_id=eval_id,
                    job_id=grp.job.id,
                    job=grp.job,
                    task_group=tg.name,
                    resources=shared_res,
                    metrics=group_alloc_metric(grp, n),
                )
            )
        return tmpl

    def _materialize(
        self,
        table,
        groups: list[LoweredGroup],
        assign: np.ndarray,
        assign_evict: Optional[np.ndarray] = None,
    ) -> dict[int, list]:
        """Turn [G, N] counts into Allocations; verify + repair per node.

        Returns leftover (unplaced) requests per group index; the caller
        aggregates failures after all passes. Host-side exact capacity
        verification replays the solver's placements with integer math and
        drops overflow (the kernel is integer too, so this only fires when
        two passes race the same capacity).

        assign_evict marks placements the kernel made on PREEMPTIBLE
        capacity: for those, exact victim allocs are picked here
        (lowest priority tier first, then closest resource distance —
        the host Preemptor's rules) and reported on outcome.preemptions.
        """
        from ... import metrics

        n = table.n
        free = self._free
        out = self._outcome
        leftovers: dict[int, list] = {}
        # the preempt path's own account: placements that needed a
        # victim, victims by their job's priority, and the time spent
        # choosing them (Σ _pick_victims — one call a placed instance,
        # between two _build_alloc calls, so a pre-timed stage)
        n_preempting = 0
        evicted_by_prio: dict[int, int] = {}
        above_lowest = 0
        pick_ns = 0
        # [T, n, 3] preemptible usage still standing, by tier index
        tier_left = (np.array(table.tier_used, dtype=np.int64)
                     if assign_evict is not None else None)
        tier_of = {p: k for k, p in enumerate(table.tier_prios)}
        n_placed = 0
        for gi, grp in enumerate(groups):
            eval_id = grp.key[0]
            placements = out.placements.setdefault(eval_id, [])
            # requests are handed out by index: iterating a PlacementRun
            # would mint a row for every one of them
            reqs = grp.requests
            n_reqs = len(reqs)
            names = grp.names if len(grp.names) == n_reqs else None
            # allocs that differ by id, name and node alone mint off one
            # template, as the compact path's do: _build_alloc's port
            # index and metric for each were 0.07 ms a placement
            tmpl = None
            if not self._batch_has_cores and not self._needs_build(grp):
                tmpl = self._mint_template(grp, n)
            ri = 0
            unplaced: list = []
            a0, a1, a2 = (int(grp.ask[0]), int(grp.ask[1]), int(grp.ask[2]))
            node_indices = np.nonzero(assign[gi, :n])[0]
            grp_by_tier: dict[int, int] = {}  # this group's victims
            for ni in node_indices:
                node = table.nodes[ni]
                take = int(assign[gi, ni])
                evict_budget = (
                    int(assign_evict[gi, ni]) if assign_evict is not None else 0
                )
                row = free[ni]
                for _ in range(take):
                    if ri >= n_reqs:
                        break
                    i, ri = ri, ri + 1
                    victims: list = []
                    if row[0] < a0 or row[1] < a1 or row[2] < a2:
                        if evict_budget > 0:
                            t_pick = now_ns()
                            victims = self._pick_victims(table, ni, grp) or []
                            pick_ns += now_ns() - t_pick
                        if not victims:
                            unplaced.append(reqs[i])  # out of exact capacity
                            continue
                    if tmpl is not None:
                        alloc = tmpl.mint(
                            generate_uuid(),
                            names[i] if names is not None else reqs[i].name,
                            node,
                        )
                    else:
                        alloc = self._build_alloc(table, grp, node, reqs[i])
                        if alloc is None:
                            unplaced.append(reqs[i])  # port assignment failed
                            continue
                    n_placed += 1
                    if victims:
                        evict_budget -= 1
                        n_preempting += 1
                        alloc.preempted_allocations = [v.id for v in victims]
                        pre = out.preemptions.setdefault(eval_id, [])
                        # Whole victims free more than the shortage, and
                        # what they leave over is room on the node for
                        # whatever is placed there next — a placement of
                        # ANOTHER eval too, whose plan then stands on
                        # this plan's eviction. The eviction stays in the
                        # preemptor's plan alone: groups are solved in
                        # the order their plans are submitted in, and the
                        # applier judges a batch's plans in that order,
                        # each on what the ones before it left
                        # (plan_apply._commit_merged), so the one that
                        # draws is refused wherever the one that evicts
                        # was.
                        for v in victims:
                            r = v.comparable_resources()
                            row[0] += r.cpu
                            row[1] += r.memory_mb
                            row[2] += r.disk_mb
                            pre.append((v, alloc.id))
                            prio = alloc_priority(v)
                            evicted_by_prio[prio] = \
                                evicted_by_prio.get(prio, 0) + 1
                            k = tier_of.get(prio)
                            if k is not None:
                                tier_left[k, ni] -= (
                                    r.cpu, r.memory_mb, r.disk_mb)
                                grp_by_tier[k] = grp_by_tier.get(k, 0) + 1
                    row[0] -= a0
                    row[1] -= a1
                    row[2] -= a2
                    placements.append(alloc)
            if ri < n_reqs:  # instances the kernel never placed
                unplaced.extend(reqs[ri:])
            if unplaced:
                leftovers[gi] = unplaced
            if max(grp_by_tier, default=0) > 0:
                above_lowest += self._victims_above_lowest(
                    grp, tier_left, grp_by_tier)
        if assign_evict is not None:
            n_evicted = sum(evicted_by_prio.values())
            metrics.incr("nomad.tpu.preempt.placements", n_placed)
            metrics.incr("nomad.tpu.preempt.placed", n_preempting)
            metrics.incr("nomad.tpu.preempt.evicted", n_evicted)
            # victims of any tier but the lowest that stands in the cell
            metrics.incr(
                "nomad.tpu.preempt.evicted_higher_tiers",
                sum(c for p, c in evicted_by_prio.items()
                    if tier_of.get(p, 0) > 0),
            )
            metrics.incr(
                "nomad.tpu.preempt.evicted_above_lowest", above_lowest)
            trace.stage_attrs(
                "preempt.victims", pick_ns, placed=n_preempting,
                evicted=n_evicted, above_lowest=above_lowest,
                by_priority={str(p): c
                             for p, c in sorted(evicted_by_prio.items())},
            )
        return leftovers

    def _victims_above_lowest(self, grp: LoweredGroup, tier_left,
                              by_tier: dict[int, int]) -> int:
        """How many of a group's victims were taken from a tier while a
        LOWER preemptible tier still stood where the group could have
        gone: on a node its feasibility admits, enough of the lower
        tiers beside the node's free capacity to hold one more instance.
        Judged once the group is materialized (what stands then stood
        all the while); unit caps are not looked at. 0 is the kernel's
        promise (lowest tier first, cluster-wide)."""
        ask = np.asarray(grp.ask[:3], dtype=np.int64)
        free = np.asarray(self._free, dtype=np.int64)
        above = 0
        for k, count in by_tier.items():
            if k == 0:
                continue
            lower = tier_left[:k].sum(axis=0)  # [n, 3]
            could = (
                grp.feasible
                & lower.any(axis=1)
                & ((free + lower) >= ask[None, :]).all(axis=1)
            )
            if could.any():
                above += count
        return above

    def _pick_victims(self, table, ni: int, grp: LoweredGroup):
        """Exact victim selection for one instance on one node: free
        enough for grp.ask from preemptible allocs, lowest priority tier
        first, closest resource distance within a tier (the Preemptor's
        scoring, reference preemption.go:198)."""
        # The node's live allocs as (priority, cpu, mem, disk, alloc),
        # lowest priority first, read from the store once a solve and
        # kept: a victim leaves the list when it is picked, so no two
        # placements take the same one. (Read anew for every placement
        # it was a third of a 1,000-placement solve's materialize.)
        cands = self._victim_cands.get(ni)
        if cands is None:
            cands = []
            for a in table._allocs_by_node(table.nodes[ni].id):
                r = a.comparable_resources()
                cands.append(
                    (alloc_priority(a), r.cpu, r.memory_mb, r.disk_mb, a))
            cands.sort(key=lambda c: c[0])
            self._victim_cands[ni] = cands
        row = self._free[ni]
        s0, s1, s2 = (max(int(grp.ask[i]) - row[i], 0) for i in range(3))

        def distance(c) -> float:
            # basic_resource_distance(shortage, the alloc's resources)
            x = (s0 - c[1]) / s0 if s0 > 0 else 0.0
            y = (s1 - c[2]) / s1 if s1 > 0 else 0.0
            z = (s2 - c[3]) / s2 if s2 > 0 else 0.0
            return math.sqrt(x * x + y * y + z * z)

        top = grp.priority - PRIORITY_DELTA
        job_id, namespace = grp.job.id, grp.job.namespace
        f0 = f1 = f2 = 0
        picks = []
        covered = False
        j, end = 0, len(cands)
        while j < end and cands[j][0] <= top and not covered:
            k = j
            while k < end and cands[k][0] == cands[j][0]:
                k += 1
            tier = [c for c in cands[j:k]
                    if c[4].job_id != job_id or c[4].namespace != namespace]
            tier.sort(key=distance)
            for c in tier:
                f0, f1, f2 = f0 + c[1], f1 + c[2], f2 + c[3]
                picks.append(c)
                if f0 >= s0 and f1 >= s1 and f2 >= s2:
                    covered = True
                    break
            j = k
        if not covered:
            return None
        # no more victims than the shortage needs: drop, highest
        # priority first, every pick the others cover without (the
        # reference's filterSuperset; with equal asks the greedy walk
        # above already stops at one)
        for c in reversed(picks[:-1]):
            r0, r1, r2 = f0 - c[1], f1 - c[2], f2 - c[3]
            if r0 >= s0 and r1 >= s1 and r2 >= s2:
                picks.remove(c)
                f0, f1, f2 = r0, r1, r2
        for c in picks:
            cands.remove(c)
        return [c[4] for c in picks]

    def _live_allocs(self, node_id: str):
        """Non-terminal allocs minus this batch's plan-stops — the same
        vacated view the dense table packs against."""
        return [
            a
            for a in self.state.allocs_by_node_terminal(node_id, False)
            if a.id not in self._stopped_ids
        ]

    def _remaining_cpu(self, node) -> int:
        """Node MHz still grantable: committed-state baseline minus
        every placement this batch already made (either path)."""
        base = self._state_cpu.get(node.id)
        if base is None:
            base = node.available_resources().cpu - sum(
                a.comparable_resources().cpu
                for a in self._live_allocs(node.id)
            )
            self._state_cpu[node.id] = base
        return base - self._batch_cpu.get(node.id, 0)

    def _build_alloc(
        self, table, grp: LoweredGroup, node, req: PlacementRequest
    ) -> Optional[Allocation]:
        tg = grp.tg
        net_idx = self._net_cache.get(node.id)
        if net_idx is None:
            net_idx = NetworkIndex()
            net_idx.set_node(node)
            net_idx.add_allocs(self._live_allocs(node.id))
            self._net_cache[node.id] = net_idx

        # Device instance assignment (mirrors rank.py's DeviceAllocator
        # use on the host path): instances already claimed by live allocs
        # AND by this batch's placements on the node are excluded.
        dev_alloc = None
        if any(t.resources.devices for t in tg.tasks):
            from ..device import DeviceAllocator

            dev_alloc = self._dev_cache.get(node.id)
            if dev_alloc is None:
                dev_alloc = DeviceAllocator(self.ctx, node)
                dev_alloc.add_allocs(self._live_allocs(node.id))
                self._dev_cache[node.id] = dev_alloc

        remaining_cpu = (
            self._remaining_cpu(node) if self._batch_has_cores else 0
        )

        # Dedicated-core id pool per node (mirrors rank.py): the dense
        # solve reserved core COUNTS (the 4th resource column); ids are
        # assigned here on materialization, shared across the batch via
        # the cache so two placements never collide.
        free_cores = None
        mhz_per_core = 0
        if any(t.resources.cores > 0 for t in tg.tasks):
            from ...structs.funcs import node_core_pool

            cached = self._core_cache.get(node.id)
            if cached is None:
                cached = node_core_pool(node, self._live_allocs(node.id))
                self._core_cache[node.id] = cached
            free_cores, mhz_per_core = cached

        if self._batch_has_cores:
            # the dense solve screened DECLARED MHz asks; cores grants
            # are DERIVED (cores x MHz/core) and may exceed them, so in
            # a cores-bearing batch EVERY slow-path group re-screens
            # against the shared ledger (rank.py does the same superset
            # re-check on the host path). Before any reservation, so no
            # rollback needed.
            group_cpu = sum(
                t.resources.cores * mhz_per_core
                if t.resources.cores > 0
                else t.resources.cpu
                for t in tg.tasks
            )
            if group_cpu > remaining_cpu:
                return None

        # Track reservations for rollback: the shared per-node caches
        # outlive this call, so a half-built placement that fails a later
        # ask must return everything it grabbed or subsequent groups see
        # phantom usage.
        granted_offers: list = []
        granted_devs: list = []

        granted_cores: list = []
        granted_cpu = 0

        def _rollback():
            for offer in granted_offers:
                net_idx.remove_reserved(offer)
            if dev_alloc is not None:
                for got in granted_devs:
                    dev_alloc.free[got["id"]].update(got["device_ids"])
            if free_cores is not None:
                free_cores.extend(granted_cores)

        task_resources: dict[str, AllocatedTaskResources] = {}
        for task in tg.tasks:
            tr = AllocatedTaskResources(
                cpu=task.resources.cpu, memory_mb=task.resources.memory_mb
            )
            if task.resources.cores > 0:
                if free_cores is None or len(free_cores) < task.resources.cores:
                    _rollback()
                    return None
                tr.reserved_cores = free_cores[: task.resources.cores]
                del free_cores[: task.resources.cores]
                granted_cores.extend(tr.reserved_cores)
                tr.cpu = task.resources.cores * mhz_per_core
            for ask in task.resources.networks:
                offer = net_idx.assign_network(ask)
                if offer is None:
                    _rollback()
                    return None
                net_idx.add_reserved(offer)
                granted_offers.append(offer)
                tr.networks.append(offer)
            for dev_ask in task.resources.devices:
                # assign() removes the picked ids from the free set, so
                # the shared per-node allocator naturally serializes the
                # batch's placements
                got = dev_alloc.assign(dev_ask) if dev_alloc else None
                if got is None:
                    _rollback()
                    return None  # instances exhausted on this node
                granted_devs.append(got)
                tr.devices.append(got)
            granted_cpu += tr.cpu
            task_resources[task.name] = tr
        shared_networks = []
        for ask in tg.networks:
            offer = net_idx.assign_network(ask)
            if offer is None:
                _rollback()
                return None
            net_idx.add_reserved(offer)
            granted_offers.append(offer)
            shared_networks.append(offer)

        if self._batch_has_cores:
            self._batch_cpu[node.id] = (
                self._batch_cpu.get(node.id, 0) + granted_cpu
            )
        alloc = Allocation(
            id=generate_uuid(),
            namespace=grp.job.namespace,
            eval_id=grp.key[0],
            name=req.name,
            node_id=node.id,
            node_name=node.name,
            job_id=grp.job.id,
            job=grp.job,
            task_group=tg.name,
            resources=AllocatedResources(
                tasks=task_resources,
                shared_disk_mb=tg.ephemeral_disk.size_mb,
                shared_networks=shared_networks,
            ),
            metrics=group_alloc_metric(grp, table.n),
        )
        if req.canary:
            alloc.deployment_status = AllocDeploymentStatus(canary=True)
        from ..util import annotate_previous_alloc

        annotate_previous_alloc(alloc, req)
        return alloc

    def _fail_all(self, out: SolveOutcome, ask: GroupAsk, dc_counts) -> None:
        metric = AllocMetric(nodes_available=dict(dc_counts))
        metric.coalesced_failures = max(0, len(ask.requests) - 1)
        out.failures.setdefault(ask.eval_obj.id, {})[ask.tg_name] = metric
