"""Lowering orchestrator state to dense tensors for the TPU placement solver.

This is the bridge between the string-typed, ragged control-plane world
(reference: scheduler/feasible.go's per-node predicate walk) and the dense
[group x node] tensor world the solver kernels operate on
(SURVEY.md §7 hard part 2: attribute vocabulary interning + fixed
constraint-kernel set; regex/version predicates stay host-side as
per-distinct-value mask precomputation).

Key trick: every hard constraint is a predicate over ONE node attribute.
We intern each referenced attribute's values into integer codes (V distinct
values << N nodes), evaluate the predicate once per distinct value with the
exact host-oracle implementation (`check_constraint` — including regex and
version operands), and broadcast to all N nodes with a single vectorized
gather. Feasibility semantics are therefore *identical* to the host oracle
by construction, not by reimplementation.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from bisect import bisect_left
from itertools import compress
from operator import is_not
from typing import Optional

import numpy as np

from ...structs import Job, Node, TaskGroup
from ...structs.structs import (
    CONSTRAINT_DISTINCT_HOSTS,
    CONSTRAINT_DISTINCT_PROPERTY,
)
from ..context import EvalContext
from ..feasible import check_constraint, resolve_target

NUM_RES = 3  # cpu MHz, memory MB, disk MB — must match structs.Resources.vector
BIG_UNITS = np.int32(1 << 30)


@dataclass
class NodeTable:
    """Interned view of all ready nodes in a snapshot."""

    nodes: list[Node]
    index_of: dict[str, int]
    cap: np.ndarray  # [N, NUM_RES] int64 — available (total - reserved)
    used: np.ndarray  # [N, NUM_RES] int64 — live alloc utilization
    datacenters: np.ndarray  # [N] int32 codes
    dc_values: list[str]
    # preemption tiers: distinct job priorities of live allocs, ascending,
    # and each tier's usage — feeds the preemption kernel's prefix sums
    tier_prios: list[int] = field(default_factory=list)
    tier_used: Optional[np.ndarray] = None  # [T, N, NUM_RES] int64
    # standing priorities above the batch's tier ceiling, left out of
    # the tiers: no group of the batch may evict them
    tiers_above: int = 0
    # dedicated-core availability: total ids and ids held by live allocs
    # (cores ride OUTSIDE the dense NUM_RES columns — a static screen
    # here, exact id assignment at materialization, allocs_fit backstop)
    cores_free: Optional[np.ndarray] = None  # [N] int64
    # lazily built per-attribute interning: ltarget -> (codes [N] int32, values)
    _attr_cache: dict[str, tuple[np.ndarray, list[str], np.ndarray]] = field(
        default_factory=dict
    )
    # lazily built driver health masks: driver -> bool [N]
    _driver_cache: dict[str, np.ndarray] = field(default_factory=dict)
    # static-port occupancy masks, lazy: port -> bool [N]
    _port_masks: Optional[dict[int, np.ndarray]] = None
    # snapshot accessor for live allocs per node (set by build_node_table)
    _allocs_by_node: Optional[object] = None

    @property
    def n(self) -> int:
        return len(self.nodes)

    def attr_codes(self, target: str) -> tuple[np.ndarray, list[str], np.ndarray]:
        """(codes [N] i32, distinct values, exists-mask [N] bool) for a
        constraint ltarget, interning on first use."""
        cached = self._attr_cache.get(target)
        if cached is not None:
            return cached
        values: list[str] = []
        code_of: dict[str, int] = {}
        codes = np.zeros(self.n, dtype=np.int32)
        exists = np.zeros(self.n, dtype=bool)
        for i, node in enumerate(self.nodes):
            val, found = resolve_target(node, target)
            exists[i] = found
            key = val if found else "\x00missing"
            code = code_of.get(key)
            if code is None:
                code = len(values)
                code_of[key] = code
                values.append(val if found else "")
            codes[i] = code
        out = (codes, values, exists)
        self._attr_cache[target] = out
        return out

    def used_port_mask(self, port: int) -> np.ndarray:
        """bool [N]: does any live alloc (or node reservation) already hold
        this static port on the node?"""
        if self._port_masks is None:
            self._port_masks = {}
        m = self._port_masks.get(port)
        if m is not None:
            return m
        m = np.zeros(self.n, dtype=bool)
        for i, node in enumerate(self.nodes):
            if port in node.reserved.reserved_ports:
                m[i] = True
                continue
            for alloc in self._allocs_by_node(node.id):
                if alloc.resources is None:
                    continue
                nets = list(alloc.resources.shared_networks)
                for tr in alloc.resources.tasks.values():
                    nets.extend(tr.networks)
                if any(
                    p.value == port
                    for net in nets
                    for p in list(net.reserved_ports) + list(net.dynamic_ports)
                ):
                    m[i] = True
                    break
        self._port_masks[port] = m
        return m

    def driver_mask(self, driver: str) -> np.ndarray:
        m = self._driver_cache.get(driver)
        if m is not None:
            return m
        m = np.zeros(self.n, dtype=bool)
        for i, node in enumerate(self.nodes):
            info = node.drivers.get(driver)
            if info is not None:
                m[i] = info.detected and info.healthy
            else:
                m[i] = node.attributes.get(f"driver.{driver}", "") in ("1", "true")
        self._driver_cache[driver] = m
        return m


def alloc_priority(alloc) -> int:
    """The tier an alloc stands in: its job's priority, 50 without a
    job (the store's rule for its usage by priority)."""
    return alloc.job.priority if alloc.job is not None else 50


class TierSlabs:
    """Committed usage by (node, priority) as the dense slabs a NodeTable
    carries, kept current from the store's index (`node_tier_usage`) by
    what changed since the last read.

    The store replaces a node's tiers wholesale, so a node whose entry
    is the SAME tuple as last time has not changed: the comparison runs
    over the whole cluster without running Python for a node, and only
    the nodes a plan touched are rewritten. That matters more than the
    arithmetic suggests: a lowering shares the interpreter with every
    other thread of the server, and waits its turn again after each
    stretch of bytecode and each large numpy call. A fresh instance has
    read nothing, so its first read writes every node that holds
    anything — the one-off form (no resident state) and the first solve
    after the node universe changed."""

    def __init__(self, index_of: dict[str, int]) -> None:
        self.index_of = index_of
        self.ids = list(index_of)  # node order
        n = len(self.ids)
        self.rows: list[tuple] = [()] * n  # a node's tiers as last read
        self.prios: list[int] = []  # ascending; a slab each
        # [T, N, cpu mem disk allocs]: a tier is where its allocs are
        self.slabs = np.zeros((0, n, NUM_RES + 1), dtype=np.int64)
        self.nodes_in: list[int] = []  # nodes that hold an alloc, by tier

    def _slab(self, prio: int) -> int:
        k = bisect_left(self.prios, prio)
        if k == len(self.prios) or self.prios[k] != prio:
            self.prios.insert(k, prio)
            self.nodes_in.insert(k, 0)
            self.slabs = np.insert(self.slabs, k, 0, axis=0)
        return k

    def read(self, tiers_of, tier_adj: dict) -> tuple[list[int], np.ndarray]:
        """(tier_prios, tier_used [T, N, NUM_RES]) for one batch.
        `tiers_of`: the snapshot's bulk reader, node ids -> for each its
        ((priority, cpu, mem, disk, allocs), ...); `tier_adj`: the
        batch's own view of a few nodes, node id -> {priority: [cpu,
        mem, disk, allocs]} to add (its stops negative). The arrays are
        the caller's own. A priority with no alloc left on any node of
        the table gets no slab — what the alloc walk of build_node_table
        yields for the same live view."""
        rows = tiers_of(self.ids)
        old, self.rows = self.rows, rows
        for i in compress(range(len(rows)), map(is_not, rows, old)):
            for tier in old[i]:
                k = self._slab(tier[0])
                self.slabs[k, i] = 0
                self.nodes_in[k] -= 1
            for tier in rows[i]:
                k = self._slab(tier[0])
                self.slabs[k, i] = tier[1:]
                self.nodes_in[k] += 1
        for by_prio in tier_adj.values():
            for prio in by_prio:  # a tier may be the batch's own alone
                self._slab(prio)
        out = self.slabs.copy()
        nodes_in = list(self.nodes_in)
        for nid, by_prio in tier_adj.items():
            i = self.index_of.get(nid)
            if i is None:
                continue  # a stop outside the batch's datacenters
            for prio, vec in by_prio.items():
                k = bisect_left(self.prios, prio)
                held = out[k, i, NUM_RES] > 0
                out[k, i] += vec
                nodes_in[k] += int(out[k, i, NUM_RES] > 0) - int(held)
        stand = [k for k, nodes in enumerate(nodes_in) if nodes > 0]
        if len(stand) < len(nodes_in):
            out = out[stand]
        return [self.prios[k] for k in stand], out[:, :, :NUM_RES]


class UsageRows:
    """Committed usage by node as the [N, NUM_RES] rows a NodeTable
    carries, kept current from the store's aggregate (`node_usage_many`)
    the way TierSlabs keeps the tiers: a node whose entry is the SAME
    tuple as last time has not changed, so only the nodes a plan touched
    are rewritten, in one numpy call. A fresh instance has read nothing:
    its first read writes every row."""

    def __init__(self, index_of: dict[str, int]) -> None:
        self.index_of = index_of
        self.ids = list(index_of)  # node order
        self.rows: list = [None] * len(self.ids)  # as last read
        self.base = np.zeros((len(self.ids), NUM_RES), dtype=np.int64)
        self.rewritten = 0  # rows the last read wrote

    def read(self, usage_many, adj: dict) -> np.ndarray:
        """The batch's `used` [N, NUM_RES], the caller's own array.
        `usage_many`: the snapshot's bulk reader, node ids -> for each
        its (cpu, mem, disk, complex); `adj`: the batch's own view of a
        few nodes, node id -> [cpu, mem, disk] to add (its stops
        negative); a node outside the table is skipped."""
        rows = usage_many(self.ids)
        old, self.rows = self.rows, rows
        changed = list(compress(range(len(rows)), map(is_not, rows, old)))
        if changed:
            self.base[changed] = [rows[i][:NUM_RES] for i in changed]
        self.rewritten = len(changed)
        out = self.base.copy()
        for nid, vec in adj.items():
            i = self.index_of.get(nid)
            if i is not None:
                out[i] += vec
        return out


def build_node_table(
    nodes: list[Node], allocs_by_node, usage_of=None
) -> NodeTable:
    """Lower ready nodes + live utilization to tensors.

    allocs_by_node: callable node_id -> live allocs (snapshot accessor).

    usage_of: optional callable node_id -> (cpu, mem, disk) committed
    usage. When given, per-node utilization comes from the store's
    incremental aggregate in O(nodes) instead of walking every live
    alloc (O(allocs) — the dominant lowering cost on a loaded cluster).
    The fast table is built with no preemption tiers — a batch that may
    preempt reads them into it from the same aggregate by priority
    (TierSlabs) — and carries NO core pools, so the solver walks the
    allocs for a batch that asks for cores; everything else about the
    table is identical.
    """
    n = len(nodes)
    cap = np.zeros((n, NUM_RES), dtype=np.int64)
    used = np.zeros((n, NUM_RES), dtype=np.int64)
    dc_values: list[str] = []
    dc_code: dict[str, int] = {}
    dcs = np.zeros(n, dtype=np.int32)
    index_of: dict[str, int] = {}
    # usage bucketed by the owning job's priority → preemption tiers
    by_prio: dict[int, np.ndarray] = {}
    cores_free = np.zeros(n, dtype=np.int64)
    # id(AllocatedResources) -> (cpu, mem, disk, reserved cores); every
    # keyed object is held by a live alloc of the snapshot being walked
    grants: dict[int, tuple] = {}
    for i, node in enumerate(nodes):
        index_of[node.id] = i
        avail = node.available_resources()
        cap[i] = (avail.cpu, avail.memory_mb, avail.disk_mb)
        cores_free[i] = node.resources.total_cores or 0
        code = dc_code.get(node.datacenter)
        if code is None:
            code = len(dc_values)
            dc_code[node.datacenter] = code
            dc_values.append(node.datacenter)
        dcs[i] = code
        if usage_of is not None:
            u = usage_of(node.id)
            used[i] = (u[0], u[1], u[2])
            continue
        # The full walk (a batch that may preempt, or asks for cores):
        # one pass over every live alloc of the cluster, so a node's sums
        # are kept in plain ints and written once, and the grant of an
        # alloc is read once for every AllocatedResources OBJECT — the
        # fast-mint path shares one among all instances of a group.
        node_tiers: dict[int, list[int]] = {}
        reserved = 0
        for alloc in allocs_by_node(node.id):
            res = alloc.resources
            got = grants.get(id(res)) if res is not None else None
            if got is None:
                r = alloc.comparable_resources()
                got = (r.cpu, r.memory_mb, r.disk_mb,
                       0 if res is None else sum(
                           len(tr.reserved_cores)
                           for tr in res.tasks.values()))
                if res is not None:
                    grants[id(res)] = got
            reserved += got[3]
            prio = alloc_priority(alloc)
            acc = node_tiers.get(prio)
            if acc is None:
                node_tiers[prio] = [got[0], got[1], got[2]]
            else:
                acc[0] += got[0]
                acc[1] += got[1]
                acc[2] += got[2]
        cores_free[i] -= reserved
        for prio, acc in node_tiers.items():
            tier = by_prio.get(prio)
            if tier is None:
                tier = by_prio[prio] = np.zeros((n, NUM_RES), dtype=np.int64)
            tier[i] = acc
            used[i] += acc
    tier_prios = sorted(by_prio)
    tier_used = (
        np.stack([by_prio[p] for p in tier_prios])
        if tier_prios
        else np.zeros((0, n, NUM_RES), dtype=np.int64)
    )
    table = NodeTable(
        nodes=nodes,
        index_of=index_of,
        cap=cap,
        used=used,
        datacenters=dcs,
        dc_values=dc_values,
        tier_prios=tier_prios,
        tier_used=tier_used,
        cores_free=cores_free,
    )
    table._allocs_by_node = allocs_by_node
    # observability: the lowered table's host-side tensor footprint —
    # the upper bound of what a cold (non-resident) solve ships to the
    # device per batch (solverobs feeds /v1/solver/status)
    from ... import solverobs

    solverobs.note_table(
        n, cap.nbytes + used.nbytes + tier_used.nbytes + dcs.nbytes
    )
    return table


@dataclass
class LoweredGroup:
    """One task group's asks, lowered. All instances of a group are
    interchangeable — the solver places `count` of them at once."""

    key: tuple  # (eval_id, tg_name)
    job: Job
    tg: TaskGroup
    count: int
    ask: np.ndarray  # [NUM_RES] int64
    feasible: np.ndarray  # [N] bool
    bias: np.ndarray  # [N] f32 — affinity/spread score offsets
    units_cap: np.ndarray  # [N] int32 — distinct_hosts/property caps
    priority: int
    names: list[str] = field(default_factory=list)  # instance names to assign
    requests: list = field(default_factory=list)  # original PlacementRequests
    restricted: bool = False  # spread-value-restricted sub-group (retryable)
    # bias WITHOUT the per-solve spread addend — what the lowered-skeleton
    # cache stores (aliases `bias` when the group has no spreads)
    bias_static: Optional[np.ndarray] = None
    # per-dimension feasibility attrition: screen name → nodes that
    # screen newly eliminated. The dense path's answer to the host
    # stack's per-checker counts — AllocMetric.constraint_filtered /
    # dimension_exhausted on the fast-mint path read from here, so
    # `alloc status` explains a dense-path failure the same way it
    # explains a host-path one.
    filtered_dims: dict = field(default_factory=dict)


def lower_group(
    ctx: EvalContext,
    table: NodeTable,
    job: Job,
    tg: TaskGroup,
    requests: list,
    eval_id: str,
) -> LoweredGroup:
    """Build the group's feasibility mask, score bias, and unit caps."""
    n = table.n
    feas = np.ones(n, dtype=bool)
    filtered_dims: dict[str, int] = {}

    def screen(dim: str, mask: np.ndarray) -> None:
        """AND `mask` into the running feasibility and attribute the
        nodes it newly eliminated to `dim` (AllocMetric attrition)."""
        nonlocal feas
        before = int(np.sum(feas))
        feas = feas & mask
        dropped = before - int(np.sum(feas))
        if dropped:
            filtered_dims[dim] = filtered_dims.get(dim, 0) + dropped

    # Datacenter membership (the GenericStack's node source filter).
    import fnmatch

    dc_ok = np.zeros(len(table.dc_values), dtype=bool)
    for vi, dc in enumerate(table.dc_values):
        dc_ok[vi] = any(fnmatch.fnmatchcase(dc, pat) for pat in job.datacenters)
    screen("datacenters", dc_ok[table.datacenters])

    # Drivers.
    for task in tg.tasks:
        screen(f"driver.{task.driver}", table.driver_mask(task.driver))

    # Constraints: job + group + task level, via per-distinct-value masks.
    constraints = list(job.constraints) + list(tg.constraints)
    for task in tg.tasks:
        constraints.extend(task.constraints)
    units_cap = np.full(n, BIG_UNITS, dtype=np.int64)
    for c in constraints:
        if c.operand == CONSTRAINT_DISTINCT_HOSTS:
            units_cap = np.minimum(units_cap, 1)
            # exclude nodes already carrying this job's allocs
            screen(
                CONSTRAINT_DISTINCT_HOSTS,
                _job_free_mask(ctx, table, job.id),
            )
            continue
        if c.operand == CONSTRAINT_DISTINCT_PROPERTY:
            cap_per_value = int(c.rtarget) if c.rtarget else 1
            codes, values, exists = table.attr_codes(c.ltarget)
            counts = _property_counts(ctx, table, job, c.ltarget)
            remaining = np.maximum(
                0, cap_per_value - counts
            )  # per distinct value
            units_cap = np.minimum(units_cap, remaining[codes])
            screen(f"{CONSTRAINT_DISTINCT_PROPERTY}.{c.ltarget}", exists)
            continue
        codes, values, exists = table.attr_codes(c.ltarget)
        rval, r_found = c.rtarget, True  # rtargets are literals for node feas
        value_ok = np.zeros(len(values), dtype=bool)
        for vi, val in enumerate(values):
            value_ok[vi] = check_constraint(
                ctx, c.operand, val, rval, True, r_found
            )
        mask = value_ok[codes]
        # Attributes that didn't resolve fail every operand except is_not_set.
        if c.operand == "is_not_set":
            mask = mask | ~exists
        else:
            mask = mask & exists
        screen(f"constraint.{c.ltarget} {c.operand}".rstrip(), mask)

    # Host volumes (mirrors feasible.py HostVolumeChecker): per-node
    # membership/writability, plus the registered-volume access screen
    # (node-independent: a claimed single-writer volume zeroes the mask).
    vol_asks = [
        v for v in tg.volumes.values() if v.type in ("", "host")
    ]
    if vol_asks:
        state = getattr(ctx, "state", None)
        for ask in vol_asks:
            registered = (
                state.volumes_by_name(job.namespace, ask.source)
                if state is not None and hasattr(state, "volumes_by_name")
                else []
            )
            vol_ok = np.zeros(n, dtype=bool)
            for i, node in enumerate(table.nodes):
                hv = node.host_volumes.get(ask.source)
                if hv is None or (hv.read_only and not ask.read_only):
                    continue
                usable = [
                    v for v in registered if v.node_id in ("", node.id)
                ]
                if usable and not any(
                    v.claimable(ask.read_only)[0] for v in usable
                ):
                    continue  # claimed single-writer: node unusable
                vol_ok[i] = True
            screen(f"host_volume.{ask.source}", vol_ok)

    # CSI volumes (mirrors feasible.py CSIVolumeChecker): node must run a
    # healthy node-capable instance of some registered, claimable volume's
    # plugin for every csi-type ask.
    csi_asks = [v for v in tg.volumes.values() if v.type == "csi"]
    if csi_asks:
        state = getattr(ctx, "state", None)
        for ask in csi_asks:
            vols = [
                v
                for v in (
                    state.volumes_by_name(job.namespace, ask.source)
                    if state is not None
                    and hasattr(state, "volumes_by_name")
                    else []
                )
                if v.type == "csi" and v.claimable(ask.read_only)[0]
            ]
            plugin_ids = {v.plugin_id for v in vols}
            csi_ok = np.array(
                [
                    any(
                        (info := node.csi_plugins.get(pid)) is not None
                        and info.get("healthy")
                        and info.get("node", True)
                        for pid in plugin_ids
                    )
                    for node in table.nodes
                ],
                dtype=bool,
            )
            screen(f"csi_volume.{ask.source}", csi_ok)

    # Network: static-port / bandwidth screens stay host-side but cheap —
    # mbits capacity folds into feasibility; a static-port ask caps the
    # group at one instance per node and excludes nodes already holding
    # the port (dynamic port selection still happens at plan build).
    net_asks = list(tg.networks) + [
        a for t in tg.tasks for a in t.resources.networks
    ]
    total_mbits = sum(a.mbits for a in net_asks)
    if total_mbits > 0:
        net_ok = np.array(
            [
                max((nw.mbits for nw in node.resources.networks), default=0)
                >= total_mbits
                for node in table.nodes
            ],
            dtype=bool,
        )
        screen("network.mbits", net_ok)
    static_ports = [p.value for a in net_asks for p in a.reserved_ports if p.value]
    if static_ports:
        units_cap = np.minimum(units_cap, 1)
        for port in static_ports:
            screen(f"network.port.{port}", ~table.used_port_mask(port))

    # Devices.
    dev_asks = [d for t in tg.tasks for d in t.resources.devices]
    if dev_asks:
        dev_ok = np.ones(n, dtype=bool)
        for i, node in enumerate(table.nodes):
            for ask in dev_asks:
                if not any(
                    d.matches(ask)
                    and sum(1 for inst in d.instances if inst.healthy) >= ask.count
                    for d in node.resources.devices
                ):
                    dev_ok[i] = False
                    break
        screen("devices", dev_ok)

    # Score bias: affinities (normalized like the host oracle) + static
    # spread boosts; the solver adds this to the binpack score for ordering.
    bias = np.zeros(n, dtype=np.float32)
    affinities = list(job.affinities) + list(tg.affinities)
    for task in tg.tasks:
        affinities.extend(task.affinities)
    if affinities:
        total_weight = sum(abs(a.weight) for a in affinities) or 1
        for a in affinities:
            codes, values, exists = table.attr_codes(a.ltarget)
            value_ok = np.zeros(len(values), dtype=bool)
            for vi, val in enumerate(values):
                value_ok[vi] = check_constraint(ctx, a.operand, val, a.rtarget, True, True)
            match = value_ok[codes] & exists
            bias += np.where(match, a.weight / total_weight, 0.0).astype(np.float32)

    bias_static = bias
    sb = spread_bias(ctx, table, job, tg)
    if sb is not None:
        bias = bias + sb

    cores_ask = sum(t.resources.cores for t in tg.tasks)
    if cores_ask > 0 and table.cores_free is not None:
        screen("cores", table.cores_free >= cores_ask)
        # dedicated ids are NOT in the dense resource columns, so cap
        # the per-node unit count here or the solver would stack more
        # instances than a node has cores and the materializer would
        # drop the overflow
        units_cap = np.minimum(
            units_cap, np.maximum(table.cores_free, 0) // cores_ask
        )

    ask = np.array(tg.combined_resources().vector(), dtype=np.int64)
    return LoweredGroup(
        key=(eval_id, tg.name),
        job=job,
        tg=tg,
        count=len(requests),
        ask=ask,
        feasible=feas,
        bias=bias,
        units_cap=np.minimum(units_cap, BIG_UNITS).astype(np.int64),
        priority=job.priority,
        names=request_names(requests),
        requests=requests,
        bias_static=bias_static,
        filtered_dims=filtered_dims,
    )


def spread_bias(
    ctx: EvalContext, table: NodeTable, job: Job, tg: TaskGroup
) -> Optional[np.ndarray]:
    """The spread boost addend [N] f32, or None when the group has no
    spreads. Split out of lower_group because it is the ONLY part of a
    spread-carrying group's lowering that reads live state (per-value
    alloc counts): the solver caches the static tensors across solves
    and re-adds this per solve."""
    spreads = list(tg.spreads) + [
        s for s in job.spreads if s.attribute not in {t.attribute for t in tg.spreads}
    ]
    if not spreads:
        return None
    bias = np.zeros(table.n, dtype=np.float32)
    sum_w = sum(abs(s.weight) for s in spreads) or 1
    for s in spreads:
        codes, values, exists = table.attr_codes(s.attribute)
        counts = _property_counts(ctx, table, job, s.attribute, tg.name)
        desired = _spread_desired(s, values, tg.count)
        # boost = (desired - used)/desired per value (targeted spread);
        # implicit even spread when no explicit targets.
        with np.errstate(divide="ignore", invalid="ignore"):
            boost = np.where(
                desired > 0, (desired - counts) / np.maximum(desired, 1), -1.0
            )
        bias += (boost[codes] * (s.weight / sum_w)).astype(np.float32)
    return bias


def request_names(requests) -> list[str]:
    """The per-row names column without materializing rows: a
    PlacementRun already holds it; plain lists walk their rows."""
    names = getattr(requests, "names", None)
    if names is not None:
        return names
    return [r.name for r in requests]


def group_lower_cacheable(job: Job, tg: TaskGroup) -> bool:
    """May this group's FULL lowered tensors (spread bias included) be
    reused across solves on the (job version, node-universe fingerprint)
    key alone? Only when the static part is cacheable AND there are no
    spreads (existing-alloc counts feed the spread bias per solve)."""
    if tg.spreads or job.spreads:
        return False
    return group_lower_static_cacheable(job, tg)


def group_lower_static_cacheable(job: Job, tg: TaskGroup) -> bool:
    """May this group's STATIC lowered tensors (feasibility, affinity
    bias, unit caps — everything except the spread addend) be cached
    across solves on the (job version, node-universe fingerprint) key
    alone?

    False whenever the static lowering reads state BEYOND the node
    fingerprint: distinct_hosts / distinct_property (proposed-alloc and
    per-value counts), volumes (claim state), static ports (live port
    occupancy), and cores (the free-core column is rebuilt per solve).
    Everything else — dc membership, drivers, attribute constraints,
    affinities, bandwidth, devices — is a pure function of (job spec,
    node objects), which the fingerprint pins. Spreads do NOT disqualify
    the static part: lower.spread_bias recomputes their addend per
    solve on top of the cached tensors."""
    constraints = list(job.constraints) + list(tg.constraints)
    for task in tg.tasks:
        constraints.extend(task.constraints)
    if any(
        c.operand in (CONSTRAINT_DISTINCT_HOSTS, CONSTRAINT_DISTINCT_PROPERTY)
        for c in constraints
    ):
        return False
    if tg.volumes:
        return False
    if any(t.resources.cores > 0 for t in tg.tasks):
        return False
    net_asks = list(tg.networks) + [
        a for t in tg.tasks for a in t.resources.networks
    ]
    if any(p.value for a in net_asks for p in a.reserved_ports):
        return False
    return True


def _job_free_mask(ctx: EvalContext, table: NodeTable, job_id: str) -> np.ndarray:
    mask = np.ones(table.n, dtype=bool)
    for i, node in enumerate(table.nodes):
        for alloc in ctx.proposed_allocs(node.id):
            if alloc.job_id == job_id:
                mask[i] = False
                break
    return mask


def _property_counts(
    ctx: EvalContext, table: NodeTable, job: Job, attribute: str, tg_name: str = ""
) -> np.ndarray:
    """Existing alloc count per distinct attribute value (host-side; the
    solver handles the within-batch delta via units caps)."""
    codes, values, _ = table.attr_codes(attribute)
    counts = np.zeros(len(values), dtype=np.int64)
    stopped: set[str] = set()
    if ctx.plan is not None:
        for allocs_ in ctx.plan.node_update.values():
            stopped.update(a.id for a in allocs_)
    for alloc in ctx.state.allocs_by_job(job.namespace, job.id):
        if alloc.terminal_status() or alloc.id in stopped:
            continue
        if tg_name and alloc.task_group != tg_name:
            continue
        idx = table.index_of.get(alloc.node_id)
        if idx is not None:
            counts[codes[idx]] += 1
    return counts


def _spread_desired(spread, values: list[str], count: int) -> np.ndarray:
    import math

    explicit = {t.value: t.percent for t in spread.targets}
    desired = np.zeros(len(values), dtype=np.float64)
    remaining = 100 - sum(explicit.values())
    implicit = [v for v in values if v not in explicit]
    implicit_pct = remaining / max(1, len(implicit))
    for vi, val in enumerate(values):
        pct = explicit.get(val, implicit_pct)
        desired[vi] = math.ceil(pct / 100.0 * count)
    return desired
