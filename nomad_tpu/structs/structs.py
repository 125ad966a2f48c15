"""Core vocabulary shared by every layer of the framework.

This is the TPU-native re-design of the reference's shared struct vocabulary
(reference: nomad/structs/structs.go — Job :3958, TaskGroup :5923, Task :6652,
Node :1812, Allocation :9110, Evaluation :10211, Plan :10505, Resources :2191).

Design departures from the reference (deliberate, TPU-first):
  * Resources are a flat numeric vector (cpu MHz, memory MB, disk MB,
    network mbits) so that lowering node/alloc state into dense
    ``(alloc x node x resource)`` tensors for the JAX placement solver is a
    simple gather, not a tree walk.
  * All structs are plain dataclasses with explicit ``copy()`` — the state
    store relies on copy-on-write discipline exactly like the reference's
    immutable-radix MemDB store.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import os
from dataclasses import dataclass, field
from typing import Any, Optional

# ---------------------------------------------------------------------------
# Constants (reference: nomad/structs/structs.go:1659,3916,9096,10140)
# ---------------------------------------------------------------------------

JOB_TYPE_CORE = "_core"
JOB_TYPE_SERVICE = "service"
JOB_TYPE_BATCH = "batch"
JOB_TYPE_SYSTEM = "system"
JOB_TYPE_SYSBATCH = "sysbatch"

JOB_STATUS_PENDING = "pending"
JOB_STATUS_RUNNING = "running"
JOB_STATUS_DEAD = "dead"

JOB_MIN_PRIORITY = 1
JOB_DEFAULT_PRIORITY = 50
JOB_MAX_PRIORITY = 100

CORE_JOB_PRIORITY = JOB_MAX_PRIORITY * 2

NODE_STATUS_INIT = "initializing"
NODE_STATUS_READY = "ready"
NODE_STATUS_DOWN = "down"

NODE_SCHEDULING_ELIGIBLE = "eligible"
NODE_SCHEDULING_INELIGIBLE = "ineligible"

ALLOC_DESIRED_STATUS_RUN = "run"
ALLOC_DESIRED_STATUS_STOP = "stop"
ALLOC_DESIRED_STATUS_EVICT = "evict"

ALLOC_CLIENT_STATUS_PENDING = "pending"
ALLOC_CLIENT_STATUS_RUNNING = "running"
ALLOC_CLIENT_STATUS_COMPLETE = "complete"
ALLOC_CLIENT_STATUS_FAILED = "failed"
ALLOC_CLIENT_STATUS_LOST = "lost"

EVAL_STATUS_BLOCKED = "blocked"
EVAL_STATUS_PENDING = "pending"
EVAL_STATUS_COMPLETE = "complete"
EVAL_STATUS_FAILED = "failed"
EVAL_STATUS_CANCELLED = "canceled"

EVAL_TRIGGER_JOB_REGISTER = "job-register"
EVAL_TRIGGER_JOB_DEREGISTER = "job-deregister"
EVAL_TRIGGER_PERIODIC_JOB = "periodic-job"
EVAL_TRIGGER_NODE_DRAIN = "node-drain"
EVAL_TRIGGER_NODE_UPDATE = "node-update"
EVAL_TRIGGER_ALLOC_STOP = "alloc-stop"
EVAL_TRIGGER_SCHEDULED = "scheduled"
EVAL_TRIGGER_ROLLING_UPDATE = "rolling-update"
EVAL_TRIGGER_DEPLOYMENT_WATCHER = "deployment-watcher"
EVAL_TRIGGER_FAILED_FOLLOWUP = "failed-follow-up"
EVAL_TRIGGER_MAX_PLANS = "max-plan-attempts"
EVAL_TRIGGER_RETRY_FAILED_ALLOC = "alloc-failure"
EVAL_TRIGGER_QUEUED_ALLOCS = "queued-allocs"
EVAL_TRIGGER_PREEMPTION = "preemption"
EVAL_TRIGGER_SCALING = "job-scaling"
EVAL_TRIGGER_FORCE_EVAL = "job-eval"

# Constraint operands (reference: nomad/structs/structs.go:8248-8258)
CONSTRAINT_DISTINCT_PROPERTY = "distinct_property"
CONSTRAINT_DISTINCT_HOSTS = "distinct_hosts"
CONSTRAINT_REGEX = "regexp"
CONSTRAINT_VERSION = "version"
CONSTRAINT_SEMVER = "semver"
CONSTRAINT_SET_CONTAINS = "set_contains"
CONSTRAINT_SET_CONTAINS_ALL = "set_contains_all"
CONSTRAINT_SET_CONTAINS_ANY = "set_contains_any"
CONSTRAINT_IS_SET = "is_set"
CONSTRAINT_IS_NOT_SET = "is_not_set"

COMPARISON_OPERANDS = ("=", "==", "is", "!=", "not", "<", "<=", ">", ">=")

DEPLOYMENT_STATUS_RUNNING = "running"
DEPLOYMENT_STATUS_PAUSED = "paused"
DEPLOYMENT_STATUS_FAILED = "failed"
DEPLOYMENT_STATUS_SUCCESSFUL = "successful"
DEPLOYMENT_STATUS_CANCELLED = "cancelled"

DEPLOYMENT_STATUSES_TERMINAL = (
    DEPLOYMENT_STATUS_FAILED,
    DEPLOYMENT_STATUS_SUCCESSFUL,
    DEPLOYMENT_STATUS_CANCELLED,
)

ALLOC_HEALTH_DESC_NO_TASKS = "Task not running by deadline"

# Reschedule/restart
RESTART_POLICY_MODE_DELAY = "delay"
RESTART_POLICY_MODE_FAIL = "fail"

DEFAULT_NAMESPACE = "default"


# Per-thread id pool behind generate_uuid: the urandom syscall AND the
# per-id hex/dash formatting are the cost (the round-12 profiler put
# generate_uuid + generate_uuids together at ~20% of c2m wall). The pool
# now holds PRE-FORMATTED ids minted in bulk — one urandom syscall and
# one formatting pass (native fastpack.uuid_hex when present) serve 256
# ids — so every per-id call site is bulk minting under the hood.
# Thread-local so no lock rides the hot path. NOT fork-safe by design:
# this codebase spawns subprocesses (fresh interpreter), never forks a
# live server.
_UUID_POOL_IDS = 256


class _UuidPool(threading.local):
    def __init__(self) -> None:
        self.ids: list[str] = []
        self.off = 0
        # raw entropy pool for bulk minting: one 64KiB urandom read
        # serves ~16 c2m-sized generate_uuids calls (the per-call
        # syscall was ~0.2s of a c2m pass)
        self.raw = b""
        self.raw_off = 0


_uuid_pool = _UuidPool()

_RAW_POOL_BYTES = 1 << 16


def _pool_entropy(n: int) -> bytes:
    pool = _uuid_pool
    off = pool.raw_off
    if off + n > len(pool.raw):
        pool.raw = os.urandom(max(_RAW_POOL_BYTES, n))
        off = 0
    pool.raw_off = off + n
    return pool.raw[off : off + n]


def generate_uuid() -> str:
    # uuid4-shaped from the bulk-minted pool: same entropy per id as
    # uuid.uuid4(), one syscall + one format pass per _UUID_POOL_IDS ids
    pool = _uuid_pool
    off = pool.off
    if off >= len(pool.ids):
        pool.ids = generate_uuids(_UUID_POOL_IDS)
        off = 0
    pool.off = off + 1
    return pool.ids[off]


def _uuid_hex_py(raw: bytes) -> list[str]:
    h = raw.hex()
    return [
        f"{b[:8]}-{b[8:12]}-{b[12:16]}-{b[16:20]}-{b[20:]}"
        for b in (h[i : i + 32] for i in range(0, len(h), 32))
    ]


def generate_uuids(k: int) -> list[str]:
    """Bulk uuid4-shaped ids: one urandom syscall + one formatting pass
    for the whole batch (the batched solver mints 100k+ allocation ids
    per solve). Formatting runs in the fastpack extension when it is
    already resolved (codec.warm_native — this function must never
    trigger the C build itself), with the pure-Python hex pass as the
    behavior-identical fallback."""
    raw = _pool_entropy(16 * k)
    from .. import codec

    fp = codec.native_module()
    if fp is not None:
        try:
            return fp.uuid_hex(raw)
        except Exception:
            pass
    return _uuid_hex_py(raw)


def now_ns() -> int:
    return time.time_ns()


# ---------------------------------------------------------------------------
# Resources
# ---------------------------------------------------------------------------

# Fixed resource vector layout used by the TPU solver lowering
# (nomad_tpu/scheduler/tpu/lower.py): indices into the dense resource axis.
RES_CPU = 0
RES_MEM = 1
RES_DISK = 2
NUM_CORE_RESOURCES = 3


@dataclass(slots=True)
class Port:
    label: str = ""
    value: int = 0
    to: int = 0
    host_network: str = "default"


@dataclass(slots=True)
class NetworkResource:
    """A network ask/offer (reference: structs.go NetworkResource :2441)."""

    mode: str = "host"
    device: str = ""
    cidr: str = ""
    ip: str = ""
    mbits: int = 0
    reserved_ports: list[Port] = field(default_factory=list)
    dynamic_ports: list[Port] = field(default_factory=list)

    def copy(self) -> "NetworkResource":
        return NetworkResource(
            mode=self.mode,
            device=self.device,
            cidr=self.cidr,
            ip=self.ip,
            mbits=self.mbits,
            reserved_ports=[dataclasses.replace(p) for p in self.reserved_ports],
            dynamic_ports=[dataclasses.replace(p) for p in self.dynamic_ports],
        )

    def port_labels(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for p in self.reserved_ports:
            out[p.label] = p.value
        for p in self.dynamic_ports:
            out[p.label] = p.value
        return out


@dataclass(slots=True)
class RequestedDevice:
    """A device ask (reference: structs.go RequestedDevice :3035)."""

    name: str = ""  # e.g. "gpu", "nvidia/gpu", "nvidia/gpu/1080ti"
    count: int = 1
    constraints: list["Constraint"] = field(default_factory=list)
    affinities: list["Affinity"] = field(default_factory=list)

    def copy(self) -> "RequestedDevice":
        return RequestedDevice(
            name=self.name,
            count=self.count,
            constraints=[c.copy() for c in self.constraints],
            affinities=[a.copy() for a in self.affinities],
        )

    def id_tuple(self) -> tuple[str, ...]:
        """vendor/type/name triple, any suffix may be absent."""
        return tuple(self.name.split("/"))


@dataclass(slots=True)
class Resources:
    """A task's resource ask, flattened to the solver's core vector.

    Reference: structs.go Resources :2191. cpu is MHz shares, memory/disk MB.
    """

    cpu: int = 100
    memory_mb: int = 300
    # memory oversubscription (reference MemoryMaxMB, 1.1+): the cgroup
    # hard cap when the operator enables oversubscription; scheduling
    # still packs on memory_mb (the reserve). 0 = no excess.
    memory_max_mb: int = 0
    disk_mb: int = 0
    networks: list[NetworkResource] = field(default_factory=list)
    devices: list[RequestedDevice] = field(default_factory=list)
    cores: int = 0  # reserved whole cores (0 = share)

    def copy(self) -> "Resources":
        return Resources(
            cpu=self.cpu,
            memory_mb=self.memory_mb,
            memory_max_mb=self.memory_max_mb,
            disk_mb=self.disk_mb,
            networks=[n.copy() for n in self.networks],
            devices=[d.copy() for d in self.devices],
            cores=self.cores,
        )

    def vector(self) -> list[float]:
        return [float(self.cpu), float(self.memory_mb), float(self.disk_mb)]

    def add(self, other: "Resources") -> None:
        self.cpu += other.cpu
        self.memory_mb += other.memory_mb
        self.disk_mb += other.disk_mb
        self.networks.extend(n.copy() for n in other.networks)

    def superset(self, other: "Resources") -> tuple[bool, str]:
        if self.cpu < other.cpu:
            return False, "cpu"
        if self.memory_mb < other.memory_mb:
            return False, "memory"
        if self.disk_mb < other.disk_mb:
            return False, "disk"
        return True, ""

    def validate(self) -> None:
        if self.cpu < 0:
            raise ValueError("resources: cpu must be >= 0")
        if self.memory_mb < 0:
            raise ValueError("resources: memory must be >= 0")
        if self.memory_max_mb and self.memory_max_mb < self.memory_mb:
            raise ValueError(
                "resources: memory_max must be >= memory (the reserve)"
            )


@dataclass(slots=True)
class NodeDeviceInstance:
    id: str = ""
    healthy: bool = True
    locality: str = ""


@dataclass(slots=True)
class NodeDeviceResource:
    """A device group present on a node (reference: structs.go NodeDeviceResource :3230)."""

    vendor: str = ""
    type: str = ""
    name: str = ""
    instances: list[NodeDeviceInstance] = field(default_factory=list)
    attributes: dict[str, Any] = field(default_factory=dict)

    def copy(self) -> "NodeDeviceResource":
        return NodeDeviceResource(
            vendor=self.vendor,
            type=self.type,
            name=self.name,
            instances=[dataclasses.replace(i) for i in self.instances],
            attributes=dict(self.attributes),
        )

    def id_string(self) -> str:
        return f"{self.vendor}/{self.type}/{self.name}"

    def matches(self, ask: RequestedDevice) -> bool:
        parts = ask.id_tuple()
        if len(parts) == 1:
            return parts[0] == self.type
        if len(parts) == 2:
            return parts == (self.vendor, self.type)
        if len(parts) == 3:
            return parts == (self.vendor, self.type, self.name)
        return False


@dataclass(slots=True)
class NodeResources:
    """What a node offers (reference: structs.go NodeResources :2797)."""

    cpu: int = 4000
    memory_mb: int = 8192
    disk_mb: int = 100 * 1024
    networks: list[NetworkResource] = field(default_factory=list)
    devices: list[NodeDeviceResource] = field(default_factory=list)
    total_cores: int = 0

    def copy(self) -> "NodeResources":
        return NodeResources(
            cpu=self.cpu,
            memory_mb=self.memory_mb,
            disk_mb=self.disk_mb,
            networks=[n.copy() for n in self.networks],
            devices=[d.copy() for d in self.devices],
            total_cores=self.total_cores,
        )

    def vector(self) -> list[float]:
        return [float(self.cpu), float(self.memory_mb), float(self.disk_mb)]


@dataclass(slots=True)
class NodeReservedResources:
    """Resources the node holds back from scheduling (reference :2977)."""

    cpu: int = 0
    memory_mb: int = 0
    disk_mb: int = 0
    reserved_ports: list[int] = field(default_factory=list)

    def copy(self) -> "NodeReservedResources":
        return NodeReservedResources(
            cpu=self.cpu,
            memory_mb=self.memory_mb,
            disk_mb=self.disk_mb,
            reserved_ports=list(self.reserved_ports),
        )

    def vector(self) -> list[float]:
        return [float(self.cpu), float(self.memory_mb), float(self.disk_mb)]


# ---------------------------------------------------------------------------
# Constraints / affinities / spread
# ---------------------------------------------------------------------------


@dataclass(slots=True)
class Constraint:
    """Hard placement restriction (reference: structs.go Constraint :8262)."""

    ltarget: str = ""
    rtarget: str = ""
    operand: str = "="

    def copy(self) -> "Constraint":
        return Constraint(self.ltarget, self.rtarget, self.operand)

    def __str__(self) -> str:
        return f"{self.ltarget} {self.operand} {self.rtarget}"

    def key(self) -> tuple[str, str, str]:
        return (self.ltarget, self.operand, self.rtarget)

    def validate(self) -> None:
        if not self.operand:
            raise ValueError("constraint: missing operand")
        if self.operand in (CONSTRAINT_REGEX, CONSTRAINT_VERSION, CONSTRAINT_SEMVER):
            if not self.ltarget:
                raise ValueError(f"constraint: {self.operand} requires ltarget")
            if not self.rtarget:
                raise ValueError(f"constraint: {self.operand} requires rtarget")


@dataclass(slots=True)
class Affinity:
    """Soft placement preference with weight in [-100, 100] (reference :8382)."""

    ltarget: str = ""
    rtarget: str = ""
    operand: str = "="
    weight: int = 50

    def copy(self) -> "Affinity":
        return Affinity(self.ltarget, self.rtarget, self.operand, self.weight)

    def validate(self) -> None:
        if self.weight == 0:
            raise ValueError("affinity: weight cannot be zero")
        if not -100 <= self.weight <= 100:
            raise ValueError("affinity: weight must be within [-100, 100]")


@dataclass(slots=True)
class SpreadTarget:
    value: str = ""
    percent: int = 0


@dataclass(slots=True)
class Spread:
    """Spread allocs across attribute values (reference: structs.go Spread :8468)."""

    attribute: str = ""
    weight: int = 50
    targets: list[SpreadTarget] = field(default_factory=list)

    def copy(self) -> "Spread":
        return Spread(
            attribute=self.attribute,
            weight=self.weight,
            targets=[dataclasses.replace(t) for t in self.targets],
        )

    def validate(self) -> None:
        if not self.attribute:
            raise ValueError("spread: missing attribute")
        if not 0 < self.weight <= 100:
            raise ValueError("spread: weight must be within (0, 100]")
        total = sum(t.percent for t in self.targets)
        if total > 100:
            raise ValueError("spread: target percentages sum over 100")


# ---------------------------------------------------------------------------
# Policies (restart / reschedule / update / migrate / ephemeral disk)
# ---------------------------------------------------------------------------


@dataclass(slots=True)
class RestartPolicy:
    """Client-side restart policy (reference: structs.go RestartPolicy :4602)."""

    attempts: int = 2
    interval_s: float = 1800.0
    delay_s: float = 15.0
    mode: str = RESTART_POLICY_MODE_FAIL

    def copy(self) -> "RestartPolicy":
        return dataclasses.replace(self)


@dataclass(slots=True)
class ReschedulePolicy:
    """Server-side reschedule policy (reference: structs.go ReschedulePolicy :4672)."""

    attempts: int = 0
    interval_s: float = 0.0
    delay_s: float = 30.0
    delay_function: str = "exponential"  # constant | exponential | fibonacci
    max_delay_s: float = 3600.0
    unlimited: bool = True

    def copy(self) -> "ReschedulePolicy":
        return dataclasses.replace(self)

    def enabled(self) -> bool:
        return self.unlimited or (self.attempts > 0 and self.interval_s > 0)


@dataclass(slots=True)
class UpdateStrategy:
    """Rolling-update / deployment strategy (reference: structs.go :4369)."""

    stagger_s: float = 30.0
    max_parallel: int = 1
    health_check: str = "checks"  # checks | task_states | manual
    min_healthy_time_s: float = 10.0
    healthy_deadline_s: float = 300.0
    progress_deadline_s: float = 600.0
    auto_revert: bool = False
    auto_promote: bool = False
    canary: int = 0

    def copy(self) -> "UpdateStrategy":
        return dataclasses.replace(self)

    def rolling(self) -> bool:
        return self.stagger_s > 0 and self.max_parallel > 0

    def requires_promotion(self) -> bool:
        return self.canary > 0 and not self.auto_promote


@dataclass(slots=True)
class MigrateStrategy:
    """Drain migration rate limits (reference: structs.go MigrateStrategy :4527)."""

    max_parallel: int = 1
    health_check: str = "checks"
    min_healthy_time_s: float = 10.0
    healthy_deadline_s: float = 300.0

    def copy(self) -> "MigrateStrategy":
        return dataclasses.replace(self)


@dataclass(slots=True)
class EphemeralDisk:
    sticky: bool = False
    size_mb: int = 300
    migrate: bool = False

    def copy(self) -> "EphemeralDisk":
        return dataclasses.replace(self)


@dataclass(slots=True)
class PeriodicConfig:
    """Cron-style launch config (reference: structs.go PeriodicConfig :4862)."""

    enabled: bool = False
    spec: str = ""
    spec_type: str = "cron"
    prohibit_overlap: bool = False
    timezone: str = "UTC"

    def copy(self) -> "PeriodicConfig":
        return dataclasses.replace(self)


@dataclass(slots=True)
class ParameterizedJobConfig:
    """Dispatch-job config (reference: structs.go ParameterizedJobConfig :5095)."""

    payload: str = "optional"  # optional | required | forbidden
    meta_required: list[str] = field(default_factory=list)
    meta_optional: list[str] = field(default_factory=list)

    def copy(self) -> "ParameterizedJobConfig":
        return ParameterizedJobConfig(
            payload=self.payload,
            meta_required=list(self.meta_required),
            meta_optional=list(self.meta_optional),
        )


@dataclass(slots=True)
class VolumeRequest:
    """Group-level volume ask (reference: structs.go VolumeRequest :7162)."""

    name: str = ""
    type: str = "host"  # host | csi
    source: str = ""
    read_only: bool = False
    access_mode: str = ""
    attachment_mode: str = ""
    per_alloc: bool = False

    def copy(self) -> "VolumeRequest":
        return dataclasses.replace(self)


@dataclass(slots=True)
class VolumeMount:
    """Task-level mount of a group volume into the task filesystem
    (reference: structs.go VolumeMount :7263)."""

    volume: str = ""
    destination: str = ""
    read_only: bool = False
    propagation_mode: str = "private"

    def copy(self) -> "VolumeMount":
        return dataclasses.replace(self)


@dataclass(slots=True)
class ConnectUpstream:
    """One mesh upstream a sidecar exposes locally (reference:
    structs.go ConsulUpstream :8210)."""

    destination_name: str = ""
    local_bind_port: int = 0

    def copy(self) -> "ConnectUpstream":
        return dataclasses.replace(self)


@dataclass(slots=True)
class SidecarService:
    """connect { sidecar_service { ... } } (reference: structs.go
    ConsulSidecarService :8080)."""

    port: str = ""  # explicit sidecar port label; default injected
    upstreams: list[ConnectUpstream] = field(default_factory=list)

    def copy(self) -> "SidecarService":
        return SidecarService(
            port=self.port,
            upstreams=[u.copy() for u in self.upstreams],
        )


@dataclass(slots=True)
class Connect:
    """The service-mesh stanza (reference: structs.go ConsulConnect
    :8016). `native=True` means the workload speaks mesh natively and
    only wants the catalog registration, no sidecar."""

    sidecar_service: Optional[SidecarService] = None
    native: bool = False

    def copy(self) -> "Connect":
        return Connect(
            sidecar_service=(
                self.sidecar_service.copy()
                if self.sidecar_service is not None
                else None
            ),
            native=self.native,
        )


@dataclass(slots=True)
class Service:
    """Service registration (reference: structs.go Service :7582)."""

    name: str = ""
    port_label: str = ""
    address_mode: str = "auto"
    tags: list[str] = field(default_factory=list)
    checks: list[dict[str, Any]] = field(default_factory=list)
    provider: str = "builtin"
    connect: Optional[Connect] = None

    def copy(self) -> "Service":
        return Service(
            name=self.name,
            port_label=self.port_label,
            address_mode=self.address_mode,
            tags=list(self.tags),
            checks=[dict(c) for c in self.checks],
            provider=self.provider,
            connect=self.connect.copy() if self.connect is not None else None,
        )


@dataclass(slots=True)
class ScalingPolicy:
    """A group's scaling bounds + opaque autoscaler policy (reference:
    structs.go ScalingPolicy :5397 — stored and served by the cluster;
    the autoscaler itself is an external consumer)."""

    id: str = ""
    type: str = "horizontal"
    namespace: str = DEFAULT_NAMESPACE
    job_id: str = ""
    group: str = ""
    min: int = 0
    max: int = 0
    enabled: bool = True
    policy: dict[str, Any] = field(default_factory=dict)
    create_index: int = 0
    modify_index: int = 0

    def copy(self) -> "ScalingPolicy":
        c = dataclasses.replace(self)
        c.policy = dict(self.policy)
        return c


@dataclass(slots=True)
class SecretEntry:
    """A namespaced secret document in the cluster's embedded secrets
    store (the tpu-native stand-in for the reference's external Vault:
    nomad/vault.go talks to a Vault server; here the KV rides raft and
    task tokens are scoped ACL tokens — same derive/renew/revoke
    lifecycle, no external daemon)."""

    path: str = ""
    namespace: str = DEFAULT_NAMESPACE
    items: dict[str, str] = field(default_factory=dict)
    create_index: int = 0
    modify_index: int = 0

    def copy(self) -> "SecretEntry":
        c = dataclasses.replace(self)
        c.items = dict(self.items)
        return c


@dataclass(slots=True)
class ServiceRegistration:
    """One task/group service instance registered in the cluster catalog
    (reference: structs/service_registration.go — the native
    service-discovery provider; the tree's consul sync is the external
    analog, command/agent/consul/service_client.go)."""

    id: str = ""
    service_name: str = ""
    namespace: str = DEFAULT_NAMESPACE
    node_id: str = ""
    datacenter: str = ""
    job_id: str = ""
    alloc_id: str = ""
    task_name: str = ""
    tags: list[str] = field(default_factory=list)
    address: str = ""
    port: int = 0
    # aggregate check verdict pushed by the owning client's check watcher
    # ("passing" | "critical" | "" when the service has no checks)
    status: str = ""
    create_index: int = 0
    modify_index: int = 0

    def copy(self) -> "ServiceRegistration":
        c = dataclasses.replace(self)
        c.tags = list(self.tags)
        return c


@dataclass(slots=True)
class LogConfig:
    max_files: int = 10
    max_file_size_mb: int = 10

    def copy(self) -> "LogConfig":
        return dataclasses.replace(self)


@dataclass(slots=True)
class TaskArtifact:
    getter_source: str = ""
    getter_options: dict[str, str] = field(default_factory=dict)
    getter_mode: str = "any"
    relative_dest: str = "local/"

    def copy(self) -> "TaskArtifact":
        return TaskArtifact(
            getter_source=self.getter_source,
            getter_options=dict(self.getter_options),
            getter_mode=self.getter_mode,
            relative_dest=self.relative_dest,
        )


@dataclass(slots=True)
class Template:
    source_path: str = ""
    dest_path: str = ""
    embedded_tmpl: str = ""
    change_mode: str = "restart"
    change_signal: str = ""
    splay_s: float = 5.0
    perms: str = "0644"

    def copy(self) -> "Template":
        return dataclasses.replace(self)


@dataclass(slots=True)
class TaskLifecycleConfig:
    hook: str = ""  # prestart | poststart | poststop
    sidecar: bool = False

    def copy(self) -> "TaskLifecycleConfig":
        return dataclasses.replace(self)


# ---------------------------------------------------------------------------
# Task / TaskGroup / Job
# ---------------------------------------------------------------------------


@dataclass(slots=True)
class Task:
    """A unit of work executed by a driver (reference: structs.go Task :6652)."""

    name: str = ""
    driver: str = "mock"
    user: str = ""
    config: dict[str, Any] = field(default_factory=dict)
    env: dict[str, str] = field(default_factory=dict)
    services: list[Service] = field(default_factory=list)
    resources: Resources = field(default_factory=Resources)
    meta: dict[str, str] = field(default_factory=dict)
    constraints: list[Constraint] = field(default_factory=list)
    affinities: list[Affinity] = field(default_factory=list)
    artifacts: list[TaskArtifact] = field(default_factory=list)
    templates: list[Template] = field(default_factory=list)
    log_config: LogConfig = field(default_factory=LogConfig)
    volume_mounts: list[VolumeMount] = field(default_factory=list)
    # vault stanza analog (reference structs.go Vault :7800): policies
    # scope the task's derived secrets token; env controls VAULT_TOKEN
    vault: Optional[dict] = None
    kill_timeout_s: float = 5.0
    kill_signal: str = ""
    leader: bool = False
    lifecycle: Optional[TaskLifecycleConfig] = None
    shutdown_delay_s: float = 0.0

    def copy(self) -> "Task":
        return Task(
            name=self.name,
            driver=self.driver,
            user=self.user,
            config=dict(self.config),
            env=dict(self.env),
            services=[s.copy() for s in self.services],
            resources=self.resources.copy(),
            meta=dict(self.meta),
            constraints=[c.copy() for c in self.constraints],
            affinities=[a.copy() for a in self.affinities],
            artifacts=[a.copy() for a in self.artifacts],
            templates=[t.copy() for t in self.templates],
            log_config=self.log_config.copy(),
            volume_mounts=[m.copy() for m in self.volume_mounts],
            vault=dict(self.vault) if self.vault else None,
            kill_timeout_s=self.kill_timeout_s,
            kill_signal=self.kill_signal,
            leader=self.leader,
            lifecycle=self.lifecycle.copy() if self.lifecycle else None,
            shutdown_delay_s=self.shutdown_delay_s,
        )

    def validate(self, job_type: str = JOB_TYPE_SERVICE) -> None:
        if not self.name:
            raise ValueError("task: missing name")
        if "/" in self.name or "\\" in self.name:
            raise ValueError("task: name cannot contain slashes")
        if not self.driver:
            raise ValueError(f"task {self.name}: missing driver")
        self.resources.validate()
        for c in self.constraints:
            c.validate()
        for a in self.affinities:
            a.validate()
        for svc in self.services:
            for check in svc.checks:
                if check.get("type") == "script" and not check.get(
                    "command"
                ):
                    raise ValueError(
                        f"task {self.name}: script check on service "
                        f"{svc.name!r} requires a command"
                    )

    def is_prestart(self) -> bool:
        return self.lifecycle is not None and self.lifecycle.hook == "prestart"

    def is_poststart(self) -> bool:
        return self.lifecycle is not None and self.lifecycle.hook == "poststart"

    def is_poststop(self) -> bool:
        return self.lifecycle is not None and self.lifecycle.hook == "poststop"

    def is_main(self) -> bool:
        return self.lifecycle is None


@dataclass(slots=True)
class TaskGroup:
    """A co-scheduled set of tasks (reference: structs.go TaskGroup :5923)."""

    name: str = ""
    count: int = 1
    tasks: list[Task] = field(default_factory=list)
    constraints: list[Constraint] = field(default_factory=list)
    affinities: list[Affinity] = field(default_factory=list)
    spreads: list[Spread] = field(default_factory=list)
    restart_policy: RestartPolicy = field(default_factory=RestartPolicy)
    reschedule_policy: Optional[ReschedulePolicy] = None
    update: Optional[UpdateStrategy] = None
    migrate: Optional[MigrateStrategy] = None
    networks: list[NetworkResource] = field(default_factory=list)
    services: list[Service] = field(default_factory=list)
    volumes: dict[str, VolumeRequest] = field(default_factory=dict)
    ephemeral_disk: EphemeralDisk = field(default_factory=EphemeralDisk)
    meta: dict[str, str] = field(default_factory=dict)
    # scaling stanza (reference TaskGroup.Scaling): bounds + opaque
    # autoscaler policy; None = group not scalable
    scaling: Optional[ScalingPolicy] = None
    stop_after_client_disconnect_s: float = 0.0
    shutdown_delay_s: float = 0.0

    def copy(self) -> "TaskGroup":
        return TaskGroup(
            name=self.name,
            count=self.count,
            tasks=[t.copy() for t in self.tasks],
            constraints=[c.copy() for c in self.constraints],
            affinities=[a.copy() for a in self.affinities],
            spreads=[s.copy() for s in self.spreads],
            restart_policy=self.restart_policy.copy(),
            reschedule_policy=(
                self.reschedule_policy.copy() if self.reschedule_policy else None
            ),
            update=self.update.copy() if self.update else None,
            migrate=self.migrate.copy() if self.migrate else None,
            networks=[n.copy() for n in self.networks],
            services=[s.copy() for s in self.services],
            volumes={k: v.copy() for k, v in self.volumes.items()},
            ephemeral_disk=self.ephemeral_disk.copy(),
            meta=dict(self.meta),
            scaling=self.scaling.copy() if self.scaling else None,
            stop_after_client_disconnect_s=self.stop_after_client_disconnect_s,
            shutdown_delay_s=self.shutdown_delay_s,
        )

    def lookup_task(self, name: str) -> Optional[Task]:
        for t in self.tasks:
            if t.name == name:
                return t
        return None

    def combined_resources(self) -> Resources:
        """Sum of task asks plus ephemeral disk, for solver lowering."""
        total = Resources(cpu=0, memory_mb=0, disk_mb=0)
        for t in self.tasks:
            total.cpu += t.resources.cpu
            total.memory_mb += t.resources.memory_mb
        total.disk_mb = self.ephemeral_disk.size_mb
        return total

    def validate(self, job: "Job") -> None:
        if not self.name:
            raise ValueError("task group: missing name")
        if self.count < 0:
            raise ValueError(f"group {self.name}: count must be >= 0")
        if not self.tasks:
            raise ValueError(f"group {self.name}: missing tasks")
        names = set()
        for t in self.tasks:
            if t.name in names:
                raise ValueError(f"group {self.name}: duplicate task {t.name}")
            names.add(t.name)
            t.validate(job.type)
        for c in self.constraints:
            c.validate()
        for s in self.spreads:
            s.validate()
        leaders = sum(1 for t in self.tasks if t.leader)
        if leaders > 1:
            raise ValueError(f"group {self.name}: only one task may be leader")
        for svc in self.services:
            for check in svc.checks:
                if check.get("type") == "script":
                    if not check.get("command"):
                        raise ValueError(
                            f"group {self.name}: script check on "
                            f"service {svc.name!r} requires a command"
                        )
                    target = check.get("task", "")
                    if not target:
                        raise ValueError(
                            f"group {self.name}: script check on group "
                            f"service {svc.name!r} requires a task field"
                        )
                    if target not in names:
                        raise ValueError(
                            f"group {self.name}: script check on "
                            f"service {svc.name!r} names unknown task "
                            f"{target!r}"
                        )


@dataclass(slots=True)
class Job:
    """The user-submitted unit of intent (reference: structs.go Job :3958)."""

    id: str = ""
    name: str = ""
    namespace: str = DEFAULT_NAMESPACE
    region: str = "global"
    type: str = JOB_TYPE_SERVICE
    priority: int = JOB_DEFAULT_PRIORITY
    all_at_once: bool = False
    datacenters: list[str] = field(default_factory=lambda: ["dc1"])
    constraints: list[Constraint] = field(default_factory=list)
    affinities: list[Affinity] = field(default_factory=list)
    spreads: list[Spread] = field(default_factory=list)
    task_groups: list[TaskGroup] = field(default_factory=list)
    update: Optional[UpdateStrategy] = None
    periodic: Optional[PeriodicConfig] = None
    parameterized: Optional[ParameterizedJobConfig] = None
    dispatched: bool = False
    payload: bytes = b""
    meta: dict[str, str] = field(default_factory=dict)
    vault_token: str = ""
    stop: bool = False
    parent_id: str = ""
    status: str = JOB_STATUS_PENDING
    status_description: str = ""
    stable: bool = False
    version: int = 0
    submit_time: int = 0
    create_index: int = 0
    modify_index: int = 0
    job_modify_index: int = 0

    def copy(self) -> "Job":
        return Job(
            id=self.id,
            name=self.name,
            namespace=self.namespace,
            region=self.region,
            type=self.type,
            priority=self.priority,
            all_at_once=self.all_at_once,
            datacenters=list(self.datacenters),
            constraints=[c.copy() for c in self.constraints],
            affinities=[a.copy() for a in self.affinities],
            spreads=[s.copy() for s in self.spreads],
            task_groups=[tg.copy() for tg in self.task_groups],
            update=self.update.copy() if self.update else None,
            periodic=self.periodic.copy() if self.periodic else None,
            parameterized=self.parameterized.copy() if self.parameterized else None,
            dispatched=self.dispatched,
            payload=self.payload,
            meta=dict(self.meta),
            vault_token=self.vault_token,
            stop=self.stop,
            parent_id=self.parent_id,
            status=self.status,
            status_description=self.status_description,
            stable=self.stable,
            version=self.version,
            submit_time=self.submit_time,
            create_index=self.create_index,
            modify_index=self.modify_index,
            job_modify_index=self.job_modify_index,
        )

    def canonicalize(self) -> None:
        if not self.name:
            self.name = self.id
        if not self.namespace:
            self.namespace = DEFAULT_NAMESPACE
        if not self.submit_time:
            self.submit_time = now_ns()
        for tg in self.task_groups:
            if tg.reschedule_policy is None and self.type in (
                JOB_TYPE_SERVICE,
                JOB_TYPE_BATCH,
            ):
                if self.type == JOB_TYPE_SERVICE:
                    tg.reschedule_policy = ReschedulePolicy(
                        attempts=0,
                        interval_s=0,
                        delay_s=30,
                        delay_function="exponential",
                        max_delay_s=3600,
                        unlimited=True,
                    )
                else:
                    tg.reschedule_policy = ReschedulePolicy(
                        attempts=1,
                        interval_s=24 * 3600,
                        delay_s=5,
                        delay_function="constant",
                        max_delay_s=0,
                        unlimited=False,
                    )
            if tg.update is None and self.update is not None:
                tg.update = self.update.copy()

    def validate(self) -> None:
        if not self.id:
            raise ValueError("job: missing ID")
        if " " in self.id:
            raise ValueError("job: ID contains a space")
        if not self.name:
            raise ValueError("job: missing name")
        if self.type not in (
            JOB_TYPE_CORE,
            JOB_TYPE_SERVICE,
            JOB_TYPE_BATCH,
            JOB_TYPE_SYSTEM,
            JOB_TYPE_SYSBATCH,
        ):
            raise ValueError(f"job: invalid type {self.type!r}")
        max_priority = CORE_JOB_PRIORITY if self.type == JOB_TYPE_CORE else JOB_MAX_PRIORITY
        if not JOB_MIN_PRIORITY <= self.priority <= max_priority:
            raise ValueError(
                f"job: priority must be within [{JOB_MIN_PRIORITY}, {max_priority}]"
            )
        if not self.datacenters:
            raise ValueError("job: missing datacenters")
        if not self.task_groups:
            raise ValueError("job: missing task groups")
        names = set()
        for tg in self.task_groups:
            if tg.name in names:
                raise ValueError(f"job: duplicate task group {tg.name}")
            names.add(tg.name)
            tg.validate(self)
        for c in self.constraints:
            c.validate()
        if self.type == JOB_TYPE_SYSTEM and any(
            tg.reschedule_policy and tg.reschedule_policy.enabled()
            for tg in self.task_groups
        ):
            raise ValueError("job: system jobs cannot have a reschedule policy")

    def lookup_task_group(self, name: str) -> Optional[TaskGroup]:
        for tg in self.task_groups:
            if tg.name == name:
                return tg
        return None

    def stopped(self) -> bool:
        return self.stop

    def is_periodic(self) -> bool:
        return self.periodic is not None and self.periodic.enabled

    def is_parameterized(self) -> bool:
        return self.parameterized is not None and not self.dispatched

    def ns_id(self) -> tuple[str, str]:
        return (self.namespace, self.id)

    def specification_changed(self, other: "Job") -> bool:
        """True when the job definition differs in a scheduling-relevant way.

        Mirrors the reference's Job.SpecChanged (structs.go:4189): compare
        everything except bookkeeping fields.
        """
        a, b = self.copy(), other.copy()
        for j in (a, b):
            j.status = ""
            j.status_description = ""
            j.stable = False
            j.version = 0
            j.submit_time = 0
            j.create_index = 0
            j.modify_index = 0
            j.job_modify_index = 0
            j.vault_token = ""
        return a != b


# ---------------------------------------------------------------------------
# Node
# ---------------------------------------------------------------------------


@dataclass(slots=True)
class DrainStrategy:
    """Node drain spec (reference: structs.go DrainStrategy :1710)."""

    deadline_s: float = 0.0  # <=0: no deadline; -1 means force
    ignore_system_jobs: bool = False
    force_deadline_ns: int = 0

    def copy(self) -> "DrainStrategy":
        return dataclasses.replace(self)

    def deadline_expired(self) -> bool:
        return (
            self.force_deadline_ns > 0 and now_ns() >= self.force_deadline_ns
        ) or self.deadline_s < 0


@dataclass(slots=True)
class NodeEvent:
    message: str = ""
    subsystem: str = "Cluster"
    details: dict[str, str] = field(default_factory=dict)
    timestamp_ns: int = 0


@dataclass(slots=True)
class HostVolumeConfig:
    name: str = ""
    path: str = ""
    read_only: bool = False


@dataclass(slots=True)
class Namespace:
    """A namespace record (reference: structs.go Namespace :5971 — OSS
    since 1.0; jobs/volumes register INTO one and ACL policies scope
    capabilities BY one)."""

    name: str = ""
    description: str = ""
    create_index: int = 0
    modify_index: int = 0

    def copy(self) -> "Namespace":
        return dataclasses.replace(self)

    def validate(self) -> None:
        import re as _re

        if not _re.fullmatch(r"[a-zA-Z0-9-]{1,128}", self.name or ""):
            raise ValueError(
                f"invalid namespace name {self.name!r} "
                "(alphanumeric and dashes, 1-128 chars)"
            )


VOLUME_ACCESS_SINGLE_WRITER = "single-node-writer"
VOLUME_ACCESS_MULTI_WRITER = "multi-node-multi-writer"
VOLUME_ACCESS_READ_ONLY = "multi-node-reader-only"


@dataclass(slots=True)
class VolumeClaim:
    """One alloc's hold on a registered volume."""

    alloc_id: str = ""
    node_id: str = ""
    read_only: bool = False
    create_index: int = 0


@dataclass(slots=True)
class Volume:
    """A cluster-registered volume (reference: the CSIVolume table,
    nomad/structs/csi.go, reshaped for host volumes — the claim/release
    lifecycle is the part that matters for parity; see
    nomad/volumewatcher/volumes_watcher.go)."""

    id: str = ""
    namespace: str = DEFAULT_NAMESPACE
    name: str = ""  # the group volume.source this volume satisfies
    type: str = "host"  # host | csi
    node_id: str = ""  # host volumes live on one node ("" = any)
    path: str = ""
    access_mode: str = VOLUME_ACCESS_MULTI_WRITER
    # CSI-only fields (reference: nomad/structs/csi.go CSIVolume)
    plugin_id: str = ""
    external_id: str = ""
    attachment_mode: str = "file-system"
    context: dict[str, str] = field(default_factory=dict)
    claims: dict[str, VolumeClaim] = field(default_factory=dict)
    create_index: int = 0
    modify_index: int = 0

    def copy(self) -> "Volume":
        c = dataclasses.replace(self)
        c.context = dict(self.context)
        c.claims = {k: dataclasses.replace(v) for k, v in self.claims.items()}
        return c

    def write_claims(self) -> list[VolumeClaim]:
        return [c for c in self.claims.values() if not c.read_only]

    def claimable(self, read_only: bool) -> tuple[bool, str]:
        """May a new claim of the given mode attach?"""
        if self.access_mode == VOLUME_ACCESS_READ_ONLY and not read_only:
            return False, "volume is read-only"
        if (
            self.access_mode == VOLUME_ACCESS_SINGLE_WRITER
            and not read_only
            and self.write_claims()
        ):
            return False, "volume has an active writer"
        return True, ""


@dataclass(slots=True)
class Node:
    """A fingerprinted machine (reference: structs.go Node :1812)."""

    id: str = ""
    name: str = ""
    datacenter: str = "dc1"
    node_class: str = ""
    attributes: dict[str, str] = field(default_factory=dict)
    meta: dict[str, str] = field(default_factory=dict)
    resources: NodeResources = field(default_factory=NodeResources)
    reserved: NodeReservedResources = field(default_factory=NodeReservedResources)
    host_volumes: dict[str, HostVolumeConfig] = field(default_factory=dict)
    # CSI plugins fingerprinted on this node: plugin_id -> info dict
    # (version/healthy/controller/node; reference: Node.CSINodePlugins)
    csi_plugins: dict[str, dict] = field(default_factory=dict)
    links: dict[str, str] = field(default_factory=dict)
    drivers: dict[str, "DriverInfo"] = field(default_factory=dict)
    status: str = NODE_STATUS_INIT
    status_description: str = ""
    scheduling_eligibility: str = NODE_SCHEDULING_ELIGIBLE
    drain_strategy: Optional[DrainStrategy] = None
    computed_class: str = ""
    events: list[NodeEvent] = field(default_factory=list)
    http_addr: str = ""
    secret_id: str = ""
    status_updated_at: int = 0
    create_index: int = 0
    modify_index: int = 0

    def copy(self) -> "Node":
        return Node(
            id=self.id,
            name=self.name,
            datacenter=self.datacenter,
            node_class=self.node_class,
            attributes=dict(self.attributes),
            meta=dict(self.meta),
            resources=self.resources.copy(),
            reserved=self.reserved.copy(),
            host_volumes={k: dataclasses.replace(v) for k, v in self.host_volumes.items()},
            csi_plugins={k: dict(v) for k, v in self.csi_plugins.items()},
            links=dict(self.links),
            drivers={k: v.copy() for k, v in self.drivers.items()},
            status=self.status,
            status_description=self.status_description,
            scheduling_eligibility=self.scheduling_eligibility,
            drain_strategy=self.drain_strategy.copy() if self.drain_strategy else None,
            computed_class=self.computed_class,
            events=[dataclasses.replace(e, details=dict(e.details)) for e in self.events],
            http_addr=self.http_addr,
            secret_id=self.secret_id,
            status_updated_at=self.status_updated_at,
            create_index=self.create_index,
            modify_index=self.modify_index,
        )

    @property
    def drain(self) -> bool:
        return self.drain_strategy is not None

    def ready(self) -> bool:
        return (
            self.status == NODE_STATUS_READY
            and not self.drain
            and self.scheduling_eligibility == NODE_SCHEDULING_ELIGIBLE
        )

    def canonicalize(self) -> None:
        if self.drain_strategy is not None:
            self.scheduling_eligibility = NODE_SCHEDULING_INELIGIBLE
        elif not self.scheduling_eligibility:
            self.scheduling_eligibility = NODE_SCHEDULING_ELIGIBLE

    def terminal_status(self) -> bool:
        return self.status == NODE_STATUS_DOWN

    def available_resources(self) -> Resources:
        """node resources minus reserved, as the solver's capacity vector."""
        return Resources(
            cpu=self.resources.cpu - self.reserved.cpu,
            memory_mb=self.resources.memory_mb - self.reserved.memory_mb,
            disk_mb=self.resources.disk_mb - self.reserved.disk_mb,
        )


@dataclass(slots=True)
class DriverInfo:
    attributes: dict[str, str] = field(default_factory=dict)
    detected: bool = False
    healthy: bool = False
    health_description: str = ""
    update_time_ns: int = 0

    def copy(self) -> "DriverInfo":
        return DriverInfo(
            attributes=dict(self.attributes),
            detected=self.detected,
            healthy=self.healthy,
            health_description=self.health_description,
            update_time_ns=self.update_time_ns,
        )


# ---------------------------------------------------------------------------
# Allocation
# ---------------------------------------------------------------------------


@dataclass(slots=True)
class AllocMetric:
    """Placement decision metadata (reference: structs.go AllocMetric :9826)."""

    nodes_evaluated: int = 0
    nodes_filtered: int = 0
    nodes_available: dict[str, int] = field(default_factory=dict)  # per DC
    class_filtered: dict[str, int] = field(default_factory=dict)
    constraint_filtered: dict[str, int] = field(default_factory=dict)
    nodes_exhausted: int = 0
    class_exhausted: dict[str, int] = field(default_factory=dict)
    dimension_exhausted: dict[str, int] = field(default_factory=dict)
    quota_exhausted: list[str] = field(default_factory=list)
    scores: dict[str, float] = field(default_factory=dict)  # node.scorer -> score
    allocation_time_ns: int = 0
    coalesced_failures: int = 0

    def copy(self) -> "AllocMetric":
        return AllocMetric(
            nodes_evaluated=self.nodes_evaluated,
            nodes_filtered=self.nodes_filtered,
            nodes_available=dict(self.nodes_available),
            class_filtered=dict(self.class_filtered),
            constraint_filtered=dict(self.constraint_filtered),
            nodes_exhausted=self.nodes_exhausted,
            class_exhausted=dict(self.class_exhausted),
            dimension_exhausted=dict(self.dimension_exhausted),
            quota_exhausted=list(self.quota_exhausted),
            scores=dict(self.scores),
            allocation_time_ns=self.allocation_time_ns,
            coalesced_failures=self.coalesced_failures,
        )

    def exhausted_node(self, node: Node, dimension: str) -> None:
        self.nodes_exhausted += 1
        if node.computed_class:
            self.class_exhausted[node.computed_class] = (
                self.class_exhausted.get(node.computed_class, 0) + 1
            )
        if dimension:
            self.dimension_exhausted[dimension] = (
                self.dimension_exhausted.get(dimension, 0) + 1
            )

    def filter_node(self, node: Optional[Node], constraint: str) -> None:
        self.nodes_filtered += 1
        if node is not None and node.computed_class:
            self.class_filtered[node.computed_class] = (
                self.class_filtered.get(node.computed_class, 0) + 1
            )
        if constraint:
            self.constraint_filtered[constraint] = (
                self.constraint_filtered.get(constraint, 0) + 1
            )

    def score_node(self, node_id: str, scorer: str, score: float) -> None:
        self.scores[f"{node_id}.{scorer}"] = score


@dataclass(slots=True)
class RescheduleEvent:
    reschedule_time_ns: int = 0
    prev_alloc_id: str = ""
    prev_node_id: str = ""
    delay_s: float = 0.0


@dataclass(slots=True)
class RescheduleTracker:
    events: list[RescheduleEvent] = field(default_factory=list)

    def copy(self) -> "RescheduleTracker":
        return RescheduleTracker(events=[dataclasses.replace(e) for e in self.events])


@dataclass(slots=True)
class DesiredTransition:
    """Server-instructed transitions (reference: structs.go DesiredTransition :9042)."""

    migrate: Optional[bool] = None
    reschedule: Optional[bool] = None
    force_reschedule: Optional[bool] = None

    def copy(self) -> "DesiredTransition":
        return dataclasses.replace(self)

    def should_migrate(self) -> bool:
        return bool(self.migrate)

    def should_force_reschedule(self) -> bool:
        return bool(self.force_reschedule)


@dataclass(slots=True)
class TaskState:
    state: str = "pending"  # pending | running | dead
    failed: bool = False
    restarts: int = 0
    started_at_ns: int = 0
    finished_at_ns: int = 0
    last_restart_ns: int = 0
    events: list[dict[str, Any]] = field(default_factory=list)

    def copy(self) -> "TaskState":
        return TaskState(
            state=self.state,
            failed=self.failed,
            restarts=self.restarts,
            started_at_ns=self.started_at_ns,
            finished_at_ns=self.finished_at_ns,
            last_restart_ns=self.last_restart_ns,
            events=[dict(e) for e in self.events],
        )

    def successful(self) -> bool:
        return self.state == "dead" and not self.failed


@dataclass(slots=True)
class AllocDeploymentStatus:
    healthy: Optional[bool] = None
    timestamp_ns: int = 0
    canary: bool = False
    modify_index: int = 0

    def copy(self) -> "AllocDeploymentStatus":
        return dataclasses.replace(self)

    def is_healthy(self) -> bool:
        return self.healthy is True

    def is_unhealthy(self) -> bool:
        return self.healthy is False


@dataclass(slots=True)
class AllocNetworkStatus:
    interface_name: str = ""
    address: str = ""
    dns: dict[str, Any] = field(default_factory=dict)


@dataclass(slots=True)
class AllocatedTaskResources:
    cpu: int = 0
    memory_mb: int = 0
    networks: list[NetworkResource] = field(default_factory=list)
    devices: list[dict[str, Any]] = field(default_factory=list)
    # dedicated core ids granted for a `cores` ask (reference
    # structs.go AllocatedCpuResources.ReservedCores): disjoint across
    # every alloc on the node; cpu above holds the DERIVED MHz
    reserved_cores: list[int] = field(default_factory=list)

    def copy(self) -> "AllocatedTaskResources":
        return AllocatedTaskResources(
            cpu=self.cpu,
            memory_mb=self.memory_mb,
            networks=[n.copy() for n in self.networks],
            devices=[dict(d) for d in self.devices],
            reserved_cores=list(self.reserved_cores),
        )


@dataclass(slots=True)
class AllocatedResources:
    """Resources actually granted to an alloc (reference: structs.go :3609)."""

    tasks: dict[str, AllocatedTaskResources] = field(default_factory=dict)
    shared_disk_mb: int = 0
    shared_networks: list[NetworkResource] = field(default_factory=list)

    def copy(self) -> "AllocatedResources":
        return AllocatedResources(
            tasks={k: v.copy() for k, v in self.tasks.items()},
            shared_disk_mb=self.shared_disk_mb,
            shared_networks=[n.copy() for n in self.shared_networks],
        )

    def comparable(self) -> Resources:
        total = Resources(cpu=0, memory_mb=0, disk_mb=self.shared_disk_mb)
        for tr in self.tasks.values():
            total.cpu += tr.cpu
            total.memory_mb += tr.memory_mb
        return total


@dataclass(slots=True)
class Allocation:
    """A placement of a task group on a node (reference: structs.go Allocation :9110)."""

    id: str = ""
    namespace: str = DEFAULT_NAMESPACE
    eval_id: str = ""
    name: str = ""  # jobid.group[index]
    node_id: str = ""
    node_name: str = ""
    job_id: str = ""
    job: Optional[Job] = None
    task_group: str = ""
    resources: Optional[AllocatedResources] = None
    desired_status: str = ALLOC_DESIRED_STATUS_RUN
    desired_description: str = ""
    desired_transition: DesiredTransition = field(default_factory=DesiredTransition)
    client_status: str = ALLOC_CLIENT_STATUS_PENDING
    client_description: str = ""
    task_states: dict[str, TaskState] = field(default_factory=dict)
    deployment_id: str = ""
    deployment_status: Optional[AllocDeploymentStatus] = None
    reschedule_tracker: Optional[RescheduleTracker] = None
    network_status: Optional[AllocNetworkStatus] = None
    followup_eval_id: str = ""
    previous_allocation: str = ""
    next_allocation: str = ""
    metrics: AllocMetric = field(default_factory=AllocMetric)
    preempted_by_allocation: str = ""
    preempted_allocations: list[str] = field(default_factory=list)
    create_index: int = 0
    modify_index: int = 0
    alloc_modify_index: int = 0
    create_time: int = 0
    modify_time: int = 0

    def copy(self, keep_job: bool = True) -> "Allocation":
        return Allocation(
            id=self.id,
            namespace=self.namespace,
            eval_id=self.eval_id,
            name=self.name,
            node_id=self.node_id,
            node_name=self.node_name,
            job_id=self.job_id,
            job=self.job if keep_job else None,  # jobs are immutable once stored
            task_group=self.task_group,
            resources=self.resources.copy() if self.resources else None,
            desired_status=self.desired_status,
            desired_description=self.desired_description,
            desired_transition=self.desired_transition.copy(),
            client_status=self.client_status,
            client_description=self.client_description,
            task_states={k: v.copy() for k, v in self.task_states.items()},
            deployment_id=self.deployment_id,
            deployment_status=(
                self.deployment_status.copy() if self.deployment_status else None
            ),
            reschedule_tracker=(
                self.reschedule_tracker.copy() if self.reschedule_tracker else None
            ),
            network_status=(
                dataclasses.replace(self.network_status, dns=dict(self.network_status.dns))
                if self.network_status
                else None
            ),
            followup_eval_id=self.followup_eval_id,
            previous_allocation=self.previous_allocation,
            next_allocation=self.next_allocation,
            metrics=self.metrics.copy(),
            preempted_by_allocation=self.preempted_by_allocation,
            preempted_allocations=list(self.preempted_allocations),
            create_index=self.create_index,
            modify_index=self.modify_index,
            alloc_modify_index=self.alloc_modify_index,
            create_time=self.create_time,
            modify_time=self.modify_time,
        )

    # -- status predicates (reference: structs.go:9400-9460) --

    def terminal_status(self) -> bool:
        """Desired or actual status is terminal."""
        if self.desired_status in (
            ALLOC_DESIRED_STATUS_STOP,
            ALLOC_DESIRED_STATUS_EVICT,
        ):
            return True
        return self.client_terminal_status()

    def client_terminal_status(self) -> bool:
        return self.client_status in (
            ALLOC_CLIENT_STATUS_COMPLETE,
            ALLOC_CLIENT_STATUS_FAILED,
            ALLOC_CLIENT_STATUS_LOST,
        )

    def server_terminal_status(self) -> bool:
        return self.desired_status in (
            ALLOC_DESIRED_STATUS_STOP,
            ALLOC_DESIRED_STATUS_EVICT,
        )

    def migrate_disk(self) -> bool:
        if self.job is None:
            return False
        tg = self.job.lookup_task_group(self.task_group)
        return tg is not None and tg.ephemeral_disk.migrate

    def comparable_resources(self) -> Resources:
        if self.resources is not None:
            return self.resources.comparable()
        if self.job is not None:
            tg = self.job.lookup_task_group(self.task_group)
            if tg is not None:
                return tg.combined_resources()
        return Resources(cpu=0, memory_mb=0, disk_mb=0)

    def index(self) -> int:
        """The alloc's name index: 'job.group[3]' -> 3."""
        l = self.name.rfind("[")
        r = self.name.rfind("]")
        if l == -1 or r == -1:
            return -1
        try:
            return int(self.name[l + 1 : r])
        except ValueError:
            return -1

    def ran_successfully(self) -> bool:
        if not self.task_states:
            return False
        return all(ts.successful() for ts in self.task_states.values())

    def should_migrate(self) -> bool:
        if self.desired_status != ALLOC_DESIRED_STATUS_STOP:
            return False
        if self.client_terminal_status():
            return False
        if self.job is None:
            return False
        tg = self.job.lookup_task_group(self.task_group)
        if tg is None:
            return False
        return tg.ephemeral_disk.sticky

    def next_reschedule_time(self) -> tuple[int, bool]:
        """(wall-clock ns when a reschedule is allowed, eligible) — reference
        structs.go Allocation.NextRescheduleTime."""
        fail_time = self.last_event_time_ns()
        policy = self.reschedule_policy()
        if policy is None or fail_time == 0:
            return 0, False
        if self.desired_status == ALLOC_DESIRED_STATUS_STOP or (
            self.client_status != ALLOC_CLIENT_STATUS_FAILED
            and self.client_status != ALLOC_CLIENT_STATUS_LOST
        ):
            return 0, False
        delay_s = self.reschedule_delay(policy)
        next_t = fail_time + int(delay_s * 1e9)
        if policy.unlimited:
            return next_t, True
        attempted = 0
        if self.reschedule_tracker:
            window_start = fail_time - int(policy.interval_s * 1e9)
            for ev in self.reschedule_tracker.events:
                if ev.reschedule_time_ns > window_start:
                    attempted += 1
        return next_t, attempted < policy.attempts

    def reschedule_policy(self) -> Optional[ReschedulePolicy]:
        if self.job is None:
            return None
        tg = self.job.lookup_task_group(self.task_group)
        return tg.reschedule_policy if tg else None

    def reschedule_delay(self, policy: ReschedulePolicy) -> float:
        n_prev = len(self.reschedule_tracker.events) if self.reschedule_tracker else 0
        fn = policy.delay_function
        if fn == "constant" or n_prev == 0:
            delay = policy.delay_s
        elif fn == "exponential":
            delay = policy.delay_s * (2**n_prev)
        elif fn == "fibonacci":
            a, b = policy.delay_s, policy.delay_s
            for _ in range(n_prev - 1):
                a, b = b, a + b
            delay = b
        else:
            delay = policy.delay_s
        if policy.max_delay_s > 0:
            delay = min(delay, policy.max_delay_s)
        return delay

    def last_event_time_ns(self) -> int:
        """Latest task finished-at, falling back to modify_time."""
        latest = 0
        for ts in self.task_states.values():
            if ts.finished_at_ns > latest:
                latest = ts.finished_at_ns
        return latest or self.modify_time

    def stub(self) -> "Allocation":
        """Job-stripped copy for list endpoints."""
        c = self.copy(keep_job=False)
        return c


def alloc_name(job_id: str, group: str, index: int) -> str:
    return f"{job_id}.{group}[{index}]"


# ---------------------------------------------------------------------------
# Evaluation
# ---------------------------------------------------------------------------


@dataclass(slots=True)
class Evaluation:
    """A request to (re)consider a job's placements (reference :10211)."""

    id: str = ""
    namespace: str = DEFAULT_NAMESPACE
    priority: int = JOB_DEFAULT_PRIORITY
    type: str = JOB_TYPE_SERVICE
    triggered_by: str = EVAL_TRIGGER_JOB_REGISTER
    job_id: str = ""
    job_modify_index: int = 0
    node_id: str = ""
    node_modify_index: int = 0
    deployment_id: str = ""
    status: str = EVAL_STATUS_PENDING
    status_description: str = ""
    wait_until_ns: int = 0
    next_eval: str = ""
    previous_eval: str = ""
    blocked_eval: str = ""
    failed_tg_allocs: dict[str, AllocMetric] = field(default_factory=dict)
    class_eligibility: dict[str, bool] = field(default_factory=dict)
    escaped_computed_class: bool = False
    quota_limit_reached: str = ""
    annotate_plan: bool = False
    queued_allocations: dict[str, int] = field(default_factory=dict)
    leader_ack: str = ""
    snapshot_index: int = 0
    create_index: int = 0
    modify_index: int = 0
    create_time: int = 0
    modify_time: int = 0

    def copy(self) -> "Evaluation":
        return Evaluation(
            id=self.id,
            namespace=self.namespace,
            priority=self.priority,
            type=self.type,
            triggered_by=self.triggered_by,
            job_id=self.job_id,
            job_modify_index=self.job_modify_index,
            node_id=self.node_id,
            node_modify_index=self.node_modify_index,
            deployment_id=self.deployment_id,
            status=self.status,
            status_description=self.status_description,
            wait_until_ns=self.wait_until_ns,
            next_eval=self.next_eval,
            previous_eval=self.previous_eval,
            blocked_eval=self.blocked_eval,
            failed_tg_allocs={k: v.copy() for k, v in self.failed_tg_allocs.items()},
            class_eligibility=dict(self.class_eligibility),
            escaped_computed_class=self.escaped_computed_class,
            quota_limit_reached=self.quota_limit_reached,
            annotate_plan=self.annotate_plan,
            queued_allocations=dict(self.queued_allocations),
            leader_ack=self.leader_ack,
            snapshot_index=self.snapshot_index,
            create_index=self.create_index,
            modify_index=self.modify_index,
            create_time=self.create_time,
            modify_time=self.modify_time,
        )

    def terminal_status(self) -> bool:
        return self.status in (
            EVAL_STATUS_COMPLETE,
            EVAL_STATUS_FAILED,
            EVAL_STATUS_CANCELLED,
        )

    def should_enqueue(self) -> bool:
        return self.status == EVAL_STATUS_PENDING

    def should_block(self) -> bool:
        return self.status == EVAL_STATUS_BLOCKED

    def make_plan(self, job: Optional[Job]) -> "Plan":
        return Plan(
            eval_id=self.id,
            priority=self.priority,
            job=job,
            all_at_once=job.all_at_once if job else False,
        )

    def next_rolling_eval(self, wait_s: float) -> "Evaluation":
        return Evaluation(
            id=generate_uuid(),
            namespace=self.namespace,
            priority=self.priority,
            type=self.type,
            triggered_by=EVAL_TRIGGER_ROLLING_UPDATE,
            job_id=self.job_id,
            job_modify_index=self.job_modify_index,
            status=EVAL_STATUS_PENDING,
            wait_until_ns=now_ns() + int(wait_s * 1e9),
            previous_eval=self.id,
            create_time=now_ns(),
            modify_time=now_ns(),
        )

    def create_blocked_eval(
        self,
        classes: dict[str, bool],
        escaped: bool,
        quota_reached: str,
        failed_tg_allocs: dict[str, AllocMetric] | None = None,
    ) -> "Evaluation":
        return Evaluation(
            id=generate_uuid(),
            namespace=self.namespace,
            priority=self.priority,
            type=self.type,
            triggered_by=EVAL_TRIGGER_QUEUED_ALLOCS,
            job_id=self.job_id,
            job_modify_index=self.job_modify_index,
            status=EVAL_STATUS_BLOCKED,
            previous_eval=self.id,
            class_eligibility=classes,
            escaped_computed_class=escaped,
            quota_limit_reached=quota_reached,
            failed_tg_allocs=failed_tg_allocs or {},
            create_time=now_ns(),
            modify_time=now_ns(),
        )

    def create_failed_followup_eval(self, wait_s: float) -> "Evaluation":
        return Evaluation(
            id=generate_uuid(),
            namespace=self.namespace,
            priority=self.priority,
            type=self.type,
            triggered_by=EVAL_TRIGGER_FAILED_FOLLOWUP,
            job_id=self.job_id,
            job_modify_index=self.job_modify_index,
            status=EVAL_STATUS_PENDING,
            wait_until_ns=now_ns() + int(wait_s * 1e9),
            previous_eval=self.id,
            create_time=now_ns(),
            modify_time=now_ns(),
        )


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------


@dataclass(slots=True)
class DeploymentStatusUpdate:
    deployment_id: str = ""
    status: str = ""
    status_description: str = ""


@dataclass(slots=True)
class Plan:
    """A scheduler's proposed state mutation (reference: structs.go Plan :10505)."""

    eval_id: str = ""
    eval_token: str = ""
    priority: int = JOB_DEFAULT_PRIORITY
    all_at_once: bool = False
    job: Optional[Job] = None
    # node_id -> allocs to stop/evict on that node
    node_update: dict[str, list[Allocation]] = field(default_factory=dict)
    # node_id -> allocs to create/update on that node
    node_allocation: dict[str, list[Allocation]] = field(default_factory=dict)
    # node_id -> allocs preempted on that node
    node_preemptions: dict[str, list[Allocation]] = field(default_factory=dict)
    annotations: Optional[dict[str, Any]] = None
    deployment: Optional["Deployment"] = None
    deployment_updates: list[DeploymentStatusUpdate] = field(default_factory=list)
    snapshot_index: int = 0
    # struct-of-arrays fresh placements (structs/placement_batch.py):
    # the solver's fast-mint path appends whole PlacementBatches here
    # instead of per-row Allocations in node_allocation — the applier,
    # codec, and store consume the columns directly.
    alloc_batches: list = field(default_factory=list)
    # Writes to node_allocation / node_update / node_preemptions, by node
    # and in all: every method below that writes them counts. A host
    # stack keeps a node's ranking while the node's count stands
    # (scheduler/rank.py RankMemo), a property set its combined counts
    # while the plan's does (scheduler/propertyset.py).
    node_writes: dict[str, int] = field(default_factory=dict)
    writes: int = 0

    def _wrote(self, node_id: str) -> None:
        self.node_writes[node_id] = self.node_writes.get(node_id, 0) + 1
        self.writes += 1

    def append_placement_batch(self, batch) -> None:
        """Attach a SoA batch of fresh placements (already job-stamped
        by the solver; no per-row copy — batch rows are solver-minted
        and referenced nowhere else, the append_fresh_alloc contract)."""
        if batch.job is None:
            batch.job = self.job
        self.alloc_batches.append(batch)

    def materialize_batches(self) -> None:
        """Fold SoA batches into node_allocation as eager per-row
        Allocations — the eager-object equivalent of this plan. Boundary
        escape hatch (and the differential identity battery's
        comparator); the hot paths never call it."""
        for b in self.alloc_batches:
            for a in b.materialize():
                self.node_allocation.setdefault(a.node_id, []).append(a)
                self._wrote(a.node_id)
        self.alloc_batches = []

    def append_stopped_alloc(
        self, alloc: Allocation, desired_desc: str, client_status: str = ""
    ) -> None:
        """Mark an alloc for stopping (reference: Plan.AppendStoppedAlloc :10556)."""
        new_alloc = alloc.copy()
        new_alloc.job = None  # normalized: job is derivable from the plan
        new_alloc.desired_status = ALLOC_DESIRED_STATUS_STOP
        new_alloc.desired_description = desired_desc
        if client_status:
            new_alloc.client_status = client_status
        self.node_update.setdefault(alloc.node_id, []).append(new_alloc)
        self._wrote(alloc.node_id)

    def append_alloc(self, alloc: Allocation, job: Optional[Job] = None) -> None:
        new_alloc = alloc.copy()
        new_alloc.job = job if job is not None else self.job
        self.node_allocation.setdefault(new_alloc.node_id, []).append(new_alloc)
        self._wrote(new_alloc.node_id)

    def append_fresh_alloc(self, alloc: Allocation, job: Optional[Job] = None) -> None:
        """append_alloc without the defensive copy — ONLY for allocs minted
        by the caller this pass and referenced nowhere else (the batch
        solver's hot path: 100k copies would dominate the solve)."""
        alloc.job = job if job is not None else self.job
        self.node_allocation.setdefault(alloc.node_id, []).append(alloc)
        self._wrote(alloc.node_id)

    def append_preempted_alloc(self, alloc: Allocation, preempting_id: str) -> None:
        new_alloc = alloc.copy()
        new_alloc.job = None
        new_alloc.desired_status = ALLOC_DESIRED_STATUS_EVICT
        new_alloc.preempted_by_allocation = preempting_id
        new_alloc.desired_description = (
            f"Preempted by alloc ID {preempting_id}"
        )
        self.node_preemptions.setdefault(alloc.node_id, []).append(new_alloc)
        self._wrote(alloc.node_id)

    def pop_update(self, alloc: Allocation) -> None:
        """Remove a pending stop for alloc (in-place update promotion)."""
        existing = self.node_update.get(alloc.node_id, [])
        n = len(existing)
        if n > 0 and existing[n - 1].id == alloc.id:
            existing.pop()
            if not existing:
                del self.node_update[alloc.node_id]
            self._wrote(alloc.node_id)

    def is_no_op(self) -> bool:
        return (
            not self.node_update
            and not self.node_allocation
            and not self.alloc_batches
            and self.deployment is None
            and not self.deployment_updates
        )


@dataclass(slots=True)
class PlanResult:
    """What the plan applier committed (reference: structs.go PlanResult :10749)."""

    node_update: dict[str, list[Allocation]] = field(default_factory=dict)
    node_allocation: dict[str, list[Allocation]] = field(default_factory=dict)
    node_preemptions: dict[str, list[Allocation]] = field(default_factory=dict)
    # The job version this plan was scheduled against, carried ONCE: allocs
    # in node_allocation with job=None re-attach to it on apply (denormalized
    # payload — see PlanApplier.apply_one).
    job: Optional[Job] = None
    deployment: Optional["Deployment"] = None
    deployment_updates: list[DeploymentStatusUpdate] = field(default_factory=list)
    # follow-up evals for the jobs whose allocs were preempted, so they
    # reschedule elsewhere (reference plan_apply.go PreemptionEvals)
    preemption_evals: list["Evaluation"] = field(default_factory=list)
    refresh_index: int = 0
    alloc_index: int = 0
    # committed SoA placement batches (possibly per-node-trimmed views of
    # the plan's batches). NEVER on the wire as a field: the codec folds
    # these into node_allocation row maps so the raft entry is
    # byte-identical to the eager form (codec._install_plan_result_encoder).
    alloc_batches: list = field(default_factory=list)

    def full_commit(self, plan: Plan) -> tuple[bool, int, int]:
        expected = sum(len(v) for v in plan.node_allocation.values()) + sum(
            len(b) for b in plan.alloc_batches
        )
        actual = sum(len(v) for v in self.node_allocation.values()) + sum(
            len(b) for b in self.alloc_batches
        )
        return expected == actual, expected, actual

    def is_no_op(self) -> bool:
        return (
            not self.node_update
            and not self.node_allocation
            and not self.alloc_batches
            and not self.deployment_updates
            and self.deployment is None
        )


# ---------------------------------------------------------------------------
# Deployment
# ---------------------------------------------------------------------------


@dataclass(slots=True)
class DeploymentState:
    """Per-task-group rollout state (reference: structs.go DeploymentState :8863)."""

    auto_revert: bool = False
    auto_promote: bool = False
    promoted: bool = False
    placed_canaries: list[str] = field(default_factory=list)
    desired_canaries: int = 0
    desired_total: int = 0
    placed_allocs: int = 0
    healthy_allocs: int = 0
    unhealthy_allocs: int = 0
    progress_deadline_s: float = 600.0
    require_progress_by_ns: int = 0

    def copy(self) -> "DeploymentState":
        return DeploymentState(
            auto_revert=self.auto_revert,
            auto_promote=self.auto_promote,
            promoted=self.promoted,
            placed_canaries=list(self.placed_canaries),
            desired_canaries=self.desired_canaries,
            desired_total=self.desired_total,
            placed_allocs=self.placed_allocs,
            healthy_allocs=self.healthy_allocs,
            unhealthy_allocs=self.unhealthy_allocs,
            progress_deadline_s=self.progress_deadline_s,
            require_progress_by_ns=self.require_progress_by_ns,
        )


@dataclass(slots=True)
class Deployment:
    """A tracked rollout of one job version (reference: structs.go Deployment :8767)."""

    id: str = ""
    namespace: str = DEFAULT_NAMESPACE
    job_id: str = ""
    job_version: int = 0
    job_modify_index: int = 0
    job_spec_modify_index: int = 0
    job_create_index: int = 0
    is_multiregion: bool = False
    task_groups: dict[str, DeploymentState] = field(default_factory=dict)
    status: str = DEPLOYMENT_STATUS_RUNNING
    status_description: str = "Deployment is running"
    create_index: int = 0
    modify_index: int = 0
    modify_time: int = 0  # wall-clock ns, for GC thresholds

    def copy(self) -> "Deployment":
        return Deployment(
            id=self.id,
            namespace=self.namespace,
            job_id=self.job_id,
            job_version=self.job_version,
            job_modify_index=self.job_modify_index,
            job_spec_modify_index=self.job_spec_modify_index,
            job_create_index=self.job_create_index,
            is_multiregion=self.is_multiregion,
            task_groups={k: v.copy() for k, v in self.task_groups.items()},
            status=self.status,
            status_description=self.status_description,
            create_index=self.create_index,
            modify_index=self.modify_index,
            modify_time=self.modify_time,
        )

    def active(self) -> bool:
        return self.status in (DEPLOYMENT_STATUS_RUNNING, DEPLOYMENT_STATUS_PAUSED)

    def requires_promotion(self) -> bool:
        return any(
            s.desired_canaries > 0 and not s.promoted
            for s in self.task_groups.values()
        )

    def has_auto_promote(self) -> bool:
        states = self.task_groups.values()
        return bool(states) and all(s.auto_promote for s in states)


def new_deployment(job: Job) -> Deployment:
    d = Deployment(
        id=generate_uuid(),
        namespace=job.namespace,
        job_id=job.id,
        job_version=job.version,
        job_modify_index=job.modify_index,
        job_spec_modify_index=job.job_modify_index,
        job_create_index=job.create_index,
        status=DEPLOYMENT_STATUS_RUNNING,
        status_description="Deployment is running",
    )
    return d
