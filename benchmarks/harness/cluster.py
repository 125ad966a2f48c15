"""The system under test and the fleet that feeds it.

One dev server agent with the TPU batch worker — the `-tpu-scheduler`
construction path, as chip_smoke.py starts it — and the benchmark's own
copy of the simulated fleet's driver: protocol-real `Node.register` and
`Node.heartbeat` RPCs from a few threads. The copy leaves out what
`nomad_tpu/testing/fleet.py` does besides: every simulated node probing
the watch hub each two seconds, which at 10,000 nodes is 5,000 probes a
second on threads that share the interpreter with the server. Visibility
is read by the benchmark's observer (observer.py) instead. The original
is the program's to change; it is listed in PERF.md for a later PR.
"""

from __future__ import annotations

import heapq
import random
import tempfile
import threading
import time
import uuid

from benchmarks.harness import spec

REGISTER_DEADLINE_S = 180.0


class SetupFailure(Exception):
    """Set-up could not bring the cluster to the state a run needs."""


def deal_classes(classes: list[dict], n: int, seed: int) -> list[int]:
    """Which class each of `n` nodes belongs to: every class gets its
    `share` (or `count`, read as a weight) of the fleet by largest
    remainder, dealt over the nodes in an order the seed picks. One
    class draws nothing."""
    if len(classes) == 1:
        return [0] * n
    weights = [float(c.get("count", c.get("share", 0))) for c in classes]
    total = sum(weights)
    if total <= 0 or min(weights) < 0:
        raise SetupFailure("node_classes need a positive share or count")
    exact = [w * n / total for w in weights]
    counts = [int(x) for x in exact]
    by_remainder = sorted(range(len(classes)),
                          key=lambda k: (counts[k] - exact[k], k))
    for k in by_remainder[:n - sum(counts)]:
        counts[k] += 1
    deal = [k for k, c in enumerate(counts) for _ in range(c)]
    random.Random(seed ^ 0xC1A55).shuffle(deal)
    return deal


class Fleet:
    """`n` nodes made from the seed, registered and kept alive.

    Node ids come from the seed, and so do the class of each node and
    the order in which a class's nodes are dealt over its datacenters:
    two runs of one seed register the same cluster."""

    def __init__(self, cs, config: dict, n: int, seed: int,
                 driver_threads: int = 16) -> None:
        from nomad_tpu import mock
        from nomad_tpu.structs import compute_node_class
        from nomad_tpu.structs.structs import (
            NodeDeviceInstance, NodeDeviceResource)

        self.cs = cs
        self.rng = random.Random(seed ^ 0xF1EE7)
        classes = spec.node_classes(config)
        deal = deal_classes(classes, n, seed)
        dealt = [0] * len(classes)  # nodes of each class so far
        self.nodes = []
        for i in range(n):
            shape = classes[deal[i]]
            dcs = list(shape.get("datacenters") or config["datacenters"])
            node = mock.node(
                id=str(uuid.UUID(int=self.rng.getrandbits(128), version=4)),
                name=f"bench-{i}",
                datacenter=dcs[dealt[deal[i]] % len(dcs)],
            )
            dealt[deal[i]] += 1
            if shape.get("name"):
                node.node_class = shape["name"]
            res = node.resources
            res.cpu = shape["cpu_mhz"]
            res.memory_mb = shape["memory_mb"]
            res.disk_mb = shape["disk_mb"]
            res.devices = [
                NodeDeviceResource(
                    vendor=d["vendor"], type=d["type"], name=d["name"],
                    instances=[NodeDeviceInstance(id=f"{d['name']}-{k}")
                               for k in range(int(d["count"]))],
                    attributes=dict(d.get("attributes", {})))
                for d in shape.get("devices", ())]
            node.attributes.update(shape.get("attributes", {}))
            # as a client does after fingerprinting: the program memoizes
            # feasibility by this digest of what the node offers
            node.computed_class = compute_node_class(node)
            self.nodes.append(node)
        self._by_id = {node.id: node for node in self.nodes}
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._heap: list[tuple[float, int, str, bool]] = []
        self._seq = 0
        self._registered = 0
        self._phased: set[str] = set()
        self.errors = {"register": 0, "heartbeat": 0}
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._drive, name=f"bench-fleet-{i}",
                             daemon=True)
            for i in range(driver_threads)
        ]

    def _push(self, due: float, node_id: str, register: bool) -> None:
        with self._cv:
            self._seq += 1
            heapq.heappush(self._heap, (due, self._seq, node_id, register))
            self._cv.notify()

    def _drive(self) -> None:
        while not self._stop.is_set():
            with self._cv:
                now = time.monotonic()
                if not self._heap or self._heap[0][0] > now:
                    wait = 0.2
                    if self._heap:
                        wait = min(wait, self._heap[0][0] - now)
                    self._cv.wait(max(wait, 0.0))
                    continue
                _, _, node_id, register = heapq.heappop(self._heap)
            self._step(node_id, register)

    def _step(self, node_id: str, register: bool) -> None:
        now = time.monotonic()
        try:
            if register:
                ttl = float(self.cs.rpc_self(
                    "Node.register", {"node": self._by_id[node_id]}
                ))
                with self._lock:
                    self._registered += 1
                # like the real client: a first heartbeat at once, which
                # promotes the node to ready
                self._push(now, node_id, False)
                return
            ttl = float(self.cs.rpc_self(
                "Node.heartbeat", {"node_id": node_id}
            ))
        except Exception:  # the RPC's failure is counted and retried
            with self._lock:
                self.errors["register" if register else "heartbeat"] += 1
            self._push(now + 0.5, node_id, register)
            return
        # the real client beats at about half the TTL it is granted. The
        # first beat promoted the node; the second falls at a random
        # phase of the period, so that the fleet beats at a steady
        # nodes/period like one that has run for long, and not all at
        # once one period after its registration storm
        first = node_id not in self._phased
        self._phased.add(node_id)
        lo = 0.0 if first else 0.9
        self._push(now + ttl * 0.5 * self.rng.uniform(lo, 1.0),
                   node_id, False)

    def populate(self, state) -> None:
        """Register every node and wait until the store has all ready."""
        now = time.monotonic()
        for node in self.nodes:
            self._push(now, node.id, True)
        for t in self._threads:
            t.start()
        n = len(self.nodes)

        def ready() -> int:
            return sum(1 for x in state.nodes() if x.status == "ready")

        end = now + REGISTER_DEADLINE_S
        while time.monotonic() < end:
            if self._registered >= n and ready() >= n:
                return
            time.sleep(0.1)
        raise SetupFailure(
            f"{self._registered}/{n} nodes registered and {ready()} ready "
            f"after {REGISTER_DEADLINE_S:.0f}s (errors {self.errors})"
        )

    def stop(self) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        for t in self._threads:
            if t.is_alive():
                t.join(timeout=10)


class Cluster:
    """The dev agent, its HTTP address and its fleet, started and
    stopped together."""

    def __init__(self, config: dict, n_nodes: int, seed: int) -> None:
        self.config = config
        self.n_nodes = n_nodes
        self.seed = seed
        self.agent = None
        self.fleet = None
        self._data_dir = None

    def start(self) -> None:
        from nomad_tpu.agent import Agent, AgentConfig

        self._data_dir = tempfile.TemporaryDirectory(prefix="bench-")
        self.agent = Agent(AgentConfig(
            server_enabled=True, dev_mode=True, use_tpu_batch_worker=True,
            data_dir=self._data_dir.name,
        ))
        self.agent.start()
        cs = self.agent.server
        if self.server.scheduler_config.inject_device_latency_s:
            raise SetupFailure("inject_device_latency_s is set: the device "
                               "would be a model of itself")
        end = time.monotonic() + 30.0
        while not cs.is_leader():
            if time.monotonic() > end:
                raise SetupFailure("no leader after 30s")
            time.sleep(0.05)
        self.fleet = Fleet(cs, self.config, self.n_nodes, self.seed)
        self.fleet.populate(self.server.state)

    @property
    def server(self):
        return self.agent.server.server

    @property
    def http(self) -> tuple[str, int]:
        return ("127.0.0.1", self.agent.http_addr[1])

    def in_flight(self) -> dict:
        """What is still on its way through the server: evals in the
        broker, plans in the queue, evals blocked on capacity."""
        srv = self.server
        b = srv.eval_broker.stats_snapshot()
        return {
            "broker": (b["total_ready"] + b["total_unacked"]
                       + b["total_blocked"] + b["total_waiting"]),
            "plan_queue": srv.plan_queue.depth(),
            "blocked_evals": int(
                dict(srv.blocked_evals.stats).get("total_blocked", 0)
            ),
        }

    def stop(self) -> None:
        if self.fleet is not None:
            self.fleet.stop()
        if self.agent is not None:
            self.agent.shutdown()
        if self._data_dir is not None:
            self._data_dir.cleanup()
