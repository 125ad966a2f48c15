"""Closed-loop load generator: sustained mixed traffic against a live
in-process cluster.

Every measurement before this was a short burst; the north star is
hours of mixed traffic from many tenants. :class:`LoadGen` drives a
live cluster (a :class:`~nomad_tpu.testing.chaos.ChaosCluster` or a
single ClusterServer) at a target eval arrival rate with a seeded mix
of job registers, scales, stops, dispatches, forced evaluations, and
node up/down churn — through the REAL front doors (``rpc_self`` →
precheck rate limits → leader forwarding → admission control), so
throttles and 429-class rejections are part of the measured loop, not
bypassed around it.

Closed-loop: the generator paces to the target rate, honors
Retry-After hints per namespace (a throttled tenant backs off exactly
as a well-behaved SDK would), records what was offered vs accepted vs
throttled, and finally drains + reads the end-to-end latency
histograms from the production metrics registry.

:func:`run_soak` is the one-call harness the tier-1 mini-soak and the
slow-marked 10-minute soak share: boot a durable ChaosCluster under a
seeded FaultPlane schedule, configure the overload knobs, run the
generator, then assert the ChaosCluster invariants (no acked write
lost, no duplicate alloc, convergence) and report shed/throttle/latency
evidence.
"""

from __future__ import annotations

import logging
import os
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

from .. import clusterobs, metrics
from ..ratelimit import RateLimitError, is_throttle_text, retry_after_from_text
from ..rpc.client import RPCError
from ..server.raft_replication import NotLeaderError
from ..structs.structs import Namespace
from .. import mock

logger = logging.getLogger("nomad_tpu.loadgen")

# counters whose deltas the report captures
_COUNTERS = (
    "nomad.broker.shed",
    "nomad.broker.rejected",
    "nomad.http.throttled",
    "nomad.rpc.throttled",
    "nomad.worker.backpressure_throttled",
    "nomad.blocked_evals.deduped",
    "nomad.blocked_evals.evicted",
)


@dataclass
class LoadGenConfig:
    rate_eval_per_s: float = 50.0
    duration_s: float = 10.0
    seed: int = 0
    namespaces: tuple = ("default", "tenant-a", "tenant-b")
    node_count: int = 10
    group_count: int = 2  # allocs per registered job
    max_live_jobs: int = 40  # per namespace; stops recycle beyond this
    node_churn_period_s: float = 4.0  # 0 = no churn
    dispatch: bool = True
    heartbeat_period_s: float = 3.0
    drain_timeout_s: float = 30.0
    # parallel submitter threads: each front-door write blocks on a
    # raft commit (~tens of ms), so a single closed loop tops out far
    # below real arrival rates — N submitters share one paced budget
    submitters: int = 4
    # scheduled one-shot events: (offset_s, fn) — run_soak uses these
    # for partition/heal cycles
    events: list = field(default_factory=list)


@dataclass
class _Counts:
    offered: int = 0
    accepted: int = 0
    throttled: int = 0
    churn_errors: int = 0
    failed: int = 0


class LoadGen:
    def __init__(self, cluster, cfg: LoadGenConfig) -> None:
        """cluster — a ChaosCluster (drives a live member, leader-
        forwarded) or any object with ``rpc_self``/``server``."""
        self.cluster = cluster
        self.cfg = cfg
        self.rng = random.Random(cfg.seed)
        # one lock covers the rng, the counts, the live-job lists, the
        # pacing clock, and the per-namespace backoffs — submitter
        # threads hold it only to plan/commit an op, never across the
        # RPC itself
        self._lock = threading.Lock()
        self.counts = _Counts()
        # jobs this generator registered AND saw acked, minus acked
        # stops — the no-acked-write-lost invariant set
        self.acked_jobs: set[str] = set()
        self._live: dict[str, list] = {ns: [] for ns in cfg.namespaces}
        self._param_jobs: dict[str, str] = {}
        self._nodes: list = []
        self._nodes_down: set[str] = set()
        self._ns_backoff: dict[str, float] = {}
        self._seq = 0
        # scenario hooks: set once run() finished registering its
        # namespaces/nodes (scripted operations start against a warmed
        # cluster), and stop() for drivers whose scripted operation
        # finishes before duration_s elapses
        self.setup_done = threading.Event()

    def stop(self) -> None:
        """End the traffic phase now (drain + report still run): the
        production-ops scenarios call this once their scripted
        operation — a secret rotation, a completed roll — is done."""
        self._traffic_deadline = time.monotonic()

    # -- cluster access -------------------------------------------------

    def _driver(self):
        """A live server to submit through (its endpoints forward to
        the leader and retry leaderless windows internally)."""
        servers = getattr(self.cluster, "servers", None)
        if servers:
            # prefer the lowest id: run_soak keeps it in the majority
            # side of any scripted partition
            for nid in sorted(servers):
                return servers[nid]
            raise RuntimeError("no live servers")
        return self.cluster

    def _rpc(self, method: str, args) -> object:
        return self._driver().rpc_self(method, args)

    # -- setup ----------------------------------------------------------

    def _retrying(self, fn, attempts: int = 20, what: str = "setup"):
        """Setup-time writes ride through churn/throttles with patience
        (the measured loop instead COUNTS those outcomes)."""
        last = None
        for _ in range(attempts):
            try:
                return fn()
            except RateLimitError as e:
                last = e
                time.sleep(min(2.0, e.retry_after_s or 0.25))
            except Exception as e:  # leaderless windows, injected drops
                last = e
                time.sleep(0.25)
        raise RuntimeError(f"loadgen {what} failed: {last}")

    def setup(self) -> None:
        cfg = self.cfg
        for ns in cfg.namespaces:
            if ns == "default":
                continue
            self._retrying(
                lambda ns=ns: self._rpc(
                    "Namespace.upsert", {"namespace": Namespace(name=ns)}
                ),
                what=f"namespace {ns}",
            )
        for i in range(cfg.node_count):
            node = mock.node()
            self._retrying(
                lambda n=node: self._rpc("Node.register", {"node": n}),
                what=f"node {i}",
            )
            self._nodes.append(node)
        if cfg.dispatch:
            from ..structs.structs import ParameterizedJobConfig

            for ns in cfg.namespaces:
                j = self._new_job(ns)
                j.type = "batch"
                j.parameterized = ParameterizedJobConfig(payload="optional")
                self._retrying(
                    lambda j=j: self._rpc("Job.register", {"job": j}),
                    what=f"param job {ns}",
                )
                self._param_jobs[ns] = j.id

    def _new_job(self, ns: str):
        self._seq += 1
        j = mock.job(id=f"load-{ns}-{self._seq}")
        j.namespace = ns
        tg = j.task_groups[0]
        tg.count = self.cfg.group_count
        tg.tasks[0].resources.cpu = 50
        tg.tasks[0].resources.memory_mb = 32
        tg.tasks[0].resources.networks = []
        return j

    # -- the traffic loop ----------------------------------------------

    def _pick_ns(self, now: float) -> Optional[str]:
        with self._lock:
            ready = [
                ns
                for ns in self.cfg.namespaces
                if self._ns_backoff.get(ns, 0.0) <= now
            ]
            return self.rng.choice(ready) if ready else None

    def _one_op(self, ns: str) -> None:
        """One eval-minting write through the front door: plan + reserve
        under the lock, the RPC itself outside it, bookkeeping back
        under it. Raises on throttle (caller counts + backs off the
        namespace)."""
        with self._lock:
            live = self._live[ns]
            r = self.rng.random()
            if (r < 0.40 and len(live) < self.cfg.max_live_jobs) or not live:
                kind, job = "register", self._new_job(ns)
            elif r < 0.40:
                # at the live-jobs cap: recycle by stopping the oldest
                kind, job = "stop", live.pop(0)
            elif r < 0.70:
                kind, job = "scale", self.rng.choice(live)
                count = self.rng.randint(1, max(2, self.cfg.group_count * 2))
            elif r < 0.80:
                kind, job = "stop", live.pop(self.rng.randrange(len(live)))
            elif r < 0.90 and self._param_jobs.get(ns):
                kind, job = "dispatch", None
            else:
                kind, job = "evaluate", self.rng.choice(live)
            if kind == "stop":
                # ambiguous-outcome safety: a stop may APPLY even when
                # its response is lost (injected serve.drop, partition
                # after delivery) — stop asserting this job's liveness
                # BEFORE the RPC, or the no-acked-write-lost invariant
                # would flag a write that in fact landed
                self.acked_jobs.discard(job.id)
        if kind == "register":
            self._rpc("Job.register", {"job": job})
            with self._lock:
                live.append(job)
                self.acked_jobs.add(job.id)
        elif kind == "scale":
            self._rpc(
                "Job.scale",
                {
                    "namespace": ns,
                    "job_id": job.id,
                    "group": job.task_groups[0].name,
                    "count": count,
                    "message": "loadgen",
                },
            )
        elif kind == "stop":
            self._rpc(
                "Job.deregister",
                {"namespace": ns, "job_id": job.id, "purge": False},
            )
        elif kind == "dispatch":
            self._rpc(
                "Job.dispatch",
                {
                    "namespace": ns,
                    "job_id": self._param_jobs[ns],
                    "meta": {},
                    "payload": b"",
                },
            )
        else:
            self._rpc(
                "Job.evaluate", {"namespace": ns, "job_id": job.id}
            )

    def _churn_node(self) -> None:
        """Flip one node down/up through the real status endpoint: the
        FSM side channels mint node-update evals and capacity-change
        unblocks — the storm the blocked-evals containment must absorb."""
        if not self._nodes:
            return
        with self._lock:
            node = self.rng.choice(self._nodes)
        try:
            if node.id in self._nodes_down:
                self._rpc(
                    "Node.update_status",
                    {"node_id": node.id, "status": "ready"},
                )
                self._nodes_down.discard(node.id)
            else:
                self._rpc(
                    "Node.update_status",
                    {"node_id": node.id, "status": "down"},
                )
                self._nodes_down.add(node.id)
        except Exception:
            self.counts.churn_errors += 1

    def _heartbeats(self) -> None:
        for node in self._nodes:
            if node.id in self._nodes_down:
                continue
            try:
                self._rpc("Node.heartbeat", {"node_id": node.id})
            except Exception:
                self.counts.churn_errors += 1

    def _claim_slot(self) -> float:
        """Shared pacing budget across submitters: 0.0 = send now, else
        seconds to wait before re-checking. Catch-up is capped at one
        interval — a stall is never answered with an unbounded burst."""
        now = time.monotonic()
        with self._lock:
            if now < self._next_send:
                return min(0.01, self._next_send - now)
            self._next_send = max(
                self._next_send + self._interval, now - self._interval
            )
            return 0.0

    def _submit_loop(self) -> None:
        while True:
            now = time.monotonic()
            if now >= self._traffic_deadline:
                return
            wait = self._claim_slot()
            if wait > 0:
                time.sleep(wait)
                continue
            ns = self._pick_ns(now)
            if ns is None:
                time.sleep(0.005)
                continue  # every namespace told to back off
            with self._lock:
                self.counts.offered += 1
            try:
                self._one_op(ns)
                with self._lock:
                    self.counts.accepted += 1
            except RateLimitError as e:
                with self._lock:
                    self.counts.throttled += 1
                    self._ns_backoff[ns] = now + min(
                        5.0, e.retry_after_s or 0.5
                    )
            except NotLeaderError:
                # locally-raised churn (LeadershipLostError included):
                # the driver was/lost the leader mid-write — real
                # overload induces elections; count and carry on
                with self._lock:
                    self.counts.churn_errors += 1
            except RPCError as e:
                text = str(e)
                with self._lock:
                    if is_throttle_text(text):
                        self.counts.throttled += 1
                        self._ns_backoff[ns] = now + min(
                            5.0, retry_after_from_text(text) or 0.5
                        )
                    elif (
                        "NotLeaderError" in text
                        or "LeadershipLostError" in text
                        or "no cluster leader" in text
                    ):
                        # LeadershipLostError is outcome-UNKNOWN (the
                        # write may still commit), so it is never acked
                        # here — but it is leadership churn, not a
                        # dropped request: the rolling-upgrade scenario
                        # gates `failed` at zero while kills are
                        # in-flight, and only churn may say otherwise
                        self.counts.churn_errors += 1
                    else:
                        # includes KeyError-not-found: a scale/evaluate
                        # raced a stop/GC of its job
                        self.counts.failed += 1
            except (ConnectionError, TimeoutError, OSError):
                with self._lock:
                    self.counts.churn_errors += 1
            except (KeyError, ValueError, LookupError):
                with self._lock:
                    self.counts.failed += 1

    def run(self) -> dict:
        cfg = self.cfg
        base = {
            name: metrics.snapshot()["counters"].get(name, 0)
            for name in _COUNTERS
        }
        e2e_base = (
            metrics.snapshot()["samples"]
            .get("nomad.eval.e2e_seconds", {})
            .get("count", 0)
        )
        self._interval = 1.0 / max(0.01, cfg.rate_eval_per_s)
        # a scenario's stop() between setup and the loop must stick: only
        # push the deadline out, never overwrite an earlier one
        self._traffic_deadline = float("inf")
        self.setup()
        start = time.monotonic()
        self._next_send = start
        self._traffic_deadline = min(
            self._traffic_deadline, start + cfg.duration_s
        )
        self.setup_done.set()
        threads = [
            threading.Thread(
                target=self._submit_loop,
                name=f"loadgen-{i}",
                daemon=True,
            )
            for i in range(max(1, cfg.submitters))
        ]
        for t in threads:
            t.start()
        # the main thread owns the background traffic: heartbeats, node
        # churn, and the scripted fault-schedule events
        next_hb = start + cfg.heartbeat_period_s
        next_churn = (
            start + cfg.node_churn_period_s
            if cfg.node_churn_period_s > 0
            else float("inf")
        )
        events = sorted(cfg.events, key=lambda e: e[0])
        ei = 0
        while True:
            now = time.monotonic()
            if now >= self._traffic_deadline:
                break
            while ei < len(events) and now - start >= events[ei][0]:
                try:
                    events[ei][1]()
                except Exception:
                    logger.exception("loadgen scheduled event failed")
                ei += 1
            if now >= next_hb:
                self._heartbeats()
                next_hb = now + cfg.heartbeat_period_s
            if now >= next_churn:
                self._churn_node()
                next_churn = now + cfg.node_churn_period_s
            time.sleep(0.02)
        for t in threads:
            t.join(timeout=30)
        wall = time.monotonic() - start
        drained = self._wait_drain()
        return self._report(base, e2e_base, wall, drained)

    def _wait_drain(self) -> bool:
        """Wait for the broker to finish (or shed) everything offered —
        bounded; an overloaded-but-degrading-gracefully cluster drains
        once arrivals stop."""
        deadline = time.monotonic() + self.cfg.drain_timeout_s
        while time.monotonic() < deadline:
            try:
                srv = self._driver().server
                if (
                    srv.eval_broker.pending_count() == 0
                    and srv.eval_broker.inflight_count() == 0
                ):
                    return True
            except Exception:
                pass
            time.sleep(0.1)
        return False

    def _report(self, base: dict, e2e_base: int, wall: float,
                drained: bool) -> dict:
        snap = metrics.snapshot()
        counters = {
            name: snap["counters"].get(name, 0) - base[name]
            for name in _COUNTERS
        }
        e2e = snap["samples"].get("nomad.eval.e2e_seconds") or {}
        report = {
            "duration_s": round(wall, 2),
            "offered": self.counts.offered,
            "accepted": self.counts.accepted,
            "throttled_client_visible": self.counts.throttled,
            "churn_errors": self.counts.churn_errors,
            "failed": self.counts.failed,
            "offered_rate_per_s": round(self.counts.offered / wall, 2)
            if wall > 0
            else 0.0,
            "accepted_rate_per_s": round(self.counts.accepted / wall, 2)
            if wall > 0
            else 0.0,
            "drained": drained,
            "counters": counters,
            "evals_completed": int(e2e.get("count", 0)) - int(e2e_base),
        }
        if e2e.get("count"):
            report["e2e_seconds"] = {
                "p50": round(e2e["p50"], 4),
                "p95": round(e2e["p95"], 4),
                "p99": round(e2e["p99"], 4),
                "max": round(e2e["max"], 4),
            }
        return report


# ---------------------------------------------------------------------------
# The soak harness: ChaosCluster + seeded fault schedule + LoadGen +
# invariants. Shared by the tier-1 mini-soak and the 10-minute soak.
# ---------------------------------------------------------------------------


def run_soak(
    data_root: str,
    *,
    duration_s: float = 20.0,
    rate: float = 100.0,
    seed: int = 42,
    n_servers: int = 3,
    admission_depth: int = 64,
    namespace_cap: int = 32,
    blocked_cap: int = 64,
    nack_delay_s: float = 1.0,
    rpc_rate: float = 0.0,
    rpc_burst: float = 0.0,
    use_tpu_worker: bool = False,
    faults: bool = True,
    partition_cycle: bool = False,
    node_count: int = 10,
    p99_bound_s: float = 15.0,
    loadgen_overrides: Optional[dict] = None,
) -> dict:
    """Boot a durable in-process cluster under a seeded fault schedule,
    drive it with closed-loop mixed traffic, and return the evidence
    dict (loadgen report + invariant verdicts + gate inputs)."""
    from .chaos import ChaosCluster

    cluster = ChaosCluster(
        n_servers,
        data_root,
        seed=seed,
        num_workers=1,
        use_tpu_batch_worker=use_tpu_worker,
    )

    def seed_background_faults() -> None:
        if not faults or cluster.plane is None:
            return
        # low-probability background noise for the whole run: dropped
        # calls ride the pool's redial/forwarder retries; lost responses
        # exercise at-most-once; slow fsync exercises backpressure. One
        # seed fixes the whole schedule (faultplane.py draw order).
        cluster.plane.drop_rpc(prob=0.01)
        cluster.plane.drop_response(prob=0.004)
        cluster.plane.slow_disk(0.01, prob=0.02)
        if use_tpu_worker:
            cluster.plane.fail_device(prob=0.02, retriable=True)

    # Server-CPU-per-node measurement (ROADMAP "bounded server-CPU-per-
    # node" gate): a PRIVATE host profiler instance samples every thread
    # for the traffic window and the per-role busy split separates
    # server-side roles (rpc/raft/worker/applier/...) from the
    # generator's own loadgen/main threads. Fresh instance — never the
    # process-global one a co-resident Agent may be running.
    from .. import hostobs

    # NOMAD_TPU_SOAK_PROFILE=0 turns the measurement apparatus off
    # (no sampler thread: sites and stacks stay empty; CPU by role is
    # still read from the threads' clocks at the window's two ends, and
    # the gated CPU stat is process_time) — also the A/B knob for isolating
    # profiler-load effects on race-timing-sensitive soaks.
    profile_on = os.environ.get("NOMAD_TPU_SOAK_PROFILE", "1") != "0"
    prof = hostobs.HostProfiler(interval_s=0.01, idle_interval_s=0.02)
    try:
        # both starts INSIDE the try: a boot failure (port bind, raft
        # store) must still tear the sampler thread + its gc hooks and
        # provider down in the finally (prof.stop is a safe no-op when
        # start never ran)
        if profile_on:
            prof.start()
        cluster.start()
        lead = cluster.wait_for_stable_leader(timeout_s=60)
        if lead is None:
            raise RuntimeError("soak cluster never elected a leader")
        from ..retry import RetryPolicy

        for cs in cluster.servers.values():
            # tighter leaderless-retry budget than production: a soak
            # submitter stuck 10s in a forwarder retry measures the
            # retry policy, not the control plane — 3s bounds the tail
            # while still riding out a normal election
            cs.forward_retry = RetryPolicy(
                base_s=0.05, max_s=0.5, deadline_s=3.0
            )
            cs.server.eval_broker.configure(
                nack_delay_s=nack_delay_s,
                admission_depth=admission_depth,
                namespace_cap=namespace_cap,
            )
            cs.server.blocked_evals.configure(cap=blocked_cap)
            if rpc_rate > 0:
                cs.set_rate_limits(rpc_rate, rpc_burst)
        seed_background_faults()

        events = []
        if partition_cycle and n_servers >= 3 and faults:
            ids = sorted(cluster.addrs)
            minority, majority = [ids[-1]], ids[:-1]

            def cut():
                cluster.plane.partition(minority, majority)

            def heal():
                # heal() drops every rpc.drop rule, the background
                # noise included — re-seed it after the cut ends
                cluster.heal("rpc.drop")
                if faults:
                    cluster.plane.drop_rpc(prob=0.01)

            third = duration_s / 3.0
            events = [(third, cut), (third + min(2.0, third / 2), heal)]

        cfg = LoadGenConfig(
            rate_eval_per_s=rate,
            duration_s=duration_s,
            seed=seed,
            node_count=node_count,
            events=events,
        )
        for k, v in (loadgen_overrides or {}).items():
            setattr(cfg, k, v)
        gen = LoadGen(cluster, cfg)
        prof.reset_stats()  # exclude cluster boot from the CPU window
        cpu_t0 = time.process_time()
        report = gen.run()
        cpu_delta = time.process_time() - cpu_t0
        prof_snap = prof.snapshot(top=1)

        # per-source attribution coverage across every member's ledger
        # (clusterobs.py): how much of the served handler seconds were
        # billed to a KNOWN node/peer/namespace
        src_total_calls = 0
        src_total_s = 0.0
        src_unattr_s = 0.0
        src_evicted = 0
        src_rows: list[dict] = []
        for cs in cluster.servers.values():
            snap = cs.source_ledger.snapshot(top=10)
            src_total_calls += snap["total_calls"]
            src_total_s += snap["total_seconds"]
            src_unattr_s += snap["unattributed_seconds"]
            src_evicted += snap["evicted"]
            src_rows.extend(snap["top"])

        # quiesce: stop injecting, let the cluster converge, then hold
        # it to the standard invariants
        cluster.heal()
        converged = cluster.converged(timeout_s=60)
        cluster.acked_jobs = set(gen.acked_jobs)
        invariants_ok = True
        invariant_error = ""
        try:
            cluster.check_invariants()
        except AssertionError as e:
            invariants_ok = False
            invariant_error = str(e)

        # Server CPU x node attribution. The GATED stat is real process
        # CPU time over the traffic window (time.process_time sums every
        # thread's actual CPU): one process hosts the whole control
        # plane here, so this is the fleet's server cost — an UPPER
        # bound, since the in-process generator's own threads count too.
        # The profiler role table rides along as the attribution view:
        # CPU seconds by thread role from the same kernel clocks (the
        # keys keep their older names), `(unaccounted)` — native
        # threads — among the server's.
        roles = prof_snap.get("threads") or {}
        client_roles = {"loadgen", "main"}
        server_busy_s = sum(
            r["busy_seconds"]
            for name, r in roles.items()
            if name not in client_roles
        )
        client_busy_s = sum(
            r["busy_seconds"]
            for name, r in roles.items()
            if name in client_roles
        )
        wall = max(report.get("duration_s") or 0.0, 1e-9)
        nodes = max(int(cfg.node_count), 1)
        report["server_cpu"] = {
            "cpu_seconds": round(cpu_delta, 3),
            "per_node_cpu_seconds": round(cpu_delta / nodes, 4),
            # cores-per-node over the traffic window: the number the
            # fleet-scale gate bounds (ROADMAP item 4)
            "per_node_cpu_fraction": round(
                cpu_delta / wall / nodes, 5
            ),
            "node_count": nodes,
            "server_busy_wall_seconds": round(server_busy_s, 3),
            "client_busy_wall_seconds": round(client_busy_s, 3),
            "busy_wall_by_role": {
                name: round(r["busy_seconds"], 3)
                for name, r in sorted(roles.items())
            },
        }
        report["server_cpu_per_node"] = report["server_cpu"][
            "per_node_cpu_seconds"
        ]
        report["source_attribution"] = {
            "total_calls": src_total_calls,
            "total_seconds": round(src_total_s, 4),
            "unattributed_seconds": round(src_unattr_s, 4),
            "evicted": src_evicted,
            "coverage": round(
                1.0 - src_unattr_s / max(src_total_s, 1e-12), 4
            )
            if src_total_calls
            else 1.0,
            "top": clusterobs.merge_top_sources(src_rows, top=5),
        }
        counters = report["counters"]
        admission_engaged = (
            counters["nomad.broker.shed"]
            + counters["nomad.broker.rejected"]
            + counters["nomad.http.throttled"]
            + counters["nomad.rpc.throttled"]
        ) > 0
        p99 = (report.get("e2e_seconds") or {}).get("p99")
        report.update(
            {
                "seed": seed,
                "fault_schedule": bool(faults),
                "fired_faults": dict(cluster.plane.fired)
                if cluster.plane is not None
                else {},
                "converged": converged,
                "invariants_ok": invariants_ok,
                "invariant_error": invariant_error,
                "admission_engaged": admission_engaged,
                "p99_bound_s": p99_bound_s,
                "p99_bounded": p99 is not None and p99 <= p99_bound_s,
            }
        )
        return report
    finally:
        cluster.shutdown()
        if profile_on:
            prof.stop()
        # the private profiler's provider must not outlive the run (it
        # would shadow a co-resident Agent's global profiler under the
        # same "nomad.host" name — provider stacks are newest-wins)
        if prof._provider_handle is not None:
            metrics.unregister_provider(
                "nomad.host", prof._provider_handle
            )
