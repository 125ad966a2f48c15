#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served placement path
still runs on the TPU.

ONE process. It starts a dev server agent with the TPU batch worker (the
`-tpu-scheduler` construction path), registers a protocol-real SimFleet
of 10,000 nodes over `Node.register`, and drives three waves of jobs
through the HTTP front door (`PUT /v1/jobs`), waiting for each on the
state store:

  A  the c2m shape: 100 service jobs x 1,000 allocs (250 MHz / 128 MB,
     the c2m constraint, datacenter spread over 4 DCs) — the compact
     kernel, the resident row scatter, the chained used' pipeline. Its
     first few jobs land alone on the empty cluster: the equal-load
     sample whose density is held to the host oracle's.
  B  50 more jobs of A's shape once A has committed — the warm wave:
     every compile it causes is counted and its new signatures named.
  C  low-priority fill to capacity, then priority-70 jobs that can only
     place by preempting — the preempt kernel and its dense readback.

It checks — and exits non-zero with the reason on the first failure —
that the device is a TPU, that every compiled solver program put its
outputs there, placed == asked per wave, exact per-node capacity from the
store, unique alloc ids, density within 1 % of the host oracle at equal
load, no device failover, no failed invoke, fastpack loaded, no
fault-injection knob live, and the compile-ledger rows the waves must
have produced. The bar on the chip is invariants plus density, NOT
bit-identity with the numpy microsolve: f32 `exp` and top_k tie order
are backend properties; whether they happened to agree is REPORTED
(`parity`).

It writes two lines to stdout, and only when every check passed. The
first is the report, one JSON object: mode, versions, per-wave counts,
compiles and signatures, density, parity, cache hits. Its figures are
set-up facts (counts, compile seconds, wall per wave) — nothing is
divided by time; speed is benchmarks/run.py's business. The LAST line is
the verdict the driver reads, exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`,
the device as jax reports it.

    python3 chip_smoke.py                  # on the chip, full size
    python3 chip_smoke.py --mesh-devices 4 # four chips: waves A and B
    python3 chip_smoke.py --rehearsal      # XLA:CPU, tiny, for tests

`--rehearsal` is the only way onto the CPU: it pins JAX_PLATFORMS=cpu
before jax loads, labels the output, and prints no time figures.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import tempfile
import time
from collections import Counter
from importlib import metadata

DCS = ("dc1", "dc2", "dc3", "dc4")
CPU_MHZ, MEM_MB = 250, 128  # the c2m ask (benchmarks/configs/c2m-10k.json)

# count = allocs per job; sample = wave-A jobs placed alone first
FULL = dict(nodes=10_000, count=1000, jobs_a=100, sample=4, jobs_b=50,
            preempt_jobs=2, preempt_count=500)
REHEARSAL = dict(nodes=64, count=64, jobs_a=8, sample=2, jobs_b=4,
                 preempt_jobs=2, preempt_count=56)

# Every wait has a deadline: a kernel the compiler refuses is nacked and
# redelivered for ever (worker.py), so without one the smoke would hang
# instead of failing. No wait outlives RUN_DEADLINE_S either, which keeps
# the whole run — reason printed — inside the 1200 s contract.
REGISTER_DEADLINE_S = 180.0
WAVE_DEADLINE_S = {"A": 420.0, "B": 180.0, "C": 240.0}
RUN_DEADLINE_S = 1100.0
RUN_START = time.monotonic()

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class SmokeFailure(Exception):
    """A failed check; the message is the reason printed on exit."""


def fail(reason: str):
    raise SmokeFailure(reason)


class JaxCounters:
    """JAX's own compile and persistent-cache counters (jax.monitoring):
    the independent witness the solver's compile ledger is held against."""

    def __init__(self) -> None:
        self.compiles: list[tuple[str, float]] = []  # (fun_name, secs)
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self) -> None:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.compiles.append((str(kw.get("fun_name", "?")), secs))

    def _event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1
        elif event == CACHE_MISS_EVENT:
            self.cache_misses += 1


class LastWarning(logging.Handler):
    """Keeps the last WARNING+ record of the nomad_tpu loggers — what a
    deadline expiry prints (a refused kernel only ever shows up there)."""

    def __init__(self) -> None:
        super().__init__(logging.WARNING)
        self.setFormatter(logging.Formatter(
            "%(name)s %(levelname)s %(message)s"
        ))
        self.last = "(no warning or error was logged)"

    def emit(self, record: logging.LogRecord) -> None:
        self.last = self.format(record)


class _OneServerCluster:
    """The two things SimFleet asks of a cluster, for one agent."""

    def __init__(self, cs) -> None:
        self.servers = {cs.node_id: cs}

    def leader(self):
        cs = next(iter(self.servers.values()))
        return cs if cs.is_leader() else None


def c2m_job(job_id: str, count: int, priority: int = 50):
    """The constrained c2m job: kernel-name constraint, datacenter
    spread (nomad_tpu.testing.build_cluster's, over HTTP)."""
    from nomad_tpu import mock
    from nomad_tpu.structs import Constraint, Spread

    job = mock.job(id=job_id)
    job.datacenters = list(DCS)
    job.priority = priority
    tg = job.task_groups[0]
    tg.count = count
    res = tg.tasks[0].resources
    res.cpu, res.memory_mb, res.networks = CPU_MHZ, MEM_MB, []
    job.constraints.append(Constraint("${attr.kernel.name}", "linux", "="))
    job.spreads = [Spread(attribute="${node.datacenter}", weight=50)]
    return job


def wait_until(pred, deadline_s: float, what, errors: LastWarning):
    """Poll `pred` to a deadline; `what()` words the failure."""
    t0 = time.monotonic()
    end = min(t0 + deadline_s, RUN_START + RUN_DEADLINE_S)
    while time.monotonic() < end:
        if pred():
            return
        time.sleep(0.1)
    fail(f"{what()}: not done after {time.monotonic() - t0:.0f}s "
         f"({time.monotonic() - RUN_START:.0f}s into the run); last "
         f"logged warning: {errors.last}")


def live_allocs(state, job) -> list:
    return [a for a in state.allocs_by_job(job.namespace, job.id)
            if not a.terminal_status()]


def density_of(state, jobs) -> tuple[int, int]:
    """(live allocs, nodes they touch)."""
    placed, nodes = 0, set()
    for job in jobs:
        for a in live_allocs(state, job):
            placed += 1
            nodes.add(a.node_id)
    return placed, len(nodes)


def oracle_density(n_nodes: int, n_jobs: int, count: int):
    """The host oracle (GenericScheduler through testing.Harness) on the
    same sample of jobs over an identical empty cluster."""
    from nomad_tpu import mock
    from nomad_tpu.testing import Harness

    h = Harness()
    for i in range(n_nodes):
        h.state.upsert_node(
            h.next_index(), mock.node(datacenter=DCS[i % len(DCS)])
        )
    jobs = [c2m_job(f"oracle-{j}", count) for j in range(n_jobs)]
    for job in jobs:
        h.state.upsert_job(h.next_index(), job)
        h.process("service", mock.eval_for_job(job))
    return density_of(h.state, jobs)


def check_store(state) -> dict:
    """Exact integers from the store: unique alloc ids, no duplicate
    (job, alloc name), and per node summed cpu/mem/disk of live allocs
    <= capacity."""
    from nomad_tpu.testing.chaos import assert_no_duplicate_allocs

    allocs = state.allocs()
    if len({a.id for a in allocs}) != len(allocs):
        fail("duplicate alloc ids in the store")
    try:
        assert_no_duplicate_allocs(state)
    except AssertionError as e:
        fail(str(e)[:600])
    live = 0
    for node in state.nodes():
        cap = node.available_resources()
        cpu = mem = disk = 0
        for a in state.allocs_by_node_terminal(node.id, False):
            r = a.comparable_resources()
            cpu, mem, disk = cpu + r.cpu, mem + r.memory_mb, disk + r.disk_mb
            live += 1
        if cpu > cap.cpu or mem > cap.memory_mb or disk > cap.disk_mb:
            fail(f"node {node.id} over capacity: used ({cpu}, {mem}, "
                 f"{disk}) > ({cap.cpu}, {cap.memory_mb}, {cap.disk_mb})")
    return {"allocs": len(allocs), "live_allocs": live}


def plan_counts(plans) -> Counter:
    """(job, node) -> placements across a solve's plans, eager rows and
    SoA batches alike."""
    out: Counter = Counter()
    for plan in plans.values():
        for nid, allocs in plan.node_allocation.items():
            out[(plan.job.id, nid)] += len(allocs)
        for b in plan.alloc_batches:
            for nid, _ti, cnt in b.touched_nodes():
                out[(plan.job.id, nid)] += cnt
    return out


def parity(n_nodes: int, count: int, mesh_devices: int) -> dict:
    """One small problem — identical empty nodes, so every score ties —
    solved by the numpy microsolve, the one-chip kernel and (when a mesh
    is configured) the sharded kernel, from one snapshot. Reported, not
    gated: any tie order is a valid placement."""
    from nomad_tpu import mock
    from nomad_tpu.scheduler.context import SchedulerConfig
    from nomad_tpu.scheduler.tpu import solve_eval_batch
    from nomad_tpu.testing import Harness

    h = Harness()
    for i in range(n_nodes):
        h.state.upsert_node(
            h.next_index(), mock.node(datacenter=DCS[i % len(DCS)])
        )
    jobs = [c2m_job(f"parity-{j}", count) for j in range(3)]
    for job in jobs:
        h.state.upsert_job(h.next_index(), job)
    snap = h.snapshot()

    def solve(**cfg) -> Counter:
        evals = [mock.eval_for_job(j) for j in jobs]
        return plan_counts(solve_eval_batch(
            snap, Harness(h.state), evals, SchedulerConfig(**cfg)
        ))

    huge = 1 << 40
    micro = solve(small_batch_threshold=huge, micro_solve_threshold=huge,
                  mesh_devices=0)
    chip = solve(small_batch_threshold=0, micro_solve_threshold=0,
                 mesh_devices=0)
    asked = 3 * count
    if sum(micro.values()) != asked or sum(chip.values()) != asked:
        fail(f"parity problem: asked {asked}, microsolve placed "
             f"{sum(micro.values())}, chip placed {sum(chip.values())}")
    out = {"nodes": n_nodes, "asked": asked,
           "chip_equals_microsolve": chip == micro,
           "mesh_equals_chip": None}
    if mesh_devices > 1:
        mesh = solve(small_batch_threshold=0, micro_solve_threshold=0,
                     mesh_devices=mesh_devices)
        out["mesh_equals_chip"] = mesh == chip
    return out


def run(args) -> tuple[dict, dict]:
    """The report and the device stamp of a run whose checks all passed."""
    size = REHEARSAL if args.rehearsal else FULL
    if args.rehearsal:
        # the ONE way onto the CPU, and it says so — before jax loads
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.mesh_devices > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count="
                f"{args.mesh_devices}"
            ).strip()
    os.environ["NOMAD_TPU_MESH_DEVICES"] = str(args.mesh_devices)

    try:
        from nomad_tpu import faultplane, metrics, solverobs
        from nomad_tpu.scheduler.tpu import resolve_device
    except ImportError as e:
        fail(f"the nomad_tpu package is not importable from here: {e}")
    try:
        device = resolve_device()
    except RuntimeError as e:
        fail(f"no TPU: {e}")
    if not args.rehearsal and device.platform != "tpu":
        fail(f"no TPU: jax resolved to platform {device.platform!r} "
             f"({device.device_kind} x{device.count}); this smoke runs on "
             "the chip — pass --rehearsal for the tiny XLA:CPU run")
    import jax

    counters = JaxCounters()
    counters.install()
    errors = LastWarning()
    logging.getLogger("nomad_tpu").addHandler(errors)

    knobs = faultplane.env_knobs_active()
    if knobs:
        fail(f"fault-injection knobs are live: {knobs}")

    from nomad_tpu import mock
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api.client import NomadClient
    from nomad_tpu.testing.fleet import SimFleet

    n_nodes, count = size["nodes"], size["count"]
    per_node = min(4000 // CPU_MHZ, 8192 // MEM_MB)  # mock.node capacity
    fill_jobs = (n_nodes * per_node
                 - (size["jobs_a"] + size["jobs_b"]) * count) // count
    mock_node = mock.node().available_resources()
    if (mock_node.cpu, mock_node.memory_mb) != (4000, 8192) or fill_jobs < 1:
        fail("fixture drift: mock.node is no longer 4000 MHz / 8192 MB")

    t_oracle = time.monotonic()
    host_placed, host_nodes = oracle_density(n_nodes, size["sample"], count)
    oracle_s = time.monotonic() - t_oracle

    waves: dict[str, dict] = {}
    agent = fleet = None
    data_dir = tempfile.TemporaryDirectory(prefix="chip-smoke-")
    try:
        agent = Agent(AgentConfig(
            server_enabled=True, dev_mode=True, use_tpu_batch_worker=True,
            data_dir=data_dir.name,
        ))
        agent.start()
        cs = agent.server
        srv = cs.server
        state = srv.state
        if srv.scheduler_config.inject_device_latency_s:
            fail("inject_device_latency_s is set: the device is modelled")
        wait_until(cs.is_leader, 30.0, lambda: "leader election", errors)
        api = NomadClient(f"http://127.0.0.1:{agent.http_addr[1]}")

        # 16 drivers: a register RPC mostly waits out the batcher's
        # window, and a storm that outlasts the first nodes' 10 s TTL
        # gets them marked down before their first heartbeat is due
        fleet = SimFleet(_OneServerCluster(cs), n_nodes, args.seed,
                         driver_threads=16, real_watchers=4,
                         datacenters=DCS)
        t0 = time.monotonic()
        if not fleet.populate(deadline_s=REGISTER_DEADLINE_S):
            fail(f"only {len(fleet.registered)}/{n_nodes} nodes "
                 f"registered in {REGISTER_DEADLINE_S:.0f}s; last logged "
                 f"warning: {errors.last}")

        def not_ready() -> int:
            return n_nodes - sum(n.status == "ready" for n in state.nodes())

        wait_until(lambda: not_ready() == 0, 60.0,
                   lambda: f"{not_ready()} registered nodes not ready",
                   errors)
        register_s = time.monotonic() - t0

        def placed(jobs) -> int:
            total = 0
            for job in jobs:
                s = state.job_summary_by_id(job.namespace, job.id)
                if s is not None:
                    total += sum(g["starting"] + g["running"]
                                 for g in s.summary.values())
            return total

        def evicted() -> int:
            return sum(1 for a in state.allocs()
                       if a.desired_status == "evict")

        def mark():
            return (len(counters.compiles), solverobs.compiles(),
                    solverobs.signatures())

        def in_flight() -> int:
            b = srv.eval_broker.stats_snapshot()
            return b["total_ready"] + b["total_unacked"]

        def drive(name: str, steps) -> None:
            """Submit each step's jobs over HTTP and wait for them on
            the store; record the wave's counts, compiles, signatures."""
            jax0, led0, sigs0 = mark()
            evicted0 = evicted()
            t_wave = time.monotonic()
            asked = got = 0
            for step_jobs in steps:
                for job in step_jobs:
                    api.jobs.register(job)
                want = sum(j.task_groups[0].count for j in step_jobs)
                wait_until(
                    lambda: placed(step_jobs) >= want,
                    WAVE_DEADLINE_S[name],
                    lambda: (f"wave {name}: {placed(step_jobs)}/{want} "
                             "placed"),
                    errors,
                )
                # exact count as the step lands: a later step (or the
                # re-placement of its victims) may evict these again
                asked += want
                got += sum(len(live_allocs(state, j)) for j in step_jobs)
            # a wave ends when the broker is empty: the evals re-placing
            # wave C's victims belong to it, and the run's counters are
            # read off a settled system
            wait_until(lambda: in_flight() == 0, WAVE_DEADLINE_S[name],
                       lambda: f"wave {name}: {in_flight()} evals still "
                               "in the broker", errors)
            wall = time.monotonic() - t_wave
            new_jax = counters.compiles[jax0:]
            ledger_new = solverobs.compiles() - led0
            by_name: dict[str, dict] = {}
            for fun, secs in new_jax:
                row = by_name.setdefault(fun, {"n": 0, "compile_s": 0.0})
                row["n"] += 1
                row["compile_s"] = round(row["compile_s"] + secs, 3)
            waves[name] = {
                "asked": asked, "placed": got,
                "evicted": evicted() - evicted0,
                "wall_s": round(wall, 3),
                "ledger_compiles": ledger_new,
                "jax_compiles": len(new_jax),
                "unledgered_compiles": len(new_jax) - ledger_new,
                "jax_compile_s": round(sum(s for _, s in new_jax), 3),
                "new_signatures": [
                    list(sig)
                    for k, sigs in solverobs.signatures().items()
                    for sig in sigs if sig not in sigs0.get(k, ())
                ],
                # per jit name: what is not a ledger kernel is the
                # unledgered remainder
                "jax_compiles_by_name": by_name,
            }
            if args.rehearsal:
                _strip_times(waves[name])
            print(f"wave {name}: {waves[name]}", file=sys.stderr)
            if got != asked:
                fail(f"wave {name}: asked {asked}, placed {got}")

        # -- wave A: the sample alone on the empty cluster, then the rest
        a_jobs = [c2m_job(f"a-{j}", count) for j in range(size["jobs_a"])]
        sample = a_jobs[: size["sample"]]
        density: dict = {}

        def sample_then_rest():
            yield sample
            chip_placed, chip_nodes = density_of(state, sample)
            ratio = (chip_placed / max(chip_nodes, 1)) / (
                host_placed / max(host_nodes, 1))
            density.update(
                sample_jobs=len(sample), chip_placed=chip_placed,
                chip_nodes=chip_nodes, host_placed=host_placed,
                host_nodes=host_nodes, ratio=round(ratio, 4),
            )
            if chip_placed != host_placed or ratio < 0.99:
                fail(f"density outside the 1% bound of the host oracle "
                     f"at equal load: {density}")
            yield a_jobs[size["sample"]:]

        drive("A", sample_then_rest())
        # -- wave B: the warm wave
        drive("B", [[c2m_job(f"b-{j}", count)
                     for j in range(size["jobs_b"])]])
        # -- wave C: fill to capacity, then preempt (one chip only: the
        # four-chip run is waves A and B)
        if args.mesh_devices <= 1:
            drive("C", [
                [c2m_job(f"fill-{j}", count, priority=20)
                 for j in range(fill_jobs)],
                [c2m_job(f"hi-{j}", size["preempt_count"], priority=70)
                 for j in range(size["preempt_jobs"])],
            ])
            if waves["C"]["evicted"] <= 0:
                fail("wave C placed without evicting anything: the "
                     "preempt kernel did not run")
            # what the benchmark's preempt-fill cell holds a run to
            # (reference/rules/preemption_lowest_first.py): one victim a
            # placement that needed one, none above the lowest tier
            # standing — the cluster is full of priority 20 and 50, so
            # every victim of the priority-70 wave is a fill job's
            took = metrics.registry().snapshot()["counters"]
            needing = int(took.get("nomad.tpu.preempt.placed", 0))
            above = int(took.get("nomad.tpu.preempt.evicted_above_lowest", 0))
            not_fill = sorted({a.job_id for a in state.allocs()
                               if a.desired_status == "evict"
                               and not a.job_id.startswith("fill-")})
            waves["C"].update(needed_a_victim=needing,
                              evicted_above_lowest=above)
            if waves["C"]["evicted"] != needing or above or not_fill:
                fail(f"wave C evicted {waves['C']['evicted']} allocs for "
                     f"{needing} placements that needed a victim, {above} "
                     "of them above the lowest tier standing; evicted "
                     f"jobs that are no fill job: {not_fill[:3]}")

        store = check_store(state)
        worker = srv.tpu_worker.stats_snapshot()
        counters_at_rest = metrics.registry().snapshot()
        # every node is full by the end of wave C, so every genuine
        # long-poll watcher must have seen allocations arrive
        watch = fleet.report()["real_watchers"]
        if args.mesh_devices <= 1 and not all(
            w.alloc_rounds for w in fleet.watchers
        ):
            fail(f"a blocking alloc watch saw no allocations: {watch}")
    finally:
        if fleet is not None:
            fleet.stop()
        if agent is not None:
            agent.shutdown()
        data_dir.cleanup()

    par = parity(256, 16, args.mesh_devices)

    # -- what must be true of the whole run
    snap = counters_at_rest
    failover = snap["counters"].get("nomad.worker.device_failover", 0)
    invoke_failed = snap["counters"].get("nomad.worker.invoke.failed", 0)
    native = snap["gauges"].get("nomad.native.available", 0)
    if failover or invoke_failed:
        fail(f"device_failover={failover} invoke.failed={invoke_failed}; "
             f"last logged warning: {errors.last}")
    if native != 1 and not os.environ.get("NOMAD_TPU_NO_FASTPACK"):
        fail("fastpack did not load (nomad.native.available != 1): the "
             f"hot paths ran in pure Python; last warning: {errors.last}")
    ledger = solverobs.snapshot()["ledger"]["kernels"]
    want = (["solve_placement_compact", "solve_placement_preempt"]
            if args.mesh_devices <= 1
            else [f"sharded_solver_compact_d{args.mesh_devices}"])
    for kernel in want + ["scatter_rows"]:
        if kernel not in ledger:
            fail(f"compile ledger has no row for {kernel}: {sorted(ledger)}")
    off_device = {k: row["platforms"] for k, row in ledger.items()
                  if row["platforms"] != [device.platform]}
    if off_device:
        fail(f"solver outputs not on {device.platform}: {off_device}")
    if worker["resident"]["platforms"] != [device.platform]:
        fail(f"resident tensors not on {device.platform}: {worker}")

    mem = jax.devices()[0].memory_stats() or {}
    out = {
        "mode": "rehearsal" if args.rehearsal else "chip",
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": device.count,
        "versions": {
            "jax": jax.__version__,
            "jaxlib": metadata.version("jaxlib"),
            "libtpu": metadata.version("libtpu"),
        },
        "nodes": n_nodes,
        "mesh_devices": args.mesh_devices,
        "seed": args.seed,
        "register_s": round(register_s, 3),
        "oracle_s": round(oracle_s, 3),
        "waves": waves,
        "density": density,
        "parity": par,
        "store": store,
        "compile": {
            "jax_compiles": len(counters.compiles),
            "jax_compile_s": round(sum(s for _, s in counters.compiles), 3),
            "ledger_compiles": solverobs.compiles(),
            "persistent_cache_hits": counters.cache_hits,
            "persistent_cache_misses": counters.cache_misses,
            "cache_dir": jax.config.jax_compilation_cache_dir,
        },
        "ledger_platforms": {k: r["platforms"] for k, r in ledger.items()},
        "resident": worker["resident"],
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "fastpack": native == 1,
        "device_failover": failover,
        "invoke_failed": invoke_failed,
        "watch": watch,
    }
    if args.rehearsal:
        _strip_times(out)
    return out, device.to_wire()


def _strip_times(obj) -> None:
    """A rehearsal prints no time figure: an XLA:CPU second is not a
    device second, under any name."""
    if isinstance(obj, dict):
        for k in [k for k in obj if k.endswith("_s")]:
            del obj[k]
        for v in obj.values():
            _strip_times(v)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearsal", action="store_true",
                   help="tiny run on XLA:CPU (pins JAX_PLATFORMS=cpu)")
    p.add_argument("--mesh-devices", type=int, default=0,
                   help="shard the solve over this many chips (A and B)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    for name in ("nomad_tpu.scheduler.tpu", "nomad_tpu.native"):
        logging.getLogger(name).setLevel(logging.INFO)
    try:
        report, device = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps(report))
    # the verdict: these keys and no others, last on stdout
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
