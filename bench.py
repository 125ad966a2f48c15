"""C2M-style scheduler benchmark — all five BASELINE.md configs.

Prints ONE JSON line whose headline is the c2m config:
  {"metric": "c2m_scheduler_throughput", "value": N, "unit": "evals/sec",
   "vs_baseline": N, "configs": {...per-config results...}, "caveats": [...]}

vs_baseline = TPU-batch evals/sec ÷ host-oracle evals/sec on the same
cluster/job shapes. The host oracle is this repo's faithful reimplementation
of the reference's per-eval iterator scheduler (scheduler/generic_sched.go).
The Go binary itself is not runnable here, so the oracle stands in as the
baseline denominator — see the "caveats" field: Go is typically much faster
than equivalent Python, so these ratios overstate the margin vs the actual
reference. Density parity (the ≤1% BASELINE criterion) is measured at EQUAL
placed load: the host sample's jobs are re-solved by the TPU backend on an
identical fresh cluster and allocs-per-touched-node is compared directly.

Configs (BASELINE.md "configs"; BENCH_CONFIG env selects one, default all):
  smoke   — 10 nodes, 1 job (TestServiceSched_JobRegister analog)
  c1k     — 1k nodes / 5k allocs, cpu+mem only (pure ScoreFit)
  c2m     — 10k nodes / 100k allocs with constraint+spread load
  preempt — 90%-full cluster, high-priority wave preempting a low tier
  drain   — service+system placed, then 10% of nodes drain (re-solve churn)
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time
from statistics import median

import numpy as np


def log(*args):
    print(*args, file=sys.stderr, flush=True)


# flipped when a native baseline was actually measured; gates its caveat
_NATIVE_CAVEAT = [False]

NATIVE_CAVEAT_TEXT = (
    "vs_native_cpp divides the TPU-batch rate by a measured C++ "
    "reimplementation of the scheduler's placement hot loop "
    "(bench_native/sched_bench.cc) on this machine — the Go toolchain "
    "is absent here so the reference binary cannot be built; the C++ "
    "loop excludes reconcile/plan-apply/state costs, so it OVERSTATES "
    "the native side and vs_native_cpp is a conservative lower bound"
)

CAVEATS = [
    "host oracle is this repo's Python reimplementation of the reference "
    "GenericScheduler; the Go reference is typically 30-100x faster than "
    "equivalent Python, so vs_baseline overstates the margin vs Go by "
    "roughly that factor",
    "drain config: service evals run the batched solver; the system eval "
    "runs the TPU backend's vectorized system scheduler (one lowered "
    "feasibility+capacity pass, per-node fallback for ports/devices)",
]


def build_cluster(n_nodes: int, n_jobs: int, count: int, constrained: bool,
                  priority: int = 50, job_prefix: str = "bench",
                  cpu: int = 250, mem: int = 128):
    from nomad_tpu import mock
    from nomad_tpu.gctune import paused_gc
    from nomad_tpu.structs import Constraint, Spread
    from nomad_tpu.structs.node_class import compute_node_class
    from nomad_tpu.testing import Harness

    # One bounded allocation burst (10k nodes + the job set), frozen on
    # exit: the built cluster IS resident heap, so it goes straight to
    # the permanent generation instead of being young-gen-scanned (with
    # every gc callback, jax's included) at the first post-build
    # collection (gctune.paused_gc).
    with paused_gc(freeze_on_exit=True):
        h = Harness()
        dcs = ["dc1", "dc2", "dc3", "dc4"]
        for i in range(n_nodes):
            n = mock.node()
            n.datacenter = dcs[i % len(dcs)]
            n.resources.cpu = 4000
            n.resources.memory_mb = 8192
            n.computed_class = compute_node_class(n)
            h.state.upsert_node(h.next_index(), n)
        jobs = add_jobs(h, n_jobs, count, constrained, priority, job_prefix,
                        cpu, mem)
    return h, jobs


def add_jobs(h, n_jobs, count, constrained, priority=50, job_prefix="bench",
             cpu=250, mem=128):
    from nomad_tpu import mock
    from nomad_tpu.structs import Constraint, Spread

    dcs = ["dc1", "dc2", "dc3", "dc4"]
    jobs = []
    for j in range(n_jobs):
        job = mock.job(id=f"{job_prefix}-{j}")
        job.datacenters = dcs
        job.priority = priority
        tg = job.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.cpu = cpu
        tg.tasks[0].resources.memory_mb = mem
        tg.tasks[0].resources.networks = []
        if constrained:
            job.constraints.append(
                Constraint("${attr.kernel.name}", "linux", "=")
            )
            job.spreads = [Spread(attribute="${node.datacenter}", weight=50)]
        h.state.upsert_job(h.next_index(), job)
        jobs.append(job)
    return jobs


def density(h, jobs) -> tuple[int, int]:
    """(total live placed, nodes touched)."""
    nodes = set()
    placed = 0
    for job in jobs:
        for a in h.state.allocs_by_job(job.namespace, job.id):
            if not a.terminal_status():
                placed += 1
                nodes.add(a.node_id)
    return placed, len(nodes)


def tpu_place(h, jobs, config=None, warm=True, resident=None):
    """Solve + submit all jobs' evals in one batch; returns (dt, plans).

    With BENCH_TRACE=1 every measured batch runs under a trace context
    (nomad_tpu/trace.py), so the BENCH breakdown comes from the SAME
    span machinery production serves at /v1/traces — not a parallel set
    of hand-wired timers. The trace rides the global recorder; the
    configs' summaries are published under each result's "trace" key."""
    from nomad_tpu import codec, mock, trace
    from nomad_tpu.scheduler.tpu import solve_eval_batch

    # the bulk id-minting/plan-row fast paths ride the fastpack
    # extension; resolve it here, outside any lock (codec.warm_native)
    codec.warm_native()

    from nomad_tpu.gctune import paused_gc

    snap = h.snapshot()
    if warm:
        # Warm the jit cache at the exact padded shapes of the measured
        # run — steady-state scheduling is the metric; compiles amortize
        # across the server's lifetime.
        solve_eval_batch(
            snap, h, [mock.eval_for_job(j) for j in jobs], config,
            resident=resident,
        )
    evals = [mock.eval_for_job(job) for job in jobs]
    ctx = trace.start_trace("bench.batch", evals=len(evals))
    t0 = time.perf_counter()
    # the whole solve->commit pipeline is one paused-GC section (the
    # inner solver/store sections nest): the gaps between per-eval plan
    # submissions were paying young-gen scans + the jax gc callback.
    # freeze_on_exit: the survivors are committed store rows — resident
    # heap by definition — so they skip the deferred scan entirely
    with trace.use(ctx), paused_gc(freeze_on_exit=True):
        plans = solve_eval_batch(snap, h, evals, config, resident=resident)
        with trace.span(ctx, "plan.submit"):
            for ev in evals:
                h.submit_plan(plans[ev.id])
    dt = time.perf_counter() - t0
    if ctx is not None:
        ctx.finish()
    return dt, plans


def trace_summary() -> dict | None:
    """Critical-path summary of the bench.batch traces recorded so far
    (BENCH_TRACE=1): top span names by total self-time, from the same
    machinery /v1/traces and `operator trace -summary` read. Drains the
    recorder so each config reports only its own batches."""
    from nomad_tpu import trace

    if not trace.enabled():
        return None
    rec = trace.recorder()
    summaries = rec.list(name="bench.batch", limit=100)
    traces = [rec.get(s["id"]) for s in summaries]
    traces = [t for t in traces if t is not None]
    if not traces:
        return None
    top = trace.critical_path(traces, top=8)
    out = {
        "batches": len(traces),
        "top_self_time_ms": {
            name: round(ns / 1e6, 3) for name, ns in top
        },
        "last_trace_id": summaries[0]["id"],
        "last_coverage": round(trace.coverage(traces[0]), 4),
    }
    rec.clear()
    return out


def spread_pct(vals) -> float:
    """(max-min)/median — the run-to-run noise indicator VERDICT r4
    weak #4 asked for (this box has one core; absolute numbers swing
    with load, so every reported rate carries its spread)."""
    m = median(vals)
    return round((max(vals) - min(vals)) / m * 100, 1) if m else 0.0


def latency_percentiles() -> dict:
    """Per-stage percentile breakdown from the histogram machinery
    (metrics.py) — the SAME bucket counts production serves at
    /v1/metrics and `operator top` renders, published into the BENCH
    json so the capture of record carries distributions, not just
    medians-of-rates (VERDICT r5 weak #1: single-number captures hid a
    96.6% spread). Cumulative over the config's run (main() resets the
    registry between configs)."""
    from nomad_tpu import metrics

    out = {}
    for name, s in sorted(metrics.snapshot()["samples"].items()):
        if "p50" not in s or not s.get("count"):
            continue
        out[name] = {
            "count": int(s["count"]),
            "mean": round(s["mean"], 5),
            "p50": round(s["p50"], 5),
            "p90": round(s["p90"], 5),
            "p95": round(s["p95"], 5),
            "p99": round(s["p99"], 5),
            "max": round(s["max"], 5),
        }
    return out


def solver_breakdown() -> dict:
    """Last solve's host/device/transfer split from the telemetry
    registry (solver._run_compact records each phase): what fraction of
    a solve was host-side prep+dispatch, device compute, and readback
    over the link — the device/transfer/host breakdown of VERDICT r4
    item 2."""
    from nomad_tpu import metrics

    s = metrics.snapshot()["samples"]
    out = {}
    for key, name in (
        ("nomad.tpu.host_prep_seconds", "host_prep_s"),
        ("nomad.tpu.device_seconds", "device_s"),
        ("nomad.tpu.readback_seconds", "readback_s"),
        ("nomad.tpu.materialize_seconds", "materialize_s"),
        ("nomad.tpu.commit_seconds", "commit_s"),
    ):
        v = s.get(key)
        if v is not None:
            out[name] = round(v["last"], 4)
    return out


def host_attribution_pass(n_nodes, n_jobs, count, constrained,
                          wall_target_s: float = 2.0,
                          max_passes: int = 40) -> dict:
    """Per-config host_attribution block from the always-on profiler
    (nomad_tpu/hostobs.py) — the SAME machinery production serves at
    /v1/profile/status and `operator profile status` renders.

    Dedicated un-measured passes (they follow the measured trials and
    never touch the reported rates): the profiler records for the WHOLE
    phase — cluster builds included, under span "-"; solve/submit work
    under the bench.batch/plan.submit spans — because a statistical
    sampler charges each sample with the full gap since its previous
    wakeup, and gating recording around sub-windows silently drops
    every gap that straddles a boundary (measured ~50% attribution
    loss). Tracing is enabled so every sample carries its active span;
    passes repeat on fresh clusters until >= wall_target_s of SOLVE
    wall has accumulated (sampling density for the 15% span-agreement
    check).

    Publishes:
      host_fraction     attributed busy seconds / phase wall (all on
                        the host here; on a real device the block-wait
                        site is named in top_sites rather than excluded)
      coverage          fraction of phase wall covered by NAMED (span x
                        function) sites — the >= 0.8 c2m gate: ledger
                        overflow into "(other)", sampler starvation, or
                        idle-misclassified work shows up as lost
                        coverage
      gc_share          GC pause seconds / phase wall
      top_sites         top-10 self-time sites with pct-of-wall (span
                        "-" = outside any trace, e.g. cluster build)
      span_agreement    profiler per-span busy seconds vs the traces'
                        stack-self-times over the SAME passes
                        (trace.stack_self_times: pre-timed stage spans
                        excluded — profiling.md § Span semantics), with
                        agreement_ok on every span carrying >= 20% of
                        total traced self-time and >= 0.3s absolute
    """
    from nomad_tpu import hostobs, trace as _trace
    from nomad_tpu.scheduler.tpu import ResidentClusterState

    if not hostobs.running():
        hostobs.start()
    was_traced = _trace.enabled()
    _trace.set_enabled(True)
    rec = _trace.recorder()
    rec.clear()
    prof = hostobs.profiler()
    prev_intervals = (prof.interval_s, prof.idle_interval_s)
    # dense sampling for the attribution window (2ms, idle backoff
    # pinned): the spans being checked to 15% need the sample count,
    # and a burst following a long idle build must not start at the
    # backed-off rate. Restored to the production cadence after.
    hostobs.configure(interval_s=0.002, idle_interval_s=0.002)
    # One collect + resident freeze BEFORE reset_stats and the phase
    # timer: a per-pass collect would dominate the attribution window
    # with self-inflicted gen2 scans, and the freeze
    # (gctune.freeze_resident_heap — the post-warmup mitigation
    # production runs) must not appear as a measured site or pause.
    # Per-pass cluster builds freeze their own survivors on section
    # exit (build_cluster), so the phase measures gc_share with the
    # full mitigation active.
    from nomad_tpu.gctune import freeze_resident_heap

    freeze_resident_heap()
    hostobs.reset_stats()
    solve_wall = 0.0
    passes = 0
    t_phase = time.perf_counter()
    try:
        h = jobs = None
        while solve_wall < wall_target_s and passes < max_passes:
            h = jobs = None  # refcount-drop the previous cluster
            h, jobs = build_cluster(n_nodes, n_jobs, count, constrained)
            resident = ResidentClusterState()
            dt, _ = tpu_place(h, jobs, warm=False, resident=resident)
            solve_wall += dt
            passes += 1
        wall = time.perf_counter() - t_phase
        snap = hostobs.snapshot(top=50)
        trace_self_ns: dict[str, int] = {}
        for s in rec.list(name="bench.batch", limit=max_passes):
            t = rec.get(s["id"])
            if t is None:
                continue
            for span, ns in _trace.stack_self_times(t).items():
                trace_self_ns[span] = trace_self_ns.get(span, 0) + ns
    finally:
        hostobs.configure(
            interval_s=prev_intervals[0], idle_interval_s=prev_intervals[1]
        )
        _trace.set_enabled(was_traced)
        rec.clear()
    wall = max(wall, 1e-9)
    busy = snap["busy_seconds"]
    other_s = sum(
        s["seconds"] for s in snap["top_sites"] if s["site"] == "(other)"
    )
    named_busy = max(0.0, busy - other_s)
    prof_spans = snap["spans"]
    trace_total_s = sum(trace_self_ns.values()) / 1e9
    agreement = {}
    agreement_ok = True
    for span, ns in sorted(trace_self_ns.items(), key=lambda kv: -kv[1]):
        trace_s = ns / 1e9
        if trace_s < 0.05 * trace_total_s:
            continue  # too small for sampling statistics to judge
        prof_s = prof_spans.get(span, 0.0)
        ratio = prof_s / max(trace_s, 1e-9)
        entry = {
            "trace_s": round(trace_s, 4),
            "profiler_s": round(prof_s, 4),
            "ratio": round(ratio, 3),
        }
        if trace_s >= max(0.3, 0.2 * trace_total_s):
            entry["gated"] = True
            if not (0.85 <= ratio <= 1.15):
                agreement_ok = False
        agreement[span] = entry
    out = {
        "passes": passes,
        "wall_s": round(wall, 3),
        "solve_wall_s": round(solve_wall, 3),
        "samples": snap["samples"],
        "host_fraction": round(min(busy / wall, 1.0), 4),
        "coverage": round(min(named_busy / wall, 1.0), 4),
        "gc_share": round(
            snap["gc"]["pause_seconds_total"] / wall, 5
        ),
        "gc_collections": snap["gc"]["collections"],
        "lock_waits": snap["locks"],
        "top_sites": [
            {
                "span": s["span"],
                "site": s["site"],
                "seconds": s["seconds"],
                "pct_of_wall": round(s["seconds"] / wall * 100, 2),
            }
            for s in snap["top_sites"]
            if s["site"] != "(other)"
        ][:10],
        "span_agreement": agreement,
        "span_agreement_ok": agreement_ok,
        "profiler_overhead_duty_cycle": snap["overhead"]["duty_cycle"],
    }
    log(
        f"[host_attribution] {passes} pass(es) / {wall:.1f}s wall: "
        f"host_fraction {out['host_fraction']}, coverage "
        f"{out['coverage']}, gc_share {out['gc_share']}, agreement_ok "
        f"{agreement_ok} ({ {k: v['ratio'] for k, v in agreement.items()} })"
    )
    return out


def host_place(h, jobs, config=None, scheduler="service"):
    from nomad_tpu import mock

    t0 = time.perf_counter()
    for job in jobs:
        h.process(scheduler, mock.eval_for_job(job), config)
    return time.perf_counter() - t0


def solver_observability(compiles_at_warmup=None) -> dict:
    """Per-config solver_observability block from the observatory
    (nomad_tpu/solverobs.py) — the SAME snapshot production serves at
    /v1/solver/status and `operator solver status` renders: compile
    counts, steady-state recompiles, mean occupancy, transfer bytes.
    With compiles_at_warmup, also reports recompiles_after_warmup — the
    gates.recompile_bound input (the shape-bucketing contract in
    kernels.py says steady-state batches compile NOTHING)."""
    from nomad_tpu import solverobs

    snap = solverobs.snapshot(sample=False)
    occ = snap["occupancy"]
    out = {
        "compiles": snap["ledger"]["compiles"],
        "cache_hits": snap["ledger"]["cache_hits"],
        "steady_recompiles": snap["ledger"]["steady_recompiles"],
        "mean_occupancy": occ["mean"],
        "last_occupancy": (occ["last_batch"] or {}).get("occupancy"),
        "h2d_bytes": snap["transfers"]["h2d_bytes"],
        "d2h_bytes": snap["transfers"]["d2h_bytes"],
        "allgather_bytes": snap["transfers"]["allgather_bytes"],
        "scatter_bytes": snap["transfers"]["scatter_bytes"],
        "sharding": snap["sharding"],
        "device_memory": snap["device_memory"],
        "live_array_highwater_bytes": snap["live_array_highwater_bytes"],
    }
    if compiles_at_warmup is not None:
        out["recompiles_after_warmup"] = (
            out["compiles"] - compiles_at_warmup
        )
    return out


def solver_internal_seconds():
    """Last kernel-side solve time from the telemetry registry — the
    solver records nomad.tpu.solve_seconds on every batch (VERDICT r2:
    solver timings were measured then dropped)."""
    from nomad_tpu import metrics

    s = metrics.snapshot()["samples"].get("nomad.tpu.solve_seconds")
    return round(s["last"], 4) if s else None


def run_service_config(name, n_nodes, n_jobs, count, constrained, host_sample,
                       min_trial_s: float = 0.0, trials: int = 3):
    from nomad_tpu.scheduler.tpu import ResidentClusterState

    log(f"[{name}] {n_nodes} nodes, {n_jobs} jobs x {count} allocs")
    # full-load TPU throughput: median of fresh-cluster trials (this box
    # has one core; single-run captures swung 30%+ across rounds). With
    # min_trial_s (c2m: 20s, VERDICT r7 next-round #3) each trial
    # repeats the measured pass on fresh clusters until it holds that
    # much work, so one load spike can't be a whole sample.
    from nomad_tpu import solverobs

    rates, solve_ss = [], []
    resident_syncs = []
    h = jobs = None
    rounds = 1
    from nomad_tpu.gctune import freeze_resident_heap

    if min_trial_s > 0:
        gc.collect()
        h, jobs = build_cluster(n_nodes, n_jobs, count, constrained)
        # post-warmup freeze: the first cluster's heap (and everything
        # resident beneath it — jax, the store machinery) leaves the
        # collector's sight, so measured-pass collections walk only
        # young objects (ISSUE gc tax; gctune.freeze_resident_heap)
        freeze_resident_heap()
        warm_dt, _ = tpu_place(h, jobs, resident=ResidentClusterState())
        rounds = max(1, int(-(-min_trial_s // max(warm_dt, 1e-9))))
        log(
            f"[{name}] sizing pass {warm_dt:.1f}s -> {rounds} pass(es)/"
            f"trial (>= {min_trial_s:.0f}s of work), {trials} trials"
        )
    else:
        # un-measured warmup at the measured passes' exact padded
        # shapes, so the recompile-bound gate below sees steady state
        # only (the sizing pass plays this role when min_trial_s > 0);
        # warm=False: one solve populates the ledger, no double pass
        gc.collect()
        h, jobs = build_cluster(n_nodes, n_jobs, count, constrained)
        freeze_resident_heap()
        tpu_place(h, jobs, warm=False, resident=ResidentClusterState())
    # everything compiled from here on is a steady-state recompile
    compiles_at_warmup = solverobs.compiles()
    # control bursts bracket every trial (trials+1 bursts total): trial
    # i pairs with the mean of bursts i and i+1, temporally adjacent on
    # both sides, so a co-tenant load spike slows the trial AND its
    # controls together and the normalization cancels it
    control_burst()  # untimed warmup: the first in-process burst reads
    # ~30% cold (branch/cache ramp) and would bias trial 1's pairing
    ctrl_bursts = [control_burst()]
    from nomad_tpu.gctune import release_frozen_garbage

    pass_no = 0
    for trial in range(trials):
        dt_total = 0.0
        for _ in range(rounds):
            # drop the previous pass's cluster BEFORE building the next:
            # two live c2m heaps tank the later trials (memory pressure +
            # giant old-gen scans when the paused GC re-enables)
            h = jobs = None
            pass_no += 1
            if pass_no % 8 == 0:
                # each dropped frozen cluster strands its cycles in the
                # permanent generation (~64MB/pass at c2m scale); an
                # unfreeze+collect in the untimed gap bounds RSS
                release_frozen_garbage()
            else:
                gc.collect()
            h, jobs = build_cluster(n_nodes, n_jobs, count, constrained)
            resident = ResidentClusterState()
            tpu_dt, _ = tpu_place(h, jobs, resident=resident)
            dt_total += tpu_dt
            resident_syncs.append(resident.last_sync)
        rates.append(rounds * len(jobs) / dt_total)
        ctrl_bursts.append(control_burst())
        solve_ss.append(solver_internal_seconds() or 0.0)
    tpu_rate = median(rates)
    ctrl_per_trial = [
        (ctrl_bursts[i] + ctrl_bursts[i + 1]) / 2 for i in range(trials)
    ]
    norm_rates = [
        r * CONTROL_REF_OPS_S / max(c, 1e-9)
        for r, c in zip(rates, ctrl_per_trial)
    ]
    # median of PER-TRIAL normalized rates (median-of-ratios), not the
    # normalized median: each ratio pairs a trial with ITS adjacent
    # controls, which is what makes the statistic drift-immune
    tpu_rate_norm = median(norm_rates)
    solve_s = round(median(solve_ss), 4)
    breakdown = solver_breakdown()
    # snapshot BEFORE the host/equal-load passes below: their different
    # group counts legitimately hit new buckets, and the gate is about
    # the measured steady-state passes only
    obs = solver_observability(compiles_at_warmup)
    tpu_placed, tpu_nodes = density(h, jobs)

    # host oracle on a sample (to completion)
    hh, hjobs = build_cluster(n_nodes, host_sample, count, constrained)
    host_dt = host_place(hh, hjobs)
    host_rate = len(hjobs) / host_dt
    host_placed, host_nodes = density(hh, hjobs)

    # density parity at EQUAL placed load: TPU solves the SAME sample-sized
    # problem on an identical fresh cluster (this is the ≤1% criterion)
    eh, ejobs = build_cluster(n_nodes, host_sample, count, constrained)
    tpu_place(eh, ejobs, warm=False)
    eq_placed, eq_nodes = density(eh, ejobs)

    # BENCH_TRACE summary BEFORE the attribution pass: the pass drains
    # and clears the global trace recorder for its own span-agreement
    # bookkeeping, which would otherwise destroy this config's measured
    # bench.batch traces (main()'s late trace_summary() would read an
    # empty ring and silently drop the "trace" key)
    tsum = trace_summary()

    # Drop every cluster built above BEFORE the attribution pass: with
    # the trial, host-sample, AND equal-load heaps still alive, every
    # gen2 collection during attribution scanned millions of dead-weight
    # objects (and ran the jax gc callback against them) — measured as
    # the dominant share of the r6 capture's 30% gc_share. Only the
    # density/rate SCALARS are needed past this point.
    h = jobs = hh = hjobs = eh = ejobs = None
    gc.collect()

    # host-attribution pass: where the host second goes, from the
    # always-on profiler (un-measured; follows the rate trials)
    attribution = host_attribution_pass(
        n_nodes, n_jobs, count, constrained,
        wall_target_s=2.0 if min_trial_s > 0 else 1.0,
        max_passes=60,
    )

    host_density = host_placed / max(1, host_nodes)
    eq_density = eq_placed / max(1, eq_nodes)
    ratio = eq_density / max(host_density, 1e-9)
    # the native C++ hot loop gets the same adjacent-burst treatment:
    # vs_native_cpp compares the two CONTROL-NORMALIZED rates, so a
    # load change between the tpu trials and this (later) native run
    # can't fake a ratio move
    ctrl_native_pre = control_burst()
    native = native_baseline(n_nodes, max(n_jobs, 50), count, constrained)
    ctrl_native = (ctrl_native_pre + control_burst()) / 2
    density_ok = ratio >= 0.99
    if not density_ok:
        log(
            f"[{name}] DENSITY GATE FAILED: equal-load ratio {ratio:.4f} "
            f"< 0.99 — the solver packs worse than the host oracle"
        )
    log(
        f"[{name}] control-normalized {tpu_rate_norm:.2f} evals/s "
        f"(spread {spread_pct(norm_rates)}%; adjacent control "
        f"{[round(c / 1e6, 2) for c in ctrl_per_trial]} Munits/s vs ref "
        f"{CONTROL_REF_OPS_S / 1e6:.2f})"
    )
    log(
        f"[{name}] tpu median {tpu_rate:.2f} evals/s over {trials} runs "
        f"x {rounds} passes "
        f"(spread {spread_pct(rates)}%, {tpu_placed} placed); host "
        f"{host_rate:.2f} evals/s over {host_sample} evals ({host_placed} "
        f"placed); equal-load density tpu {eq_density:.2f} vs host "
        f"{host_density:.2f} allocs/node (ratio {ratio:.3f}, "
        f"pass={density_ok}); breakdown {breakdown}; resident sync "
        f"{resident_syncs}"
    )
    log(
        f"[{name}] solver observability: {obs['compiles']} compiles "
        f"({obs['recompiles_after_warmup']} after warmup), "
        f"{obs['cache_hits']} cache hits, mean occupancy "
        f"{obs['mean_occupancy']}, h2d {obs['h2d_bytes']}B / d2h "
        f"{obs['d2h_bytes']}B"
    )
    out = {
        "tpu_evals_per_s": round(tpu_rate, 2),
        "tpu_evals_per_s_runs": [round(r, 2) for r in rates],
        "tpu_spread_pct": spread_pct(rates),
        # the drift-immune headline: per-trial rates normalized by
        # temporally-adjacent control bursts (docs/operations.md
        # "Reading a bench capture"). Raw rates above stay published —
        # they are this box's actual throughput — but only the
        # normalized figure is comparable across captures.
        "control_normalized_evals_per_s": round(tpu_rate_norm, 2),
        "control_normalized_runs": [round(r, 2) for r in norm_rates],
        "control_normalized_spread_pct": spread_pct(norm_rates),
        "control_ref_ops_s": CONTROL_REF_OPS_S,
        "control_ops_s_runs": [round(c) for c in ctrl_per_trial],
        "passes_per_trial": rounds,
        "tpu_solver_internal_s": solve_s,
        "solve_breakdown": breakdown,
        "solver_observability": obs,
        "host_attribution": attribution,
        **({"trace": tsum} if tsum is not None else {}),
        "resident_sync_modes": resident_syncs,
        "host_evals_per_s": round(host_rate, 2),
        "host_sample_evals": host_sample,
        "vs_host": round(tpu_rate / host_rate, 2),
        "tpu_placed": tpu_placed,
        "host_placed": host_placed,
        "equal_load_density_tpu": round(eq_density, 3),
        "equal_load_density_host": round(host_density, 3),
        "equal_load_density_ratio": round(ratio, 4),
        "density_within_1pct": density_ok,
    }
    if native is not None:
        native_norm = (
            native["evals_per_s"] * CONTROL_REF_OPS_S / max(ctrl_native, 1e-9)
        )
        out["native_cpp_evals_per_s"] = native["evals_per_s"]
        out["native_cpp_normalized_evals_per_s"] = round(native_norm, 2)
        out["vs_native_cpp_raw"] = round(
            tpu_rate / max(native["evals_per_s"], 1e-9), 4
        )
        # the PAIRED statistic: both sides normalized by their own
        # adjacent controls — the gated figure
        out["vs_native_cpp"] = round(
            tpu_rate_norm / max(native_norm, 1e-9), 4
        )
        _NATIVE_CAVEAT[0] = True
        log(
            f"[{name}] native C++ hot loop {native['evals_per_s']:.0f} "
            f"evals/s ({native_norm:.0f} control-normalized) -> "
            f"vs_native_cpp {out['vs_native_cpp']} (raw "
            f"{out['vs_native_cpp_raw']})"
        )
    return out


def run_preempt_config():
    """BASELINE config 4: oversubscription → preemption across tiers."""
    from nomad_tpu.scheduler.context import SchedulerConfig

    n_nodes, fill_jobs, fill_count = 500, 25, 180
    hi_jobs, hi_count = 20, 50
    log(
        f"[preempt] {n_nodes} nodes, fill {fill_jobs}x{fill_count} @prio20, "
        f"wave {hi_jobs}x{hi_count} @prio70"
    )
    cfg = SchedulerConfig(preemption_service=True)

    def build():
        h, fills = build_cluster(
            n_nodes, fill_jobs, fill_count, False, priority=20,
            job_prefix="fill", cpu=400, mem=800,
        )
        tpu_place(h, fills, warm=False)  # setup, not measured
        his = add_jobs(h, hi_jobs, hi_count, False, priority=70,
                       job_prefix="hi", cpu=400, mem=800)
        return h, fills, his

    # TPU: one batched preemption solve (priority-tier kernel),
    # median of 3 fresh builds
    rates = []
    h = fills = his = None
    for _ in range(3):
        h = fills = his = None
        gc.collect()
        h, fills, his = build()
        tpu_dt, plans = tpu_place(h, his, cfg)
        rates.append(len(his) / tpu_dt)
    tpu_rate = median(rates)
    tpu_placed, _ = density(h, his)
    tpu_preempted = sum(
        len(v) for p in plans.values() for v in p.node_preemptions.values()
    )

    # host oracle: per-eval preemption scoring, all 20 evals
    hh, _, hhis = build()
    host_dt = host_place(hh, hhis, cfg)
    host_rate = len(hhis) / host_dt
    host_placed, _ = density(hh, hhis)
    host_preempted = sum(
        1
        for p in hh.plans
        for allocs in p.node_preemptions.values()
        for _ in allocs
    )
    log(
        f"[preempt] tpu {tpu_rate:.2f} evals/s, placed {tpu_placed}, "
        f"preempted {tpu_preempted}; host {host_rate:.2f} evals/s, placed "
        f"{host_placed}, preempted {host_preempted}"
    )
    return {
        "tpu_evals_per_s": round(tpu_rate, 2),
        "tpu_evals_per_s_runs": [round(r, 2) for r in rates],
        "tpu_spread_pct": spread_pct(rates),
        "host_evals_per_s": round(host_rate, 2),
        "host_sample_evals": len(hhis),
        "vs_host": round(tpu_rate / host_rate, 2),
        "tpu_placed": tpu_placed,
        "host_placed": host_placed,
        "tpu_preempted": tpu_preempted,
        "host_preempted": host_preempted,
    }


def run_drain_config():
    """BASELINE config 5: mixed service+system under node-drain churn."""
    from nomad_tpu import mock
    from nomad_tpu.scheduler.tpu import solve_eval_batch
    from nomad_tpu.structs import DrainStrategy

    n_nodes, svc_jobs, svc_count, drain_n = 1000, 20, 100, 100
    log(
        f"[drain] {n_nodes} nodes, {svc_jobs}x{svc_count} service + 1 system "
        f"job, drain {drain_n} nodes"
    )

    def build():
        h, svcs = build_cluster(n_nodes, svc_jobs, svc_count, False)
        tpu_place(h, svcs, warm=False)
        sysjob = mock.system_job(id="bench-sys")
        sysjob.datacenters = ["dc1", "dc2", "dc3", "dc4"]
        sysjob.task_groups[0].tasks[0].resources.cpu = 100
        sysjob.task_groups[0].tasks[0].resources.memory_mb = 64
        sysjob.task_groups[0].tasks[0].resources.networks = []
        h.state.upsert_job(h.next_index(), sysjob)
        h.process("system", mock.eval_for_job(sysjob))
        return h, svcs, sysjob

    def drain_nodes(h):
        from nomad_tpu.structs.structs import DesiredTransition

        nodes = h.state.nodes()[:drain_n]
        for n in nodes:
            h.state.update_node_drain(
                h.next_index(), n.id, DrainStrategy(deadline_s=300)
            )
        # The node drainer marks each draining node's allocs for
        # migration (drainer.py / reference drainer/watch_nodes.go);
        # without the marks a drain eval is a no-op and the config
        # measures nothing but reconcile overhead.
        drained = {n.id for n in nodes}
        marks = {
            a.id: DesiredTransition(migrate=True)
            for nid in drained
            for a in h.state.allocs_by_node_terminal(nid, False)
        }
        h.state.update_alloc_desired_transition(h.next_index(), marks, [])
        return drained

    def drain_evals(h, svcs, sysjob, drained):
        from nomad_tpu import mock as m

        evs = []
        for job in svcs:
            if any(
                a.node_id in drained and not a.terminal_status()
                for a in h.state.allocs_by_job(job.namespace, job.id)
            ):
                evs.append(m.eval_for_job(job, triggered_by="node-update"))
        return evs, m.eval_for_job(sysjob, triggered_by="node-update")

    # TPU path: batched solve for services, vectorized system scheduler;
    # median of 3 fresh builds (drain was the noisiest config in r4)
    from nomad_tpu.scheduler.context import SchedulerConfig

    tpu_cfg = SchedulerConfig(backend="tpu")
    rates = []
    h = svcs = sysjob = None
    for _ in range(3):
        h = svcs = sysjob = None
        gc.collect()
        h, svcs, sysjob = build()
        drained = drain_nodes(h)
        evs, sysev = drain_evals(h, svcs, sysjob, drained)
        # warm at post-drain shapes against a throwaway snapshot
        solve_eval_batch(h.snapshot(), h, [mock.eval_for_job(j) for j in svcs])
        t0 = time.perf_counter()
        plans = solve_eval_batch(h.snapshot(), h, evs)
        for ev in evs:
            h.submit_plan(plans[ev.id])
        h.process("system", sysev, tpu_cfg)
        tpu_dt = time.perf_counter() - t0
        rates.append((len(evs) + 1) / tpu_dt)
    n_evals = len(evs) + 1
    tpu_rate = median(rates)
    tpu_placed, _ = density(h, svcs)

    # host path: identical cluster, same drain, host scheduler throughout
    hh, hsvcs, hsysjob = build()
    hdrained = drain_nodes(hh)
    hevs, hsysev = drain_evals(hh, hsvcs, hsysjob, hdrained)
    t0 = time.perf_counter()
    for ev in hevs:
        hh.process("service", ev)
    hh.process("system", hsysev)
    host_dt = time.perf_counter() - t0
    host_rate = (len(hevs) + 1) / host_dt
    host_placed, _ = density(hh, hsvcs)
    log(
        f"[drain] {n_evals} drain evals: tpu {tpu_rate:.2f} evals/s "
        f"({tpu_placed} live), host {host_rate:.2f} evals/s "
        f"({host_placed} live)"
    )
    return {
        "tpu_evals_per_s": round(tpu_rate, 2),
        "tpu_evals_per_s_runs": [round(r, 2) for r in rates],
        "tpu_spread_pct": spread_pct(rates),
        "host_evals_per_s": round(host_rate, 2),
        "host_sample_evals": len(hevs) + 1,
        "vs_host": round(tpu_rate / host_rate, 2),
        "drain_evals": n_evals,
        "tpu_live_after_drain": tpu_placed,
        "host_live_after_drain": host_placed,
    }


def native_baseline(n_nodes, n_evals, count, constrained) -> dict | None:
    """Measured native-code calibration (VERDICT r3 next-round #1b).

    The Go toolchain is absent in this environment, so the reference
    scheduler cannot be built here; bench_native/sched_bench.cc is a
    C++ reimplementation of the host scheduler's per-eval placement
    loop (feasibility + power-of-N-choices + ScoreFitBinPack) measured
    on THIS machine — a compiled-language stand-in with a measured
    basis instead of the former "Go is 30-100x faster" hand-wave. It
    deliberately excludes reconcile/plan-apply/state costs, making the
    native denominator FASTER than a full Go pass and vs_native
    conservative for the TPU side."""
    import hashlib
    import shutil
    import subprocess
    from pathlib import Path

    src = Path(__file__).parent / "bench_native" / "sched_bench.cc"
    # the only quiet exit: a box with no C++ compiler has no native
    # comparator. A build or a run that FAILS raises — a crashed child
    # must end the capture non-zero, not drop a key from it.
    if shutil.which("g++") is None:
        return None
    tag = hashlib.sha256(src.read_bytes()).hexdigest()[:12]
    cache = Path(
        os.environ.get("NOMAD_TPU_BIN_DIR")
        or Path.home() / ".cache" / "nomad_tpu" / "bin"
    )
    out = cache / f"nomad-sched-bench-{tag}"
    if not out.exists():
        cache.mkdir(parents=True, exist_ok=True)
        tmp = str(out) + ".tmp"
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-o", tmp, str(src)],
            capture_output=True, text=True, timeout=120, check=True,
        )
        os.replace(tmp, out)
    proc = subprocess.run(
        [str(out), str(n_nodes), str(n_evals), str(count),
         "1" if constrained else "0"],
        capture_output=True, text=True, timeout=300, check=True,
    )
    return json.loads(proc.stdout)


def run_plan_apply_config():
    """Applier-side throughput at c2m scale (VERDICT r3 next-round #2).

    Solver-produced plans flow plan queue → batched applier (one
    enqueue_batch item: per-node conflict partition → merged verify →
    ONE raft apply with a bulk store transaction; conflicting plans fall
    back serial — plan_apply.py). Reports queue→applied evals/s and its
    ratio to the solver-internal rate; the gate is apply_vs_solve >= 0.6
    on the trial medians so verification never becomes the pipeline's
    bottleneck (reference overlaps these the thread way,
    plan_apply.go:54-63 + plan_apply_pool.go:18).

    Bench hygiene (r5 verdict weak #1 + r7 next-round #3: the gate
    margin sat inside load noise and single-pass trials swung 96.6%
    run-to-run): one un-measured warmup pass sizes the trial — each
    measured trial repeats the solve+apply cycle on fresh clusters
    until it holds >= BENCH_MIN_TRIAL_S (default 20s) of work, so a
    scheduler-tick load spike is amortized instead of being the whole
    sample; 5 trials, gate on the median at apply_vs_solve >= 0.6."""
    from nomad_tpu import mock
    from nomad_tpu.scheduler.tpu import solve_eval_batch
    from nomad_tpu.server.plan_apply import PlanApplier
    from nomad_tpu.server.plan_queue import PlanQueue
    from nomad_tpu.server.raft import FSM, InmemLog

    n_nodes, n_jobs, count = SERVICE_CONFIGS["c2m"][:3]
    trials = max(1, int(os.environ.get("BENCH_PLAN_APPLY_TRIALS", "5")))
    min_trial_s = float(os.environ.get("BENCH_MIN_TRIAL_S", "20"))
    solve_rates, apply_rates, merged_counts = [], [], []
    apply_dts = []
    results = None
    rounds = 1

    from nomad_tpu.gctune import release_frozen_garbage

    pass_no = [0]

    def one_pass():
        """Fresh cluster, one solve + one batched apply; returns the
        timed (solve_dt, apply_dt) with build cost excluded."""
        nonlocal results
        pass_no[0] += 1
        if pass_no[0] % 8 == 0:
            # reclaim the dropped clusters' frozen cycles (see the
            # c2m trial loop) — this config leaks the same ~64MB/pass
            release_frozen_garbage()
        else:
            gc.collect()
        h, jobs = build_cluster(n_nodes, n_jobs, count, constrained=True)
        snap = h.snapshot()
        solve_eval_batch(snap, h, [mock.eval_for_job(j) for j in jobs])
        evals = [mock.eval_for_job(j) for j in jobs]
        t0 = time.perf_counter()
        plans = solve_eval_batch(snap, h, evals)
        solve_dt = time.perf_counter() - t0

        state = h.state
        raft_log = InmemLog(FSM(state), start_index=state.latest_index())
        queue = PlanQueue()
        queue.set_enabled(True)
        applier = PlanApplier(
            queue, state, raft_log.apply, raft_log.apply_async
        )
        applier.start()
        t0 = time.perf_counter()
        futs = queue.enqueue_batch([plans[ev.id] for ev in evals])
        results = [f.result(timeout=300) for f in futs]
        apply_dt = time.perf_counter() - t0
        applier.stop()
        queue.set_enabled(False)
        return solve_dt, apply_dt

    # warmup: jit, codec, allocator pools all hot now — and the pass
    # duration sizes the measured trials to >= min_trial_s of work
    warm_solve, warm_apply = one_pass()
    rounds = max(
        1, int(-(-min_trial_s // max(warm_solve + warm_apply, 1e-9)))
    )
    log(
        f"[plan_apply] {n_nodes} nodes, {n_jobs} plans x {count} allocs: "
        f"warmup pass {warm_solve + warm_apply:.1f}s -> {rounds} "
        f"pass(es)/trial (>= {min_trial_s:.0f}s of work), {trials} trials"
    )
    for _ in range(trials):
        t_solve = t_apply = 0.0
        for _ in range(rounds):
            s_dt, a_dt = one_pass()
            t_solve += s_dt
            t_apply += a_dt
        solve_rates.append(rounds * n_jobs / t_solve)
        apply_rates.append(rounds * n_jobs / t_apply)
        apply_dts.append(t_apply / rounds)
        from nomad_tpu import metrics as _metrics

        s = _metrics.snapshot()["samples"].get(
            "nomad.plan_apply.batch_merged"
        )
        merged_counts.append(int(s["last"]) if s else 0)
    applied = sum(
        len(v) for r in results for v in r.node_allocation.values()
    )
    apply_rate = median(apply_rates)
    solve_rate = median(solve_rates)
    ratio = apply_rate / solve_rate
    breakdown = solver_breakdown()
    # the queue->applied wall time of one whole batch IS the commit
    # stage here (the worker records nomad.tpu.commit_seconds live)
    breakdown["commit_s"] = round(median(apply_dts), 4)
    log(
        f"[plan_apply] solve median {solve_rate:.2f} evals/s, apply "
        f"median {apply_rate:.2f} evals/s over {trials} trials x "
        f"{rounds} passes (spread {spread_pct(apply_rates)}%, {applied} "
        f"allocs committed/pass, {merged_counts} plans merged/batch), "
        f"apply/solve {ratio:.2f} on medians (pass={ratio >= 0.6}); "
        f"breakdown {breakdown}"
    )
    return {
        "apply_evals_per_s": round(apply_rate, 2),
        "apply_evals_per_s_runs": [round(r, 2) for r in apply_rates],
        "apply_spread_pct": spread_pct(apply_rates),
        "solve_evals_per_s": round(solve_rate, 2),
        "solve_evals_per_s_runs": [round(r, 2) for r in solve_rates],
        "passes_per_trial": rounds,
        "min_trial_s": min_trial_s,
        "apply_vs_solve": round(ratio, 3),
        "allocs_committed": applied,
        "plans_merged_per_batch": merged_counts,
        "stage_breakdown": breakdown,
        "apply_vs_solve_ge_0_6": ratio >= 0.6,
    }


class _MiniServer:
    """Just enough server for a TPUBatchWorker: broker + queue + applier
    + raft-backed state (the real Server wires identically). Shared by
    the pipeline and smoke_interactive configs."""

    def __init__(self, state):
        from nomad_tpu.server.eval_broker import EvalBroker
        from nomad_tpu.server.plan_apply import PlanApplier
        from nomad_tpu.server.plan_queue import PlanQueue
        from nomad_tpu.server.raft import FSM, InmemLog

        self.state = state
        self.fsm = FSM(state)
        self.log = InmemLog(self.fsm, start_index=state.latest_index())
        self.eval_broker = EvalBroker()
        self.eval_broker.set_enabled(True)
        self.plan_queue = PlanQueue()
        self.plan_queue.set_enabled(True)
        self.plan_applier = PlanApplier(
            self.plan_queue, state, self.raft_apply, self.raft_apply_async
        )
        self.plan_applier.start()
        # partial-commit retry evals must re-enqueue (the real Server's
        # FSM side channel) or a worker could silently drop conflicted
        # work and look faster than it is
        self.fsm.on_eval_update = self._on_eval_update

    def _on_eval_update(self, evals):
        for ev in evals:
            if ev.should_enqueue():
                self.eval_broker.enqueue(ev)

    def raft_apply(self, msg_type, payload):
        return self.log.apply(msg_type, payload)

    def raft_apply_async(self, msg_type, payload):
        return self.log.apply_async(msg_type, payload)

    def shutdown(self):
        self.plan_applier.stop()
        self.plan_queue.set_enabled(False)
        self.eval_broker.set_enabled(False)


def run_pipeline_config():
    """Solve/commit overlap proof (round-6 tentpole acceptance): with a
    simulated 0.15s device round-trip injected into every dense solve
    (SchedulerConfig.inject_device_latency_s — a sleep model, not a
    measured device), the two-stage TPUBatchWorker must beat
    the non-overlapped solve-then-commit loop on the same workload by
    >= 1.5x. This is the evidence VERDICT r5 item #2 called testable
    without the chip: batch N+1's dequeue/lower/device dispatch runs
    while batch N's plans materialize and commit."""
    from nomad_tpu import mock
    from nomad_tpu.scheduler.context import SchedulerConfig
    from nomad_tpu.scheduler.tpu import solve_eval_batch
    from nomad_tpu.server.worker import TPUBatchWorker

    n_nodes = int(os.environ.get("BENCH_PIPE_NODES", "2000"))
    # 64 jobs x 300 allocs = 60% fill of the 2k-node cluster across 8
    # batches — enough batches that pipeline fill/drain doesn't
    # dominate, and per-batch host work comparable to the injected RTT
    # so the overlap (not the GIL floor) is what's measured
    n_jobs = int(os.environ.get("BENCH_PIPE_JOBS", "64"))
    count = int(os.environ.get("BENCH_PIPE_COUNT", "300"))
    batch_size = int(os.environ.get("BENCH_PIPE_BATCH", "8"))
    latency = float(os.environ.get("BENCH_INJECT_LATENCY_S", "0.15"))
    log(
        f"[pipeline] {n_nodes} nodes, {n_jobs} jobs x {count} allocs, "
        f"batches of {batch_size}, injected device RTT {latency}s"
    )

    def run_once(pipeline: bool) -> float:
        gc.collect()
        h, jobs = build_cluster(n_nodes, n_jobs, count, False)
        cfg = SchedulerConfig(
            backend="tpu", inject_device_latency_s=latency
        )
        # warm the jit cache at the per-batch shapes, un-measured
        warm_cfg = SchedulerConfig(backend="tpu")
        solve_eval_batch(
            h.snapshot(), h,
            [mock.eval_for_job(j) for j in jobs[:batch_size]], warm_cfg,
        )
        srv = _MiniServer(h.state)
        worker = TPUBatchWorker(
            srv, batch_size=batch_size, config=cfg, pipeline=pipeline
        )
        for job in jobs:
            srv.eval_broker.enqueue(mock.eval_for_job(job))

        def all_placed():
            # end-to-end completion: every job's allocs COMMITTED, not
            # just evals acked — retries (if any) are paid, not dropped
            for job in jobs:
                live = sum(
                    1
                    for a in h.state.allocs_by_job(job.namespace, job.id)
                    if not a.terminal_status()
                )
                if live < count:
                    return False
            return True

        t0 = time.perf_counter()
        worker.start()
        deadline = t0 + 600
        # coarse poll: all_placed() walks every job's allocs under the
        # GIL, so a tight poll steals cycles from the very overlap being
        # measured
        while not all_placed() and time.perf_counter() < deadline:
            time.sleep(0.05)
        dt = time.perf_counter() - t0
        done = all_placed()
        worker.stop()
        srv.shutdown()
        if not done:
            log(f"[pipeline] WARNING: workload incomplete after {dt:.0f}s")
            incomplete[0] += 1
        return n_jobs / dt

    incomplete = [0]
    piped, serial = [], []
    for _ in range(3):
        piped.append(run_once(pipeline=True))
        serial.append(run_once(pipeline=False))
    piped_rate, serial_rate = median(piped), median(serial)
    # the verdict is the MEDIAN OF TEMPORALLY-ADJACENT PAIR RATIOS, not
    # a ratio of medians: both comparator sides drift together over a
    # full-capture run (shared-host co-tenancy — the round-13 overhead
    # gate's measured finding), and pairing cancels exactly the drift
    # that cross-run medians pair badly against
    pair_ratios = [p / max(s, 1e-9) for p, s in zip(piped, serial)]
    ratio = median(pair_ratios)
    # Gate re-based 1.5 -> 1.3 with the round-16 device-model fix: the
    # injected RTT is now a SERIALLY-BUSY queue (one modeled chip —
    # solver._inject_rtt), where the old model let two in-flight
    # batches' windows overlap like a second device and the measured
    # ratio rode that to 1.74-1.82. Under the honest model the ideal
    # ratio is (host + rtt) / max(host, rtt); for this config's shape
    # (host ~0.09s, rtt 0.15s) that ceiling is ~1.6, and the gate holds
    # the measured overlap at >= ~80% of it. ideal_overlap_ratio is
    # published per run so the gate's headroom is always visible.
    host_s = max(n_jobs / max(serial_rate, 1e-9) / (n_jobs / batch_size)
                 - latency, 1e-9)
    ideal = (host_s + latency) / max(host_s, latency)
    # Gate re-based again (r10): >= 0.8 x the IN-RUN ideal, which is
    # what the 1.3 bar always encoded (0.8 x the then-current ~1.6
    # ceiling). A static bar punishes host-side speedups: faster host
    # passes shrink host_s, the ceiling falls toward 1 (less host work
    # to hide under the RTT), and the fixed 1.3 ends up ABOVE the
    # theoretical maximum. Gating on the fraction-of-ideal keeps the
    # claim ("the overlap machinery hides most of what is hideable")
    # invariant under host-phase perf changes.
    ok = ratio >= 0.8 * ideal and incomplete[0] == 0
    log(
        f"[pipeline] pipelined {piped_rate:.2f} evals/s (spread "
        f"{spread_pct(piped)}%) vs non-overlapped {serial_rate:.2f} "
        f"(spread {spread_pct(serial)}%) -> overlap ratio {ratio:.2f} "
        f"(pairs {[round(r, 2) for r in pair_ratios]}, ideal "
        f"{ideal:.2f} under the serialized device model, pass={ok})"
    )
    return {
        "pipelined_evals_per_s": round(piped_rate, 2),
        "pipelined_runs": [round(r, 2) for r in piped],
        "pipelined_spread_pct": spread_pct(piped),
        "non_overlapped_evals_per_s": round(serial_rate, 2),
        "non_overlapped_runs": [round(r, 2) for r in serial],
        "non_overlapped_spread_pct": spread_pct(serial),
        "injected_device_latency_s": latency,
        "incomplete_runs": incomplete[0],
        "overlap_ratio": round(ratio, 3),
        "overlap_pair_ratios": [round(r, 3) for r in pair_ratios],
        "ideal_overlap_ratio": round(ideal, 3),
        "overlap_ge_0_8_ideal": ok,
    }


# The round-8 smoke single-eval wall (1 / 220.38 evals/s, an XLA:CPU
# figure from a box that is gone; its capture file left the tree with
# PR 21): the basis of the smoke_interactive_p50 gate — the interactive
# fast path must land a single eval in at most HALF this, measured with
# the same solve+submit methodology. What the gate should be is the
# benchmark PR's decision (ROADMAP S1/S2).
R08_SMOKE_EVAL_S = 1.0 / 220.38

# Control-workload yardstick (the drift-immune c2m verdict): units/s of
# control_burst() on this box measured near-idle at r10 calibration
# time, the same pin-a-constant discipline as R08_SMOKE_EVAL_S. This
# box's background co-tenancy drifts the measured host throughput
# +/-40% across captures on UNCHANGED code (r07->r09 re-measured 122.3
# -> 113.3 -> 79.9); the control bursts ride temporally adjacent to
# every measured trial, so each trial's normalized rate cancels the
# load that slowed both — the r13 paired-adjacent-ratio recipe that
# already made the pipeline-overlap and interactive gates load-proof.
# Pinned from each leg's best observed steady rate on this box (LCG
# 9.9 Mops/s, 128MB sweep 15.5ms): ref = total units / (lcg_s + mem_s)
# at those healths. The box's effective CPU speed itself drifts ~40%
# across hour windows (LCG alone read 6.9 and 9.9 Mops/s on the same
# idle box) — which is WHY rates gate on the paired-control statistic.
CONTROL_REF_OPS_S = 524_000_000.0
# Two legs sized ~equal near-idle, matching the measured pass's mix:
#   interpreter leg — integer LCG, register-only (zero memory traffic):
#     tracks interpreter/ALU throughput, which the host-side scheduler
#     phases ride on. ~0.4s.
#   memory leg — repeated full sweeps of a fixed 128MB buffer: tracks
#     memory-subsystem bandwidth, which the XLA solve phase rides on.
#     An ALU-only control is BLIND to co-tenant cache/bandwidth
#     pressure (measured in the first r10 attempt: device phase slowed
#     17% while the LCG leg slowed 2%) — this leg slows with it. ~0.4s.
CONTROL_LCG_OPS = 4_000_000
CONTROL_MEM_SWEEPS = 24
CONTROL_MEM_WORDS = 16_777_216  # int64 words: one 128MB sweep
_CONTROL_SINK = [0]
_CONTROL_BUF: list = [None]


def control_burst() -> float:
    """Fixed two-leg in-run control workload — deterministic work, no
    jax/device touch — as a yardstick for the interpreter AND
    memory-subsystem throughput every measured pass rides on. ~0.8s per
    burst: long enough that OS scheduling jitter stays ~2% (0.2s bursts
    measured 20-40% swings). Returns units/s (units = LCG ops + summed
    words, a fixed constant); a trial's control-normalized rate is
    raw * CONTROL_REF_OPS_S / (mean of its two adjacent bursts)."""
    buf = _CONTROL_BUF[0]
    if buf is None:
        buf = _CONTROL_BUF[0] = np.arange(CONTROL_MEM_WORDS, dtype=np.int64)
    x = 1
    acc = 0
    t0 = time.perf_counter()
    for _ in range(CONTROL_LCG_OPS):
        x = (x * 1103515245 + 12345) & 0xFFFFFFFF
    for _ in range(CONTROL_MEM_SWEEPS):
        acc += int(buf.sum())
    dt = time.perf_counter() - t0
    _CONTROL_SINK[0] = x ^ acc  # defeat a hypothetical dead-code elision
    return (CONTROL_LCG_OPS + CONTROL_MEM_SWEEPS * CONTROL_MEM_WORDS) / dt


def run_smoke_interactive_config():
    """Interactive single-eval latency, three views (ISSUE 15 tentpole
    yardstick):

      direct — N fresh-cluster single-eval passes (solve via the host
        microsolve + plan submit), the SAME methodology the r08 smoke
        capture used. Gate: p50 <= R08_SMOKE_EVAL_S / 2 — the "2x
        single-eval latency" acceptance, apples to apples.
      lane (unloaded) — the full worker stack (broker -> priority lane
        -> microsolve -> plan applier -> raft), one eval at a time:
        what a quiet cluster's `job register` actually pays end to end.
      lane (loaded) — the same stack while a mega-batch stream (with
        the modeled 0.15s device RTT) saturates the worker: the
        priority lane must keep interactive p50 far below the batch
        cadence. Gate: loaded interactive p50 <= 1/4 of the batch
        lane's p50 — without the lane an interactive eval rides a mega
        batch and pays exactly that batch p50.

    The per-stage milliseconds (dispatch / micro / submit / commit
    p50s) are published in remaining_ms_p50 — the round-12 profiler's
    naming of where the interactive millisecond goes — and every
    nomad.worker.lane.* counter lands in the payload."""
    from nomad_tpu import metrics as _metrics
    from nomad_tpu import mock
    from nomad_tpu.scheduler.context import SchedulerConfig
    from nomad_tpu.scheduler.tpu import ResidentClusterState, solve_eval_batch
    from nomad_tpu.server.worker import TPUBatchWorker

    direct_passes = int(os.environ.get("BENCH_IA_DIRECT", "30"))
    lane_evals = int(os.environ.get("BENCH_IA_LANE", "30"))
    loaded_probes = int(os.environ.get("BENCH_IA_LOADED", "16"))
    latency = float(os.environ.get("BENCH_INJECT_LATENCY_S", "0.15"))
    log(
        f"[smoke_interactive] {direct_passes} direct passes, "
        f"{lane_evals} unloaded + {loaded_probes} loaded lane evals, "
        f"mega-batch RTT {latency}s"
    )

    def wait_live(h, job, want, deadline_s=30.0):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < deadline_s:
            live = sum(
                1
                for a in h.state.allocs_by_job(job.namespace, job.id)
                if not a.terminal_status()
            )
            if live >= want:
                return True
            time.sleep(0.0002)
        return False

    # -- direct: the r08-methodology single-eval wall -------------------
    gc.collect()
    direct = []
    h, jobs = build_cluster(10, 1, 10, False)
    resident = ResidentClusterState()
    tpu_place(h, jobs, warm=False, resident=resident)  # warm caches
    for i in range(direct_passes):
        h = jobs = None
        h, jobs = build_cluster(10, 1, 10, False)
        resident = ResidentClusterState()
        dt, _ = tpu_place(h, jobs, resident=resident)
        direct.append(dt)
    direct_p50 = median(direct)
    micro_s = _metrics.snapshot()["samples"].get("nomad.tpu.micro_seconds")
    direct_used_micro = bool(micro_s and micro_s.get("count"))

    # -- lane, unloaded: full worker stack, one eval at a time ----------
    h, jobs = build_cluster(10, 1, 10, False)
    srv = _MiniServer(h.state)
    worker = TPUBatchWorker(
        srv, batch_size=8, config=SchedulerConfig(backend="tpu")
    )
    worker.start()
    unloaded = []
    ia_jobs = add_jobs(h, lane_evals, 1, False, priority=70,
                       job_prefix="ia-quiet")
    for job in ia_jobs:
        t0 = time.perf_counter()
        srv.eval_broker.enqueue(mock.eval_for_job(job))
        ok = wait_live(h, job, 1)
        unloaded.append(time.perf_counter() - t0)
        if not ok:
            log(f"[smoke_interactive] WARNING: {job.id} never placed")
    worker.stop()
    srv.shutdown()
    unloaded_p50 = median(unloaded[2:] or unloaded)

    # -- lane, loaded: interactive probes against a mega-batch stream --
    gc.collect()
    h, mega = build_cluster(400, 24, 100, False)
    cfg = SchedulerConfig(backend="tpu", inject_device_latency_s=latency)
    # warm the jit cache at the mega-batch shapes, un-measured
    solve_eval_batch(
        h.snapshot(), h,
        [mock.eval_for_job(j) for j in mega[:8]],
        SchedulerConfig(backend="tpu"),
    )
    srv = _MiniServer(h.state)
    worker = TPUBatchWorker(srv, batch_size=8, config=cfg)
    worker.start()
    for job in mega:
        srv.eval_broker.enqueue(mock.eval_for_job(job))
    loaded = []
    ia2 = add_jobs(h, loaded_probes, 2, False, priority=70,
                   job_prefix="ia-loaded")
    time.sleep(0.3)  # let the mega stream occupy the pipeline first
    for job in ia2:
        t0 = time.perf_counter()
        srv.eval_broker.enqueue(mock.eval_for_job(job))
        ok = wait_live(h, job, 2)
        loaded.append(time.perf_counter() - t0)
        if not ok:
            log(f"[smoke_interactive] WARNING: {job.id} never placed")
        time.sleep(0.05)
    # drain the mega stream so the batch-lane histogram is complete
    deadline = time.perf_counter() + 300
    while time.perf_counter() < deadline:
        done = all(
            sum(
                1
                for a in h.state.allocs_by_job(j.namespace, j.id)
                if not a.terminal_status()
            ) >= 100
            for j in mega
        )
        if done:
            break
        time.sleep(0.05)
    worker.stop()
    srv.shutdown()
    loaded_p50 = median(loaded)

    snap = _metrics.snapshot()
    samples = snap["samples"]
    counters = snap["counters"]
    batch_s = samples.get("nomad.worker.lane.batch_seconds") or {}
    batch_p50 = batch_s.get("p50")
    # where the interactive millisecond goes (profiler/stage naming)
    remaining = {}
    for key, name in (
        ("nomad.tpu.batch_dispatch_seconds", "dispatch"),
        ("nomad.tpu.micro_seconds", "micro_solve"),
        ("nomad.plan.submit_seconds", "plan_submit"),
        ("nomad.tpu.commit_seconds", "commit"),
        ("nomad.broker.wait_seconds", "broker_wait"),
    ):
        s = samples.get(key)
        if s and s.get("count"):
            remaining[name] = round(s["p50"] * 1e3, 3)
    lanes = {
        k.rsplit(".", 1)[1]: int(v)
        for k, v in counters.items()
        if k.startswith("nomad.worker.lane.")
    }
    p50_gate = direct_p50 <= R08_SMOKE_EVAL_S / 2
    lane_gate = (
        batch_p50 is not None and loaded_p50 <= 0.25 * batch_p50
    )
    log(
        f"[smoke_interactive] direct p50 {direct_p50 * 1e3:.2f}ms (gate "
        f"<= {R08_SMOKE_EVAL_S / 2 * 1e3:.2f}ms, pass={p50_gate}); lane "
        f"unloaded p50 {unloaded_p50 * 1e3:.2f}ms; loaded p50 "
        f"{loaded_p50 * 1e3:.2f}ms vs batch p50 "
        f"{(batch_p50 or 0) * 1e3:.0f}ms (pass={lane_gate}); lanes "
        f"{lanes}; remaining ms {remaining}"
    )
    return {
        # headline: single evals per second at the direct p50
        "tpu_evals_per_s": round(1.0 / max(direct_p50, 1e-9), 2),
        "single_eval_p50_s": round(direct_p50, 6),
        "single_eval_runs_ms": [round(d * 1e3, 3) for d in direct],
        "single_eval_spread_pct": spread_pct(direct),
        "r08_single_eval_s": round(R08_SMOKE_EVAL_S, 6),
        "direct_used_micro": direct_used_micro,
        "lane_unloaded_p50_s": round(unloaded_p50, 6),
        "lane_loaded_p50_s": round(loaded_p50, 6),
        "lane_loaded_runs_ms": [round(d * 1e3, 3) for d in loaded],
        "batch_lane_p50_s": round(batch_p50, 6) if batch_p50 else None,
        "lane_counters": lanes,
        "remaining_ms_p50": remaining,
        "injected_device_latency_s": latency,
        "smoke_interactive_p50_ok": bool(p50_gate),
        "smoke_interactive_lane_ok": bool(lane_gate),
    }


def run_soak_config():
    """Sustained-traffic soak: closed-loop mixed traffic (job
    register/scale/stop, dispatch, node churn) against a live 3-server
    durable cluster under a SEEDED FaultPlane schedule (rpc drops, lost
    responses, slow fsync, device faults, a partition/heal cycle), with
    the overload controls engaged — bounded broker admission,
    per-namespace RPC rate limits, plan-queue backpressure
    (nomad_tpu/testing/loadgen.py run_soak).

    Unlike every other config, this one runs WITH faults injected by
    design: the claim under test is graceful degradation, and its gates
    (invariants hold, p99 bounded, admission engaged) are only
    meaningful under fault load. The chaos tripwire still applies to
    the PERF configs — the soak installs its plane for its own run and
    uninstalls it before returning.

    Env knobs: BENCH_SOAK_S (duration, default 30; the slow-tier run
    uses 600), BENCH_SOAK_RATE (target offered eval arrival rate/s —
    size it at >= 10x the capture-of-record c2m steady rate for the
    acceptance run), BENCH_SOAK_SEED, BENCH_SOAK_P99_S (e2e p99 bound),
    BENCH_SOAK_DEPTH (broker admission depth)."""
    import shutil
    import tempfile

    from nomad_tpu.testing.loadgen import run_soak

    duration = float(os.environ.get("BENCH_SOAK_S", "30"))
    rate = float(os.environ.get("BENCH_SOAK_RATE", "120"))
    seed = int(os.environ.get("BENCH_SOAK_SEED", "42"))
    p99_bound = float(os.environ.get("BENCH_SOAK_P99_S", "15"))
    depth = int(os.environ.get("BENCH_SOAK_DEPTH", "96"))
    log(
        f"[soak] {duration:.0f}s at {rate:.0f} evals/s offered, seed "
        f"{seed}, admission depth {depth}, faults ON"
    )
    root = tempfile.mkdtemp(prefix="nomad-tpu-soak-")
    try:
        report = run_soak(
            root,
            duration_s=duration,
            rate=rate,
            seed=seed,
            admission_depth=depth,
            namespace_cap=max(8, depth // 2),
            blocked_cap=depth,
            rpc_rate=float(os.environ.get("BENCH_SOAK_RPC_RATE", "40")),
            rpc_burst=float(os.environ.get("BENCH_SOAK_RPC_BURST", "80")),
            use_tpu_worker=True,
            partition_cycle=True,
            p99_bound_s=p99_bound,
        )
    finally:
        shutil.rmtree(root, ignore_errors=True)
    c = report["counters"]
    log(
        f"[soak] offered {report['offered']} ({report['offered_rate_per_s']}"
        f"/s), accepted {report['accepted']}, client-throttled "
        f"{report['throttled_client_visible']}; shed "
        f"{c['nomad.broker.shed']}, rejected {c['nomad.broker.rejected']}, "
        f"throttled http {c['nomad.http.throttled']} rpc "
        f"{c['nomad.rpc.throttled']}, backpressure "
        f"{c['nomad.worker.backpressure_throttled']}; e2e "
        f"{report.get('e2e_seconds')}; converged {report['converged']}, "
        f"invariants {report['invariants_ok']}"
        + (f" ({report['invariant_error']})" if report["invariant_error"] else "")
        + f", faults fired {report['fired_faults']}"
    )
    cpu = report.get("server_cpu") or {}
    src = report.get("source_attribution") or {}
    log(
        f"[soak] server cpu {cpu.get('cpu_seconds')}s "
        f"({cpu.get('per_node_cpu_fraction')} cores/node over "
        f"{cpu.get('node_count')} nodes); source attribution "
        f"coverage {src.get('coverage')} over {src.get('total_calls')} "
        f"calls, top {src.get('top')}"
    )
    # flight-recorder verdict (docs/incidents.md): the soak runs with
    # faults ON, so captured incidents are signal, not failure — the
    # capture line makes "did the blackbox see what the fault plane
    # did" auditable from the bench JSON alone
    from nomad_tpu import blackbox as _bb

    rec = _bb.recorder()
    report["blackbox"] = rec.stats()
    report["incidents"] = [
        {"id": r["id"], "reason": r["reason"]} for r in rec.incidents()
    ]
    bstats = report["blackbox"]
    log(
        f"[soak] blackbox: {int(bstats['journal_recorded'])} journal "
        f"rows ({int(bstats['journal_evicted'])} evicted), triggers "
        f"fired {int(bstats['triggers_fired'])} (deduped "
        f"{int(bstats['triggers_deduped'])}), incidents captured "
        f"{int(bstats['incidents_captured'])}"
        + (
            " " + ",".join(r["reason"] for r in report["incidents"])
            if report["incidents"] else ""
        )
    )
    return report


def run_fleet_config():
    """Fleet-scale survival (ROADMAP fleet-scale item): a simulated
    client fleet — real registration/heartbeat/alloc-watch RPCs
    multiplexed over a cooperative driver pool
    (nomad_tpu/testing/fleet.py) — held against a live cluster through
    a registration storm, steady state, a mass partition (heartbeat
    wheel expiry storm → batched down-marks), and a mass reconnect
    (node door admission + register batcher).

    Gates: the whole fleet registers through the admission door; every
    silent victim is down-marked within its TTL bound; the reconnect
    storm recovers; BOTH storms commit node-status raft entries in
    coalesced batches (entries <= victims / min_avg_batch); heartbeat
    RPC p99 stays bounded THROUGH the storms; server CPU per node per
    second stays under the soak gate; chaos invariants hold.

    Env knobs: BENCH_FLEET_NODES (default 5000 — the acceptance run's
    floor), BENCH_FLEET_S (steady-state seconds, default 600 for the
    acceptance run's 10-minute hold), BENCH_FLEET_SEED,
    BENCH_FLEET_SERVERS, BENCH_FLEET_TTL_S, BENCH_FLEET_P99_S,
    BENCH_FLEET_CPU_PER_NODE, BENCH_FLEET_DRIVERS,
    BENCH_FLEET_FRACTION (partition fraction)."""
    import shutil
    import tempfile

    from nomad_tpu.testing.fleet import run_fleet_scale

    n_nodes = int(os.environ.get("BENCH_FLEET_NODES", "5000"))
    steady = float(os.environ.get("BENCH_FLEET_S", "600"))
    seed = int(os.environ.get("BENCH_FLEET_SEED", "42"))
    n_servers = int(os.environ.get("BENCH_FLEET_SERVERS", "1"))
    ttl = float(os.environ.get("BENCH_FLEET_TTL_S", "10"))
    log(
        f"[fleet] {n_nodes} nodes on {n_servers} server(s), "
        f"{steady:.0f}s steady, ttl {ttl:.0f}s, seed {seed}"
    )
    root = tempfile.mkdtemp(prefix="nomad-tpu-fleet-")
    try:
        report = run_fleet_scale(
            root,
            seed=seed,
            n_servers=n_servers,
            n_nodes=n_nodes,
            steady_s=steady,
            heartbeat_ttl_s=ttl,
            driver_threads=int(os.environ.get("BENCH_FLEET_DRIVERS", "8")),
            real_watchers=8,
            partition_fraction=float(
                os.environ.get("BENCH_FLEET_FRACTION", "0.2")
            ),
            register_deadline_s=max(60.0, n_nodes / 50.0),
            rate=float(os.environ.get("BENCH_FLEET_RATE", "10")),
            p99_bound_s=float(os.environ.get("BENCH_FLEET_P99_S", "1.0")),
            cpu_per_node_bound=float(
                os.environ.get("BENCH_FLEET_CPU_PER_NODE", "0.002")
            ),
        )
    finally:
        shutil.rmtree(root, ignore_errors=True)
    cpu = report["server_cpu"]
    log(
        f"[fleet] registered {report['fleet']['registered']}/{n_nodes} "
        f"in {report['populate_s']}s ({report['register_throttled']:.0f} "
        f"throttles); victims {report['victims']}: down in "
        f"{report['expiry_detect_s']}s over {report['expire_batches']:.0f} "
        f"batches (avg {report['avg_expiry_batch']}), reconnect in "
        f"{report['reconnect_s']}s over {report['reconnect_batches']:.0f} "
        f"entries (avg {report['avg_reconnect_batch']}); hb p99 "
        f"{report['hb_p99_s']}s; cpu/node "
        f"{cpu['per_node_cpu_fraction']} cores; converged "
        f"{report['converged']}, invariants {report['invariants_ok']}"
        + (
            f" ({report['invariant_error']})"
            if report["invariant_error"]
            else ""
        )
    )
    return report


SERVICE_CONFIGS = {
    # name: (nodes, jobs, count/job, constrained, host_sample >= 20
    #        except smoke, which has a single job by definition)
    "smoke": (10, 1, 10, False, 1),
    "c1k": (1000, 50, 100, False, 20),
    "c2m": (10000, 100, 1000, True, 20),
}

SHARDED_CAVEAT_TEXT = (
    "c2m_sharded's device phase uses the injected-latency model (the "
    "pipeline config's precedent): per-mesh device time is "
    "BENCH_SHARDED_RTT_S x (shard rows / total rows), the scaling a "
    "real mesh's LOCAL phase has by construction. The 8 'devices' here "
    "are XLA virtual CPU devices sharing this box's cores, so raw "
    "XLA:CPU wall cannot strong-scale; the gate is still a real "
    "regression bound — the XLA:CPU kernel compute and host "
    "phases run inside the modeled budget, so a sharded kernel whose "
    "per-device work stops shrinking (e.g. a replicated full-sort "
    "waterfill) blows the D=8 budget and fails the gate"
)


def run_c2m_sharded_config():
    """c2m-scale solve with the node axis sharded over a device mesh:
    100k+ nodes split over 8 virtual devices, solved end-to-end through
    the production mesh path (SchedulerConfig.mesh_devices → SolverMesh
    top-k kernels + NamedSharding resident tensors + delta syncs).

    Measures eval throughput at mesh sizes 1 and 8 on the SAME sharded
    code and problem. The device phase rides the injected-latency model
    (SHARDED_CAVEAT_TEXT): latency = BENCH_SHARDED_RTT_S x (1/D), the
    linear local-phase scaling real hardware provides; the XLA:CPU
    kernel's real compute is the FLOOR under the model (solver._inject_rtt
    sleeps from dispatch, compute proceeds async), so the published
    sharded_scaling only reaches the gate when per-device work + host
    overhead genuinely fit the shrinking budget.

    sharded_scaling = (rate_D8 / rate_D1) / 8, where rate is the
    PIPELINED end-to-end eval throughput: rounds run through the same
    two-phase overlap as the production TPUBatchWorker
    (solve_eval_batch_begin of batch N+1 overlaps batch N's device
    wait; consecutive batches chain on the in-flight used' tensor, with
    the chain composing with the resident shards), so throughput is
    bounded by max(host phase, device phase) — and scales with the mesh
    exactly while the device phase dominates.
    """
    from nomad_tpu import solverobs
    from nomad_tpu.gctune import freeze_resident_heap, paused_gc
    from nomad_tpu import mock
    from nomad_tpu.scheduler.context import SchedulerConfig
    from nomad_tpu.scheduler.tpu import (
        ResidentClusterState,
        solve_eval_batch_begin,
    )
    from nomad_tpu.scheduler.tpu.sharding import solver_mesh

    n_nodes = int(os.environ.get("BENCH_SHARDED_NODES", "100000"))
    n_jobs = int(os.environ.get("BENCH_SHARDED_JOBS", "16"))
    count = int(os.environ.get("BENCH_SHARDED_COUNT", "500"))
    base_rtt = float(os.environ.get("BENCH_SHARDED_RTT_S", "10.0"))
    rounds = int(os.environ.get("BENCH_SHARDED_ROUNDS", "6"))
    settle = int(os.environ.get("BENCH_SHARDED_SETTLE", "2"))
    device_counts = (1, 8)
    log(
        f"[c2m_sharded] {n_nodes} nodes, {n_jobs} jobs x {count}, mesh "
        f"sizes {device_counts}, device model base {base_rtt}s x 1/D, "
        f"{rounds} pipelined rounds"
    )

    def run_rounds(h, cfg, resident, rounds_jobs, syncs=None):
        """Pipelined steady-state rounds (the TPUBatchWorker overlap,
        inline): begin(N+1) runs while batch N's device work is in
        flight; N+1 chains on N's used' so the batches place
        conflict-free; finish(N) + submit then completes N. Returns the
        per-round completion walls (batch N's submit to batch N+1's) —
        the medianable steady-state cadence."""
        prev = None
        walls = []
        t0 = t_last = time.perf_counter()
        with paused_gc(freeze_on_exit=True):
            for jobs in rounds_jobs:
                snap = h.snapshot()
                evals = [mock.eval_for_job(j) for j in jobs]
                chain = prev[0].chain if prev is not None else None
                pend = solve_eval_batch_begin(
                    snap, h, evals, cfg, resident=resident,
                    used_chain=chain,
                )
                if syncs is not None:
                    syncs.append(
                        f"{resident.last_sync}"
                        + ("+chain" if pend.chain_accepted else "")
                    )
                if prev is not None:
                    p_pend, p_evals = prev
                    plans = p_pend.finish()
                    for ev in p_evals:
                        h.submit_plan(plans[ev.id])
                    now = time.perf_counter()
                    walls.append(now - t_last)
                    t_last = now
                prev = (pend, evals)
            p_pend, p_evals = prev
            plans = p_pend.finish()
            for ev in p_evals:
                h.submit_plan(plans[ev.id])
            now = time.perf_counter()
            walls.append(now - t_last)
        return time.perf_counter() - t0, walls

    per_mesh = {}
    recompiles_after_warmup = 0
    for d in device_counts:
        mesh = solver_mesh(d)
        cfg = SchedulerConfig(
            small_batch_threshold=0,
            mesh_devices=d,
            inject_device_latency_s=base_rtt / d,
        )
        gc.collect()
        h, warm_jobs = build_cluster(
            n_nodes, n_jobs, count, False, job_prefix=f"shard{d}-warm"
        )
        freeze_resident_heap()
        resident = ResidentClusterState(mesh=mesh)
        # warm rounds WITHOUT the latency model (compiles don't sleep):
        # THREE rounds so the steady-state machinery compiles too before
        # anything is measured — round 2 consumes the chain, round 3
        # ships the first delta-sync scatter (the full sync happens at
        # round 1, and round 2's diff is clean because round 1 is still
        # in flight at its begin)
        import copy as _copy

        warm_cfg = _copy.copy(cfg)
        warm_cfg.inject_device_latency_s = 0.0
        warm_s, _ = run_rounds(h, warm_cfg, resident, [
            warm_jobs,
            add_jobs(h, n_jobs, count, False, job_prefix=f"shard{d}-w2"),
            add_jobs(h, n_jobs, count, False, job_prefix=f"shard{d}-w3"),
        ])
        compiles0 = solverobs.compiles()
        syncs: list = []
        rounds_jobs = [
            add_jobs(h, n_jobs, count, False, job_prefix=f"shard{d}-r{r}")
            for r in range(settle + rounds)
        ]
        wall, walls = run_rounds(h, cfg, resident, rounds_jobs, syncs=syncs)
        recompiles_after_warmup += solverobs.compiles() - compiles0
        # steady-state cadence: the settle rounds absorb pipeline fill
        # and the executable's first-runs transient; the median of the
        # rest is the per-round completion interval one load spike
        # cannot own
        steady = walls[settle:] if len(walls) > settle + 1 else walls
        round_s = median(steady)
        rate = n_jobs / round_s
        per_mesh[d] = {
            "devices": d,
            "injected_device_s": round(base_rtt / d, 4),
            "warm_s": round(warm_s, 2),
            "rounds": rounds,
            "wall_s": round(wall, 3),
            "round_walls_s": [round(w, 3) for w in walls],
            "steady_round_s": round(round_s, 3),
            "evals_per_s": round(rate, 3),
            "spread_pct": spread_pct(steady),
            "resident_sync_modes": syncs,
        }
        log(
            f"[c2m_sharded] D={d}: {rate:.3f} evals/s (steady round "
            f"{round_s:.2f}s, walls {[round(w, 2) for w in walls]}), "
            f"syncs {syncs}, injected {base_rtt / d:.3f}s"
        )
        h = warm_jobs = rounds_jobs = None
    obs = solver_observability()
    obs["recompiles_after_warmup"] = recompiles_after_warmup
    d1, d8 = device_counts[0], device_counts[-1]
    scaling = (
        per_mesh[d8]["evals_per_s"]
        / max(per_mesh[d1]["evals_per_s"], 1e-9)
    ) / (d8 / d1)
    shards = (obs.get("sharding") or {}).get("last_shards") or []
    mean_shard_occ = (
        round(
            sum(s["occupancy"] for s in shards) / len(shards), 4
        )
        if shards else None
    )
    log(
        f"[c2m_sharded] scaling {scaling:.3f} x linear (gate >= 0.7); "
        f"mean shard occupancy {mean_shard_occ}; allgather "
        f"{obs['allgather_bytes']}B, scatter {obs['scatter_bytes']}B, "
        f"recompiles after warmup {recompiles_after_warmup}"
    )
    return {
        "tpu_evals_per_s": per_mesh[d8]["evals_per_s"],
        "per_mesh": {str(k): v for k, v in per_mesh.items()},
        "sharded_scaling": round(scaling, 4),
        "sharded_scaling_linear_gate": 0.7,
        "device_model_base_rtt_s": base_rtt,
        "mean_shard_occupancy": mean_shard_occ,
        "solver_observability": obs,
        "caveat": SHARDED_CAVEAT_TEXT,
    }


POOL_CAVEAT_TEXT = (
    "c2m_pool models each solver-pool member as a RemoteSolver with its "
    "OWN SchedulerConfig under the injected-latency device model "
    "(docs/solver-pool.md): the serially-busy `_device_free_at` queue is "
    "per-config, so every member is an independent chip exactly as a "
    "real pool member's device is. Members share one state store (the "
    "perfectly-synced-replica limit — production replicas trail by a "
    "raft beat, which the warm loop's delta sync bounds), so the ratio "
    "isolates PLACEMENT-PLANE capacity: it proves the dispatch fan-out "
    "and per-member resident state scale, not the replication fabric."
)


def run_c2m_pool_config():
    """Solver-pool horizontal-scaling bench (docs/solver-pool.md): the
    same c2m-shaped eval stream dispatched to a pool of 1 vs 2 warm
    RemoteSolver members, each an independent serially-busy chip under
    the injected-latency model. Gates committed-eval throughput at
    >= 1.5x from one member to two.

    The drive loop mirrors the leader's TPUBatchWorker dispatch: each
    mega-batch goes to a pool member on its own thread (the SolverPool
    dispatch-thread idiom), the 'leader' submits plan columns as batches
    land, and up to pool-size batches stay in flight. A single member
    serializes batches on its solve lock + device window; two members
    overlap two batches — the ratio IS the placement-plane scaling.

    Drift-normalized (the c2m verdict discipline): pool sizes interleave
    ABBA within one process, so this box's co-tenancy drift hits both
    sides equally and the RATIO is trustworthy even when raw rates are
    not. Each trial rebuilds cluster state fresh so trial N's accumulated
    allocs never tax trial N+1's snapshots asymmetrically."""
    import queue as _queue
    import threading as _threading

    from nomad_tpu.gctune import freeze_resident_heap, paused_gc
    from nomad_tpu import mock
    from nomad_tpu.scheduler.context import SchedulerConfig
    from nomad_tpu.scheduler.tpu.remote_solve import RemoteSolver

    n_nodes = int(os.environ.get("BENCH_POOL_NODES", "2000"))
    n_jobs = int(os.environ.get("BENCH_POOL_JOBS", "8"))
    count = int(os.environ.get("BENCH_POOL_COUNT", "100"))
    rtt = float(os.environ.get("BENCH_POOL_RTT_S", "0.8"))
    n_batches = int(os.environ.get("BENCH_POOL_BATCHES", "6"))
    pairs = int(os.environ.get("BENCH_POOL_PAIRS", "2"))
    pool_sizes = (1, 2)
    gate = float(os.environ.get("BENCH_POOL_SCALING_GATE", "1.5"))
    log(
        f"[c2m_pool] {n_nodes} nodes, {n_batches} batches of {n_jobs} "
        f"jobs x {count}, pool sizes {pool_sizes}, device model "
        f"{rtt}s/batch per member, {pairs} interleaved trial pairs"
    )

    class _Host:
        """RemoteSolver host duck-type: the bench's shared store stands
        in for every member's raft replica (POOL_CAVEAT_TEXT)."""

        def __init__(self, state):
            self.state = state

    def run_trial(pool_size: int) -> float:
        """One trial: fresh cluster, fresh members, one unmeasured warm
        batch per member (compile + full resident sync), then n_batches
        dispatched round-robin with pool_size in flight. Returns
        committed evals/s over the measured window."""
        gc.collect()
        h, _ = build_cluster(
            n_nodes, n_jobs, count, False, job_prefix=f"pool{pool_size}-warm"
        )
        freeze_resident_heap()
        host = _Host(h.state)
        members = [
            RemoteSolver(
                host,
                config=SchedulerConfig(
                    backend="tpu",
                    small_batch_threshold=0,
                    inject_device_latency_s=rtt,
                ),
                node_id=f"bench-m{i}",
            )
            for i in range(pool_size)
        ]
        # warm OUTSIDE the injected-latency model: one batch per member
        # compiles the kernels (first trial only — the jit cache is
        # process-wide) and takes the full resident upload, so every
        # measured batch rides the delta-sync path on a warm replica
        for i, m in enumerate(members):
            m.config.inject_device_latency_s = 0.0
            warm_jobs = add_jobs(
                h, n_jobs, count, False, job_prefix=f"pool{pool_size}-w{i}"
            )
            warm_evals = [mock.eval_for_job(j) for j in warm_jobs]
            out = m.solve(warm_evals, h.snapshot().index, timeout_s=60.0)
            for ev in warm_evals:
                h.submit_plan(out["plans"][ev.id])
            m.config.inject_device_latency_s = rtt
        batches = [
            [
                mock.eval_for_job(j)
                for j in add_jobs(
                    h, n_jobs, count, False,
                    job_prefix=f"pool{pool_size}-b{b}",
                )
            ]
            for b in range(n_batches)
        ]
        min_index = h.snapshot().index
        done_q: _queue.Queue = _queue.Queue()

        def dispatch(i: int, member, evals) -> None:
            try:
                done_q.put((i, member.solve(
                    evals, min_index, timeout_s=rtt * n_batches + 60.0
                ), None))
            except Exception as e:  # noqa: BLE001 - surfaced on the drive loop
                done_q.put((i, None, e))

        t0 = time.perf_counter()
        with paused_gc(freeze_on_exit=True):
            next_b = 0
            in_flight = 0
            completed = 0
            while completed < n_batches:
                # keep pool_size batches in flight, round-robin — the
                # least-in-flight pick SolverPool makes degenerates to
                # round-robin under uniform batch cost
                while next_b < n_batches and in_flight < pool_size:
                    _threading.Thread(
                        target=dispatch,
                        args=(next_b, members[next_b % pool_size],
                              batches[next_b]),
                        name=f"bench-pool-dispatch-{next_b}",
                        daemon=True,
                    ).start()
                    next_b += 1
                    in_flight += 1
                i, out, err = done_q.get()
                if err is not None:
                    raise err
                # the 'leader' commits: plan columns apply on the
                # authoritative store, exactly RemotePendingBatch.finish
                for ev in batches[i]:
                    h.submit_plan(out["plans"][ev.id])
                in_flight -= 1
                completed += 1
        wall = time.perf_counter() - t0
        rate = (n_batches * n_jobs) / wall
        assert all(m.warmups == 1 for m in members), (
            "pool members must warm exactly once, before measurement"
        )
        log(
            f"[c2m_pool] pool={pool_size}: {rate:.3f} evals/s "
            f"({n_batches} batches in {wall:.2f}s, member solves "
            f"{[m.solves for m in members]}, syncs "
            f"{[m.last_sync for m in members]})"
        )
        return rate

    # ABBA interleave: linear host drift cancels between the sides
    order: list = []
    for p in range(pairs):
        order.extend(pool_sizes if p % 2 == 0 else pool_sizes[::-1])
    rates: dict = {s: [] for s in pool_sizes}
    for size in order:
        rates[size].append(run_trial(size))
    per_pool = {
        str(s): {
            "members": s,
            "trial_evals_per_s": [round(r, 3) for r in rates[s]],
            "evals_per_s": round(median(rates[s]), 3),
            "spread_pct": spread_pct(rates[s]),
        }
        for s in pool_sizes
    }
    s1, s2 = pool_sizes
    scaling = per_pool[str(s2)]["evals_per_s"] / max(
        per_pool[str(s1)]["evals_per_s"], 1e-9
    )
    log(
        f"[c2m_pool] scaling {scaling:.3f}x from {s1} -> {s2} members "
        f"(gate >= {gate})"
    )
    return {
        "tpu_evals_per_s": per_pool[str(s2)]["evals_per_s"],
        "per_pool": per_pool,
        "pool_scaling": round(scaling, 4),
        "pool_scaling_gate": gate,
        "device_model_rtt_s": rtt,
        "caveat": POOL_CAVEAT_TEXT,
    }


SHARDED_DEVICES = 8


def _force_virtual_cpu_mesh(env) -> None:
    """The c2m_sharded cell is a VIRTUAL mesh: 8 XLA:CPU devices under a
    sleep model of the device phase (SHARDED_CAVEAT_TEXT). It asks for
    the CPU explicitly — before jax loads — so it can never reach for
    (or, as a child, contend for) the chip the other cells run on."""
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{SHARDED_DEVICES}"
        ).strip()


def _run_sharded_subprocess() -> dict:
    """Run the c2m_sharded config in a child process so ITS backend can
    be forced to 8 virtual CPU devices without the parent paying for it:
    `xla_force_host_platform_device_count` partitions the CPU client
    across the virtual devices and slows every other config. The child
    is this same script with BENCH_CONFIG=c2m_sharded and an explicit
    JAX_PLATFORMS=cpu — the parent holds the chip, and a child that
    reached for it would fail or hang; its JSON line carries the config
    block (device stamp, latency_percentiles and solver_observability
    included) and is spliced into the parent's results verbatim. A
    child that fails raises: the run ends non-zero."""
    import subprocess

    env = dict(os.environ)
    env["BENCH_CONFIG"] = "c2m_sharded"
    _force_virtual_cpu_mesh(env)
    env.pop("BENCH_STRICT", None)  # parent owns the exit-code policy
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=2400,
    )
    for raw in proc.stderr.splitlines():
        log(raw)
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    if proc.returncode != 0 or not lines:
        raise RuntimeError(
            f"c2m_sharded subprocess failed rc={proc.returncode}: "
            f"{proc.stderr[-2000:]}"
        )
    payload = json.loads(lines[-1])
    block = payload["configs"]["c2m_sharded"]
    block["device"] = payload["device"]
    return block


def main():
    # Fault-injection tripwire: a capture taken while chaos knobs are
    # live (NOMAD_TPU_INJECT_* env vars, or an installed FaultPlane)
    # measures the injected faults, not the system — it must never be
    # certifiable. The payload still prints (debugging under injection
    # is legitimate) but every gate is forced to fail.
    from nomad_tpu import faultplane as _chaos

    chaos_knobs = _chaos.env_knobs_active()
    if chaos_knobs:
        log(
            f"CHAOS INJECTION ACTIVE ({', '.join(chaos_knobs)}): "
            f"this capture CANNOT gate — results are fault-distorted"
        )
    sel = os.environ.get("BENCH_CONFIG", "all")
    if sel == "c2m_sharded":
        # the solo run of the virtual-mesh cell; must happen before jax
        # loads. The full run executes this config in a subprocess
        # instead (_run_sharded_subprocess).
        _force_virtual_cpu_mesh(os.environ)
    # No probe and no fallback: the device is whatever jax resolves, a
    # silent landing on XLA:CPU raises (an explicit JAX_PLATFORMS=cpu
    # run is labelled `cpu`), and the payload names its device.
    from nomad_tpu.scheduler.tpu import resolve_device

    device = resolve_device()
    log(f"bench device: {device.to_wire()}")
    # always-on host profiler: runs through every measured pass (the
    # production posture — the overhead gate in tests/test_hostobs.py
    # holds it >= 0.95x unprofiled) and feeds each config's
    # host_attribution block
    from nomad_tpu import hostobs as _hostobs

    _hostobs.start()
    if os.environ.get("BENCH_TRACE"):
        # per-batch span emission through the production tracing
        # subsystem (trace.py); each config's critical-path summary
        # lands under its result's "trace" key
        from nomad_tpu import trace as _trace

        _trace.configure(max_traces=256, enabled_=True)
    names = (
        ["smoke", "smoke_interactive", "c1k", "c2m", "c2m_sharded",
         "c2m_pool", "preempt", "drain", "plan_apply", "pipeline",
         "soak"]
        if sel == "all"
        else [sel]
    )
    results = {}
    for name in names:
        # per-config histogram baseline: the registry accumulates
        # process-wide, so reset between configs keeps each config's
        # latency_percentiles attributable to its own passes
        from nomad_tpu import metrics as _metrics
        from nomad_tpu import solverobs as _solverobs

        _metrics.registry().reset()
        # fresh observatory too: compile/transfer counts stay
        # attributable per config (the jit cache itself stays warm —
        # cross-config cache hits are real and correctly counted)
        _solverobs._install(_solverobs.SolverObservatory())
        # fresh host-profiler ledgers for the same reason
        _hostobs.reset_stats()
        if name in SERVICE_CONFIGS:
            n_nodes, n_jobs, count, constrained, sample = SERVICE_CONFIGS[name]
            results[name] = run_service_config(
                name, n_nodes, n_jobs, count, constrained, sample,
                # c2m: >= 20s of work per trial, median of 5 (VERDICT
                # r7 next-round #3 — the 96.6%-spread fix)
                min_trial_s=(
                    float(os.environ.get("BENCH_MIN_TRIAL_S", "20"))
                    if name == "c2m" else 0.0
                ),
                trials=5 if name == "c2m" else 3,
            )
        elif name == "c2m_sharded":
            if sel == "all":
                # subprocess: its own 8-virtual-device backend, already
                # carrying latency_percentiles/solver_observability —
                # the parent's registry never saw its passes
                results[name] = _run_sharded_subprocess()
                continue
            results[name] = run_c2m_sharded_config()
        elif name == "c2m_pool":
            results[name] = run_c2m_pool_config()
        elif name == "smoke_interactive":
            results[name] = run_smoke_interactive_config()
        elif name == "preempt":
            results[name] = run_preempt_config()
        elif name == "drain":
            results[name] = run_drain_config()
        elif name == "plan_apply":
            results[name] = run_plan_apply_config()
        elif name == "pipeline":
            results[name] = run_pipeline_config()
        elif name == "soak":
            results[name] = run_soak_config()
        elif name == "fleet":
            results[name] = run_fleet_config()
        else:
            raise SystemExit(f"unknown BENCH_CONFIG {name}")
        results[name]["latency_percentiles"] = latency_percentiles()
        # every config carries the solver_observability block; service
        # configs computed theirs at the warmup boundary already
        results[name].setdefault(
            "solver_observability", solver_observability()
        )
        tsum = trace_summary()
        if tsum is not None:
            results[name]["trace"] = tsum

    headline = "c2m" if "c2m" in results else names[0]
    hl = results[headline]
    # Explicit gates (VERDICT r4 weak #5): a density regression or an
    # applier falling behind the solver must fail LOUDLY, not hide in a
    # sub-key. Every gate that exists in this run must pass.
    gates = {}
    for cname, r in results.items():
        if "density_within_1pct" in r:
            gates[f"{cname}_density"] = bool(r["density_within_1pct"])
        if "apply_vs_solve_ge_0_6" in r:
            gates[f"{cname}_apply_vs_solve_0_6"] = bool(
                r["apply_vs_solve_ge_0_6"]
            )
        if "overlap_ge_0_8_ideal" in r:
            gates[f"{cname}_overlap_0_8_ideal"] = bool(r["overlap_ge_0_8_ideal"])
        # interactive fast-path gates (ISSUE 15): single-eval p50 at
        # most half the r08 capture's, and the priority lane keeping
        # loaded interactive latency far under the mega-batch cadence
        if "smoke_interactive_p50_ok" in r:
            gates["smoke_interactive_p50"] = bool(
                r["smoke_interactive_p50_ok"]
            )
            gates["smoke_interactive_lane"] = bool(
                r["smoke_interactive_lane_ok"]
            )
        # recompile-bound regression guard (shape-bucketing contract,
        # kernels.py): after the warmup pass, steady-state batches in
        # the smoke and c2m configs must trigger ZERO compiles
        so = r.get("solver_observability") or {}
        if (
            cname in ("smoke", "c2m", "c2m_sharded")
            and "recompiles_after_warmup" in so
        ):
            gates[f"{cname}_recompile_bound"] = (
                so["recompiles_after_warmup"] == 0
            )
        # sharded-solver linear-scaling gate (docs/sharding.md): the
        # mesh path's throughput from 1 -> 8 devices must hold >= 0.7x
        # linear under the per-shard device model
        if "sharded_scaling" in r:
            gates["sharded_scaling"] = (
                r["sharded_scaling"] >= r["sharded_scaling_linear_gate"]
            )
            # resident tensors upload once: after each mesh's first
            # ("full") sync, steady rounds must ship delta scatters or
            # nothing — a mid-run "full" is a resident re-upload
            gates[f"{cname}_delta_only"] = not any(
                mode.startswith("full")
                for mesh in r["per_mesh"].values()
                for mode in mesh["resident_sync_modes"][1:]
            )
        # solver-pool horizontal-scaling gate (docs/solver-pool.md):
        # committed-eval throughput from 1 -> 2 warm pool members must
        # hold >= 1.5x under the per-member serially-busy device model;
        # drift-normalized by the config's ABBA trial interleave
        if "pool_scaling" in r:
            gates["pool_scaling"] = (
                r["pool_scaling"] >= r["pool_scaling_gate"]
            )
        # drift-immune throughput gates (ISSUE 16): both gate on the
        # PAIRED control-normalized statistic, never the raw rate —
        # this box's co-tenancy drifts raw rates +/-40% across captures
        # on unchanged code, so a raw-rate gate can fake both a win and
        # a regression. Floors are env-tunable for slower boxes.
        if cname == "c2m" and "control_normalized_evals_per_s" in r:
            gates["c2m_target_rate"] = r[
                "control_normalized_evals_per_s"
            ] >= float(os.environ.get("BENCH_C2M_TARGET", "250"))
        if cname == "c2m" and "vs_native_cpp" in r:
            gates["c2m_vs_native_cpp"] = r["vs_native_cpp"] >= float(
                os.environ.get("BENCH_VS_NATIVE_FLOOR", "0.25")
            )
        # host-attribution gates (the host-profiling layer's acceptance
        # criteria): named (span x function) sites must cover >= 80% of
        # measured host wall on the c2m config, and the profiler's
        # span-correlated self-times must agree with the traces'
        # stack-self-times within 15% on every span >= 20% of wall
        ha = r.get("host_attribution") or {}
        if cname == "c2m" and "coverage" in ha:
            gates["c2m_host_coverage"] = ha["coverage"] >= 0.8
            gates["c2m_span_agreement"] = bool(ha["span_agreement_ok"])
            # GC-tax ceiling (ISSUE 12): with the post-warmup resident
            # freeze + pipeline-wide paused sections, GC pauses must
            # stay a rounding error of c2m wall. BENCH_GC_SHARE tunes
            # the ceiling; 5% default (pre-fix captures measured the
            # jax gc callback alone at 16.5-17%).
            gates["c2m_gc_share"] = ha["gc_share"] <= float(
                os.environ.get("BENCH_GC_SHARE", "0.05")
            )
        # soak gates: graceful degradation under the seeded fault
        # schedule — safety invariants hold, e2e p99 stays bounded,
        # and admission control demonstrably engaged (nonzero
        # shed/reject/throttle counts)
        if "invariants_ok" in r:
            gates[f"{cname}_invariants"] = bool(
                r["invariants_ok"] and r["converged"]
            )
            gates[f"{cname}_p99_bounded"] = bool(r["p99_bounded"])
            gates[f"{cname}_admission_engaged"] = bool(
                r["admission_engaged"]
            )
        # cluster-observability gates (clusterobs.py): server CPU per
        # simulated node stays bounded (the ROADMAP fleet-scale gate,
        # measurable per-run now) and per-source attribution covers
        # the served handler seconds — fan-out cost is ATTRIBUTABLE,
        # not just bounded
        if "server_cpu" in r:
            bound = float(
                os.environ.get("BENCH_SOAK_CPU_PER_NODE", "0.5")
            )
            gates[f"{cname}_cpu_per_node_bounded"] = (
                r["server_cpu"]["per_node_cpu_fraction"] <= bound
            )
        if "source_attribution" in r:
            gates[f"{cname}_source_coverage"] = (
                r["source_attribution"]["coverage"] >= 0.8
            )
        # fleet-scale survival gates (nomad_tpu/testing/fleet.py): the
        # storm phases complete inside their bounds, and both mass
        # transitions commit node-status raft writes in coalesced
        # batches — the "entries <= constant x batches" claim
        if "reconnect_batched" in r:
            gates[f"{cname}_survival"] = bool(
                r["registered_all"]
                and r["expiry_detected"]
                and r["reconnect_recovered"]
            )
            gates[f"{cname}_raft_batched"] = bool(
                r["expiry_batched"] and r["reconnect_batched"]
            )
            gates[f"{cname}_cpu_per_node"] = bool(r["cpu_bounded"])
    if chaos_knobs:
        # refuse to gate: an injected-fault run can never certify
        gates["no_chaos_injection"] = False
    gates_ok = all(gates.values())
    if not gates_ok:
        log(f"BENCH GATES FAILED: {gates}")
    print(
        json.dumps(
            {
                "metric": f"{headline}_scheduler_throughput",
                # headline = the drift-immune statistic when the config
                # measured one (raw rates ride in configs.*)
                "value": hl.get(
                    "control_normalized_evals_per_s",
                    hl.get("tpu_evals_per_s", hl.get("apply_evals_per_s")),
                ),
                "unit": "evals/sec",
                "vs_baseline": hl.get("vs_host", hl.get("apply_vs_solve")),
                "configs": results,
                "gates": gates,
                "gates_pass": all(gates.values()),
                "chaos_injection_active": chaos_knobs,
                "loadavg": list(os.getloadavg()),
                "platform": device.platform,
                "device": device.to_wire(),
                "caveats": CAVEATS
                + ([NATIVE_CAVEAT_TEXT] if _NATIVE_CAVEAT[0] else [])
                + (
                    [SHARDED_CAVEAT_TEXT]
                    if "c2m_sharded" in results else []
                ),
            }
        )
    )
    # BENCH_STRICT=1: fail the PROCESS on a gate regression (CI usage).
    # Default stays exit-0 so harnesses that capture the JSON line keep
    # working; the gates ride in the payload either way.
    if not gates_ok and os.environ.get("BENCH_STRICT"):
        sys.exit(2)


if __name__ == "__main__":
    main()
