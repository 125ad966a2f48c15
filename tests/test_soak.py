"""Sustained-traffic soak: the closed-loop load generator driving a
live fault-injected cluster (nomad_tpu/testing/loadgen.py).

Tier-1 runs the fast seeded mini-soak (~15s wall: a few seconds of
traffic at an offered rate far above what the overload knobs admit,
under background rpc-drop / lost-response / slow-fsync faults), gating
on what `run_soak` reports: ChaosCluster invariants hold, the cluster
converges, admission control demonstrably engaged, e2e p99 bounded, and
the broker drains once arrivals stop.

The 10-minute acceptance-shaped soak (partition/heal cycle included) is
slow-marked; run it with `pytest -m 'soak and slow'`.
"""

from __future__ import annotations

import os

import pytest

from nomad_tpu import metrics
from nomad_tpu.metrics import Registry
from nomad_tpu.testing import chaos
from nomad_tpu.testing.loadgen import LoadGen, LoadGenConfig, run_soak

pytestmark = pytest.mark.soak


@pytest.fixture(autouse=True)
def _fresh_world():
    """Plane-free, a private registry, and a private flight recorder
    per soak (counters in the report are deltas, but a clean registry
    keeps the e2e histogram attributable; a fresh recorder keeps the
    zero-incidents gate honest across test order)."""
    from nomad_tpu import blackbox

    chaos.uninstall()
    old = metrics._install_registry(Registry())
    old_rec = blackbox._install(blackbox.FlightRecorder())
    yield
    blackbox._install(old_rec)
    metrics._install_registry(old)
    chaos.uninstall()


def test_mini_soak_overload_with_faults(tmp_path):
    """Fast seeded mini-soak, faults ON: offered rate ~10x what the
    tight admission/rate-limit knobs accept, so every control engages
    while the safety invariants must keep holding."""
    report = run_soak(
        str(tmp_path),
        duration_s=6.0,
        rate=120.0,
        seed=1234,
        admission_depth=24,
        namespace_cap=10,
        blocked_cap=24,
        nack_delay_s=0.5,
        rpc_rate=10.0,
        rpc_burst=15.0,
        use_tpu_worker=False,
        faults=True,
        partition_cycle=False,
        node_count=8,
        p99_bound_s=20.0,
        loadgen_overrides={"submitters": 6},
    )
    # safety: nothing acked was lost, no duplicate allocs, log converged
    assert report["invariants_ok"], report["invariant_error"]
    assert report["converged"]
    # liveness: traffic flowed and the backlog drained once it stopped
    assert report["offered"] > 0
    assert report["accepted"] > 0
    assert report["evals_completed"] > 0
    assert report["drained"]
    # degradation engaged: shed / front-door 429s / throttles fired
    assert report["admission_engaged"], report["counters"]
    # the work that WAS admitted completed in bounded time
    assert report["p99_bounded"], report.get("e2e_seconds")
    # the seeded fault schedule actually fired faults during the run
    assert report["fault_schedule"] and report["fired_faults"]
    # cluster observability (clusterobs.py): server CPU was measured
    # and attributed per simulated node, and the per-source ledger
    # covered the served handler seconds (coverage >= 0.8)
    cpu = report["server_cpu"]
    assert cpu["cpu_seconds"] > 0, cpu
    assert report["server_cpu_per_node"] == cpu["per_node_cpu_seconds"]
    assert cpu["per_node_cpu_fraction"] > 0
    # process CPU over the window is physically bounded by cores x wall
    # (the profiler's busy-WALL role table is not — C-call parking)
    assert cpu["cpu_seconds"] <= (os.cpu_count() or 1) * (
        report["duration_s"] + 30.0
    )
    assert cpu["busy_wall_by_role"], cpu
    src = report["source_attribution"]
    assert src["total_calls"] > 0
    assert src["coverage"] >= 0.8, src
    # traffic is node- and tenant-attributed, never all "(unknown)"
    assert any(
        r["source"].startswith(("node:", "ns:", "srv:"))
        for r in src["top"]
    ), src["top"]


def test_mini_soak_seed_fixes_fault_schedule(tmp_path):
    """Same seed => the background fault schedule derives from one RNG
    draw order (faultplane.py); the report records it for reproduction."""
    report = run_soak(
        str(tmp_path),
        duration_s=2.0,
        rate=30.0,
        seed=77,
        admission_depth=16,
        namespace_cap=8,
        nack_delay_s=0.5,
        faults=True,
        node_count=4,
        loadgen_overrides={
            "submitters": 2,
            "dispatch": False,
            "node_churn_period_s": 0.0,
        },
    )
    assert report["seed"] == 77
    assert report["invariants_ok"], report["invariant_error"]
    assert report["converged"]


@pytest.mark.slow
def test_soak_sustained_10min(tmp_path):
    """The acceptance-shaped soak: 10 minutes of sustained overload
    with node churn, dispatch traffic, background faults, AND a
    partition/heal cycle. Gates exactly like the mini-soak."""
    report = run_soak(
        str(tmp_path),
        duration_s=600.0,
        rate=200.0,
        seed=42,
        admission_depth=96,
        namespace_cap=48,
        blocked_cap=96,
        nack_delay_s=1.0,
        rpc_rate=40.0,
        rpc_burst=80.0,
        use_tpu_worker=True,
        faults=True,
        partition_cycle=True,
        node_count=12,
        p99_bound_s=30.0,
        loadgen_overrides={"submitters": 8},
    )
    assert report["invariants_ok"], report["invariant_error"]
    assert report["converged"]
    assert report["admission_engaged"], report["counters"]
    assert report["p99_bounded"], report.get("e2e_seconds")
    assert report["drained"]


def test_loadgen_unit_against_single_server(tmp_path):
    """LoadGen also drives a bare ClusterServer (no ChaosCluster, no
    faults): the closed loop, pacing, and report plumbing in isolation."""
    from nomad_tpu.server.cluster import ClusterServer

    cs = ClusterServer("solo", data_dir=str(tmp_path), num_workers=1)
    cs.start()
    try:
        assert chaos.plane is None
        cfg = LoadGenConfig(
            rate_eval_per_s=30.0,
            duration_s=2.0,
            seed=5,
            node_count=3,
            submitters=2,
            dispatch=True,
            node_churn_period_s=0.0,
        )
        gen = LoadGen(cs, cfg)
        report = gen.run()
        assert report["offered"] > 0
        assert report["accepted"] > 0
        assert report["failed"] == 0
        assert report["drained"]
        # nothing configured => nothing shed or throttled
        assert report["counters"]["nomad.broker.shed"] == 0
        assert report["counters"]["nomad.rpc.throttled"] == 0
        # every job the generator acked exists and is running
        live = {j.id for j in cs.server.state.jobs() if not j.stop}
        assert gen.acked_jobs <= live
        # flight-recorder false-positive gate (docs/incidents.md): the
        # blackbox journaled this clean run (leadership + broker
        # events) but every default trigger threshold stayed out of
        # reach — a healthy cluster captures ZERO incidents
        from nomad_tpu import blackbox

        rec = blackbox.recorder()
        assert rec.recorded > 0, "blackbox journaled nothing"
        assert rec.incidents() == [], rec.incidents()
        assert rec.stats()["triggers_fired"] == 0
    finally:
        cs.shutdown()


# ---------------------------------------------------------------------------
# Duplicate-alloc invariant forensics (the ~1/7 bench-soak flake,
# CHANGES round 15): the failure path must carry evidence — plan-apply
# snapshot index vs raft commit index, the two allocs' minting entries
# — so the next session fixes the race on evidence instead of theory.
# ---------------------------------------------------------------------------


def test_duplicate_alloc_failure_carries_store_forensics(tmp_path):
    """A constructed duplicate on a live single server must raise with
    the full evidence bundle: both alloc ids, their create/modify
    indexes, the minting evals' snapshot_index, the server's raft
    commit/applied indexes, and the raft log entries carrying each id."""
    import json
    import time

    from nomad_tpu import mock
    from nomad_tpu.server.cluster import ClusterServer
    from nomad_tpu.structs import generate_uuid

    def wait(pred, timeout_s=10.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if pred():
                return True
            time.sleep(0.05)
        return False

    cs = ClusterServer("forensics", data_dir=str(tmp_path), num_workers=1)
    cs.start()
    try:
        assert wait(cs.is_leader)
        cs.server.raft_apply("node_register", mock.node())
        job = mock.job(id="dup-job")
        job.task_groups[0].count = 1
        cs.server.job_register(job)
        assert wait(
            lambda: any(
                not a.terminal_status()
                for a in cs.server.state.allocs_by_job("default", "dup-job")
            )
        )
        first = next(
            a
            for a in cs.server.state.allocs_by_job("default", "dup-job")
            if not a.terminal_status()
        )
        # mint the duplicate THROUGH raft (a real log entry to scan)
        dup = first.copy()
        dup.id = generate_uuid()
        cs.server.raft_apply("alloc_update", [dup])
        with pytest.raises(AssertionError) as exc:
            chaos.assert_no_duplicate_allocs(
                cs.server.state, label="forensics", cluster_server=cs
            )
        msg = str(exc.value)
        assert "forensics:" in msg
        detail = json.loads(msg.rsplit("forensics: ", 1)[1])
        ids = {row["id"] for row in detail["allocs"]}
        assert ids == {first.id, dup.id}
        for row in detail["allocs"]:
            assert row["create_index"] > 0
            assert row["eval_id"]
        # the first alloc's eval carries its plan-apply snapshot index
        assert any("eval" in row for row in detail["allocs"])
        raft = detail["raft"]
        assert raft["commit_index"] >= raft["snapshot_last_index"]
        # both ids located in the raft log (minting entries)
        assert all(detail["mint_entries"][i] for i in ids), detail
    finally:
        cs.shutdown()


@pytest.mark.slow
def test_soak_duplicate_alloc_repro_seed42(tmp_path):
    """Regression harness for the r15/r17 bench-soak duplicate-alloc
    race (30s, partition_cycle, TPU worker, seed 42 — flipped ~1/7 on
    the pre-fix commit). The r17 forensics proved both duplicate ids
    were minted by the SAME eval in ONE merged plan-apply raft entry;
    the merge round now trims the later (eval, name) entrant
    (plan_apply._trim_duplicate_mints), so the known-flaky
    configuration must hold its invariants on EVERY attempt — the
    xfail-with-evidence posture is retired with the fix."""
    attempts = int(os.environ.get("NOMAD_TPU_DUP_REPRO_ATTEMPTS", "6"))
    for i in range(attempts):
        report = run_soak(
            str(tmp_path / f"a{i}"),
            duration_s=30.0,
            rate=120.0,
            seed=42,
            use_tpu_worker=True,
            faults=True,
            partition_cycle=True,
            node_count=10,
        )
        assert report["invariants_ok"], (
            f"attempt {i + 1}/{attempts}: "
            + report.get("invariant_error", "")[:3000]
        )
        assert report["converged"], f"attempt {i + 1}/{attempts}"
