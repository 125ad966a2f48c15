#!/usr/bin/env bash
# Pre-release slow battery: everything tier-1 skips, in one invocation.
#
#   scripts/slow-suite.sh            # the full slow-marked set
#   scripts/slow-suite.sh -k soak    # narrow with any extra pytest args
#
# Covers the slow-marked soak (10-minute sustained traffic with faults,
# tests/test_soak.py), the long chaos scenarios (fsync churn etc.,
# tests/test_chaos.py), the production-ops resilience acceptance
# batteries (tests/test_scenarios.py: 25-seed secret rotation, 25-seed
# rolling upgrade, long spot-node churn — narrow with `-m scenario`),
# the fleet-scale survival soak (>=5k simulated nodes held 10 minutes
# through a mass-expiry + mass-reconnect storm, tests/test_fleet.py —
# narrow with `-m fleet`), and the profiler/observability overhead
# batteries at full length — plus anything else that grows a `slow`
# mark. Runs on the CPU backend
# (the tier-1 posture); point JAX_PLATFORMS elsewhere to exercise a
# real device.
#
# After the pytest battery, runs the smoke_interactive bench config
# (interactive fast path: direct single-eval p50 vs the r08 basis +
# the loaded priority-lane ratio; skip with SLOW_SUITE_NO_INTERACTIVE=1)
# and the c2m_sharded bench sweep (100k+ nodes over mesh sizes 1 and 8
# through the production mesh path), failing if the sharded_scaling
# gate (>= 0.7x linear) or the zero-full-reupload/recompile-bound
# gates regress. Skip the sweep with SLOW_SUITE_NO_SHARDED=1 (e.g. on
# a box mid-perf-capture, where a concurrent sweep would skew the
# capture). Every bench run here is a process of its own, one after
# another — a chip belongs to one process at a time — and the sharded
# sweep asks for the CPU itself (8 virtual devices).
#
# Exit code: nonzero on any pytest failure or sharded-gate failure.
# Budget ~30+ minutes.
set -euo pipefail
cd "$(dirname "$0")/.."

: "${JAX_PLATFORMS:=cpu}"
export JAX_PLATFORMS

python -m pytest tests/ -q -m slow \
  --continue-on-collection-errors \
  -p no:cacheprovider -p no:xdist -p no:randomly \
  "$@"

if [ "${SLOW_SUITE_NO_INTERACTIVE:-0}" != "1" ]; then
  echo "[slow-suite] interactive fast-path gates (BENCH_CONFIG=smoke_interactive)"
  python - <<'PY'
import json, os, subprocess, sys

env = dict(os.environ, BENCH_CONFIG="smoke_interactive")
proc = subprocess.run(
    [sys.executable, "bench.py"], env=env, capture_output=True, text=True
)
sys.stderr.write(proc.stderr[-2000:])
if proc.returncode != 0:
    sys.exit(f"smoke_interactive run failed rc={proc.returncode}")
payload = json.loads(proc.stdout.strip().splitlines()[-1])
cfg = payload["configs"]["smoke_interactive"]
print(
    "[slow-suite] smoke_interactive: direct p50 %.2fms (gate %s), "
    "loaded lane p50 %.1fms vs batch p50 %sms (gate %s)"
    % (
        cfg["single_eval_p50_s"] * 1e3,
        cfg["smoke_interactive_p50_ok"],
        cfg["lane_loaded_p50_s"] * 1e3,
        (cfg["batch_lane_p50_s"] or 0) * 1e3,
        cfg["smoke_interactive_lane_ok"],
    )
)
ok = cfg["smoke_interactive_p50_ok"] and cfg["smoke_interactive_lane_ok"]
sys.exit(0 if ok else "smoke_interactive gates failed")
PY
fi

if [ "${SLOW_SUITE_NO_SHARDED:-0}" != "1" ]; then
  echo "[slow-suite] c2m_sharded device-count sweep (BENCH_CONFIG=c2m_sharded)"
  BENCH_CONFIG=c2m_sharded python - <<'PY'
import json, os, subprocess, sys

env = dict(os.environ, BENCH_CONFIG="c2m_sharded")
proc = subprocess.run(
    [sys.executable, "bench.py"], env=env, capture_output=True, text=True
)
sys.stderr.write(proc.stderr[-2000:])
if proc.returncode != 0:
    sys.exit(f"c2m_sharded sweep failed rc={proc.returncode}")
cfg = json.loads(proc.stdout.strip().splitlines()[-1])["configs"]["c2m_sharded"]
# After the warmup sync ("full"), every steady-round resident sync must
# be a delta scatter or clean — a "full" mid-run means the resident
# shards re-uploaded (docs/sharding.md § re-upload vs delta-sync triage).
steady_fulls = sum(
    1
    for mesh in cfg["per_mesh"].values()
    for mode in mesh["resident_sync_modes"][1:]
    if mode.startswith("full")
)
recompiles = cfg["solver_observability"]["recompiles_after_warmup"]
print(
    "[slow-suite] sharded_scaling=%.3f (gate >= 0.7), "
    "steady_full_reuploads=%d, recompiles_after_warmup=%d"
    % (cfg["sharded_scaling"], steady_fulls, recompiles)
)
ok = (
    cfg["sharded_scaling"] >= cfg["sharded_scaling_linear_gate"]
    and steady_fulls == 0
    and recompiles == 0
)
sys.exit(0 if ok else "c2m_sharded gates failed")
PY
fi
