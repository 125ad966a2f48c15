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
# Exit code: nonzero on any pytest failure.
# Budget ~30+ minutes.
set -euo pipefail
cd "$(dirname "$0")/.."

: "${JAX_PLATFORMS:=cpu}"
export JAX_PLATFORMS

python -m pytest tests/ -q -m slow \
  --continue-on-collection-errors \
  -p no:cacheprovider -p no:xdist -p no:randomly \
  "$@"
