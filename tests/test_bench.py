"""bench.py contract test: the driver runs it at round end, so a
breakage found THERE costs the round's numbers. The smoke config runs
here as an EXPLICIT CPU run (JAX_PLATFORMS=cpu): there is no probe and no
fallback, the payload names its device, and a run that lands on XLA:CPU
without having asked for it does not start."""

import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench(env):
    return subprocess.run(
        [sys.executable, "bench.py"],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT, timeout=600,
    )


def test_bench_smoke_contract():
    proc = _bench(dict(os.environ, BENCH_CONFIG="smoke", JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    # the one-line contract the driver records
    assert out["metric"] == "smoke_scheduler_throughput"
    assert out["unit"] == "evals/sec"
    assert out["value"] > 0 and out["vs_baseline"] > 0
    # an explicitly requested CPU run is labelled `cpu`, with the device
    # as jax reports it — no probe verdict, no fallback label
    assert out["platform"] == "cpu"
    # (count: conftest's virtual-device XLA_FLAGS reach the child)
    assert out["device"]["platform"] == out["device"]["kind"] == "cpu"
    assert out["device"]["count"] >= 1
    assert not [k for k in out if k.endswith("_available")]
    smoke = out["configs"]["smoke"]
    assert smoke["tpu_placed"] == smoke["host_placed"] == 10
    assert smoke["density_within_1pct"] in (True, False)


def test_bench_does_not_start_on_an_unrequested_cpu():
    """No TPU and no explicit request for the CPU: jax would resolve to
    XLA:CPU by itself, and the bench refuses instead of measuring a
    different experiment under the same name."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = _bench(dict(env, BENCH_CONFIG="smoke"))
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert proc.stdout.strip() == ""
