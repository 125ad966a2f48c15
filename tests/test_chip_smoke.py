"""chip_smoke.py contract test. The driver runs the script on the chip
after every PR; what can be held here, on a box with no chip, is the two
ends of that contract: the explicit rehearsal passes at a tiny size and
says what it is, and without the rehearsal argument the script refuses —
fast, non-zero, naming the missing TPU — instead of passing on XLA:CPU.
"""

import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args, env=None, cwd=REPO_ROOT, timeout=300):
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, cwd=cwd, timeout=timeout,
        env=dict(os.environ, **(env or {})),
    )


def test_rehearsal_passes_on_cpu_and_says_so(tmp_path):
    proc = _run("--rehearsal",
                env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert proc.returncode == 0, proc.stderr[-3000:]
    report_line, verdict_line = proc.stdout.strip().splitlines()[-2:]
    # the last line is the driver's verdict: these keys and no others
    verdict = json.loads(verdict_line)
    assert verdict == {"ok": True, "device": verdict["device"]}
    assert sorted(verdict["device"]) == ["count", "kind", "platform"]
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    out = json.loads(report_line)
    assert out["mode"] == "rehearsal" and out["platform"] == "cpu"
    assert out["device_count"] == verdict["device"]["count"]
    for name, wave in out["waves"].items():
        assert wave["placed"] == wave["asked"] > 0, (name, wave)
        # a CPU second is never printed under any name
        assert not any(k.endswith("_s") for k in wave), wave
    assert not any(k.endswith("_s") for k in out)
    assert out["waves"]["C"]["evicted"] > 0
    assert out["density"]["ratio"] >= 0.99
    assert out["device_failover"] == 0 and out["invoke_failed"] == 0
    # every wave went through the device path, not the host fast paths
    assert set(out["ledger_platforms"]) >= {
        "solve_placement_compact", "solve_placement_preempt", "scatter_rows",
    }
    assert all(p == ["cpu"] for p in out["ledger_platforms"].values())
    # XLA:CPU keeps the microsolve's tie order bit for bit
    assert out["parity"]["chip_equals_microsolve"] is True
    assert out["compile"]["cache_dir"] == str(tmp_path / "cache")


def test_without_a_chip_it_refuses_fast_and_names_the_tpu():
    t0 = time.monotonic()
    proc = _run(env={"JAX_PLATFORMS": "cpu"}, timeout=60)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 30
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""  # no result line


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    """The driver's second negative: the script with nothing else of the
    repo beside it must not pass."""
    script = tmp_path / "chip_smoke.py"
    script.write_bytes(
        open(os.path.join(REPO_ROOT, "chip_smoke.py"), "rb").read()
    )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(script), "--rehearsal"],
        capture_output=True, text=True, cwd=tmp_path, timeout=60, env=env,
    )
    assert proc.returncode != 0
    assert "not importable" in proc.stderr
    assert proc.stdout.strip() == ""
