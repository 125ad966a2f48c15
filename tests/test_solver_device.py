"""Where the solver runs and where its compiled programs are kept
(scheduler/tpu/device.py), and how the worker classifies a failure of
the device it was given (server/worker.py)."""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_real_jax_runtime_error_is_a_retriable_device_error():
    """The failover branch must be reachable by what the runtime
    actually raises on this jax (jax.errors.JaxRuntimeError), not only
    by the chaos plane's injected DeviceFault."""
    import jax
    import jax.numpy as jnp

    from nomad_tpu.faultplane import DeviceFault
    from nomad_tpu.server.worker import _retriable_device_error

    with pytest.raises(jax.errors.JaxRuntimeError) as ei:
        # a real runtime refusal: RESOURCE_EXHAUSTED from the allocator
        jnp.zeros((1 << 42,), jnp.float32).block_until_ready()
    assert _retriable_device_error(ei.value)
    assert _retriable_device_error(DeviceFault("x", retriable=True))
    assert not _retriable_device_error(DeviceFault("x", retriable=False))
    # a host-side bug is not a sick device: it must nack, not fail over
    assert not _retriable_device_error(RuntimeError("plain"))
    assert not _retriable_device_error(ValueError("shape"))


def test_resolve_device_refuses_a_cpu_nobody_asked_for():
    import jax

    from nomad_tpu.scheduler.tpu import device

    # the tests' own explicit request (conftest) resolves and is stamped
    requested = jax.config.jax_platforms
    assert requested == "cpu"
    device.resolve_device.cache_clear()
    assert device.resolve_device().to_wire() == {
        "platform": "cpu", "kind": "cpu", "count": jax.device_count(),
    }
    try:
        # the same backend with no request for it on record: refused.
        # `tpu,cpu` asks for the TPU — only a leading `cpu` asks for the
        # CPU. (The backend is already up; the option is only read.)
        for unrequested in (None, "tpu,cpu"):
            device.resolve_device.cache_clear()
            jax.config.update("jax_platforms", unrequested)
            with pytest.raises(RuntimeError, match="no accelerator"):
                device.resolve_device()
    finally:
        jax.config.update("jax_platforms", requested)
        device.resolve_device.cache_clear()


_TINY_SOLVE = """
import json, sys
import jax
from jax import monitoring
hits = []
monitoring.register_event_listener(
    lambda e, **kw: hits.append(e) if e.endswith("/cache_hits") else None)
import numpy as np
from nomad_tpu.scheduler.tpu.kernels import solve_placement  # package init
cap = np.full((256, 3), 4000, np.int32)
used = np.zeros((256, 3), np.int32)
asks = np.full((8, 3), 100, np.int32)
counts = np.full((8,), 4, np.int32)
feas = np.ones((8, 256), bool)
bias = np.zeros((8, 256), np.float32)
ucap = np.full((8, 256), 1 << 20, np.int32)
assign, _ = solve_placement(cap, used, asks, counts, feas, bias, ucap)
print(json.dumps({"placed": int(np.asarray(assign).sum()),
                  "cache_dir": jax.config.jax_compilation_cache_dir,
                  "hits": len(hits)}))
"""


def _tiny_solve(env):
    proc = subprocess.run(
        [sys.executable, "-c", _TINY_SOLVE], capture_output=True, text=True,
        cwd=REPO_ROOT, timeout=120, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_is_placed_from_outside_and_hits(tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is where the solver's
    programs are kept — two processes in turn: the second one finds the
    first one's (sub-second jits included, the thresholds are lowered)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    first = _tiny_solve(env)
    assert first == {"placed": 32, "cache_dir": str(tmp_path / "cc"),
                     "hits": 0}
    assert os.listdir(tmp_path / "cc")
    second = _tiny_solve(env)
    assert second["placed"] == 32 and second["hits"] >= 1


def test_compile_cache_defaults_to_the_checkout():
    """Unset, the directory is <checkout>/.jax_cache — derived from the
    package's own path, never a temporary, a pid or a time."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    out = _tiny_solve(dict(env, JAX_PLATFORMS="cpu"))
    assert out["cache_dir"] == os.path.join(REPO_ROOT, ".jax_cache")
