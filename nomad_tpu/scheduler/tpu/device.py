"""Where the solver runs, and where its compiled programs are kept.

Two decisions every solver-side entry point (the TPU worker,
benchmarks/run.py, chip_smoke.py) shares, made here once so none of
them can make it differently:

  * `resolve_device()` — the backend is whatever jax resolves, and a
    resolution that lands on XLA:CPU is an ERROR unless `jax_platforms`
    (JAX_PLATFORMS) names `cpu` explicitly. A solver that quietly runs
    on the host is a different system with the same API; tests ask for
    the CPU on purpose (tests/conftest.py) and keep working.
  * `configure_compile_cache()` — jax's persistent compilation cache at
    a path that can be placed from outside (JAX_COMPILATION_CACHE_DIR)
    and is otherwise FIXED (`<checkout>/.jax_cache`): a directory that
    moves between runs never hits, so it is never derived from a pid, a
    time, or a temporary.
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass
from pathlib import Path

import jax

logger = logging.getLogger("nomad_tpu.scheduler.tpu")

# <checkout>/nomad_tpu/scheduler/tpu/device.py -> <checkout>/.jax_cache
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


@dataclass(frozen=True)
class SolverDevice:
    platform: str
    device_kind: str
    count: int

    def to_wire(self) -> dict:
        """The device stamp every measured payload carries."""
        return {
            "platform": self.platform,
            "kind": self.device_kind,
            "count": self.count,
        }


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache somewhere stable and
    lower its thresholds so the sub-second jits (the row scatters) are
    kept too. Called from the package __init__ before any kernel is
    imported. Returns the directory in use."""
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        # jax fills the option from JAX_COMPILATION_CACHE_DIR itself;
        # only an unset option gets the checkout-local default
        cache_dir = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def cpu_requested() -> bool:
    """Does `jax_platforms` (JAX_PLATFORMS) explicitly ask for the CPU
    as the default backend? Its FIRST entry decides: `tpu,cpu` asks for
    the TPU and merely keeps the host platform available."""
    platforms = jax.config.jax_platforms or ""
    return platforms.split(",")[0].strip() == "cpu"


@functools.cache
def resolve_device() -> SolverDevice:
    """Resolve the solver's backend (once per process: a failure is not
    cached), log it, and refuse a silent landing on the CPU. Initializes
    the jax backend — the caller holds the chip from here on."""
    devices = jax.devices()
    dev = SolverDevice(
        platform=devices[0].platform,
        device_kind=devices[0].device_kind,
        count=len(devices),
    )
    if dev.platform == "cpu" and not cpu_requested():
        raise RuntimeError(
            "no accelerator: jax resolved to platform 'cpu' "
            f"(jax_platforms={jax.config.jax_platforms!r}) and the "
            "solver does not fall back to XLA:CPU — attach a TPU, or "
            "set JAX_PLATFORMS=cpu to run on the host on purpose"
        )
    logger.info(
        "solver device: platform=%s kind=%s count=%d compile cache=%s",
        dev.platform, dev.device_kind, dev.count,
        jax.config.jax_compilation_cache_dir,
    )
    return dev
