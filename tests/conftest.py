"""Test environment: force an 8-device virtual CPU platform so multi-chip
sharding paths compile and run without TPU hardware.

The jax.config.update below is the tests' EXPLICIT request for the CPU
(the solver refuses to land there silently — scheduler/tpu/device.py);
it must run before backend init.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

# Cloud metadata fingerprinters probe link-local addresses with a short
# timeout; point them at a closed local port so every Client.start gets
# an instant connection-refused instead of a blackhole timeout. Tests
# that exercise them override with a fake metadata server.
for _var in ("AWS_ENV_URL", "GCE_ENV_URL", "AZURE_ENV_URL"):
    os.environ.setdefault(_var, "http://127.0.0.1:1/")
