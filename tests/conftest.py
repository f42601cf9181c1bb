"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax's
backend initializes.

Tests run on the CPU backend, chosen from outside the program
(`JAX_PLATFORMS=cpu`, set here in case the caller did not and repeated
through `jax.config` so a platform list inherited from the environment
cannot win). Multi-chip behavior (replica mesh axis, partition
sharding, psum quorum) is exercised on the virtual CPU mesh; the chip
itself is driven only by `chip_smoke.py` and the benchmark
(`benchmarks/run.py`), one process per chip.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
