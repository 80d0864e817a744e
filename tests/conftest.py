"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Tests never touch a chip: sharding correctness is validated on a forced
8-device CPU platform, Pallas kernels run in interpret mode where a test
asks for it, and tests/test_pallas_lowering.py lowers and compiles them for
the TPU without one.  The chip itself is met by ``chip_smoke.py``.

Run as ``env JAX_PLATFORMS=cpu python -m pytest tests/``; the config update
below makes a bare ``pytest`` behave the same.
"""

import os

# XLA_FLAGS is read lazily at first backend initialization, so setting it
# here (before any backend exists) works.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
