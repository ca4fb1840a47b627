"""Test harness config: an 8-device virtual CPU mesh.

Tests run on the CPU, even where a TPU is attached; sharding and
collective paths run on 8 virtual CPU devices.  Both settings must be
made before any JAX backend is initialised.  This virtual mesh exists for
tests only: chip_smoke.py and the entry points use the real devices.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# lockdep: validate asyncio lock acquisition ORDER across the whole
# suite (reference common/lockdep role) — a violation raises at the
# offending acquisition, failing that test with the two sites involved
from ceph_tpu.common.lockdep import lockdep_enable  # noqa: E402

lockdep_enable()
