"""Test configuration: run on a virtual 8-device CPU mesh.

Tests and drive scripts run on the CPU: the shell sets
``JAX_PLATFORMS=cpu`` (the driver's command does) and the update below
holds the platform choice to the CPU in any case, so a test never
reaches for an accelerator.  XLA_FLAGS must be set before the CPU client
is created, which happens at the first traced op — conftest import is
early enough — and gives the backend the 8 virtual devices the sharding
tests use.

The chip is not reached from here.  It is reached only through the
builder's chip tool, with ``python chip_smoke.py`` (one chip) and
``python chip_smoke.py --chips 4``.  The chip's compiler can be asked
for a chip that is not attached, and ``tests/test_chip_compile.py`` does
so — inside its own fixture; nothing here loads it or describes a
topology.

All tests run in float32 (the TPU solver dtype); tolerance constants in the
tests reflect that.
"""

import os

prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
