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

import faulthandler
import signal
import tempfile

import pytest

#: Seconds one test's setup or call may take: 3.6 x the slowest phase
#: measured (83.6 s).  A parked test then costs its run five minutes,
#: not the files queued behind it on the same xdist worker.
TEST_LIMIT_S = 300


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Fail ``item`` by name, with every thread's stack, when the phase
    outlasts TEST_LIMIT_S.  The handler raises in the main thread (pytest
    and xdist run the tests there) as soon as it is back in Python."""

    def on_alarm(signum, frame):
        with tempfile.TemporaryFile("w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            pytest.fail(f"{item.nodeid} exceeded its limit of "
                        f"{TEST_LIMIT_S} s\n{f.read()}", pytrace=False)

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


pytest_runtest_setup = pytest_runtest_call  # fixtures get the same limit


@pytest.fixture
def clean_faults():
    """No armed fault reaches a test or outlives it (the files that arm
    faults ask for it with ``pytestmark = usefixtures``)."""
    from cup3d_tpu.resilience import faults

    faults.clear()
    yield
    faults.clear()
