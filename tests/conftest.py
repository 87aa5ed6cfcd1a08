"""Test bootstrap: force an 8-device virtual CPU platform.

Mirrors the reference's keystone test trick (SURVEY.md §4): everything
distributed is testable on one host — the master runs in-process and the
device mesh comes from XLA's forced host platform.

The platform is forced through ``jax.config`` as well as the tier-1
command's ``JAX_PLATFORMS=cpu``, so a bare ``pytest`` lands on the CPU
too. ``XLA_FLAGS`` is read lazily at first backend creation, which has
not happened yet when this file runs.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

assert len(jax.devices()) == 8, (
    "tests need the 8-device virtual CPU platform, got: " + str(jax.devices())
)

import glob  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _cleanup_shm():
    """Remove checkpoint shm segments staged during tests."""
    yield
    for path in glob.glob("/dev/shm/dlrover_tpu_ckpt_*"):
        try:
            os.unlink(path)
        except OSError:
            pass


@pytest.fixture(autouse=True)
def _fresh_step_clock():
    """The step clock is the process's: a test starts without the one
    the test before it ticked (its beat thread, its learnt period)."""
    yield
    from dlrover_tpu.observability.profiler import reset_step_clock

    reset_step_clock()
