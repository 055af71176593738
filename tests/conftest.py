"""Test fixtures: force an 8-device CPU mesh before JAX initializes.

Mirrors the reference's CI strategy of multiple MPI ranks on one machine
(docker-compose.test.yml, .buildkite/gen-pipeline.sh:98-99): here the
"ranks" are 8 virtual CPU devices via
--xla_force_host_platform_device_count (SURVEY.md §4).
"""

import os

# XLA_FLAGS is read at backend-creation time, so setting it here (before
# anything touches a device) is early enough; the platform is pinned through
# jax.config so the suite runs on the CPU whatever JAX_PLATFORMS says.
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def hvd():
    """An initialized horovod_tpu with a fresh coordinator, torn down after
    the test."""
    import horovod_tpu as hvd_mod
    hvd_mod.init()
    yield hvd_mod
    hvd_mod.shutdown()
