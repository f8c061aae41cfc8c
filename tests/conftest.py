"""Test config: force an 8-device virtual CPU mesh BEFORE the backend initializes.

This replaces the reference's "multi-node without a cluster" approach
(real gRPC on loopback) with a virtual device mesh, per SURVEY.md §4.

Tests always run on the CPU backend, whatever ``JAX_PLATFORMS`` says:
``jax.config.update`` below wins as long as no backend has been initialized
yet, and XLA_FLAGS is read at backend init, so setting it here is in time.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from p2pfl_tpu.settings import set_test_settings  # noqa: E402


@pytest.fixture(autouse=True)
def _fast_settings():
    set_test_settings()
    from p2pfl_tpu.management.logger import logger

    logger.set_level("DEBUG")
    yield


@pytest.fixture(autouse=True)
def _no_leaked_nodes():
    """Cross-test isolation: a test that fails before stopping its nodes
    must not leave live heartbeater/gossiper threads interfering with every
    test after it (observed: leaked gRPC heartbeaters evicting neighbors
    suite-wide). Stops leftovers and makes the leak visible."""
    yield
    from p2pfl_tpu.node import stop_leaked_nodes

    leaked = stop_leaked_nodes()
    if leaked:
        import warnings

        warnings.warn(f"test leaked running nodes (now stopped): {leaked}", stacklevel=1)
