"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; all sharding/collective
tests run on XLA's host platform with 8 virtual devices (the driver
separately dry-run-compiles the multi-chip path via __graft_entry__).

Tier-1 runs under ``JAX_PLATFORMS=cpu``; the ``jax.config.update``
below holds a plain ``pytest`` run to the CPU backend too.  XLA_FLAGS
must be set before the backend initializes (it does so lazily, so
doing it here is early enough).
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: high-cardinality soaks -- deselected by the tier-1 "
        "\"-m 'not slow'\" gate, run by the dedicated CI soak steps")
