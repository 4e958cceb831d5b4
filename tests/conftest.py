"""Test configuration: run all JAX code on a virtual 8-device CPU mesh.

Mirrors how the reference tests "multi-node" behavior with localhost processes
(SURVEY.md §4): we substitute 8 virtual CPU devices for a TPU slice so every
sharding/collective path is exercised in CI without TPU hardware.  Tests
force the CPU: they must never take a chip.
"""

import os

# XLA flags are read at backend init; set before anything initialises one.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices
