"""Test configuration: run all JAX code on a virtual 8-device CPU mesh.

Mirrors how the reference tests "multi-node" behavior with localhost processes
(SURVEY.md §4): we substitute 8 virtual CPU devices for a TPU slice so every
sharding/collective path is exercised in CI without TPU hardware.  Tests
force the CPU: they must never take a chip.
"""

import os
import subprocess
import sys

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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def build_native(root=REPO):
    """Build ``<root>/native/build/*.so`` with ``<root>/scripts/build-native.sh``
    where a library is missing or older than a source (the libraries are
    git-ignored: a fresh checkout has none).  Returns None when they stand,
    and the build's output when it failed."""
    srcs = [os.path.join(root, "native", f)
            for f in ("tunnel_frames.cc", "tunnel_arq.cc")]
    libs = [os.path.join(root, "native", "build", f)
            for f in ("libtunnelframes.so", "libtunnelarq.so")]
    newest = max(os.path.getmtime(p) for p in srcs)
    if all(os.path.exists(p) and os.path.getmtime(p) >= newest for p in libs):
        return None
    script = os.path.join(root, "scripts", "build-native.sh")
    try:
        subprocess.run([script], check=True, stdout=subprocess.PIPE,
                       stderr=subprocess.STDOUT)
    except (OSError, subprocess.CalledProcessError) as e:
        out = getattr(e, "stdout", b"") or b""
        return (f"{script} failed ({e}):\n"
                + out.decode("utf-8", "replace")[-1500:])
    return None


def pytest_configure(config):
    """The native codec and ARQ core are what a deployed tunnel runs, and
    the package opens them once, at import: build them before a worker starts
    or a test module is imported, in the controlling process only.  A build
    that fails does not end the session: the tests that ask for ``native_libs``
    fail with its output."""
    if hasattr(config, "workerinput"):
        return
    error = build_native()
    if error:
        sys.stderr.write(f"native libraries not built: {error}\n")


@pytest.fixture(scope="session")
def native_builder():
    """The build step itself, for the test that points it at a copy of
    ``native/`` (tests/benchmarks has a ``conftest`` of its own, so this
    module is not importable by name)."""
    return build_native


@pytest.fixture(scope="session")
def native_libs():
    """Fails, with the build's output, where the native libraries do not
    stand.  After a good ``pytest_configure`` this is four ``stat`` calls."""
    error = build_native()
    if error:
        pytest.fail(f"native libraries not built: {error}", pytrace=False)


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices
