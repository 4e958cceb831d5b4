"""Test configuration: run all JAX code on a virtual 8-device CPU mesh.

Mirrors how the reference tests "multi-node" behavior with localhost processes
(SURVEY.md §4): we substitute 8 virtual CPU devices for a TPU slice so every
sharding/collective path is exercised in CI without TPU hardware.  Tests
force the CPU: they must never take a chip.
"""

import contextlib
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
# The CPU compiler's optimiser is most of a test's wall and is under test in
# no file: the product's programs are compiled for the TPU.  A subprocess a
# test starts inherits the variable as it inherits the platform.  The files
# that compile for a described chip turn it back on (the ``chip`` fixture),
# and a test that needs it asks for ``full_optimiser``.
os.environ["JAX_DISABLE_MOST_OPTIMIZATIONS"] = "1"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_disable_most_optimizations", True)

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


@contextlib.contextmanager
def _optimiser_on():
    cheap = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    try:
        yield
    finally:
        jax.config.update("jax_disable_most_optimizations", cheap)


@pytest.fixture
def full_optimiser():
    """The CPU compiler's optimiser back on, for the test that holds a whole
    jitted program to the same arithmetic run an operation at a time TO THE
    BIT (the benchmark's reference draws its weights in one jitted call, the
    program's ``init_params`` an operation at a time): unoptimised, the one
    program keeps an intermediate that the separate operations round, and a
    weight in a dozen differs in its last place.  The setting is no part of a
    jitted function's cache key, so what was compiled without it is dropped on
    the way in and what was compiled with it on the way out."""
    jax.clear_caches()
    with _optimiser_on():
        yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def chip():
    """One described (not attached) v5e chip to compile for, for the module
    that asks (tests/test_tpu_compile*.py).  While it stands, the optimiser
    this run otherwise does without is on: those tests assert on the
    optimised program.  And the persistent compile cache is off: a compile for
    a described chip is written to it but cannot be read back without a chip,
    so the next run would warn and compile again."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # (else the compiler logs under /tmp)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with _optimiser_on():
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()
