"""The native libraries are built, not shipped (``native/build`` is
git-ignored): tests/conftest.py builds them before anything is collected, so
that every test process runs the codec and the ARQ core a deployed tunnel
runs, and a checkout's first run counts what its second does."""

import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_native_libraries_are_live_in_this_process():
    """No fixture: the package opened both libraries when it was imported,
    which is after ``pytest_configure`` built them."""
    from p2p_llm_tunnel_tpu.protocol import native
    from p2p_llm_tunnel_tpu.transport import arq

    assert native.available(), "protocol/native.py runs the Python codec"
    assert arq.native_available(), "transport/arq.py runs the Python core"
    assert type(arq.make_arq()) is arq.NativeArq


def test_build_step_rebuilds_a_stale_library_and_leaves_a_fresh_one(
        tmp_path, native_builder):
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "native"), os.path.join(root, "native"),
                    ignore=shutil.ignore_patterns("build"))
    os.makedirs(os.path.join(root, "scripts"))
    shutil.copy(os.path.join(REPO, "scripts", "build-native.sh"),
                os.path.join(root, "scripts"))
    libs = [os.path.join(root, "native", "build", f)
            for f in ("libtunnelframes.so", "libtunnelarq.so")]

    def mtimes():
        return [os.stat(p).st_mtime_ns for p in libs]

    assert native_builder(root) is None  # none there: built
    assert sorted(os.listdir(os.path.join(root, "native", "build"))) == [
        "libtunnelarq.so", "libtunnelframes.so"]  # and no temporary left
    built = mtimes()

    assert native_builder(root) is None  # fresh: left alone
    assert mtimes() == built

    later = max(built) / 1e9 + 10  # one source newer than the libraries
    os.utime(os.path.join(root, "native", "tunnel_arq.cc"), (later, later))
    assert native_builder(root) is None
    assert all(now != was for now, was in zip(mtimes(), built))
