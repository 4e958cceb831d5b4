"""Where ``serve``'s engine runs, decided before any program is compiled:
``--replicas`` puts each engine's decode output on its own device; the
compile-cache helper sets a directory only where none was given; and ``serve
--backend tpu`` refuses a backend it was not asked to run on.
"""

from __future__ import annotations

import os

import jax
import pytest


# ---------------------------------------------------------------------------
# --replicas: one engine per device
# ---------------------------------------------------------------------------

def test_each_replica_dispatches_on_its_own_device(cpu_devices):
    """cli.py builds replica i under ``jax.default_device(d[i])`` and then
    commits it there.  Without the commit the arrays are uncommitted, the
    engine loop (which runs outside that context) dispatches on device 0,
    and the donated cache follows — four replicas on one chip."""
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    def replica(i, commit):
        with jax.default_device(cpu_devices[i]):
            eng = InferenceEngine(engine_cfg=EngineConfig(
                model="tiny", num_slots=2, max_seq=64, dtype="float32",
                decode_steps=2, seed=i, prefix_cache=True,
            ))
        if commit:
            eng.commit_to(cpu_devices[i])
        return eng

    for i in range(4):
        eng = replica(i, commit=True)
        outs, _ = eng._dispatch_decode(view=64, steps=2)
        assert {d.id for d in outs[0].devices()} == {cpu_devices[i].id}
        assert eng.resident_devices() == [cpu_devices[i].id]
    # The control: what the parent commit did for every replica but the first.
    eng = replica(3, commit=False)
    eng._dispatch_decode(view=64, steps=2)
    assert {d.id for d in eng.kv_cache["k"].devices()} == {cpu_devices[0].id}


# ---------------------------------------------------------------------------
# the compile-cache helper
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_updates(monkeypatch):
    """Record jax.config.update calls instead of moving this process's cache."""
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    return calls


def test_compile_cache_set_from_outside_sets_nothing_in_code(
        monkeypatch, cache_updates, tmp_path):
    from p2p_llm_tunnel_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert cache_updates == []  # JAX reads the variable itself


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_updates):
    from p2p_llm_tunnel_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable() == want
    assert cache_updates == [("jax_compilation_cache_dir", want)]


# ---------------------------------------------------------------------------
# serve --backend tpu serves the TPU, or the CPU when asked
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("platform,asked,refused", [
    ("tpu", None, False),
    ("tpu", "cpu", False),
    ("cpu", "cpu", False),     # the documented way to run tests and rehearsals
    ("cpu", "cpu,tpu", False),
    ("cpu", None, True),       # JAX fell back: say so, do not serve
    ("cpu", "", True),
    ("cpu", "tpu,cpu", True),
    ("gpu", "cpu", True),
])
def test_require_tpu_backend(monkeypatch, platform, asked, refused):
    from p2p_llm_tunnel_tpu.cli import require_tpu_backend

    if asked is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", asked)
    if not refused:
        require_tpu_backend(platform, "serve --backend tpu")
        return
    with pytest.raises(SystemExit) as e:
        require_tpu_backend(platform, "serve --backend tpu")
    assert repr(platform) in str(e.value)  # names the platform it found


def test_serve_backend_tpu_refuses_before_building_an_engine(monkeypatch):
    """Through the CLI's own start-up path: on this CPU-only test process,
    with no explicit JAX_PLATFORMS=cpu, ``serve --backend tpu`` exits and
    no engine is ever constructed."""
    import asyncio

    import p2p_llm_tunnel_tpu.cli as cli_mod
    import p2p_llm_tunnel_tpu.engine.engine as eng_mod

    built = []
    monkeypatch.setattr(
        eng_mod, "InferenceEngine", lambda **kw: built.append(kw)
    )
    monkeypatch.setattr(cli_mod, "_BACKEND", None)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    args = cli_mod.build_parser().parse_args(
        ["serve", "--room", "r", "--backend", "tpu"]
    )
    with pytest.raises(SystemExit, match="'cpu'"):
        asyncio.run(cli_mod._engine_backend(args))
    assert built == []
