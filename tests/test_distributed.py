"""Multi-host runtime hooks (parallel/distributed.py) — the parts testable
in one process: mesh construction fallback, env discovery, init guard."""

import jax

from p2p_llm_tunnel_tpu.parallel.distributed import (
    init_distributed,
    make_hybrid_mesh,
)


def test_hybrid_mesh_single_process_falls_back_to_flat():
    mesh = make_hybrid_mesh(tp=4, dp_dcn=1, sp=2)
    assert mesh.axis_names == ("dp", "ep", "tp", "sp")
    assert dict(mesh.shape) == {"dp": 1, "ep": 1, "tp": 4, "sp": 2}
    # tp fastest-varying: adjacent tp coordinates are adjacent devices.
    grid = mesh.devices
    assert grid[0, 0, 0, 0].id + 1 == grid[0, 0, 1, 0].id


def test_cli_rejects_partial_multihost_flags(monkeypatch):
    """--coordinator without rank info must fail loudly, not silently
    start an independent single-host server per pod host."""
    import asyncio

    import pytest

    from p2p_llm_tunnel_tpu.cli import build_parser, _engine_backend

    args = build_parser().parse_args(
        ["serve", "--backend", "tpu", "--model", "tiny",
         "--coordinator", "host0:8476"]
    )
    with pytest.raises(SystemExit, match="num-processes"):
        asyncio.run(_engine_backend(args))


def test_init_distributed_swallows_double_init(monkeypatch):
    """A second init (router building several engines) must be a no-op."""

    # The exact jax 0.9 message — the guard must match what JAX really says.
    def boom(**kw):
        raise RuntimeError("distributed.initialize should only be called once.")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    init_distributed("host0:8476", 4, 1)  # must not raise


def test_init_distributed_propagates_real_failures(monkeypatch):
    def boom(**kw):
        raise RuntimeError("connection refused")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    import pytest

    with pytest.raises(RuntimeError, match="refused"):
        init_distributed("host0:8476", 4, 1)


def test_init_distributed_forwards_args(monkeypatch):
    seen = {}
    monkeypatch.setattr(
        jax.distributed, "initialize", lambda **kw: seen.update(kw)
    )
    init_distributed("host0:8476", 4, 1, local_device_ids="0,1")
    assert seen == {
        "coordinator_address": "host0:8476",
        "num_processes": 4,
        "process_id": 1,
        "local_device_ids": [0, 1],
    }
