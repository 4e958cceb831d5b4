"""Multi-host runtime: jax.distributed initialization + DCN-aware meshes.

This is the scale-out tier of the two-tier communication design (SURVEY.md
§5): WAN traffic rides the encrypted tunnel channel, chip-to-chip traffic
rides XLA collectives — over ICI inside a slice, over DCN between hosts.
Where a GPU framework would stand up NCCL/MPI ranks, a JAX multi-host run
is N identical processes that each call ``jax.distributed.initialize``
against one coordinator and then see the GLOBAL device set; GSPMD inserts
the right collective (ICI or DCN) from the mesh placement alone.

Usage (one serve peer per host, same command on every host):

    tunnel serve --backend tpu --model llama3-70b --tp 8 \
        --coordinator host0:8476 --num-processes 4 --process-id $RANK

`make_hybrid_mesh` keeps collective-heavy axes (tp, sp) INSIDE a slice
(ICI) and spreads only dp/ep — whose per-decode-step traffic is zero or
token-sized — across hosts (DCN), matching the bandwidth hierarchy
(ICI ~100s GB/s vs DCN ~10s GB/s per host).

Scope note: every BASELINE.md config fits ONE host (a v5e-8 / v5p-8 slice
is one process with 8 local devices — engine tp=8 works today with no
flags from this module).  Driving the engine loop SPMD across hosts —
rank 0 broadcasting each dispatch's host inputs, other ranks replaying —
lives in parallel/spmd_serve.py (r5; PARITY A8 closed), proven by the
2-process CPU run in tests/test_spmd_serve.py.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

from p2p_llm_tunnel_tpu.parallel.mesh import AXES
from p2p_llm_tunnel_tpu.utils.logging import get_logger

log = get_logger(__name__)


def init_distributed(
    coordinator: str,
    num_processes: int,
    process_id: int,
    local_device_ids: Optional[str] = None,
) -> None:
    """Join the multi-host runtime; after this jax.devices() is GLOBAL.

    Idempotent per process (jax.distributed refuses double init; we guard
    so a router constructing several engines can call it freely).  The
    equivalent of the reference stack's "connect to the signal server"
    step, but for the chip tier: one coordinator, N processes, all
    addressed by rank.
    """
    kwargs = {}
    if local_device_ids:
        kwargs["local_device_ids"] = [
            int(x) for x in str(local_device_ids).split(",")
        ]
    log.info(
        "joining multi-host runtime: coordinator=%s rank=%d/%d",
        coordinator, process_id, num_processes,
    )
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
            **kwargs,
        )
    except RuntimeError as e:
        # Double-init (e.g. a router constructing several engines) is fine;
        # anything else is a real join failure.  The installed jax (0.9)
        # phrases it "distributed.initialize should only be called once."
        if "only be called once" not in str(e):
            raise
        log.debug("jax.distributed already initialized: %s", e)


def make_hybrid_mesh(
    tp: int = 1,
    dp_dcn: int = 1,
    sp: int = 1,
    ep: int = 1,
) -> Mesh:
    """Mesh whose dp axis crosses hosts (DCN) and tp/sp/ep stay slice-local.

    Built with mesh_utils.create_hybrid_device_mesh so each host's devices
    form one contiguous ICI submesh: tp collectives (the per-decode-step
    all-gathers of BASELINE config 4) never leave a slice; only the dp
    axis — which moves no tensor traffic during inference (requests are
    routed, not sharded, across replicas) — spans the slower DCN tier.

    Falls back to the flat single-host mesh when there is only one
    process (e.g. CPU tests), where ICI/DCN distinction is meaningless.
    """
    if jax.process_count() == 1 and dp_dcn == 1:
        from p2p_llm_tunnel_tpu.parallel.mesh import make_mesh

        return make_mesh(tp=tp, dp=1, sp=sp, ep=ep)
    from jax.experimental import mesh_utils

    # tp LAST in mesh_shape = fastest-varying = ICI neighbours, matching
    # make_mesh's layout; then transpose to the canonical AXES order.
    devices = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=(1, ep, sp, tp),
        dcn_mesh_shape=(dp_dcn, 1, 1, 1),
        process_is_granule=False,
    )
    assert devices.shape == (dp_dcn, ep, sp, tp), devices.shape
    return Mesh(np.transpose(devices, (0, 1, 3, 2)), AXES)


# Pod-env flag discovery (TUNNEL_COORDINATOR or MEGASCALE_COORDINATOR_ADDRESS,
# TUNNEL_NUM_PROCESSES, TUNNEL_PROCESS_ID) lives in cli.py's argument
# defaults — the one place that consumes it; this module stays env-free.
