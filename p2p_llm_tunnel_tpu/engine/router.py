"""Data-parallel replica routing: fan requests across engine replicas.

The SURVEY.md §2 parallelism table calls for DP as "replica groups …;
request router shards streams across replicas".  Each replica is one
InferenceEngine (its own slots/KV cache — typically its own chip or
tp-mesh); the router admits each request to the least-loaded replica, so
concurrent streams from one or many proxy peers spread across all chips.

Placement of replicas on distinct devices is the caller's job (one process
per chip, or build each engine under `jax.default_device(d)` and then
`engine.commit_to(d)` — cli.py); the router itself is pure dispatch policy.
"""

from __future__ import annotations

import functools
from typing import List, Optional

from p2p_llm_tunnel_tpu.engine.api import EngineAPI
from p2p_llm_tunnel_tpu.engine.engine import InferenceEngine, device_section
from p2p_llm_tunnel_tpu.protocol.frames import RequestHeaders
from p2p_llm_tunnel_tpu.utils.logging import get_logger

log = get_logger(__name__)


class ReplicaRouter:
    """Least-loaded dispatch over N engine replicas."""

    def __init__(self, engines: List[InferenceEngine],
                 model_name: Optional[str] = None):
        if not engines:
            raise ValueError("need at least one engine replica")
        self.engines = engines
        self.apis = [EngineAPI(e, model_name) for e in engines]
        self._rr = 0

    def _load(self, engine: InferenceEngine) -> float:
        sched = engine.scheduler
        return sched.queue_depth + sched.occupancy * sched.num_slots

    def pick(self) -> int:
        """Least-loaded replica; round-robin tiebreak so idle replicas all
        see traffic (and stay warm) under light load."""
        loads = [self._load(e) for e in self.engines]
        low = min(loads)
        candidates = [i for i, l in enumerate(loads) if l == low]
        self._rr = (self._rr + 1) % len(candidates)
        return candidates[self._rr % len(candidates)]

    async def start(self) -> None:
        for e in self.engines:
            await e.start()

    async def stop(self) -> None:
        for e in self.engines:
            await e.stop()

    async def handle(self, req: RequestHeaders, body: bytes):
        idx = self.pick()
        log.debug("routing stream %d to replica %d", req.stream_id, idx)
        return await self.apis[idx].handle(req, body)


def router_backend(router: ReplicaRouter):
    """Adapter: ReplicaRouter as a serve-endpoint Backend."""

    async def backend(req: RequestHeaders, body: bytes):
        return await router.handle(req, body)

    backend.device_section = functools.partial(
        device_section, router.engines
    )
    return backend
