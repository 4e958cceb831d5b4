"""What the three routed families' tests ask of a traced run (ISSUE 39):
every ``engine.decode_burst`` and ``engine.prefill_segment`` record of a
routed model says which implementation its grouped expert products ran
(``moe``), and ``engine_moe_kernel_dispatches_total`` grows by exactly the
records that say the kernel, as ``engine_decode_kernel_steps_total`` is
held to ``attn`` in tests/test_decode_rows.py."""

import asyncio
import contextlib
import time

from p2p_llm_tunnel_tpu.models.moe import RAGGED
from p2p_llm_tunnel_tpu.ops.pallas_grouped_matmul import GROUPED_KERNEL
from p2p_llm_tunnel_tpu.utils.metrics import METRICS_CATALOG, global_metrics
from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

COUNTER = "engine_moe_kernel_dispatches_total"
#: The engine-scope records a dispatch closes as; each carries its ``seq``.
DISPATCHES = ("engine.decode_burst", "engine.prefill_segment",
              "engine.pool_copy")


def _counters():
    return {k: v for k, v in global_metrics.snapshot().items()
            if k.startswith("engine_") and k.endswith("_total")}


async def dispatches_closed(eng, timeout: float = 10.0) -> None:
    """Wait until ``eng``'s last dispatches are closed: the last decode
    burst's record closes, and its routed layers' counts reach the
    counters, an iteration of the engine's loop AFTER its tokens were handed
    out.  (A fixed ``sleep(0.3)`` stood here and lost that race under six
    test workers: ISSUE 45.)  With the journal on: until it holds a closed
    record for every dispatch opened since the first it holds, up to the
    last one ``eng`` opened, and no routed layer's counts are left queued
    (ISSUE 46: the engine takes them with the record and when it idles).  With it off: until the engine's counters have
    stood still for three looks.  ``timeout`` seconds at most."""
    deadline = time.monotonic() + timeout
    before, still = None, 0
    while time.monotonic() < deadline:
        last = eng._last_dispatch if global_tracer.enabled else None
        if last is not None:
            closed = {r.attrs.get("seq") for r in global_tracer.records()
                      if r.name in DISPATCHES}
            if closed and closed >= set(
                    range(min(closed), last.attrs["seq"] + 1)) \
                    and not eng._moe_pending:
                return
        else:
            now = _counters()
            still = still + 1 if now == before else 0
            if still == 3:
                return
            before = now
        await asyncio.sleep(0.02)


@contextlib.contextmanager
def tracing():
    global_tracer.clear()
    global_tracer.configure(enabled=True, sample=1.0, capacity=65536)
    try:
        yield global_tracer
    finally:
        global_tracer.configure(enabled=False)
        global_tracer.clear()


def run_traced(eng, prompt, new):
    """One request through ``eng`` -> (its tokens, the counter's growth,
    the dispatch records)."""
    async def main():
        await eng.start()
        try:
            before = global_metrics.counter(COUNTER)
            toks = [ev.token_id async for ev in eng.generate(
                prompt, max_new_tokens=new, stop_ids=())]
            await dispatches_closed(eng)
            return toks, global_metrics.counter(COUNTER) - before
        finally:
            await eng.stop()

    with tracing() as tracer:
        toks, grew = asyncio.run(asyncio.wait_for(main(), 300))
        records = [r for r in tracer.records() if r.name in (
            "engine.decode_burst", "engine.prefill_segment")]
    return toks, grew, records


def check(eng, grew, records, kernel: bool):
    """The records' ``moe`` against the rule, the counter and /healthz."""
    assert COUNTER in METRICS_CATALOG
    want = GROUPED_KERNEL if kernel else RAGGED
    kinds = {r.name for r in records}
    assert kinds == {"engine.decode_burst", "engine.prefill_segment"}
    for r in records:
        program = r.attrs["program"]
        tokens = (r.attrs["positions"] if r.name == "engine.prefill_segment"
                  else (r.attrs["slots"] + 1) * r.attrs.get("block", 1))
        assert r.attrs["moe"] == eng._moe_branch(program, tokens), r.attrs
    assert {r.attrs["moe"] for r in records
            if r.name == "engine.decode_burst"} == {want}
    assert grew == sum(r.attrs["moe"] != RAGGED for r in records)
    assert (grew > 0) == kernel
    products = eng._model_section()["expert_products"]
    assert products["decode"] == want
    assert products["chunk_prefill"] in (RAGGED, GROUPED_KERNEL)
