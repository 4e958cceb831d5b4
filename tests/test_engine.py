"""Engine integration: continuous batching must match serial generation."""

import asyncio
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer, StreamDecoder
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    decode_step,
    init_kv_cache,
    init_params,
    prefill_into_cache,
)

import pytest

# Compile-heavy (JAX jit of engine/model programs): excluded from
# `make test-fast` (VERDICT r4 item 8).
pytestmark = pytest.mark.slow

ECFG = EngineConfig(model="tiny", num_slots=4, max_seq=64, dtype="float32", seed=0)


def make_engine():
    return InferenceEngine(engine_cfg=ECFG)


async def collect(engine, prompt, max_new=8, stop_ids=(), **kw):
    """Token ids from one generation; stop tokens disabled by default so
    lengths are deterministic under random weights."""
    out = []
    async for ev in engine.generate(
        prompt, max_new_tokens=max_new, stop_ids=stop_ids, **kw
    ):
        out.append(ev.token_id)
    return out


def reference_greedy(engine, prompt, max_new):
    """Single-request greedy decode straight through the model functions."""
    cfg, params = engine.mcfg, engine.params
    cache = init_kv_cache(cfg, 1, ECFG.max_seq, jnp.float32)
    t = 16
    while t < len(prompt):
        t *= 2
    tokens = jnp.zeros((1, t), jnp.int32).at[0, : len(prompt)].set(jnp.array(prompt))
    last, cache = prefill_into_cache(
        cfg, params, tokens, jnp.array([len(prompt)]), cache, jnp.array([0])
    )
    out = [int(jnp.argmax(last[0]))]
    pos = len(prompt)
    for _ in range(max_new - 1):
        logits, cache = decode_step(
            cfg, params, cache, jnp.array([out[-1]]), jnp.array([pos])
        )
        out.append(int(jnp.argmax(logits[0])))
        pos += 1
    return out


def test_greedy_deterministic():
    async def run():
        engine = make_engine()
        await engine.start()
        try:
            a = await collect(engine, [1, 2, 3, 4], max_new=6)
            b = await collect(engine, [1, 2, 3, 4], max_new=6)
            assert a == b and len(a) == 6
        finally:
            await engine.stop()

    asyncio.run(run())


def test_engine_matches_reference_decode():
    """The slot-batched engine must reproduce a hand-rolled greedy loop."""
    async def run():
        engine = make_engine()
        await engine.start()
        try:
            prompt = [5, 6, 7, 8, 9]
            got = await collect(engine, prompt, max_new=8)
            want = reference_greedy(engine, prompt, 8)
            assert got == want
        finally:
            await engine.stop()

    asyncio.run(run())


def test_concurrent_requests_match_serial():
    """Continuous batching must not change any request's greedy output."""
    async def run():
        engine = make_engine()
        await engine.start()
        try:
            prompts = [[1 + i, 2 + i, 3 + i] for i in range(6)]  # > num_slots
            serial = [await collect(engine, p, max_new=5) for p in prompts]
            concurrent = await asyncio.gather(
                *[collect(engine, p, max_new=5) for p in prompts]
            )
            assert list(concurrent) == serial
        finally:
            await engine.stop()

    asyncio.run(run())


def test_finish_reason_length():
    async def run():
        engine = make_engine()
        await engine.start()
        try:
            events = []
            async for ev in engine.generate([1, 2], max_new_tokens=3, stop_ids=()):
                events.append(ev)
            assert len(events) == 3
            assert events[-1].finish_reason == "length"
            assert all(e.finish_reason is None for e in events[:-1])
        finally:
            await engine.stop()

    asyncio.run(run())


def test_stop_token_ends_generation():
    async def run():
        engine = make_engine()
        await engine.start()
        try:
            # Learn what greedy emits, then use its 3rd token as a stop token.
            toks = await collect(engine, [9, 8, 7], max_new=6)
            stop = toks[2]
            events = []
            async for ev in engine.generate(
                [9, 8, 7], max_new_tokens=6, stop_ids=(stop,)
            ):
                events.append(ev)
            assert events[-1].finish_reason == "stop"
            assert [e.token_id for e in events] == toks[:3]
            assert events[-1].text == ""  # stop token text suppressed
        finally:
            await engine.stop()

    asyncio.run(run())


def test_queueing_beyond_slots():
    """More requests than slots: all must finish, via queue + readmission."""
    async def run():
        engine = make_engine()
        await engine.start()
        try:
            results = await asyncio.gather(
                *[collect(engine, [i + 1, i + 2], max_new=4, stop_ids=())
                  for i in range(10)]
            )
            assert all(len(r) == 4 for r in results)
        finally:
            await engine.stop()

    asyncio.run(run())


def test_cancel_during_prefill_does_not_kill_loop():
    """Consumer abandoning its generator mid-prefill must not crash the
    engine loop for everyone else (code-review r2 finding #1)."""
    async def run():
        engine = make_engine()
        await engine.start()
        try:
            agen = engine.generate([1, 2, 3], max_new_tokens=8, stop_ids=())
            # Start the request, then abandon it before (likely) prefill done.
            task = asyncio.create_task(agen.__anext__())
            await asyncio.sleep(0)
            task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await task
            await agen.aclose()
            # Engine must still serve other requests normally.
            out = await collect(engine, [4, 5, 6], max_new=4)
            assert len(out) == 4
        finally:
            await engine.stop()

    asyncio.run(run())


def test_stop_unblocks_inflight_consumers():
    """stop() must terminate generators that are mid-stream, not hang them."""
    async def run():
        engine = make_engine()
        await engine.start()

        async def consume():
            out = []
            async for ev in engine.generate([1, 2], max_new_tokens=10_000 // 2,
                                            stop_ids=()):
                out.append(ev.token_id)
            return out

        task = asyncio.create_task(consume())
        await asyncio.sleep(0.05)  # let it get going
        await engine.stop()
        out = await asyncio.wait_for(task, 5.0)
        assert isinstance(out, list)

    asyncio.run(run())


def test_stop_is_concurrent_safe_and_idempotent():
    """Regression for the tunnelcheck TC13 finding on stop(): SIGTERM
    drain and a teardown path can both call stop(), and the
    await-task-then-clear sequence used to be a read-modify-write of the
    task handles across awaits.  stop() is now serialized behind a lock
    and idempotent — concurrent and repeated calls must all complete
    cleanly, with the stop tail (snapshot, executor shutdown) running
    exactly once."""
    async def run():
        engine = make_engine()
        await engine.start()

        saves = []
        original = engine.save_prefix_snapshot
        engine.save_prefix_snapshot = lambda: saves.append(1) or original()

        await asyncio.gather(engine.stop(), engine.stop(), engine.stop())
        await engine.stop()  # already stopped: a clean no-op
        assert saves == [1], "stop tail must run exactly once"
        assert engine._task is None and engine._watchdog_task is None

    asyncio.run(run())


def test_stop_survives_cancellation_midway():
    """Cancelling stop() mid-tail (teardown under asyncio.wait_for) must
    not leave the engine half-stopped with consumers parked: the cancel
    asyncio delivers into the awaited loop task is absorbed (the loop is
    dead either way) and the tail still runs — consumers unblocked,
    executor released; the done flag is only set once the tail completed,
    so an abort elsewhere leaves stop() re-runnable instead of a silent
    no-op."""
    async def run():
        engine = make_engine()
        await engine.start()

        gate = asyncio.Event()
        real_task = engine._task
        engine._task = asyncio.create_task(gate.wait())  # park the stop tail

        stopping = asyncio.create_task(engine.stop())
        await asyncio.sleep(0.05)  # inside `await self._task`, parked on gate
        stopping.cancel()  # propagates into the parked await (fut_waiter)
        with contextlib.suppress(asyncio.CancelledError):
            await stopping
        assert engine._stopped is True, "cancelled stop must finish the tail"
        assert engine._task is None

        await real_task  # the real loop exited on _running=False
        await engine.stop()  # already stopped: a clean no-op

    asyncio.run(run())


def test_stream_decoder_multibyte():
    tok = ByteTokenizer()
    text = "héllo ✓"
    ids = tok.encode(text)
    dec = StreamDecoder(tok)
    out = "".join(dec.push(i) for i in ids)
    assert out == text


def test_engine_crash_surfaces_instead_of_hanging():
    """A dispatch exception must fail in-flight consumers with an error and
    reject later submissions — never a silent 200 or a hung queue."""
    async def run():
        engine = make_engine()
        await engine.start()

        def boom(*a, **k):
            raise RuntimeError("injected dispatch failure")

        engine._dispatch_prefill_batch = boom
        with pytest.raises(RuntimeError):
            await collect(engine, [1, 2, 3], max_new=4)
        with pytest.raises(RuntimeError, match="crashed"):
            await collect(engine, [4, 5], max_new=2)
        # stop() remains clean after a crash.
        await engine.stop()

    asyncio.run(run())
