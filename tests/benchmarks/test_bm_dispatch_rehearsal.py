"""The dispatch-ledger readers end to end on the CPU, in this process: a
tiny engine serves traced requests while ``jax.profiler`` records (as
serve_wrapper.py does on SIGUSR1: Python tracer off, a ``bench_window``
annotation held open), then the readers get the program's journal and the
trace file, as run.py hands them over.  The span readers report; the clock
is fitted from the program's own annotations; with no device plane every
device reader returns nothing.

(No stack of processes here: test_bm_rehearsal.py counts the children that
stacks leave on the machine, and runs the same readers through run.py on
its traced closed cell, where a reader that raised would fail the run.)"""

import asyncio
import glob
import os
import time

import pytest

from benchmarks import dispatch_trace as dt
from benchmarks import xplane_read
from test_bm_dispatch_ledger import Ctx, reader

SLOTS, ROWS, CHUNK = 4, 2, 16


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """(journal events as run.py's fetch_spans keeps them, xplane path,
    the timed window on the monotonic clock)."""
    import jax

    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.utils.tracing import (
        TraceContext,
        global_tracer,
        mint_trace_id,
    )

    trace_dir = str(tmp_path_factory.mktemp("trace"))

    async def main():
        engine = InferenceEngine(engine_cfg=EngineConfig(
            model="tiny", num_slots=SLOTS, max_seq=256, dtype="float32",
            mux=True, prefix_cache=True, prefill_chunk=CHUNK,
            prefill_rows=ROWS, decode_steps=8, decode_steps_eager=4))
        await engine.start()
        try:
            async def one(i):
                await asyncio.sleep(0.03 * i)
                ids = [5 + (7 * i + j) % 200 for j in range(10 + 9 * i)]
                async for _ in engine.generate(
                        ids, max_new_tokens=8 + 3 * (i % 3),
                        trace=TraceContext(mint_trace_id(), "")):
                    pass

            await asyncio.gather(*(one(i) for i in range(8)))
            await asyncio.sleep(0.3)   # the last burst's record closes
        finally:
            await engine.stop()

    global_tracer.clear()
    global_tracer.configure(enabled=True, sample=1.0, capacity=65536)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench_window"):
            asyncio.run(main())
        t1 = time.monotonic()
        jax.profiler.stop_trace()
        events = [ev for ev in global_tracer.chrome_trace()["traceEvents"]
                  if ev.get("ph") in ("X", "i")]
    finally:
        global_tracer.configure(enabled=False)
        global_tracer.clear()
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    return events, path, (t0, t1)


def ctx_of(rehearsal, summary=None):
    events, _path, (t0, t1) = rehearsal
    ctx = Ctx(events, summary)

    class Window:
        pass
    Window.t0, Window.t1 = t0, t1
    ctx.load = Window
    return ctx


def test_the_span_readers_read_the_programs_own_journal(rehearsal):
    ctx = ctx_of(rehearsal)
    fill = reader("dispatch_fill")
    prefill = fill.read(ctx, "engine.prefill_segment", ["tokens"],
                        ["positions"])
    decode = fill.read(ctx, "engine.decode_burst", ["live_rows", "steps"],
                       ["slots", "steps"])
    assert 0.0 < prefill <= 100.0 and 0.0 < decode <= 100.0
    # eight prompts of 10..73 tokens in rows of 16: well under full
    assert prefill < 95.0
    waits = reader("prefill_wait").waits_ms(ctx)
    assert len(waits) == 8                       # every traced request
    execs = [ev["dur"] / 1000.0 for ev in ctx.spans
             if ev["name"] == "engine.prefill_exec"]
    assert all(w >= 0.0 for w in waits) and max(waits) <= max(execs)
    assert reader("prefill_wait").read(ctx, 50) is not None


def test_the_clock_is_fitted_and_the_device_readers_return_nothing(
        rehearsal):
    events, path, _window = rehearsal
    summary = dt.summarize(xplane_read.read(path), events)
    records = [ev for ev in events if "seq" in (ev.get("args") or {})
               and ev["name"] in dt.ANNOTATIONS]
    fit = summary["fit"]
    # every dispatch the journal recorded is an annotation on the trace
    assert fit["annotations"] >= len(records) >= 10
    # ... read a few instructions after ``mono_us`` was
    assert fit["residual_p50_us"] < 200.0
    # the annotation's own start and end lie inside its record, on one clock
    assert summary["window"] is not None
    assert summary["pairs"] == {} and summary["scopes"] is None
    device = reader("dispatch_device")
    ctx = ctx_of(rehearsal, summary)
    for what in ("step", "own", "scopes"):
        assert device.read(ctx, what, scopes=["kv_read"]) is None
