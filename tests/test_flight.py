"""Engine flight recorder, compile/cold-start profiler, postmortem black
box (ISSUE 12).

Three layers, matching where the machinery lives:
- pure ring/journal/bundle logic (utils/flight.py) — no asyncio, no JAX;
- serve-endpoint surfaces over a loopback channel with a fake backend
  (/healthz?postmortem=1, engine_degraded_reason, flight tracks in the
  ?trace=1 export, the drain-timeout trigger) — fast;
- engine-backed behavior: one flight record per loop iteration, the
  warmup grid in the compile journal, mid-serve cold-compile detection on
  a deliberately un-warmed bucket, and the two-run seeded postmortem
  bundle identity `make chaos` pins (CHAOS_TEST_SEED varies the
  workload; waived wall-clock fields excluded via postmortem_canonical).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import gc
import json
import logging
import os
import random
import threading
import time

import pytest

from p2p_llm_tunnel_tpu.endpoints.serve import run_serve
from p2p_llm_tunnel_tpu.testing.frame_client import FrameClient
from p2p_llm_tunnel_tpu.transport import loopback_pair
from p2p_llm_tunnel_tpu.utils.flight import (
    FLIGHT_SCHEMA,
    LOOP_PARTS,
    POSTMORTEM_SCHEMA,
    STARTUP_PHASES,
    STARTUP_SCHEMA,
    BlackBox,
    CompileWatch,
    FlightRecorder,
    GcWatch,
    IterationSplit,
    global_blackbox,
    global_compile_watch,
    global_flight,
    postmortem_canonical,
)
from p2p_llm_tunnel_tpu.utils.metrics import global_metrics
from p2p_llm_tunnel_tpu.utils.slo import global_slo
from p2p_llm_tunnel_tpu.utils.tracing import (
    global_tracer,
    validate_chrome_trace,
)

SEED = int(os.environ.get("CHAOS_TEST_SEED", "5"))


@pytest.fixture(autouse=True)
def _clean_blackbox_state():
    """Each test starts from empty global rings (the bench
    global_metrics.reset() convention, black-box edition)."""
    global_flight.reset()
    global_compile_watch.reset()
    global_blackbox.reset()
    yield
    global_flight.reset()
    global_compile_watch.reset()
    global_blackbox.reset()


# ---------------------------------------------------------------------------
# pure recorder / journal / bundle logic
# ---------------------------------------------------------------------------


def test_flight_ring_bound_and_unknown_field_rejected():
    rec = FlightRecorder(capacity=8)
    for i in range(50):
        rec.record_iteration(t=float(i), dur_ms=1.0, queue_depth=i)
    assert rec.iterations == 50
    rows = rec.records()
    assert len(rows) == 8  # cap respected
    assert rows[-1]["iter"] == 50 and rows[0]["iter"] == 43
    with pytest.raises(ValueError, match="FLIGHT_SCHEMA"):
        rec.record_iteration(queue_dept=1)  # tunnelcheck: disable=TC16  the typo class, on purpose: pins the runtime guard
    # Every documented field is accepted.
    rec.record_iteration(**{
        k: 0 for k in FLIGHT_SCHEMA if k != "iter"
    })


def test_flight_chrome_events_are_schema_valid_counters_and_slices():
    rec = FlightRecorder(capacity=16)
    rec.record_iteration(t=1.5, dur_ms=2.0, queue_depth=3,
                         budget_tokens=128, active_slots=2,
                         backlog_rows=1, decode_steps=4)
    evs = rec.chrome_events()
    # One slice an iteration, loadable next to the span journal; every
    # number of the record rides the slice's args (no counter tracks).
    trace = global_tracer.chrome_trace()
    trace["traceEvents"] = list(trace["traceEvents"]) + evs
    assert validate_chrome_trace(trace)
    assert [e["ph"] for e in evs] == ["M", "X"]
    slice_ev = next(e for e in evs if e["ph"] == "X")
    assert slice_ev["name"] == "engine.flight"
    assert slice_ev["args"]["queue_depth"] == 3
    for key, value in (("budget_tokens", 128), ("active_slots", 2),
                       ("backlog_rows", 1), ("decode_steps", 4)):
        assert slice_ev["args"][key] == value


def test_compile_watch_journal_marks_and_cold_counter():
    cw = CompileWatch(capacity=8)
    cw.note(program="decode", key="decode[128,4]", shape=[128, 4],
            seconds=1.25, phase="warmup")
    mark = cw.mark()
    cw.note(program="chunk", key="chunk[64,128]", shape=[64, 128],
            seconds=0.5, phase="serve", cold=True)
    assert [e["key"] for e in cw.since(mark)] == ["chunk[64,128]"]
    assert cw.cold_total == 1
    assert cw.events()[0]["aot_hit"] is False


# -- the start-up journal (ISSUE 40) -----------------------------------------


def _a_start(cw, t=100.0):
    """A start written by hand: the five phases that tile the process, two
    programs of an AOT phase and the serial pass's run of one of them."""
    cw._process = (t, "proc")
    cw.add_span("startup.imports", t0=t, t1=t + 20.0)
    cw.add_span("startup.tokenizer", t0=t + 20.0, t1=t + 21.0, entries=32000)
    cw.add_span("startup.backend", t0=t + 21.0, t1=t + 27.0,
                platform="tpu", device_kind="TPU v5 lite", devices=1)
    cw.add_span("startup.params", t0=t + 27.5, t1=t + 30.0, source="random",
                quant="int8", bytes=7 << 30)
    cw.add_span("startup.engine_build", t0=t + 27.0, t1=t + 33.0)
    cw.add_span("startup.aot", t0=t + 33.0, t1=t + 45.0, threads=4)
    for key, lower, comp, hit in (("decode[1024,8]", 0.7, 9.3, False),
                                  ("chunk[8,128,1024]", 0.9, 0.1, True)):
        cw.note(program=key.split("[")[0], key=key, shape=[1], phase="aot",
                seconds=lower + comp, trace_lower_s=lower, compile_s=comp,
                persistent_hit=hit)
    cw.note(program="decode", key="decode[1024,8]", shape=[1], seconds=0.2,
            phase="warmup", aot_hit=True)
    cw.add_span("startup.warmup", t0=t + 33.0, t1=t + 50.0)
    cw.add_span("startup.process", t0=t, t1=t + 50.0, clock="proc")
    cw.add_event("startup.ready", t=t + 50.0)
    cw.add_span("startup.tunnel", t0=t + 50.1, t1=t + 53.0)


def test_startup_journal_section_sums_phases_and_programs():
    cw = CompileWatch()
    assert cw.startup_section()["ready"] is False
    assert cw.startup_section()["programs"] == 0
    assert cw.startup_section()["slowest_program"] is None
    _a_start(cw)
    section = cw.startup_section()
    assert section["ready"] is True and section["to_ready_s"] == 50.0
    assert section["phases_s"]["startup.backend"] == 6.0
    assert section["phases_s"]["startup.tunnel"] == 2.9
    # the AOT phase's records are the programs; the serial pass's run of
    # the same key adds none
    assert section["programs"] == 2
    assert section["persistent_hits"] == 1
    assert section["persistent_misses"] == 1
    assert section["slowest_program"] == {"key": "decode[1024,8]",
                                          "seconds": 10.0}
    # the phases that tile the process do: no overlap, nothing left over
    tiles = [r for r in cw.startup_records() if r["name"] in STARTUP_PHASES]
    assert [r["name"] for r in tiles] == list(STARTUP_PHASES)
    assert sum(r["dur"] for r in tiles) == pytest.approx(50.0)
    # compile events keep their journal; what was cache_hit is aot_hit
    assert [e["aot_hit"] for e in cw.events()] == [False, False, True]
    assert "cache_hit" not in cw.events()[0]


def test_startup_journal_rejects_a_field_outside_its_schema():
    cw = CompileWatch()
    with pytest.raises(ValueError, match="STARTUP_SCHEMA"):
        cw.add_span("startup.backend", t0=1.0, platfrom="tpu")  # tunnelcheck: disable=TC16  deliberate drift: pins the runtime guard
    with pytest.raises(ValueError, match="STARTUP_SCHEMA"):
        cw.note(program="decode", key="k", shape=[], seconds=0.1,
                phase="aot", compile_secs=0.1)
    assert cw.startup_records() == [] and cw.events() == []
    # every field a compile event carries is a declared one
    cw.note(program="decode", key="k", shape=[], seconds=0.1, phase="aot")
    assert set(cw.events()[0]) <= set(STARTUP_SCHEMA)


def test_startup_journal_chrome_events_ride_a_lane_of_their_own():
    cw = CompileWatch()
    assert cw.chrome_events() == []
    _a_start(cw)
    evs = cw.chrome_events()
    assert validate_chrome_trace({"traceEvents": evs})
    lanes = {e["args"]["name"]: e["tid"] for e in evs if e["ph"] == "M"}
    assert lanes["startup"] == 1002 and len(lanes) == 2  # + one thread's
    by_name = {e["name"]: e for e in evs if e["ph"] != "M"}
    process = by_name["startup.process"]
    assert process["ph"] == "X" and process["ts"] == 100_000_000
    assert process["dur"] == 50_000_000
    assert process["args"] == {"clock": "proc"}
    assert by_name["startup.ready"]["ph"] == "i"
    assert by_name["startup.ready"]["ts"] == 150_000_000
    programs = [e for e in evs if e["name"] == "startup.program"]
    assert {e["tid"] for e in programs} == {1003}
    assert programs[0]["args"]["trace_lower_s"] == 0.7
    assert programs[0]["args"]["persistent_hit"] is False
    # every name is a catalogued span, every attr a declared field
    from p2p_llm_tunnel_tpu.utils.tracing import SPAN_CATALOG
    for e in evs:
        if e["ph"] != "M":
            assert e["name"] in SPAN_CATALOG
            assert set(e["args"]) <= set(STARTUP_SCHEMA)


def test_startup_records_survive_a_request_ring_that_turns_over():
    """The request ring of a traced run turns over (1,200 events through a
    ring of 1,024; a benchmark run's holds 262,144); the journal's list is
    its own and is never evicted."""
    _a_start(global_compile_watch)
    before = global_compile_watch.chrome_events()
    global_tracer.configure(enabled=True, capacity=1024)
    try:
        for i in range(1200):
            global_tracer.add_event("engine.first_token", trace_id="ab" * 8)
        assert len(global_tracer.records()) == 1024
        assert global_compile_watch.chrome_events() == before
        assert global_compile_watch.startup_section()["programs"] == 2
    finally:
        global_tracer.configure(enabled=False, capacity=4096)
        global_tracer.clear()


# -- where an iteration's wall went (ISSUE 57) -------------------------------


class _SlowHandback(concurrent.futures.Executor):
    """A stubbed executor: the call runs at once, on a thread of its own,
    and its result is handed back 50 ms after the work was done: what an
    event loop busy with its stream writers does to the engine loop."""

    def submit(self, fn, *args):
        future = concurrent.futures.Future()

        def work():
            result = fn(*args)
            time.sleep(0.05)
            future.set_result(result)

        threading.Thread(target=work).start()
        return future


def test_iteration_split_tiles_the_wall_and_times_a_stubbed_executor():
    rec = FlightRecorder(capacity=4)

    def slow(x):
        time.sleep(0.02)
        return x

    def fetch(split):  # what the executor thread does around a device_get
        began = time.monotonic()
        time.sleep(0.01)
        split.fetched(began)

    async def main():
        loop = asyncio.get_running_loop()
        split = IterationSplit(rec)
        split.enter("admit")
        assert rec.current_phase() == "admit"
        split.evicted(time.monotonic() - 0.003, 5)
        await asyncio.sleep(0.01)
        split.enter("decode_dispatch")
        assert await split.call(loop, _SlowHandback(), slow, 7) == 7
        split.enter("decode_fetch")
        fetch(split)
        split.enter("process")
        split.enter("admit")  # a phase entered again adds to its part
        await asyncio.sleep(0.002)
        return split.fields()

    fields = asyncio.run(main())
    assert set(fields) <= set(FLIGHT_SCHEMA)
    assert set(LOOP_PARTS.values()) <= set(fields)
    parts = [fields[part] for part in LOOP_PARTS.values()]
    assert abs(sum(parts) - fields["dur_ms"]) <= 0.01 * len(parts)
    assert fields["admit_ms"] >= 10 + 2 and fields["prefill_ms"] == 0
    assert fields["exec_ms"] >= 20
    assert fields["lag_ms"] >= 45  # the await's wall less the call's own
    assert fields["dispatch_ms"] >= fields["exec_ms"] + fields["lag_ms"] - 0.01
    at = fields["at_ms"]
    assert list(at) == ["admit", "decode_dispatch", "decode_fetch", "process"]
    assert at["admit"] < at["decode_dispatch"] < at["decode_fetch"]
    (wait_at, wait_len), = fields["waits_ms"]
    assert wait_len == fields["wait_ms"] >= 10
    assert at["decode_fetch"] <= wait_at
    assert wait_at + wait_len <= at["process"] + 0.01
    assert fields["evict_ms"] >= 3 and fields["evicted_pages"] == 5
    assert fields["gc_ms"] >= 0 and fields["gc_full"] >= 0
    rec.record_iteration(**fields)  # every field is the schema's
    assert rec.records()[0]["wait_ms"] == fields["wait_ms"]


def test_a_long_hold_is_said_once_a_second_on_the_records_own_clock(caplog):
    from p2p_llm_tunnel_tpu.utils import flight

    rec = FlightRecorder(capacity=8)
    sums = ("engine_loop_host_seconds_total",
            "engine_loop_wait_seconds_total", "engine_loop_lag_seconds_total")
    before = [global_metrics.counter(name) for name in sums]
    with caplog.at_level(logging.INFO, logger=flight.log.name):
        for t, dur, wait in (
                (100.0, 49.0, 0.0),    # under LONG_HOLD_MS of host time
                (100.1, 80.0, 40.0),   # 40 ms of it: the rest was the chip
                (100.2, 80.0, 10.0),   # 70 ms: said
                (100.5, 300.0, 0.0),   # inside the second: counted, unsaid
                (101.0, 60.0, 0.0),    # ends at 101.06 < 100.28 + 1
                (101.3, 60.0, 0.0)):   # a second on: said, with the count
            rec.record_iteration(t=t, dur_ms=dur, wait_ms=wait, lag_ms=1.5)
    said = [r.getMessage() for r in caplog.records
            if "held the loop" in r.getMessage()]
    assert len(said) == 2
    assert "iteration 3 held the loop 70.0 ms" in said[0]
    assert "0 more such" in said[0]
    assert "iteration 6" in said[1] and "2 more such" in said[1]
    grown = [global_metrics.counter(name) - was
             for name, was in zip(sums, before)]
    assert grown == pytest.approx([0.579, 0.050, 0.009])
    assert flight.LONG_HOLD_MS == 50.0 and flight.LONG_HOLD_EVERY_S == 1.0
    # a record from before the split (no wait_ms) adds nothing
    rec.record_iteration(t=200.0, dur_ms=500.0)
    assert global_metrics.counter(sums[0]) - before[0] == pytest.approx(0.579)


def test_gc_watch_counts_each_collection_and_writes_a_span_with_the_journal_on():
    names = ("process_gc_pause_seconds_total", "process_gc_collections_total",
             "process_gc_full_collections_total")
    watch = GcWatch()
    watch.install()
    watch.install()  # once a process
    assert gc.callbacks.count(watch._on_gc) == 1
    was_on = gc.isenabled()
    gc.disable()  # only the collections this test forces
    try:
        before = [global_metrics.counter(name) for name in names]
        gc.collect(0)
        gc.collect(1)  # (a collection of every generation takes seconds in
        #                a test worker's heap: this test makes one, below)
        assert watch.totals()[1] == 0 and watch.collections == 2
        # the hook itself publishes nothing: it may run under any lock
        assert [global_metrics.counter(n) for n in names] == before
        watch.publish()
        grown = [global_metrics.counter(n) - b for n, b in zip(names, before)]
        assert grown[0] == pytest.approx(watch.pause_s) and grown[0] > 0
        assert grown[1:] == [2, 0]
        assert global_tracer.records() == []  # the journal is off
        global_tracer.configure(enabled=True)
        try:
            t0 = time.monotonic()
            gc.collect(0)  # young: counted; a span only if it took 1 ms
            gc.collect()
            watch.publish()
            (span,) = [r for r in global_tracer.records()
                       if r.name == "process.gc_pause"
                       and r.attrs["generation"] == 2]
            assert span.track == "process" and span.trace_id is None
            assert span.attrs["generation"] == 2
            assert span.attrs["collected"] >= 0
            assert t0 <= span.ts and span.ts + span.dur <= time.monotonic()
            written = len(global_tracer.records())
            watch.publish()  # nothing is written twice
            assert len(global_tracer.records()) == written <= 2
        finally:
            global_tracer.configure(enabled=False)
            global_tracer.clear()
        assert global_metrics.counter(names[1]) - before[1] == 4
        assert global_metrics.counter(names[2]) - before[2] == 1
        assert watch.totals()[1] == 1
    finally:
        gc.callbacks.remove(watch._on_gc)
        if was_on:
            gc.enable()


def test_the_split_and_the_collectors_names_are_catalogued():
    from p2p_llm_tunnel_tpu.utils.flight import WALLCLOCK_WAIVED, _waived
    from p2p_llm_tunnel_tpu.utils.metrics import METRICS_CATALOG
    from p2p_llm_tunnel_tpu.utils.tracing import SPAN_CATALOG

    new = ("segments_ms", "drain_ms", "at_ms", "wait_ms", "waits_ms",
           "exec_ms", "lag_ms", "evict_ms", "evicted_pages", "gc_ms",
           "gc_full")
    assert set(new) <= set(FLIGHT_SCHEMA)
    assert set(LOOP_PARTS.values()) <= set(FLIGHT_SCHEMA)
    # what follows the clock or the collector is waived from the bundles'
    # identity; a count of pages is not
    assert all(_waived(f) for f in new if f != "evicted_pages")
    assert not _waived("evicted_pages")
    counters = ("engine_loop_host_seconds_total",
                "engine_loop_wait_seconds_total",
                "engine_loop_lag_seconds_total",
                "process_gc_pause_seconds_total",
                "process_gc_collections_total",
                "process_gc_full_collections_total")
    assert set(counters) <= set(METRICS_CATALOG)
    assert set(counters) <= WALLCLOCK_WAIVED
    assert "engine_decode_fetch_ms" not in METRICS_CATALOG
    assert "process.gc_pause" in SPAN_CATALOG


def test_startup_journal_is_bounded_by_dropping_the_latest():
    from p2p_llm_tunnel_tpu.utils.flight import STARTUP_CAPACITY

    cw = CompileWatch()
    cw.add_span("startup.process", t0=1.0, t1=2.0, clock="proc")
    for i in range(STARTUP_CAPACITY + 10):
        cw.add_event("startup.ready", t=float(i))
    recs = cw.startup_records()
    assert len(recs) == STARTUP_CAPACITY
    assert recs[0]["name"] == "startup.process"


def test_process_start_is_the_kernels_and_falls_back_to_the_given_line(
        monkeypatch):
    import time

    from p2p_llm_tunnel_tpu.utils import flight

    now = time.monotonic()
    t, clock = flight.process_start(now)
    if os.path.exists("/proc/self/stat"):
        # this test process began before this line and after the machine
        assert clock == "proc" and 0.0 < now - t < 86400.0
    else:
        assert (t, clock) == (now, "cli.main")
    # /proc absent (or an answer that is no past instant): the given line
    def no_sysconf(_name):
        raise ValueError("unrecognized configuration name")

    monkeypatch.setattr(flight.os, "sysconf", no_sysconf)
    assert flight.process_start(now) == (now, "cli.main")
    cw = CompileWatch()
    assert cw.process_began(now) == (now, "cli.main")
    assert cw.process_began(now + 5.0) == (now, "cli.main")  # settled once
    cw.mark_ready()
    root = cw.startup_records()[0]
    assert root["name"] == "startup.process" and root["ts"] == now
    assert root["attrs"] == {"clock": "cli.main"}


def test_attribution_is_by_thread_and_read_once():
    """jax.monitoring calls its listeners in the compiling thread: what it
    reports is kept by thread, so a program's record finds nothing another
    thread compiled, nothing older than itself, and only while a warm-up
    listens."""
    import time

    from p2p_llm_tunnel_tpu.utils import flight

    def noted(cw, phase, seconds):
        cw.note(program="decode", key="decode[1]", shape=[1], phase=phase,
                seconds=seconds)
        return cw.events()[-1]

    cw = CompileWatch()
    flight._on_jax_event(flight._EV_COMPILE, 1.0)   # nobody listens yet
    assert noted(cw, "aot", 1.0)["compile_s"] is None
    seen = {}

    def other():
        flight._on_jax_event(flight._EV_CACHE_MISS)
        flight._on_jax_event(flight._EV_CACHE_HIT)   # the program's: last
        seen["other"] = noted(cw, "warmup", 1.0)

    cw.listen(True)
    cw.listen(True)                                  # a second replica
    try:
        flight._on_jax_event(flight._EV_COMPILE, 7.0)  # before the record
        time.sleep(0.12)
        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert seen["other"]["persistent_hit"] is True
        assert seen["other"]["compile_s"] is None    # not that thread's
        flight._on_jax_event(flight._EV_TRACE, 0.25)
        flight._on_jax_event(flight._EV_MLIR, 0.5)
        flight._on_jax_event(flight._EV_COMPILE, 2.0)
        flight._on_jax_event("/jax/some/other/event", 9.0)
        cw.listen(False)                             # one is still warming
        rec = noted(cw, "warmup", 0.05)              # the serial pass: JAX's
        assert rec["trace_lower_s"] == 0.75 and rec["compile_s"] == 2.0
        assert rec["persistent_hit"] is None         # no cache was asked
        assert noted(cw, "warmup", 0.05)["compile_s"] is None  # read once
        flight._on_jax_event(flight._EV_COMPILE, 0.5)
        rec = noted(cw, "aot", 0.75)     # AOT: the wall less XLA's part
        assert rec["compile_s"] == 0.5 and rec["trace_lower_s"] == 0.25
        flight._on_jax_event(flight._EV_TRACE, 0.125)  # the launch probe's
    finally:
        cw.listen(False)
    assert flight._listening == 0 and flight._HEARD == {}
    assert noted(cw, "serve", 1.0)["trace_lower_s"] is None


def test_postmortem_canonical_strips_waived_wallclock_fields():
    bundle = {
        "trigger": "manual",
        "captured_unix_s": 1234.5,
        "flight": [{"iter": 1, "dur_ms": 3.2, "queue_depth": 2,
                    "min_slack_s": 0.4}],
        "metrics": {"engine_tokens_total": 8.0, "engine_ttft_ms_p50": 12.0,
                    "engine_warmup_compile_s": 4.0},
        "spans": [{"name": "x", "ts": 1.0, "dur": 2.0, "span_id": "a",
                   "parent_id": "b", "trace_id": "c"}],
    }
    canon = postmortem_canonical(bundle)
    assert canon == {
        "trigger": "manual",
        "flight": [{"iter": 1, "queue_depth": 2}],
        "metrics": {"engine_tokens_total": 8.0},
        "spans": [{"name": "x"}],
    }


def test_blackbox_capture_schema_store_and_archive(tmp_path):
    bb = BlackBox(directory=str(tmp_path / "pm"))
    bundle = bb.capture("manual", attribution="unit test")
    # The builder and the declared schema move in lockstep (runtime half
    # of tunnelcheck TC16).
    assert set(bundle) == set(POSTMORTEM_SCHEMA)
    assert bundle["schema_version"] == 1
    assert bundle["trigger"] == "manual"
    assert bundle["attribution"] == "unit test"
    assert bb.captured == 1 and bb.last()["trigger"] == "manual"
    # Archived atomically (off-thread; flush joins the writer): one
    # parseable JSON file, path recorded.
    bb.flush()
    (path,) = bb.paths()
    assert json.loads(open(path).read())["trigger"] == "manual"
    assert not path.endswith(".tmp")
    with pytest.raises(ValueError, match="unknown postmortem trigger"):
        bb.capture("kaboom")


def test_slo_breach_transition_triggers_postmortem_capture():
    """An objective worsening to burning/breached through publish() is a
    black-box trigger (the on_alert hook flight.py wires)."""
    from p2p_llm_tunnel_tpu.utils.slo import default_objectives

    global_slo.configure(enabled=True, objectives=default_objectives(),
                         min_events=5)
    try:
        for _ in range(20):
            global_slo.record("availability", False)
        global_slo.publish()
        assert global_blackbox.captured == 1
        bundle = global_blackbox.last()
        assert bundle["trigger"] == "slo"
        assert bundle["attribution"].startswith("availability:")
        assert bundle["slo"]["availability"]["state"] in (
            "burning", "breached"
        )
        # Staying bad is not a NEW transition: no capture storm.
        global_slo.publish()
        assert global_blackbox.captured == 1
    finally:
        global_slo.configure(enabled=False,
                             objectives=default_objectives())
        global_slo.reset()


# ---------------------------------------------------------------------------
# serve endpoint surfaces over loopback (fake backend; fast)
# ---------------------------------------------------------------------------


async def _stack(backend, **serve_kwargs):
    serve_ch, client_ch = loopback_pair()
    serve_task = asyncio.create_task(
        run_serve(serve_ch, backend=backend, **serve_kwargs)
    )
    client = FrameClient(client_ch)
    await client.handshake(timeout=10.0)
    return serve_task, serve_ch, client


async def _teardown(serve_task, serve_ch, client):
    client.close()
    serve_task.cancel()
    serve_ch.close()
    await asyncio.gather(serve_task, return_exceptions=True)


def _echo_backend():
    async def chunks():
        yield b"ok"

    async def backend(req, body):
        return 200, {"content-type": "text/plain"}, chunks()

    return backend


def test_healthz_postmortem_surface_and_degraded_reason():
    async def main():
        serve_task, ch, client = await _stack(_echo_backend())
        try:
            # Healthy: no bundle, and the reason field is present + null.
            h = await client.wait(
                await client.request("GET", "/healthz"), 10.0
            )
            payload = json.loads(h.text)
            assert "engine_degraded_reason" in payload
            assert payload["engine_degraded_reason"] is None
            r = await client.wait(
                await client.request("GET", "/healthz?postmortem=1"), 10.0
            )
            body = json.loads(r.text)
            assert body == {"postmortem": None, "captured": 0, "paths": []}
            # A watchdog-degraded engine answers with the reason AND the
            # captured bundle.  The engine's two detectors read apart: the
            # decode-stall watchdog as "stall" (also the reading of a bare
            # gauge with no reason published), the thrash detector as
            # "memory".
            global_metrics.set_gauge("engine_degraded", 1.0)
            global_blackbox.capture("watchdog", attribution="decode_dispatch")
            try:
                for published, read in (("", "stall"), ("stall", "stall"),
                                        ("memory", "memory")):
                    global_metrics.set_info("engine_degraded_reason",
                                            published)
                    h = await client.wait(
                        await client.request("GET", "/healthz"), 10.0
                    )
                    payload = json.loads(h.text)
                    assert payload["status"] == "degraded"
                    assert payload["engine_degraded_reason"] == read
                r = await client.wait(
                    await client.request("GET", "/healthz?postmortem=1"),
                    10.0,
                )
                body = json.loads(r.text)
                assert body["captured"] == 1
                assert body["postmortem"]["trigger"] == "watchdog"
                assert body["postmortem"]["attribution"] == "decode_dispatch"
                assert set(body["postmortem"]) == set(POSTMORTEM_SCHEMA)
            finally:
                global_metrics.set_gauge("engine_degraded", 0.0)
                global_metrics.set_info("engine_degraded_reason", "")
        finally:
            await _teardown(serve_task, ch, client)

    asyncio.run(main())


def test_healthz_trace_export_carries_flight_tracks():
    async def main():
        serve_task, ch, client = await _stack(_echo_backend())
        try:
            global_flight.record_iteration(
                t=1.0, dur_ms=2.0, queue_depth=5, budget_tokens=64,
                active_slots=1, backlog_rows=0,
            )
            r = await client.wait(
                await client.request("GET", "/healthz?trace=1"), 10.0
            )
            obj = json.loads(r.text)
            assert validate_chrome_trace(obj)
            flights = [e for e in obj["traceEvents"]
                       if e.get("name") == "engine.flight"]
            assert len(flights) == 1
            assert flights[0]["args"]["queue_depth"] == 5
            assert flights[0]["args"]["budget_tokens"] == 64
            assert {e["ph"] for e in obj["traceEvents"]} <= {"M", "X", "i"}
        finally:
            await _teardown(serve_task, ch, client)

    asyncio.run(main())


def test_drain_timeout_captures_postmortem_and_closes():
    """A drain that cannot finish (a wedged in-flight stream) abandons it
    at the budget, captures trigger 'drain', and still closes cleanly."""
    async def main():
        hang = asyncio.Event()

        def backend_factory():
            async def chunks():
                yield b"first"
                await hang.wait()  # never set: the wedge

            async def backend(req, body):
                return 200, {"content-type": "text/plain"}, chunks()

            return backend

        drain = asyncio.Event()
        serve_ch, client_ch = loopback_pair()
        serve_task = asyncio.create_task(run_serve(
            serve_ch, backend=backend_factory(), drain=drain,
            drain_timeout=0.3,
        ))
        client = FrameClient(client_ch)
        await client.handshake(timeout=10.0)
        try:
            sid = await client.request("GET", "/wedge")
            await asyncio.sleep(0.2)  # stream is mid-body now
            drain.set()
            await asyncio.wait_for(serve_task, 10.0)  # clean return
            assert global_blackbox.captured == 1
            bundle = global_blackbox.last()
            assert bundle["trigger"] == "drain"
            assert "1 stream(s) unfinished" in bundle["attribution"]
            assert sid is not None
        finally:
            client.close()
            serve_ch.close()
            if not serve_task.done():
                serve_task.cancel()
            await asyncio.gather(serve_task, return_exceptions=True)

    asyncio.run(main())


def test_fleet_postmortem_federation_over_stub_peerset():
    """GET /healthz?postmortem=1&fleet=1: per-peer bundles via the same
    bounded scrape machinery, stale peers marked — exercised against a
    stub PeerSet so the zero/dead-peer shape is pinned without a fabric."""
    from p2p_llm_tunnel_tpu.endpoints.proxy import _fleet_postmortem_response

    class StubState:
        async def scrape_fleet(self, path):
            assert path == "/healthz?postmortem=1"
            return {
                "p0": json.dumps(
                    {"postmortem": {"trigger": "watchdog"}, "captured": 1,
                     "paths": []}
                ).encode(),
                "p1": None,  # dead/wedged peer
            }

    async def main():
        resp = await _fleet_postmortem_response(StubState())
        assert resp.status == 200
        body = json.loads(resp.body)
        assert body["stale"] == ["p1"]
        assert body["peers"]["p1"] is None
        assert body["peers"]["p0"]["postmortem"]["trigger"] == "watchdog"
        assert body["peers"]["proxy"]["captured"] == 0

    asyncio.run(main())


# ---------------------------------------------------------------------------
# traceview --flight
# ---------------------------------------------------------------------------


def test_traceview_flight_summary(tmp_path, capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "traceview_under_test",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "traceview.py"),
    )
    traceview = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(traceview)

    for i in range(3):
        global_flight.record_iteration(
            t=float(i), dur_ms=1.0, queue_depth=4 - i, budget_tokens=128,
            admitted=1, prefill_rows=2, decode_steps=4, active_slots=2,
            cold_compiles=1 if i == 2 else 0, backlog_rows=0,
        )
    trace = global_tracer.chrome_trace()
    trace["traceEvents"] = (
        list(trace["traceEvents"]) + global_flight.chrome_events()
    )
    out = traceview.summarize_flight(trace)
    assert out["iterations"] == 3
    assert out["admitted_total"] == 3
    assert out["prefill_rows_total"] == 6
    assert out["decode_steps_total"] == 12
    assert out["cold_compiles"] == 1
    assert out["queue_depth_max"] == 4
    assert len(out["tail"]) == 3
    assert out["split"] is None  # records from before the split say nothing

    path = tmp_path / "trace.json"
    path.write_text(json.dumps(trace))
    assert traceview.main([str(path), "--flight"]) == 0
    printed = capsys.readouterr().out
    assert "flight: 3 iteration(s)" in printed
    assert "cold compiles 1" in printed
    # --json twin stays machine-readable.
    assert traceview.main([str(path), "--flight", "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["iterations"] == 3

    # ISSUE 57: where the iterations' wall went
    parts = dict.fromkeys(LOOP_PARTS.values(), 0.0)
    for i, (dur, wait) in enumerate(((100.0, 60.0), (200.0, 20.0))):
        global_flight.record_iteration(
            t=10.0 + i, dur_ms=dur, wait_ms=wait, lag_ms=2.0, exec_ms=90.0,
            evict_ms=1.5, evicted_pages=3, gc_ms=4.0, gc_full=1,
            **dict(parts, dispatch_ms=dur))
    trace["traceEvents"] = (list(global_tracer.chrome_trace()["traceEvents"])
                            + global_flight.chrome_events())
    split = traceview.summarize_flight(trace)["split"]
    assert split["iterations"] == 2 and split["host_ms"] == 220.0
    assert split["host_share_pct"] == pytest.approx(100.0 * 220.0 / 300.0)
    assert split["parts_ms"]["dispatch_ms"] == 300.0
    assert split["evicted_pages"] == 6 and split["gc_full"] == 2
    assert split["longest_hold"]["iter"] == 5
    path.write_text(json.dumps(trace))
    assert traceview.main([str(path), "--flight"]) == 0
    printed = capsys.readouterr().out
    assert "host 220.0 ms (73.3 %)" in printed
    assert "longest hold: iteration 5, 180.0 ms of host time" in printed


def test_traceview_startup_phase_table_and_slowest_programs(tmp_path,
                                                            capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "traceview_under_test",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "traceview.py"),
    )
    traceview = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(traceview)

    _a_start(global_compile_watch)
    global_flight.record_iteration(t=1.0, dur_ms=1.0, queue_depth=1)
    trace = global_tracer.chrome_trace()
    trace["traceEvents"] = (
        list(trace["traceEvents"]) + global_flight.chrome_events()
        + global_compile_watch.chrome_events()
    )
    out = traceview.summarize_startup(trace)
    assert [p["span"] for p in out["phases"]][:3] == [
        "startup.imports", "startup.process", "startup.tokenizer"]
    seconds = {p["span"]: p["seconds"] for p in out["phases"]}
    assert seconds["startup.process"] == 50.0
    assert seconds["startup.backend"] == 6.0
    assert out["programs"] == 2
    assert out["persistent_hits"] == 1 and out["persistent_misses"] == 1
    assert out["trace_lower_s_per_program"] == pytest.approx(0.8)
    assert [p["key"] for p in out["slowest"]] == [
        "decode[1024,8]", "chunk[8,128,1024]"]
    # the per-request view leaves the journal's lane to its own view
    assert not any(name.startswith("startup.")
                   for name in traceview.summarize(trace)["engine_scope"])

    path = tmp_path / "trace.json"
    path.write_text(json.dumps(trace))
    assert traceview.main([str(path), "--startup"]) == 0
    printed = capsys.readouterr().out
    assert "startup.backend" in printed and "6.000" in printed
    assert "2 program(s); compile cache on disk: 1 hit(s), 1 miss(es)" \
        in printed
    assert "decode[1024,8]" in printed and "lower 0.7 + compile 9.3" in printed
    assert traceview.main([str(path), "--startup", "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["programs"] == 2
    # a capture of a process with no journal says so
    path.write_text(json.dumps(global_tracer.chrome_trace()))
    assert traceview.main([str(path), "--startup"]) == 0
    assert "no startup.* spans" in capsys.readouterr().out


def test_healthz_surfaces_carry_the_startup_journal_under_any_backend():
    """The serve loop answers both itself: the section without --trace,
    the lane beside the flight tracks, under the plain HTTP backend too
    (an empty journal: no phases, not ready)."""
    async def main():
        serve_task, ch, client = await _stack(_echo_backend())
        try:
            h = await client.wait(
                await client.request("GET", "/healthz"), 10.0)
            assert json.loads(h.text)["startup"] == {
                "ready": False, "to_ready_s": None, "phases_s": {},
                "programs": 0, "persistent_hits": 0,
                "persistent_misses": 0, "slowest_program": None}
            _a_start(global_compile_watch)
            global_tracer.configure(enabled=True, capacity=64)
            for _ in range(300):     # the small ring turns over five times
                global_tracer.add_event("engine.first_token",
                                        trace_id="cd" * 8)
            h = await client.wait(
                await client.request("GET", "/healthz"), 10.0)
            section = json.loads(h.text)["startup"]
            assert section["ready"] and section["to_ready_s"] == 50.0
            r = await client.wait(
                await client.request("GET", "/healthz?trace=1"), 10.0)
            obj = json.loads(r.text)
            assert validate_chrome_trace(obj)
            names = [e["name"] for e in obj["traceEvents"]
                     if e.get("cat") == "startup"]
            assert names.count("startup.program") == 3
            assert "startup.process" in names and "startup.ready" in names
        finally:
            global_tracer.configure(enabled=False, capacity=4096)
            global_tracer.clear()
            await _teardown(serve_task, ch, client)

    asyncio.run(main())


# ---------------------------------------------------------------------------
# engine-backed behavior (tiny model, CPU)
# ---------------------------------------------------------------------------


def _engine(**overrides):
    from p2p_llm_tunnel_tpu.engine.engine import (
        EngineConfig,
        InferenceEngine,
    )

    kw = dict(model="tiny", num_slots=2, max_seq=128, dtype="float32",
              decode_steps=4, decode_steps_eager=0)
    kw.update(overrides)
    return InferenceEngine(engine_cfg=EngineConfig(**kw))


def _prompt(seed: int, n: int = 12):
    rng = random.Random(seed)
    return [rng.randrange(2, 200) for _ in range(n)]


def test_engine_records_one_flight_row_per_iteration():
    async def main():
        global_flight.configure(capacity=6)  # tiny cap: bound under churn
        iters0 = global_metrics.counter("engine_flight_iterations_total")
        try:
            engine = _engine()
            await engine.start()
            try:
                async for _ in engine.generate(_prompt(1), max_new_tokens=24):
                    pass
            finally:
                await engine.stop()
            # Exactly one record per non-idle iteration (the counter is
            # incremented by record_iteration itself), and the ring cap
            # held while the counter ran past it.
            iters = (global_metrics.counter("engine_flight_iterations_total")
                     - iters0)
            assert global_flight.iterations == iters > 6
            assert len(global_flight.records()) == 6
            rows = global_flight.records()
            # Decode iterations carry the burst shape; the schema is the
            # registry's (no stray fields can exist — record_iteration
            # validated them).
            assert any(r["decode_steps"] == 4 and r["decode_rows"] == 1
                       for r in rows)
            assert all(set(r) <= set(FLIGHT_SCHEMA) for r in rows)
        finally:
            global_flight.configure(capacity=1024)

    asyncio.run(main())


def test_warmup_compile_journal_covers_grid_and_gauges():
    async def main():
        engine = _engine()
        await engine.start()
        try:
            await engine.warmup()
            events = global_compile_watch.events()
            keys = {e["key"] for e in events}
            # The full decode (view x steps) grid appears in the journal.
            for view in engine._warmup_views():
                assert f"decode[{view},{engine.ecfg.decode_steps}]" in keys
            assert all(e["phase"] in ("warmup", "aot") for e in events)
            assert not any(e["cold"] for e in events)
            # the total as a catalogued gauge; count and slowest program
            # in the start-up journal's section (ISSUE 40: the two gauges
            # that carried them are gone from the catalog)
            assert global_metrics.gauge("engine_warmup_compile_s") > 0
            section = global_compile_watch.startup_section()
            assert section["programs"] == len(keys) >= 1
            mx = section["slowest_program"]["seconds"]
            assert 0 < mx <= global_metrics.gauge("engine_warmup_compile_s")
            from p2p_llm_tunnel_tpu.utils.metrics import METRICS_CATALOG
            assert "engine_warmup_programs" not in METRICS_CATALOG
            assert "engine_warmup_compile_max_s" not in METRICS_CATALOG
            assert engine._warmup_done
            assert global_metrics.counter("engine_cold_compiles_total") == 0
        finally:
            await engine.stop()

    asyncio.run(main())


def test_midserve_cold_compile_detected_on_unwarmed_bucket(monkeypatch):
    """A deliberately-capped warmup leaves the big kv-view bucket out of
    the grid; a long generation then reaches it on the serving path — the
    cold compile must be counted, journaled cold, and stamped on the
    flight record (the test_warmup_aot bug class, surfaced at runtime)."""
    monkeypatch.setenv("TUNNEL_WARMUP_VIEW_CAP", "1")

    async def main():
        cold0 = global_metrics.counter("engine_cold_compiles_total")
        engine = _engine(max_seq=512, decode_steps=8)
        await engine.start()
        try:
            await engine.warmup()
            assert engine._warmup_done
            # The cap kept warmup to the smallest bucket only.
            warmed = {k for k in engine._programs_ready
                      if k.startswith("decode[")}
            assert warmed == {"decode[128,8]"}
            # Generate far enough that the view bucket grows past 128:
            # need = pos + 2*8 + 1 > 128 -> ~110 tokens of context.
            async for _ in engine.generate(_prompt(2, n=16),
                                           max_new_tokens=160):
                pass
        finally:
            await engine.stop()
        assert global_metrics.counter("engine_cold_compiles_total") > cold0
        cold_events = [e for e in global_compile_watch.events() if e["cold"]]
        assert cold_events
        assert all(e["phase"] == "serve" for e in cold_events)
        # The capped-out decode view bucket is among the detected holes
        # (so is the never-hinted prefill prompt bucket — warmup without
        # TUNNEL_WARMUP_PREFILL_TOKENS compiles no prefill program, a
        # real grid hole this profiler now surfaces).
        assert any(e["key"].startswith("decode[256") for e in cold_events)
        assert any(r["cold_compiles"] for r in global_flight.records())

    asyncio.run(main())


def _wedge_second_decode(engine, release: threading.Event):
    """Monkeypatch: the SECOND decode-burst dispatch blocks the executor
    thread until ``release`` — a deterministic stand-in for a wedged XLA
    dispatch (the decode-stall watchdog's incident class)."""
    orig = engine._dispatch_decode
    calls = {"n": 0}

    def wedged(**kw):
        calls["n"] += 1
        if calls["n"] >= 2:
            release.wait(timeout=30)
        return orig(**kw)

    engine._dispatch_decode = wedged


async def _watchdog_incident_bundle(seed: int) -> dict:
    """One seeded watchdog incident: two requests admitted, first burst
    dispatched, second dispatch wedges, watchdog trips and captures.

    The engine is WARMED first (prefill width hinted) so no compile stall
    can trip the tight watchdog budget before the deliberate wedge — the
    wedge is the incident."""
    global_metrics.reset()
    global_flight.reset()
    global_compile_watch.reset()
    global_blackbox.reset()
    global_tracer.configure(enabled=False)
    global_tracer.clear()
    engine = _engine(watchdog_budget_s=0.25)
    release = threading.Event()
    os.environ["TUNNEL_WARMUP_PREFILL_TOKENS"] = "12"
    try:
        await engine.start()
        await engine.warmup()
    finally:
        del os.environ["TUNNEL_WARMUP_PREFILL_TOKENS"]
    _wedge_second_decode(engine, release)
    consumers = []
    try:
        async def consume(p):
            async for _ in engine.generate(p, max_new_tokens=16):
                pass

        consumers = [
            asyncio.create_task(consume(_prompt(seed))),
            asyncio.create_task(consume(_prompt(seed + 1))),
        ]
        for _ in range(400):
            if global_blackbox.captured:
                break
            await asyncio.sleep(0.025)
        bundle = global_blackbox.last()
        assert bundle is not None, "watchdog never captured"
        return bundle
    finally:
        release.set()
        for t in consumers:
            t.cancel()
        await asyncio.gather(*consumers, return_exceptions=True)
        await engine.stop()


def test_postmortem_bundle_identity_two_seeded_runs():
    """The acceptance pin: the same seeded watchdog incident yields a
    bundle IDENTICAL across two runs once the explicitly-waived
    wall-clock fields are stripped — flight tail, compile journal,
    scheduler/slot snapshot, config, metrics counters, attribution, all
    byte-for-byte.  (`make chaos` runs this at two seeds with
    TUNNEL_POSTMORTEM_DIR=artifacts/postmortem to archive the bundles.)"""
    async def main():
        b1 = await _watchdog_incident_bundle(SEED)
        b2 = await _watchdog_incident_bundle(SEED)
        assert b1["trigger"] == "watchdog"
        # Attribution: the loop phase the stall wedged in.
        assert b1["attribution"] in (
            "decode_dispatch", "decode_fetch", "process", "segments",
        )
        c1, c2 = postmortem_canonical(b1), postmortem_canonical(b2)
        assert c1 == c2, "postmortem bundles diverged across seeded runs"
        # The bundle is substantive, not vacuously equal: flight rows,
        # compile events, the slot table, and real token counters.
        assert c1["flight"], "no flight records in the bundle"
        assert c1["compile_events"]
        assert any(s is not None for s in c1["engine"]["scheduler"]["slots"])
        assert c1["metrics"]["engine_tokens_total"] > 0
        assert c1["engine"]["config"]["model"] == "tiny"
        # And JSON-serializable end to end (the /healthz + archive form).
        json.dumps(b1, default=str)

    asyncio.run(main())


def test_a_stopped_engine_takes_its_degraded_verdict_with_it():
    """The verdict lives in the process-wide registry (``/healthz`` reads it
    there).  An engine that stops while degraded must clear it: nothing of
    it is left running to do so, and the next engine of the process — or a
    bare ``run_serve``, as in tests/test_fleet.py — would read degraded."""
    async def main():
        engine = _engine()
        await engine.start()
        engine.degraded = True
        engine.degraded_reason = "memory"
        global_metrics.set_gauge("engine_degraded", 1.0)
        global_metrics.set_info("engine_degraded_reason", "memory")
        await engine.stop()

    asyncio.run(main())
    assert global_metrics.gauge("engine_degraded") == 0.0
    assert global_metrics.info("engine_degraded_reason", "") == ""
