"""The dispatch ledger (ISSUE 25): one record for every device dispatch of
the engine loop, the requests' own parts of the prefill dispatches, and the
always-on counters that carry the same numbers to /metrics.

Tiny engine on the CPU: chunked prefill through the mux backlog, the prefix
pool on (so pool copies and cached prompt tokens occur), both decode burst
widths.
"""

import asyncio
import contextlib
import gc

import pytest

from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
from p2p_llm_tunnel_tpu.utils.flight import (
    FLIGHT_SCHEMA,
    LOOP_PARTS,
    global_flight,
    global_gc,
)
from p2p_llm_tunnel_tpu.utils.metrics import METRICS_CATALOG, global_metrics
from p2p_llm_tunnel_tpu.utils.tracing import (
    SPAN_CATALOG,
    TraceContext,
    global_tracer,
    mint_trace_id,
)

COUNTERS = (
    "engine_prefill_tokens_total", "engine_prefill_positions_total",
    "engine_decode_steps_total", "engine_decode_row_steps_total",
    "engine_decode_slot_steps_total", "engine_tokens_total",
    "engine_decode_kernel_steps_total",
    "engine_decode_state_kernel_steps_total",
)
#: the sums an untraced run leaves of its loop's host time and of the
#: collector (ISSUE 57)
HOST_COUNTERS = (
    "engine_loop_host_seconds_total", "engine_loop_wait_seconds_total",
    "engine_loop_lag_seconds_total", "process_gc_pause_seconds_total",
    "process_gc_collections_total", "process_gc_full_collections_total",
)
#: flight records and the growth of HOST_COUNTERS of each run_engine(trace)
FLIGHT = {}
CHUNK = 16
SLOTS = 4
ROWS = 2
SHARED = list(range(100, 148))  # three whole pool blocks of 16


@contextlib.contextmanager
def tracing(enabled: bool):
    global_tracer.clear()
    global_tracer.configure(enabled=enabled, sample=1.0, capacity=65536)
    try:
        yield
    finally:
        global_tracer.configure(enabled=False)
        global_tracer.clear()


def prompts():
    """Ten prompts of one to four segments; the last four share a prefix
    that the first of them leaves in the pool."""
    out = [[7 + (i * 13 + j) % 90 for j in range(n)]
           for i, n in enumerate((9, 16, 17, 40, 33, 64))]
    out += [SHARED + [200 + i, 201 + i, 202 + i] for i in range(4)]
    return out


async def _drive(engine, trace: bool, delay_shared: bool = True):
    async def one(i, ids, wait):
        await asyncio.sleep(wait)
        ctx = TraceContext(mint_trace_id(), "") if trace else None
        n = 0
        async for _ev in engine.generate(ids, max_new_tokens=6 + 5 * (i % 3),
                                         trace=ctx):
            n += 1
        return n

    jobs = []
    for i, ids in enumerate(prompts()):
        # the sharers arrive once the first of them has been pooled
        late = delay_shared and ids[:len(SHARED)] == SHARED and i > 6
        jobs.append(one(i, ids, 1.5 if late else 0.02 * i))
    return await asyncio.gather(*jobs)


def collect_once(engine):
    """Force ONE collection of every generation, inside the loop's third
    decode dispatch (the executor thread, the middle of an iteration)."""
    dispatch, calls = engine._dispatch_decode, [0]

    def dispatching():
        calls[0] += 1
        if calls[0] == 3:
            gc.collect()
        return dispatch()

    engine._dispatch_decode = dispatching


def run_engine(trace: bool, patch=None):
    """Counter growth, journal records and emitted-token counts of one run.
    The collector runs once, where ``collect_once`` says (its own schedule
    is off meanwhile), and the run's flight records are kept in FLIGHT."""
    async def main():
        engine = InferenceEngine(engine_cfg=EngineConfig(
            model="tiny", num_slots=SLOTS, max_seq=256, dtype="float32",
            mux=True, prefix_cache=True, prefill_chunk=CHUNK,
            prefill_rows=ROWS, decode_steps=8, decode_steps_eager=4,
        ))
        if patch:
            patch(engine)
        collect_once(engine)
        await engine.start()
        try:
            global_gc.publish()  # (collections from before this run)
            before = {c: global_metrics.counter(c)
                      for c in COUNTERS + HOST_COUNTERS}
            emitted = await _drive(engine, trace)
            # the burst dispatched under the last tokens is fetched, and its
            # record closed, by the loop's next pass
            await asyncio.sleep(0.3)
            grown = {c: global_metrics.counter(c) - before[c]
                     for c in before}
        finally:
            await engine.stop()
        return grown, emitted

    global_flight.reset()
    gc.disable()
    try:
        with tracing(trace):
            grown, emitted = asyncio.run(main())
            FLIGHT[trace] = (global_flight.records(), grown)
            return grown, global_tracer.records(), emitted
    finally:
        gc.enable()


@pytest.fixture(scope="module")
def traced():
    return run_engine(trace=True)


def named(records, name):
    return [r for r in records if r.name == name]


def test_new_names_are_catalogued():
    for name in ("engine.prefill_part", "engine.pool_copy",
                 "engine.prefill_segment", "engine.decode_burst",
                 "process.gc_pause"):
        assert name in SPAN_CATALOG
    for name in COUNTERS + HOST_COUNTERS:
        assert name in METRICS_CATALOG
    # (ISSUE 57: the flight record's fetch_ms / wait_ms say it, and more)
    assert "engine_decode_fetch_ms" not in METRICS_CATALOG


def test_tc09_passes_on_the_engine():
    from pathlib import Path

    from tools.tunnelcheck import run_paths

    root = Path(__file__).resolve().parents[1] / "p2p_llm_tunnel_tpu"
    active, _ = run_paths([root / "engine" / "engine.py",
                           root / "utils" / "tracing.py"], rules=["TC09"])
    assert active == [], [v.render(root) for v in active]


def test_prefill_records_and_counters_agree_exactly(traced):
    grown, records, _ = traced
    segs = named(records, "engine.prefill_segment")
    assert segs and {r.attrs["program"] for r in segs} == {"chunk_prefill"}
    assert sum(r.attrs["tokens"] for r in segs) == \
        grown["engine_prefill_tokens_total"]
    assert sum(r.attrs["positions"] for r in segs) == \
        grown["engine_prefill_positions_total"]
    for r in segs:
        a = r.attrs
        # the rung the dispatch was padded to (ISSUE 30): the smallest of
        # chunk_row_ladder(ROWS) = (1, 2) that holds its rows
        assert a["rows_padded"] == a["rows"] <= ROWS
        assert a["positions"] == a["rows_padded"] * a["t"]
        assert a["t"] == CHUNK and 0 < a["tokens"] <= a["rows"] * CHUNK
    assert {r.attrs["rows_padded"] for r in segs} == {1, 2}  # both rungs ran
    # the pool spared the sharers their prefix: fewer tokens than prompts
    assert grown["engine_prefill_tokens_total"] < \
        sum(len(p) for p in prompts())


def test_decode_records_and_counters_agree_exactly(traced):
    grown, records, emitted = traced
    bursts = named(records, "engine.decode_burst")
    assert {r.attrs["steps"] for r in bursts} == {4, 8}  # both widths ran
    assert sum(r.attrs["steps"] for r in bursts) == \
        grown["engine_decode_steps_total"]
    row_steps = sum(r.attrs["live_rows"] * r.attrs["steps"] for r in bursts)
    assert row_steps == grown["engine_decode_row_steps_total"]
    assert sum(r.attrs["slots"] * r.attrs["steps"] for r in bursts) == \
        grown["engine_decode_slot_steps_total"]
    assert all(r.attrs["slots"] == SLOTS and
               0 <= r.attrs["live_rows"] <= SLOTS for r in bursts)
    # the branch each burst's program ran, and the steps a kernel took of
    # them (none here: a CPU backend keeps the einsum; the kernel path is
    # held the same way in tests/test_decode_rows.py)
    assert {r.attrs["attn"] for r in bursts} == {"einsum"}
    assert sum(r.attrs["steps"] for r in bursts
               if r.attrs["attn"] != "einsum") == \
        grown["engine_decode_kernel_steps_total"] == 0
    # (a model without a recurrent state names no branch of its update)
    assert all("state_update" not in r.attrs for r in bursts)
    assert grown["engine_decode_state_kernel_steps_total"] == 0
    # every emitted token but a request's first came out of a live row-step
    assert grown["engine_tokens_total"] == sum(emitted)
    assert row_steps >= grown["engine_tokens_total"] - len(emitted)


def test_every_dispatch_has_its_own_sequence_number(traced):
    _, records, _ = traced
    ledger = [r for r in records if r.trace_id is None and "seq" in r.attrs]
    assert {r.name for r in ledger} == {
        "engine.prefill_segment", "engine.decode_burst", "engine.pool_copy"}
    seqs = [r.attrs["seq"] for r in ledger]
    assert len(set(seqs)) == len(seqs)
    # dispatched one by one on one executor: start order is sequence order
    by_start = [r.attrs["seq"] for r in sorted(ledger, key=lambda r: r.ts)]
    assert by_start == sorted(seqs)


def test_pool_copies_are_recorded_both_ways(traced):
    _, records, _ = traced
    copies = named(records, "engine.pool_copy")
    assert {r.attrs["program"] for r in copies} == {
        "cache_to_pool", "pool_to_cache"}
    for r in copies:
        a = r.attrs
        assert 0 < a["rows"] <= a["rows_padded"] == ROWS
        assert 0 < a["blocks"] <= a["blocks_padded"]


def test_a_requests_parts_lie_in_its_prefill_exec_and_add_up(traced):
    _, records, _ = traced
    seqs = {r.attrs["seq"]: r for r in named(records,
                                             "engine.prefill_segment")}
    execs = named(records, "engine.prefill_exec")
    assert len(execs) == len(prompts())
    cached = 0
    for ex in execs:
        request = next(r for r in named(records, "engine.request")
                       if r.trace_id == ex.trace_id)
        parts = [r for r in named(records, "engine.prefill_part")
                 if r.trace_id == ex.trace_id]
        a = ex.attrs
        assert len(parts) == a["parts"] >= 1
        assert sum(p.attrs["tokens"] for p in parts) == \
            a["prompt_tokens"] - a["cached_tokens"]
        assert a["iterations"] >= 1
        cached += a["cached_tokens"]
        for part in parts:
            assert part.parent_id == request.span_id
            assert part.ts >= ex.ts - 1e-6
            assert part.ts + part.dur <= ex.ts + ex.dur + 1e-6
            seg = seqs[part.attrs["seq"]]  # the dispatch that carried it
            assert (part.ts, part.dur) == (seg.ts, seg.dur)
        ordered = sorted(parts, key=lambda p: p.attrs["start"])
        assert ordered[0].attrs["start"] == a["cached_tokens"]
        assert [p.attrs["final"] for p in ordered] == \
            [False] * (len(parts) - 1) + [True]
    assert cached >= len(SHARED)  # a sharer reused the pooled prefix


def test_with_the_recorder_off_nothing_is_built_and_counters_still_count():
    def no_records(engine):
        def refuse(*_a, **_k):
            raise AssertionError("a dispatch record was built with the "
                                 "span journal off")
        engine._open_dispatch = refuse

    grown, records, emitted = run_engine(trace=False, patch=no_records)
    assert records == []
    assert sum(emitted) == grown["engine_tokens_total"] > 0
    assert grown["engine_prefill_positions_total"] >= \
        grown["engine_prefill_tokens_total"] > 0
    assert grown["engine_decode_slot_steps_total"] == \
        SLOTS * grown["engine_decode_steps_total"] > 0
    assert 0 < grown["engine_decode_row_steps_total"] <= \
        grown["engine_decode_slot_steps_total"]


@pytest.mark.parametrize("trace", [True, False], ids=["traced", "untraced"])
def test_a_flight_record_says_where_its_iterations_wall_went(traced, trace):
    """ISSUE 57, on the runs this file makes anyway: the parts tile the
    iteration, the phases' starts are in the loop's order, the waits lie in
    the fetches, and what the executor and the event loop took is inside
    the wall.  Always on: the journal's switch changes nothing here.  (The
    untraced run is the one the test above has made.)"""
    if trace not in FLIGHT:
        run_engine(trace=False)
    records, grown = FLIGHT[trace]
    assert len(records) > 10
    phases = list(LOOP_PARTS)
    for rec in records:
        assert set(rec) <= set(FLIGHT_SCHEMA)
        parts = [rec[part] for part in LOOP_PARTS.values()]
        assert abs(sum(parts) - rec["dur_ms"]) <= 0.001 * len(parts), rec
        at = rec["at_ms"]
        assert at["admit"] == 0.0 and set(at) <= set(phases)
        starts = [at[p] for p in phases if p in at]
        assert starts == sorted(starts), at
        assert abs(sum(w[1] for w in rec["waits_ms"]) - rec["wait_ms"]) < 0.01
        # no whole-prompt wave in this engine (mux, chunked): a burst's
        # fetch and each segment's are all its blocking fetches
        assert rec["wait_ms"] <= rec["fetch_ms"] + rec["segments_ms"] + 0.01
        assert all(0 <= at_ms and at_ms + length <= rec["dur_ms"] + 0.01
                   for at_ms, length in rec["waits_ms"])
        assert 0 <= rec["lag_ms"] and 0 <= rec["exec_ms"]
        assert rec["exec_ms"] + rec["lag_ms"] <= rec["dur_ms"] + 0.01
        assert rec["evict_ms"] == 0 and rec["evicted_pages"] == 0
    assert any(rec["wait_ms"] > 0 for rec in records)
    assert sum(rec["exec_ms"] > 0 for rec in records) > len(records) // 2
    assert any(rec["segments_ms"] > 0 for rec in records)
    # the sums an untraced run leaves: the records', to the rounding
    dur = sum(r["dur_ms"] for r in records) / 1e3
    wait = sum(r["wait_ms"] for r in records) / 1e3
    assert grown["engine_loop_wait_seconds_total"] == pytest.approx(wait)
    assert grown["engine_loop_host_seconds_total"] == pytest.approx(
        dur - wait, abs=1e-3)
    assert grown["engine_loop_lag_seconds_total"] == pytest.approx(
        sum(r["lag_ms"] for r in records) / 1e3)


@pytest.mark.parametrize("trace", [True, False], ids=["traced", "untraced"])
def test_a_collection_lands_in_one_record_three_counters_and_one_span(
        traced, trace):
    if trace not in FLIGHT:
        run_engine(trace=False)
    records, grown = FLIGHT[trace]
    hit = [r for r in records if r["gc_ms"] > 0]
    assert len(hit) == 1 and hit[0]["gc_full"] == 1
    assert hit[0]["gc_ms"] <= hit[0]["dispatch_ms"] + 0.01
    assert sum(r["gc_full"] for r in records) == 1
    assert grown["process_gc_collections_total"] == 1
    assert grown["process_gc_full_collections_total"] == 1
    assert grown["process_gc_pause_seconds_total"] == pytest.approx(
        hit[0]["gc_ms"] / 1e3, abs=1e-5)
    if trace:
        pauses = named(traced[1], "process.gc_pause")
        assert len(pauses) == 1 and pauses[0].track == "process"
        assert pauses[0].attrs["generation"] == 2
        assert "collected" in pauses[0].attrs
        assert pauses[0].dur == pytest.approx(hit[0]["gc_ms"] / 1e3, abs=1e-5)
        start = pauses[0].ts - hit[0]["t"]
        assert 0 <= start <= hit[0]["dur_ms"] / 1e3


def test_the_annotation_is_named_as_the_span_and_carries_the_join_keys():
    """The profiler annotation around a dispatch call is what ties the
    journal's clock to a device trace's: same name as the span, ``seq`` and
    the dispatch instant in this process's monotonic microseconds."""
    import jax

    made = []

    class Spy:
        def __init__(self, name, **kwargs):
            made.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    real = jax.profiler.TraceAnnotation
    jax.profiler.TraceAnnotation = Spy
    try:
        _, records, _ = run_engine(trace=True)
    finally:
        jax.profiler.TraceAnnotation = real
    ledger = {r.attrs["seq"]: r for r in records
              if r.trace_id is None and "seq" in r.attrs}
    assert len(made) == len(ledger) > 0
    for name, kwargs in made:
        rec = ledger[kwargs["seq"]]
        assert name == rec.name
        assert kwargs["mono_us"] == int(rec.ts * 1e6)
        if name == "engine.decode_burst":
            assert kwargs["steps"] == rec.attrs["steps"]
        elif name == "engine.prefill_segment":
            assert kwargs["tokens"] == rec.attrs["tokens"]


def test_the_engine_publishes_the_precision_it_was_built_with():
    """/healthz ``config.quant`` / ``config.kv_quant`` read these."""
    InferenceEngine(engine_cfg=EngineConfig(
        model="tiny", num_slots=2, max_seq=64, quant="int8",
        kv_quant="int8"))
    assert global_metrics.info("config_quant") == "int8"
    assert global_metrics.info("config_kv_quant") == "int8"
    InferenceEngine(engine_cfg=EngineConfig(
        model="tiny", num_slots=2, max_seq=64))
    assert global_metrics.info("config_quant") == "none"
    assert global_metrics.info("config_kv_quant") == "none"
