"""The readers of the program's dispatch ledger: the xplane decoder against
``jax.profiler.ProfileData``, the clock fit, the pairing of device runs
with dispatch records (by order, the trace's head left out), scopes, and
each reader on a hand-made journal and trace whose answers are known.  The
same on a recorded chip trace is in test_bm_dispatch_recorded.py."""

import importlib.util
import os

import pytest

from benchmarks import dispatch_trace as dt
from benchmarks import trace_reduce as tr
from benchmarks import xplane_read

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
READERS = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmarks",
                       "layer_metrics")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(READERS, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---- hand-made planes, with the decoder's interface -------------------------

class FakeLine:
    def __init__(self, name, events):
        self.name, self._events = name, events

    def __len__(self):
        return len(self._events)

    def events(self, stats=True):
        return iter(self._events)


class FakePlane:
    def __init__(self, name, lines):
        self.name = name
        self.lines = [FakeLine(n, evs) for n, evs in lines.items()]

    def line(self, name):
        return [ln for ln in self.lines if ln.name == name]


#: monotonic clock + OFFSET = trace clock
OFFSET = -1000.0


def annotation(name, seq, start, **work):
    """An annotation on the trace's clock whose ``mono_us`` is the instant
    the program read on its own clock."""
    stats = dict(work, seq=seq, mono_us=int(round((start - OFFSET) * 1e6)))
    return (name, start, start + 0.001, stats)


def record(name, seq, start, end, **attrs):
    """A dispatch record of the journal (monotonic microseconds)."""
    return {"name": name, "ph": "X", "ts": int((start - OFFSET) * 1e6),
            "dur": int((end - start) * 1e6), "args": dict(attrs, seq=seq)}


def op(scope, start, end, name="fusion.1"):
    path = f"jit(_decode_fn)/jit(main)/while/body/{scope}/dot_general:" \
        if scope else None
    return (name, start, end, {"tf_op": path} if path else {})


def make_trace():
    """A 3 s window (10..13).  Decode: a burst dispatched before the profile
    began runs first (no annotation: the head), then bursts 1 and 2 (4 and 8
    steps), each started on the device *after* its successor's dispatch, as
    under pipelining with a prefill between.  Prefill: two runs, the first
    carrying requests a and b, the second b alone."""
    host = {"python": [
        (tr.WINDOW_NAME, 10.0, 13.0, {}),
        annotation("engine.decode_burst", 1, 10.05, steps=4),
        annotation("engine.prefill_segment", 2, 10.30, tokens=300),
        annotation("engine.decode_burst", 3, 10.35, steps=8),
        annotation("engine.prefill_segment", 4, 11.00, tokens=100),
        annotation("engine.decode_burst", 5, 11.05, steps=4),
        annotation("engine.pool_copy", 6, 11.50),
    ]}
    modules = [
        ("jit__decode_fn(1)", 10.00, 10.40, {}),          # head: no record
        ("jit__chunk_prefill_fn(2)", 10.40, 10.50, {}),   # seq 2
        ("jit__decode_fn(1)", 10.50, 10.58, {}),          # seq 1: 4 steps
        ("jit__chunk_prefill_fn(2)", 11.10, 11.20, {}),   # seq 4
        ("jit__decode_fn(1)", 11.20, 11.40, {}),          # seq 3: 8 steps
        ("jit_cache_to_pool(3)", 11.60, 11.65, {}),
        ("jit__decode_fn(1)", 12.90, 13.10, {}),          # seq 5: cut
    ]
    ops = [op(None, 10.0, 10.4, "while.1"), op("kv_read", 10.0, 10.1),
           op("attn", 10.1, 10.2), op("ffn", 10.2, 10.4),
           op("kv_write", 10.4, 10.5), op("pool_copy", 11.6, 11.65),
           op(None, 12.0, 12.25, "copy.150")]
    device = {tr.MODULES_LINE: modules, tr.OPS_LINE: ops}
    journal = [
        # a record ends when the host has fetched the run's result
        record("engine.decode_burst", 1, 10.05, 10.60, steps=4,
               live_rows=3, slots=4),
        record("engine.prefill_segment", 2, 10.30, 10.62, tokens=300,
               positions=1024, rows=2, rows_padded=8),
        record("engine.decode_burst", 3, 10.35, 11.42, steps=8,
               live_rows=1, slots=4),
        record("engine.prefill_segment", 4, 11.00, 11.44, tokens=100,
               positions=1024, rows=1, rows_padded=8),
        record("engine.decode_burst", 5, 11.05, 13.20, steps=4,
               live_rows=4, slots=4),
    ]
    planes = [FakePlane("/host:CPU", host),
              FakePlane("/device:TPU:0", device)]
    return planes, journal


class Load:
    t0, t1 = 1009.0, 1014.0   # the timed window, on the monotonic clock


class Ctx:
    def __init__(self, spans, summary=None):
        self.spans = spans
        self.load = Load
        self.cell = "test.cell"
        self.dispatch_trace = summary


# ---- the decoder -------------------------------------------------------------

def test_the_decoder_reads_what_profile_data_reads():
    """Every plane, line and event of a trace ``jax.profiler`` wrote, with
    the same names, times and event stats as JAX's own reader gives."""
    from jax.profiler import ProfileData

    path = os.path.join(DATA, "cpu_window.xplane.pb")
    theirs = ProfileData.from_file(path)
    mine = xplane_read.read(path)
    seen = 0
    for a, b in zip(theirs.planes, mine):
        assert a.name == b.name
        for la, lb in zip(a.lines, b.lines):
            assert la.name == lb.name
            for ev, (name, start, end, stats) in zip(la.events, lb.events()):
                assert ev.name == name
                assert ev.start_ns / 1e9 == pytest.approx(start, abs=1e-9)
                assert ev.duration_ns / 1e9 == pytest.approx(end - start,
                                                             abs=1e-9)
                for key, value in ev.stats:
                    assert str(stats[key]) == str(value)
                seen += 1
    assert seen > 500
    window = [e for p in mine for ln in p.lines for e in ln.events()
              if e[0] == tr.WINDOW_NAME]
    assert window[0][2] - window[0][1] == pytest.approx(0.360393728)


def test_scope_is_the_innermost_named_one():
    path = "jit(_decode_fn)/jit(main)/while/body/closed_call/{}/dynamic_slice:"
    assert dt.scope_of(path.format("kv_read")) == "kv_read"
    assert dt.scope_of("jit(cache_to_pool)/jit(main)/pool_copy/gather:") == \
        "pool_copy"
    assert dt.scope_of("jit(f)/attn/while/body/kv_write/scatter:") == \
        "kv_write"
    assert dt.scope_of("jit(f)/while/body/add:") is None
    assert dt.scope_of("jit(f)/ffn:") is None  # an operation, not a scope
    assert dt.scope_of(None) is None and dt.scope_of("") is None


# ---- clock and pairing -------------------------------------------------------

def test_the_clock_offset_is_the_median_and_residuals_are_reported():
    anns = [{"start": 5.0 + i + e, "mono_us": int((1005.0 + i) * 1e6)}
            for i, e in enumerate((0.0, 0.00001, -0.00001, 0.0005, 0.0))]
    fit = dt.clock_fit(anns)
    assert fit["offset_s"] == pytest.approx(-1000.0)
    assert fit["annotations"] == 5
    assert fit["residual_max_us"] == pytest.approx(500.0, abs=1.0)
    assert fit["residual_p50_us"] == pytest.approx(10.0, abs=1.0)
    assert dt.clock_fit([]) is None


def test_runs_pair_by_order_and_the_head_is_left_out():
    planes, journal = make_trace()
    out = dt.summarize(planes, journal)
    assert out["window"] == (10.0, 13.0)
    assert out["fit"]["offset_s"] == pytest.approx(OFFSET)
    assert out["fit"]["annotations"] == 6      # the pool copy's too
    decode = out["pairs"]["engine.decode_burst"]
    # the run that started at 10.0 was dispatched before the profile began;
    # by start times alone it could have taken burst 1's annotation (10.05 <
    # 10.50 holds for its successor too): the records' ends decide
    assert [r["annotation"] and r["annotation"]["seq"] for r in decode] == \
        [None, 1, 3, 5]
    prefill = out["pairs"]["engine.prefill_segment"]
    assert [r["annotation"]["seq"] for r in prefill] == [2, 4]


def test_without_a_head_every_run_is_paired():
    runs = [(1.0, 1.5), (2.0, 2.5)]
    anns = [{"seq": 1, "start": 0.9}, {"seq": 2, "start": 1.2},
            {"seq": 3, "start": 2.4}]       # the third never ran in the trace
    paired = dt.pair_family(runs, anns, {1: 1.6, 2: 2.6, 3: 3.5})
    assert [a["seq"] for a in paired] == [1, 2]


def test_a_run_never_pairs_with_a_dispatch_that_came_after_it():
    runs = [(1.0, 1.5), (2.0, 2.5), (3.0, 3.5)]
    anns = [{"seq": 7, "start": 1.8}, {"seq": 8, "start": 2.8}]
    paired = dt.pair_family(runs, anns, {7: 2.6, 8: 3.6})
    assert [a and a["seq"] for a in paired] == [None, 7, 8]


def test_no_journal_record_means_no_pairing_is_claimed():
    """With nothing to hold the shift against, every run stays unpaired
    rather than paired by guess."""
    runs = [(1.0, 1.5), (2.0, 2.5)]
    anns = [{"seq": 1, "start": 0.9}, {"seq": 2, "start": 1.2}]
    assert dt.pair_family(runs, anns, {}) == [None, None]
    assert dt.pair_family(runs, [], {}) == [None, None]
    assert dt.pair_family([], anns, {1: 1.0}) == []


def test_scopes_are_self_times_inside_the_window():
    planes, journal = make_trace()
    own = dt.summarize(planes, journal)["scopes"]
    assert own["kv_read"] == pytest.approx(0.1)
    assert own["kv_write"] == pytest.approx(0.1)
    assert own["pool_copy"] == pytest.approx(0.05)
    assert own["attn"] == pytest.approx(0.1)
    assert own["ffn"] == pytest.approx(0.2)
    # the loop without its children is nothing; the unscoped copy counts
    assert own["unscoped"] == pytest.approx(0.25)


def test_a_trace_without_device_annotations_or_scopes_gives_nothing():
    planes, journal = make_trace()
    cpu = dt.summarize(planes[:1], journal)             # a CPU rehearsal
    assert cpu["pairs"] == {} and cpu["scopes"] is None
    assert cpu["fit"]["annotations"] == 6               # the clock still fits
    bare = [FakePlane("/host:CPU", {"python": [(tr.WINDOW_NAME, 10., 13.,
                                                {})]}), planes[1]]
    parent = dt.summarize(bare, [])                     # a program before it
    assert parent["fit"] is None and parent["pairs"] == {}
    device = {tr.MODULES_LINE: [], tr.OPS_LINE: [op(None, 10.0, 10.4)]}
    unscoped = dt.summarize([planes[0], FakePlane("/device:TPU:0", device)],
                            journal)
    assert unscoped["scopes"] is None
    for summary, what in ((cpu, "step"), (cpu, "scopes"), (parent, "step"),
                          (parent, "own"), (unscoped, "scopes")):
        assert reader("dispatch_device").read(
            Ctx(journal, summary), what, scopes=["kv_read"]) is None
    assert reader("dispatch_device").read(Ctx(journal, None), "step") is None


# ---- the readers -------------------------------------------------------------

def test_fill_is_real_work_over_dispatched_work():
    _, journal = make_trace()
    fill = reader("dispatch_fill")
    ctx = Ctx(journal)
    assert fill.read(ctx, "engine.prefill_segment", ["tokens"],
                     ["positions"]) == pytest.approx(100 * 400 / 2048)
    assert fill.read(ctx, "engine.decode_burst", ["live_rows", "steps"],
                     ["slots", "steps"]) == \
        pytest.approx(100 * (3 * 4 + 1 * 8 + 4 * 4) / (4 * 16))
    # records that started outside the timed window are not counted
    Load.t1 = 1010.5
    try:
        assert fill.read(ctx, "engine.decode_burst", ["live_rows", "steps"],
                         ["slots", "steps"]) == \
            pytest.approx(100 * (3 * 4 + 1 * 8) / (4 * 12))
    finally:
        Load.t1 = 1014.0
    # a program from before the ledger: spans without the attrs
    old = [{"name": "engine.decode_burst", "ph": "X", "ts": int(1010e6),
            "dur": 5, "args": {"rows": 3}}]
    assert fill.read(Ctx(old), "engine.decode_burst", ["live_rows", "steps"],
                     ["slots", "steps"]) is None
    assert fill.read(Ctx([]), "engine.prefill_segment", ["tokens"],
                     ["positions"]) is None


def request_spans(tid, start, end, parts, **attrs):
    """A request's prefill_exec and its parts [(seq, start, end)], on the
    trace's clock."""
    def span(name, s, e, **args):
        return {"name": name, "ph": "X", "ts": int((s - OFFSET) * 1e6),
                "dur": int(round((e - s) * 1e6)),
                "args": dict(args, trace_id=tid)}
    out = [span("engine.prefill_exec", start, end, parts=len(parts),
                prompt_tokens=200, cached_tokens=0, iterations=3, **attrs)]
    out += [span("engine.prefill_part", s, e, seq=seq, tokens=100,
                 start=0, final=False) for seq, s, e in parts]
    return out


def test_prefill_wait_is_the_span_less_the_union_of_its_parts():
    wait = reader("prefill_wait")
    spans = (
        # 1.0 s, parts cover 0.3 + 0.4, overlapping by 0.1: 0.4 s waited
        request_spans("a", 10.0, 11.0, [(2, 10.1, 10.4), (4, 10.3, 10.7)])
        # 0.5 s with one part of 0.2: 0.3 s
        + request_spans("b", 10.2, 10.7, [(2, 10.3, 10.5)])
        # a part recorded for a request whose count disagrees: left out
        + request_spans("c", 10.0, 10.9, [(2, 10.3, 10.5)])[:1]
        # started outside the timed window: left out
        + request_spans("d", 20.0, 21.0, [(9, 20.1, 20.2)]))
    ctx = Ctx(spans)
    assert sorted(wait.waits_ms(ctx)) == pytest.approx([300.0, 400.0])
    assert wait.read(ctx, 50) == pytest.approx(350.0)
    parent = [s for s in spans if s["name"] == "engine.prefill_exec"]
    for s in parent:
        s["args"].pop("parts")
    assert wait.read(Ctx(parent), 50) is None


def test_decode_step_by_the_ledger_divides_paired_runs_by_their_steps():
    planes, journal = make_trace()
    summary = dt.summarize(planes, journal)
    device = reader("dispatch_device")
    # paired and whole inside the window: 0.08 s for 4 steps, 0.2 s for 8;
    # the head run and the run the window cuts are left out
    assert device.read(Ctx(journal, summary), "step") == \
        pytest.approx(1000.0 * 0.28 / 12)
    assert device.read(Ctx(journal, summary), "scopes",
                       scopes=["kv_read", "kv_write", "pool_copy"]) == \
        pytest.approx(100.0 * 0.25 / 0.8)
    with pytest.raises(ValueError, match="unknown quantity"):
        device.read(Ctx(journal, summary), "other")


def test_own_device_share_needs_its_sample_and_every_part_paired(capsys):
    planes, journal = make_trace()
    device = reader("dispatch_device")

    def requests(n):
        spans = list(journal)
        for i in range(n):
            # request i: parts in prefill runs seq 2 (0.1 s) and 4 (0.1 s)
            spans += request_spans(f"r{i}", 10.2, 11.5,
                                   [(2, 10.3, 10.62), (4, 11.0, 11.44)])
        # one whose prefill_exec is cut by the window, one with a part in a
        # run the trace never paired: neither counts
        spans += request_spans("cut", 12.5, 13.5, [(4, 11.0, 11.44)])
        spans += request_spans("lost", 10.2, 11.5, [(77, 10.3, 10.6)])
        return spans

    few = requests(4)
    summary = dt.summarize(planes, few)
    assert device.read(Ctx(few, summary), "own", least=5) is None
    assert "4 requests" in capsys.readouterr().out
    enough = requests(5)
    summary = dt.summarize(planes, enough)
    assert device.read(Ctx(enough, summary), "own", least=5) == \
        pytest.approx(100.0 * 0.2 / 1.3)
    assert "5 requests" in capsys.readouterr().out
