"""The dispatch-ledger readers on a recorded excerpt of a chip run
(``data/chip_dispatch_excerpt.json.gz``: mistral-7b.chat-open on TPU v5
lite, PR 25): the program's annotations, the device's runs and operations
with their scopes, and the journal of the same seconds."""

import gzip
import json
import os

import pytest

from benchmarks import dispatch_trace as dt
from test_bm_dispatch_ledger import Ctx, FakePlane, reader

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "chip_dispatch_excerpt.json.gz"),
                   "rt") as f:
        doc = json.load(f)
    planes = [FakePlane(name, {line: [tuple(ev) for ev in events]
                               for line, events in lines.items()})
              for name, lines in doc["planes"].items()]
    return planes, doc["spans"], doc["load"]


@pytest.fixture(scope="module")
def summary(recorded):
    planes, spans, _load = recorded
    return dt.summarize(planes, spans)


def ctx_of(recorded, summary):
    _planes, spans, load = recorded
    ctx = Ctx(spans, summary)

    class Window:
        t0, t1 = load["t0"], load["t1"]
    ctx.load = Window
    return ctx


def test_the_recorded_clock_fits_to_microseconds(summary):
    fit = summary["fit"]
    assert fit["annotations"] == 26
    assert fit["offset_s"] == pytest.approx(-1835.423014, abs=1e-5)
    assert fit["residual_p50_us"] < 2.0 and fit["residual_max_us"] < 10.0


def test_recorded_runs_pair_in_dispatch_order_with_the_head_left_out(
        summary):
    def seqs(runs):
        return [r["annotation"] and r["annotation"]["seq"] for r in runs]

    decode = summary["pairs"]["engine.decode_burst"]
    # the first decode run was dispatched before the excerpt's first
    # annotation; every later one is its burst's, in order
    assert seqs(decode) == [None, 481, 483, 488, 493, 496, 500]
    assert all(r["annotation"]["steps"] == 4 for r in decode[1:])
    prefill = summary["pairs"]["engine.prefill_segment"]
    assert seqs(prefill) == [480, 482, 486, 487, 491, 492, 495, 499, 503]
    # no run starts before its dispatch or ends after its record's end
    records = summary["records"]
    for span, runs in summary["pairs"].items():
        for run in runs:
            if run["annotation"]:
                start, end = records[span][run["annotation"]["seq"]]
                assert start <= run["start"] and run["end"] <= end + 0.002
    assert seqs(dt.in_window(summary, decode)) == [481, 483, 488, 493, 496]


def test_the_recorded_decode_step_by_the_ledger(recorded, summary):
    device = reader("dispatch_device")
    ctx = ctx_of(recorded, summary)
    # five paired bursts of four steps inside the window
    assert device.read(ctx, "step") == pytest.approx(18.76504465)
    # a step runs the 32 layers once: the old reader's count, on these runs
    paired = [r for r in dt.in_window(
        summary, summary["pairs"]["engine.decode_burst"])]
    assert sum(r["annotation"]["steps"] for r in paired) == 20


def test_the_recorded_own_share_and_its_sample_floor(recorded, summary,
                                                     capsys):
    device = reader("dispatch_device")
    ctx = ctx_of(recorded, summary)
    # two requests have their whole prefill_exec inside the 1.2 s
    assert device.read(ctx, "own", least=5) is None
    assert "2 requests" in capsys.readouterr().out
    assert device.read(ctx, "own", least=1) == pytest.approx(52.864725)


def test_recorded_scopes_and_the_span_readers(recorded, summary):
    own = summary["scopes"]
    assert set(own) == set(dt.SCOPES) | {"unscoped"}
    assert own["ffn"] == pytest.approx(0.15356, abs=1e-5)
    assert own["kv_read"] == pytest.approx(0.038463, abs=1e-5)
    ctx = ctx_of(recorded, summary)
    assert reader("dispatch_device").read(
        ctx, "scopes", scopes=["kv_read", "kv_write", "pool_copy"]) == \
        pytest.approx(20.7255419)
    fill = reader("dispatch_fill")
    assert fill.read(ctx, "engine.prefill_segment", ["tokens"],
                     ["positions"]) == pytest.approx(24.73828125)
    assert fill.read(ctx, "engine.decode_burst", ["live_rows", "steps"],
                     ["slots", "steps"]) == pytest.approx(56.1141304)
    wait = reader("prefill_wait")
    assert len(wait.waits_ms(ctx)) == 23
    assert wait.read(ctx, 50) == pytest.approx(190.385)


def test_the_roofline_share_reads_the_ledgers_step(recorded, summary):
    """``decode_roofline``: the least step time for what the client had in
    flight over the step the dispatch records give (18.77 ms here), and
    nothing without the chip's peaks or a traced window."""
    from types import SimpleNamespace

    from benchmarks import roofline

    repo = os.path.dirname(os.path.dirname(os.path.dirname(DATA)))
    with open(os.path.join(repo, "benchmarks", "configs",
                           "mistral-7b.json")) as f:
        config = json.load(f)
    with open(os.path.join(repo, "benchmarks", "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    share = reader("roofline_share")
    ctx = ctx_of(recorded, summary)
    # 17 requests decoding all through the traced window, 200 prompt words
    # and 10 tokens each so far
    ctx.config, ctx.peaks, ctx.trace_span = config, peaks, (100.0, 103.0)
    ctx.plan = SimpleNamespace(all_requests=lambda: [
        SimpleNamespace(index=i, prompt_words=200) for i in range(17)])
    ctx.load = SimpleNamespace(outcomes=[
        SimpleNamespace(index=i, first_token=90.0, last_token=110.0,
                        tokens_seen=40, asked=64, token_times=[(95.0, 10)])
        for i in range(17)])
    least = roofline.least_step_seconds(config, peaks, 17.0, 17 * 210.0)
    got = share.read(ctx)
    assert got == pytest.approx(100.0 * least["seconds"] * 1000.0
                                / 18.76504465)
    assert 45.0 < got < 55.0 and least["bound"] == "memory"
    ctx.peaks = None          # a CPU rehearsal has no peaks
    assert share.read(ctx) is None
    ctx.peaks, ctx.trace_span = peaks, None
    assert share.read(ctx) is None
    # a run whose trace paired no decode burst has no step to hold it against
    bare = Ctx([], dict(summary, pairs={}))
    bare.peaks, bare.trace_span = peaks, (100.0, 103.0)
    assert share.read(bare) is None
