"""The readers of the engine loop's host time (ISSUE 57):
``layer_metrics/loop_host.py`` and ``layer_metrics/idle_by_phase.py`` on a
hand-made journal and trace whose answers are known."""

import os

import pytest

from benchmarks import dispatch_trace as dt
from benchmarks import trace_reduce as tr
# hand-made planes with the decoder's interface; monotonic clock + OFFSET =
# trace clock; the timed window 1009..1014 on the monotonic clock
from test_bm_dispatch_ledger import OFFSET, READERS, FakePlane, Load, reader

HERE = os.path.dirname(os.path.abspath(__file__))


class Ctx:
    def __init__(self, spans, planes=None):
        self.spans = spans
        self.load = Load
        self.cell = "test.cell"
        if planes is not None:
            self.dispatch_trace = dt.summarize(planes, spans)
            self.device_busy = reader("idle_by_phase").busy_intervals(planes)


def flight(iteration, start, dur_ms, at_ms, waits_ms=(), **more):
    """An ``engine.flight`` slice; ``start`` on the trace's clock."""
    rec = dict(iter=iteration, t=start - OFFSET, dur_ms=dur_ms,
               at_ms=at_ms, waits_ms=[list(w) for w in waits_ms],
               wait_ms=sum(w[1] for w in waits_ms), lag_ms=0.5,
               evict_ms=0.0, gc_ms=0.0, **more)
    return {"name": "engine.flight", "ph": "X",
            "ts": int(rec["t"] * 1e6), "dur": int(dur_ms * 1e3), "args": rec}


def pause(start, end, generation=2):
    return {"name": "process.gc_pause", "ph": "X",
            "ts": int((start - OFFSET) * 1e6),
            "dur": int(round((end - start) * 1e6)),
            "args": {"generation": generation, "collected": 7}}


def planes(annotated=True, device=True):
    """A 3 s window (10..13) whose device idles in (10.5, 10.7), (11.0,
    11.4) and (12.5, 13.0): 1.1 s."""
    host = [(tr.WINDOW_NAME, 10.0, 13.0, {})]
    if annotated:
        host += [("engine.decode_burst", t, t + 0.001,
                  {"seq": i, "steps": 4,
                   "mono_us": int(round((t - OFFSET) * 1e6))})
                 for i, t in enumerate((10.05, 11.05, 12.05))]
    out = [FakePlane("/host:CPU", {"python": host})]
    if device:
        ops = [("fusion.1", 10.0, 10.5, {}), ("fusion.2", 10.7, 11.0, {}),
               ("fusion.1", 11.4, 12.0, {}), ("fusion.3", 12.0, 12.5, {}),
               # nested in the last: the intervals are merged
               ("fusion.4", 12.1, 12.2, {})]
        out.append(FakePlane("/device:TPU:0", {
            tr.OPS_LINE: ops,
            tr.MODULES_LINE: [("jit__decode_fn(1)", 10.0, 12.5, {})]}))
    return out


def journal():
    return [
        # the gap (10.5, 10.7) spans this iteration's dispatch (10.5-10.6,
        # the host at work) and its fetch (10.6-10.7, all of it a wait)
        flight(1, 10.4, 400.0,
               {"admit": 0.0, "prefill_dispatch": 50.0,
                "decode_dispatch": 100.0, "decode_fetch": 200.0,
                "process": 350.0, "segments": 380.0, "drain": 390.0},
               waits_ms=[(200.0, 100.0)]),
        # (11.0, 11.4): admit to 11.05, dispatch to 11.2, then a park
        flight(2, 10.9, 300.0, {"decode_dispatch": 150.0, "admit": 0.0}),
        # (12.5, 13.0): a park, 0.1 s of admit, a park with a collection
        flight(3, 12.6, 100.0, {"admit": 0.0}),
        pause(12.8, 12.85),
        pause(10.1, 10.2, generation=1),  # under a busy device: no idle
    ]


# ---- the loop's own share -----------------------------------------------------

def test_host_share_is_the_records_wall_less_their_waits():
    host = reader("loop_host")
    assert host.read(Ctx(journal()), what="host") == pytest.approx(
        100.0 * (300.0 + 300.0 + 100.0) / 800.0)
    # only the records that start inside the timed window
    late = journal() + [flight(4, 1014.5 + OFFSET, 1000.0, {"admit": 0.0})]
    assert host.read(Ctx(late), what="host") == pytest.approx(87.5)


def test_gc_pauses_over_the_windows_seconds():
    host = reader("loop_host")
    assert host.read(Ctx(journal()), what="gc") == pytest.approx(
        1000.0 * (0.05 + 0.1) / 5.0)
    # records with the split and no pause: "no pauses" is a reading
    quiet = [ev for ev in journal() if ev["name"] == "engine.flight"]
    assert host.read(Ctx(quiet), what="gc") == 0.0


def test_a_journal_from_before_the_split_reads_nothing():
    old = journal()
    for ev in old:
        ev["args"].pop("wait_ms", None)
    host = reader("loop_host")
    assert host.read(Ctx(old), what="host") is None
    assert host.read(Ctx(old), what="gc") is None
    assert reader("idle_by_phase").read(Ctx(old, planes())) is None
    assert host.read(Ctx([]), what="host") is None


# ---- idle time by phase ---------------------------------------------------------

def test_idle_seconds_go_to_the_phases_they_overlap():
    idle = reader("idle_by_phase")
    ctx = Ctx(journal(), planes())
    gaps = idle.idle_gaps(ctx.device_busy, ctx.dispatch_trace["window"])
    assert gaps == [(10.5, 10.7), (11.0, 11.4), (12.5, 13.0)]
    host = reader("loop_host")
    by, rows = idle.attribute(gaps, host.flight_records(ctx),
                              host.gc_pauses(ctx), OFFSET)
    gc_s = by.pop("gc")
    assert gc_s == pytest.approx(0.05)
    want = {"decode_dispatch": 0.1 + 0.15, "wait": 0.1,
            "admit": 0.05 + 0.1, "park": 0.2 + 0.4}
    assert sorted(by) == sorted(want)
    for what, seconds in want.items():
        assert by[what] == pytest.approx(seconds), what
    assert sum(by.values()) == pytest.approx(1.1)
    # a gap is named by where most of it lies
    named = {round(start, 1): (iteration, what)
             for _len, start, iteration, what, _rec in rows}
    assert named[11.0] == (None, "park")
    assert named[12.5] == (None, "park")
    assert named[10.5][0] == 1


def test_the_share_is_idle_under_host_work_over_all_idle(capsys):
    idle = reader("idle_by_phase")
    share = idle.read(Ctx(journal(), planes()))
    # dispatch 0.1 + admit and dispatch 0.2 + admit 0.1 + the collection
    # in the park 0.05; the wait and the parks are the traffic's
    assert share == pytest.approx(100.0 * 0.45 / 1.1)
    said = capsys.readouterr().out
    assert "largest residual" in said and "park" in said
    assert "iteration 1" in said and "lag_ms 0.5" in said


def test_what_the_ring_no_longer_holds_is_said_to_be_unrecorded():
    idle, host = reader("idle_by_phase"), reader("loop_host")
    late = [ev for ev in journal() if ev["args"].get("iter") != 1]
    late[0]["args"]["iter"] = 7
    ctx = Ctx(late, planes())
    gaps = idle.idle_gaps(ctx.device_busy, ctx.dispatch_trace["window"])
    by, _rows = idle.attribute(gaps, host.flight_records(ctx),
                               host.gc_pauses(ctx), OFFSET)
    assert by["unrecorded"] == pytest.approx(0.2)


def test_no_holes_is_a_reading():
    full = planes()
    full[1] = FakePlane("/device:TPU:0", {
        tr.OPS_LINE: [("fusion.1", 10.0, 12.9996, {})],
        tr.MODULES_LINE: [("jit__decode_fn(1)", 10.0, 13.0, {})]})
    assert reader("idle_by_phase").read(Ctx(journal(), full)) == 0.0


@pytest.mark.parametrize("missing", ["device", "annotated"])
def test_a_trace_without_device_planes_or_annotations_reads_nothing(missing):
    ctx = Ctx(journal(), planes(**{missing: False}))
    assert reader("idle_by_phase").read(ctx) is None


def test_the_six_entries_are_files_of_the_layer():
    import json

    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"].startswith(
        ("loop_host_share_pct.", "gc_pause_ms_per_s.",
         "idle_host_share_pct."))]
    assert {m["layer"] for m in mine} == {"scheduler + loop"}
    for m in mine:
        with open(os.path.join(READERS, m["name"] + ".json")) as f:
            spec = json.load(f)
        assert "counters" not in spec
        assert m["better"] == "lower" and m["workloads"]
        closed = m["name"].endswith(".closed")
        assert m["moves"] == ("out_tok_per_s" if closed else "ttft_p50_ms")
