"""The reduction from a device trace to busy time, operations, gaps and
programs: on hand-made planes whose answers are known, and on a recorded
trace."""

import json
import os

import pytest

from benchmarks import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def planes(ops, modules, window=(10.0, 13.0), device="/device:TPU:0"):
    return {
        "/host:CPU": {"bench-trace": [(tr.WINDOW_NAME, window[0], window[1])]},
        device: {tr.OPS_LINE: ops, tr.MODULES_LINE: modules},
    }


def test_merge_joins_overlapping_intervals():
    assert tr.merge([(0, 1), (0.5, 2), (3, 4), (4, 5), (7, 8)]) == \
        [(0, 2), (3, 5), (7, 8)]
    assert tr.merge([]) == []
    assert tr.merge([(0, 5), (1, 2)]) == [(0, 5)]


def test_clip_cuts_events_to_the_window():
    evs = [("a", 0.0, 1.0), ("b", 0.5, 2.5), ("c", 2.9, 3.5), ("d", 4, 5)]
    assert tr.clip(evs, (1.0, 3.0)) == [("b", 1.0, 2.5), ("c", 2.9, 3.0)]


def test_busy_is_the_union_clipped_to_the_window_so_edge_gaps_count():
    ops = [("f1", 9.0, 10.5),      # starts before the window: 0.5 counts
           ("f2", 10.4, 11.0),     # overlaps f1: the union, not the sum
           ("f3", 12.0, 12.5)]     # then idle to the window's end
    out = tr.reduce(planes(ops, []))
    assert out["window_s"] == pytest.approx(3.0)
    assert out["busy_s"] == pytest.approx(1.0 + 0.5)
    # gaps: 11.0-12.0 inside, 12.5-13.0 at the edge
    lengths = sorted(g[1] for g in out["idle_gaps"])
    assert lengths == [pytest.approx(0.5), pytest.approx(1.0)]


def test_names_are_cut_to_instruction_and_result_type():
    hlo = ("%fusion.276 = bf16[33,1,4096]{2,0,1:T(8,128)(2,1)S(1)} "
           "fusion(bf16[33,1,4096]{2,0,1} %get-tuple-element.1829)")
    assert tr.short_name(hlo) == "fusion.276 bf16[33,1,4096]"
    assert tr.short_name("%while.26 = (s32[]{:T(128)}, bf16[3]) while(...)") \
        == "while.26"
    assert tr.short_name("copy.3") == "copy.3"


def test_a_loop_is_counted_without_what_runs_inside_it():
    ops = [("while.1", 10.0, 12.0), ("fusion.1", 10.0, 10.5),
           ("fusion.2", 10.5, 11.9), ("fusion.1", 12.0, 12.25)]
    own = tr.self_times(ops)
    assert own["while.1"] == pytest.approx(0.1)
    assert own["fusion.1"] == pytest.approx(0.75)
    assert own["fusion.2"] == pytest.approx(1.4)
    out = tr.reduce(planes(ops, []))
    assert out["busy_s"] == pytest.approx(2.25)  # the union, nesting or not
    assert [n for n, _ in out["device_ops"]][:2] == ["fusion.2", "fusion.1"]


def test_top_operations_by_device_time():
    ops = [("fusion.1", 10.0, 10.2), ("fusion.2", 10.2, 10.3),
           ("fusion.1", 11.0, 11.4)]
    out = tr.reduce(planes(ops, []))
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.6)]
    assert out["device_ops"][1] == ["fusion.2", pytest.approx(0.1)]


def test_programs_count_runs_time_and_body_passes():
    # one decode program run: 2 steps x 3 layers of a body op, one op once
    body = [("fusion.7", 10.0 + 0.01 * i, 10.005 + 0.01 * i) for i in range(6)]
    ops = body + [("sample.1", 10.07, 10.08)]
    modules = [("jit__decode_fn(123)", 10.0, 10.1),
               ("jit__decode_fn(123)", 12.95, 13.2),   # cut by the edge
               ("jit__chunk_prefill_fn(9)", 11.0, 11.5)]
    out = tr.reduce(planes(ops, modules))
    dec = out["programs"]["jit__decode_fn"]
    assert dec["runs"] == 1 and dec["device_s"] == pytest.approx(0.1)
    assert dec["body_runs"] == 6
    assert out["programs"]["jit__chunk_prefill_fn"]["runs"] == 1


def test_gaps_are_named_by_the_programs_around_them():
    ops = [("a", 10.0, 10.5), ("b", 11.5, 13.0)]
    modules = [("jit__decode_fn(1)", 10.0, 10.5),
               ("jit__chunk_prefill_fn(2)", 11.5, 13.0)]
    out = tr.reduce(planes(ops, modules))
    assert out["idle_gaps"][0][0] == \
        "after jit__decode_fn / before jit__chunk_prefill_fn"
    assert out["idle_gaps"][0][1] == pytest.approx(1.0)


def test_two_devices_average_their_busy_time():
    p = planes([("a", 10.0, 12.0)], [])
    p["/device:TPU:1"] = {tr.OPS_LINE: [("a", 10.0, 11.0)],
                          tr.MODULES_LINE: []}
    out = tr.reduce(p)
    assert out["busy_s"] == pytest.approx(1.5)
    assert out["devices"] == ["/device:TPU:0", "/device:TPU:1"]


def test_no_device_plane_gives_no_device_number():
    out = tr.reduce({"/host:CPU": {"t": [(tr.WINDOW_NAME, 1.0, 3.0)]}})
    assert out["busy_s"] is None and out["window_s"] == pytest.approx(2.0)
    assert out["programs"] == {} and out["device_ops"] == []


def test_no_trace_file_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.newest_xplane(str(tmp_path))


# ---- recorded traces --------------------------------------------------------

def recorded():
    import gzip

    with gzip.open(os.path.join(DATA, "chip_trace_excerpt.json.gz"), "rt") as f:
        doc = json.load(f)
    return {p: {line: [tuple(e) for e in evs] for line, evs in lines.items()}
            for p, lines in doc["planes"].items()}


def test_recorded_chip_trace_programs_and_steps():
    """0.26 s of mistral-7b.chat-open on the chip: two decode runs of four
    steps each with one chunk prefill between them."""
    out = tr.reduce(recorded())
    dec = out["programs"]["jit__decode_fn"]
    assert dec["runs"] == 2
    assert dec["body_runs"] == 2 * 4 * 32       # runs x steps x layers
    assert dec["device_s"] == pytest.approx(0.16549131, rel=1e-6)
    chunk = out["programs"]["jit__chunk_prefill_fn"]
    assert chunk["runs"] == 1 and chunk["body_runs"] == 32
    assert chunk["device_s"] == pytest.approx(0.08788923, rel=1e-6)


def test_recorded_chip_trace_busy_share_and_names():
    out = tr.reduce(recorded())
    assert out["window_s"] == pytest.approx(0.258427199)
    assert 0.999 < out["busy_s"] / out["window_s"] <= 1.0
    names = [n for n, _ in out["device_ops"]]
    assert "constant_dynamic-slice_fusion.14 bf16[1,33,1024,8,128]" in names
    assert all(len(n) <= 80 for n in names)
    # self times never add up to more than the busy time
    assert sum(s for _, s in out["device_ops"]) <= out["busy_s"]
    assert all(g[1] < 1e-5 for g in out["idle_gaps"])


def test_the_program_time_reader_on_the_recorded_trace():
    import importlib.util

    path = os.path.join(os.path.dirname(DATA), "..", "..", "benchmarks",
                        "layer_metrics", "program_time.py")
    spec = importlib.util.spec_from_file_location("program_time", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    class Ctx:
        trace = tr.reduce(recorded())
        polls, trace_span = [], None

    assert reader.read(Ctx, ["_no_such_program"], "ktok") is None
    assert reader.read(Ctx, ["_chunk_prefill_fn"], "ktok") is None  # no polls
    # 87.9 ms of chunk prefill over the 250 prompt tokens the polls give
    Ctx.trace_span = (10.0, 13.0)
    Ctx.polls = [(9.0, {"engine_prefill_tokens_total": 1000.0}),
                 (14.0, {"engine_prefill_tokens_total": 1000.0 + 250 * 5 / 3})]
    assert reader.read(Ctx, ["_chunk_prefill_fn"], "ktok") == \
        pytest.approx(87.88923 / 0.25)
    with pytest.raises(ValueError, match="unknown divisor"):
        reader.read(Ctx, ["_decode_fn"], "step")   # retired with its metrics


def test_prompt_tokens_between_the_trace_edges_are_interpolated():
    import importlib.util

    path = os.path.join(os.path.dirname(DATA), "..", "..", "benchmarks",
                        "layer_metrics", "program_time.py")
    spec = importlib.util.spec_from_file_location("program_time", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    class Ctx:
        polls = [(10.0, {"c": 100.0}), (11.0, {"c": 200.0}),
                 (12.0, {"c": 200.0}), (13.0, {"c": 500.0})]
        trace_span = (10.5, 12.5)

    assert reader.counter_between(Ctx, "c") == pytest.approx(350.0 - 150.0)
    Ctx.trace_span = (9.0, 12.0)   # no poll before the edge
    assert reader.counter_between(Ctx, "c") is None
    Ctx.trace_span = (10.5, 12.5)  # a counter the program does not publish
    assert reader.counter_between(Ctx, "no_such_counter") is None


def test_reading_a_recorded_xplane_file(tmp_path):
    """A trace written by ``jax.profiler`` (here on the CPU, Python tracer
    off, with the wrapper's annotation): the file is found, read, and the
    window's edges are the annotation's."""
    import shutil

    run_dir = tmp_path / "plugins" / "profile" / "2026_01_01"
    run_dir.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "cpu_window.xplane.pb"),
                run_dir / "host.xplane.pb")
    path = tr.newest_xplane(str(tmp_path))
    planes_read = tr.read_planes(path)
    assert "/host:CPU" in planes_read
    window = tr.find_window(planes_read)
    assert window[1] - window[0] == pytest.approx(0.360393728)
    out = tr.reduce(planes_read)
    assert out["busy_s"] is None and out["programs"] == {}
    summary = tmp_path / "out.json"
    assert tr.main(str(tmp_path), str(summary)) == 0
    assert json.loads(summary.read_text())["window_s"] == \
        pytest.approx(0.360393728)
