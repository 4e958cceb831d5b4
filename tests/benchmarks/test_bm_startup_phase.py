"""``layer_metrics/startup_phase.py``, the reader under the nine ``setup_*``
per-layer metrics (ISSUE 40): every reduction on a recorded start-up
journal whose answers are known, what a program without the journal gives
(nothing, never 0), the nine entries against the contract, and then the
journal of a real start in this process (a tiny engine through
``cli._engine_backend``, as ``/healthz?trace=1`` exports it and
``run.fetch_spans`` keeps it) through ``run.read_layer_metrics`` and the
metrics' own files: all nine read.

(No stack of processes here, for test_bm_dispatch_rehearsal.py's reason;
the same through signal + serve + proxy: test_bm_startup_rehearsal.py.)"""

import asyncio
import json
import os
import time

import pytest

from benchmarks import run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(REPO, "benchmarks")
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

NINE = {
    "setup_to_ready_s": "s", "setup_imports_s": "s", "setup_backend_s": "s",
    "setup_build_s": "s", "setup_warmup_s": "s",
    "setup_lower_s_per_program": "s", "setup_programs": "programs",
    "setup_cache_misses": "programs", "setup_after_ready_s": "s",
}
#: Every cell's serve process writes the journal, so every cell's list
#: carries the nine, in ``workloads``' order: a PR that adds a cell appends
#: its name to the nine lists (benchmarks/README.md, "Adding a cell").
CELLS = [w["name"] for w in BENCH["workloads"]]


def reader():
    return run._reader(DATA, "startup_phase")


class Window:
    t0, t1 = 160.0, 208.0


class Ctx:
    def __init__(self, spans):
        self.spans, self.load = spans, Window


def span(name, t0, t1, **args):
    return {"name": name, "cat": "startup", "ph": "X", "pid": 1, "tid": 1002,
            "ts": int(t0 * 1e6), "dur": int((t1 - t0) * 1e6), "args": args}


def program(key, phase, lower, comp, hit, t0=133.0):
    return span("startup.program", t0, t0 + lower + comp, program="decode",
                key=key, phase=phase, trace_lower_s=lower, compile_s=comp,
                persistent_hit=hit, thread="warm-aot_0")


#: A start as the serve process of a 7B cell might write it: process start
#: at 100 s of the monotonic clock, ready at 150 s, the window at 160 s.
RECORDED = [
    span("startup.imports", 100.0, 120.0),
    span("startup.tokenizer", 120.0, 121.5, entries=32000),
    span("startup.backend", 121.5, 127.5, platform="tpu"),
    span("startup.engine_build", 127.5, 133.0),
    span("startup.aot", 133.0, 145.0, threads=4),
    program("decode[1024,8]", "aot", 0.75, 0.25, True),
    program("chunk[8,128,1024]", "aot", 0.5, 9.5, False),
    program("copy_in", "aot", 0.25, 0.0625, True),
    # the serial pass's run of a key the AOT phase compiled: not counted
    program("decode[1024,8]", "warmup", 0.0, 0.01, None, t0=145.0),
    span("startup.warmup", 133.0, 150.0),
    span("startup.process", 100.0, 150.0, clock="proc"),
    {"name": "startup.ready", "cat": "startup", "ph": "i", "s": "t",
     "pid": 1, "tid": 1002, "ts": 150_000_000, "args": {}},
    span("startup.tunnel", 150.25, 153.0),
    # the request path's records lie beside them and are not read
    {"name": "engine.decode_burst", "ph": "X", "ts": 170_000_000,
     "dur": 90_000, "args": {"seq": 1, "steps": 8}},
]

KNOWN = {
    "setup_to_ready_s": 50.0,
    "setup_imports_s": 21.5,          # imports + tokenizer
    "setup_backend_s": 6.0,
    "setup_build_s": 5.5,
    "setup_warmup_s": 17.0,
    "setup_lower_s_per_program": 0.5,  # (0.75 + 0.5 + 0.25) / 3
    "setup_programs": 3,
    "setup_cache_misses": 1,
    "setup_after_ready_s": 10.0,       # the window's 160 less ready's 150
}


def read(name, spans):
    spec = run.metric_spec(DATA, name)
    assert spec["reader"] == "startup_phase"
    return reader().read(Ctx(spans), **spec["args"])


@pytest.mark.parametrize("name", sorted(NINE))
def test_every_reduction_on_a_recorded_journal(name):
    assert read(name, RECORDED) == pytest.approx(KNOWN[name])


@pytest.mark.parametrize("name", sorted(NINE))
def test_a_program_without_the_journal_gives_nothing_not_zero(name):
    """The parent of the PR that added the journal: the request path's
    records and no ``startup.*`` span."""
    assert read(name, [RECORDED[-1]]) is None
    assert read(name, []) is None


def test_the_parts_add_up_to_the_whole_on_the_recorded_journal():
    parts = sum(KNOWN[n] for n in ("setup_imports_s", "setup_backend_s",
                                   "setup_build_s", "setup_warmup_s"))
    assert parts >= 0.97 * KNOWN["setup_to_ready_s"]


def test_a_start_with_no_tokenizer_reads_its_imports_alone():
    spans = [ev for ev in RECORDED if ev["name"] != "startup.tokenizer"]
    assert read("setup_imports_s", spans) == pytest.approx(20.0)


def test_with_no_aot_phase_the_serial_passs_records_are_read():
    """A deployment without TUNNEL_WARMUP_PAR compiles in its serial
    pass: those records carry the parts then."""
    spans = [span("startup.process", 100.0, 150.0),
             program("decode[1024,8]", "warmup", 0.75, 5.0, False),
             program("chunk[8,128,1024]", "warmup", 0.25, 4.0, False)]
    assert read("setup_programs", spans) == 2
    assert read("setup_lower_s_per_program", spans) == pytest.approx(0.5)
    assert read("setup_cache_misses", spans) == 2


def test_a_warm_start_reads_zero_misses_and_a_silent_jax_reads_nothing():
    warm = [program("decode[1024,8]", "aot", 0.75, 0.25, True),
            program("copy_in", "aot", 0.25, 0.0625, True)]
    assert read("setup_cache_misses", warm) == 0
    silent = [program("decode[1024,8]", "aot", 0.75, 0.25, None)]
    assert read("setup_cache_misses", silent) is None
    assert read("setup_programs", silent) == 1


def test_an_unknown_quantity_is_an_error():
    with pytest.raises(ValueError, match="unknown quantity"):
        reader().read(Ctx(RECORDED), "no_such_quantity")


# ---- the nine entries against the contract -----------------------------------

@pytest.mark.parametrize("name", sorted(NINE))
def test_each_of_the_nine_is_declared_for_every_cell(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": NINE[name], "better": "lower",
                     "source": "program_span", "layer": "start-up",
                     "moves": "setup_s", "workloads": CELLS}
    spec = run.metric_spec(DATA, name)
    assert set(spec) == {"reader", "args", "layer", "source", "unit",
                         "moves"}
    assert "counters" not in spec    # WINDOW_COUNTERS stay what a cell reads


def test_the_nine_are_of_what_moves_setup_s_and_every_cell_reads_them():
    """What moves ``setup_s`` is the start-up layer's and nothing else is,
    wherever the entries stand in ``per_layer`` and whatever a later PR
    appends after them; the nine are among them, in every cell's list."""
    moving = {m["name"] for m in BENCH["per_layer"]
              if m["moves"] == "setup_s"}
    assert moving == {m["name"] for m in BENCH["per_layer"]
                      if m["layer"] == "start-up"}
    assert set(NINE) <= moving
    for cell in CELLS:
        mine = {m["name"] for m in run.metrics_of(BENCH, "per_layer", cell)}
        assert set(NINE) <= mine


# ---- a real start's journal through run.py's own path ------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """(events as run.fetch_spans keeps them, the window's start): a tiny
    engine started through the CLI's own path with the threaded AOT phase
    and a compile cache, as the cells' serve processes are."""
    import jax
    from jax._src import compilation_cache

    import p2p_llm_tunnel_tpu.cli as cli_mod
    from p2p_llm_tunnel_tpu.utils.flight import global_compile_watch

    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("compile-cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    patch = pytest.MonkeyPatch()
    patch.setenv("TUNNEL_WARMUP_PAR", "2")
    patch.setenv("TUNNEL_WARMUP_VIEW_CAP", "256")

    async def main():
        global_compile_watch.reset()
        cli_mod._BACKEND = None
        cli_mod._ENGINES.clear()
        global_compile_watch.process_began(time.monotonic())
        args = cli_mod.build_parser().parse_args(
            ["serve", "--room", "r", "--backend", "tpu", "--model", "tiny",
             "--slots", "4", "--max-seq", "256"])
        await cli_mod._engine_backend(args)
        engine = cli_mod._ENGINES[0]
        plan = len(engine.warmup_plan())
        await engine.stop()
        return plan

    try:
        plan = asyncio.run(main())
        events = [ev for ev in global_compile_watch.chrome_events()
                  if ev.get("ph") in ("X", "i")]
    finally:
        patch.undo()
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min)
        compilation_cache.reset_cache()
        cli_mod._BACKEND = None
        cli_mod._ENGINES.clear()
        global_compile_watch.reset()
    return events, time.monotonic() + 0.5, plan


@pytest.mark.parametrize("cell", CELLS)
def test_all_nine_read_in_every_cells_list_on_a_real_starts_journal(
        rehearsal, cell):
    events, t0, plan = rehearsal
    ctx = run.Context()
    ctx.cell, ctx.spans = cell, events
    ctx.load = type("Window", (), {"t0": t0, "t1": t0 + 3.0})
    wanted = [m for m in run.metrics_of(BENCH, "per_layer", cell)
              if m["name"] in NINE]
    got = run.read_layer_metrics(ctx, wanted, DATA)
    assert set(got) == set(NINE)
    for name, unit in NINE.items():
        assert got[name]["unit"] == unit
        assert got[name]["value"] is not None
    value = {k: v["value"] for k, v in got.items()}
    parts = (value["setup_imports_s"] + value["setup_backend_s"]
             + value["setup_build_s"] + value["setup_warmup_s"])
    assert 0.97 * value["setup_to_ready_s"] <= parts
    assert parts <= value["setup_to_ready_s"] + 1e-3
    # the plan plus the two copy programs; the AOT records, the planned
    # programs', none of them in the empty cache
    assert value["setup_programs"] == plan + 2
    assert value["setup_cache_misses"] == plan
    assert 0.0 < value["setup_lower_s_per_program"] < 30.0
    assert 0.0 < value["setup_after_ready_s"] < 60.0
