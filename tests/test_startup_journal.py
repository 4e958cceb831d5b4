"""The start-up journal on a real (tiny, CPU) engine started the way ``tunnel
serve --backend tpu`` starts one: ``cli._engine_backend`` twice in this
process against one temporary compile-cache directory, the threaded AOT
phase on, then the operators' surfaces over a loopback tunnel (ISSUE 40).

One module-scoped pair of starts (about 20 s); every test reads what they
left.  The journal's own logic, without an engine: tests/test_flight.py."""

from __future__ import annotations

import asyncio
import json
import os
import time

import pytest

import p2p_llm_tunnel_tpu.cli as cli_mod
from p2p_llm_tunnel_tpu.endpoints.serve import run_serve
from p2p_llm_tunnel_tpu.testing.frame_client import FrameClient
from p2p_llm_tunnel_tpu.transport import loopback_pair
from p2p_llm_tunnel_tpu.utils.flight import (
    STARTUP_PHASES,
    STARTUP_SCHEMA,
    global_compile_watch,
)
from p2p_llm_tunnel_tpu.utils.metrics import global_metrics
from p2p_llm_tunnel_tpu.utils.tracing import (
    SPAN_CATALOG,
    global_tracer,
    validate_chrome_trace,
)

ARGS = ["serve", "--room", "r", "--backend", "tpu", "--model", "tiny",
        "--slots", "4", "--max-seq", "256"]


def _write_tokenizer(path: str) -> str:
    """A word-level file of ByteTokenizer's 259 entries, so both starts
    build the same programs (benchmarks/stack.py ``write_tokenizer``'s
    layout: what every benchmark cell hands ``--tokenizer``)."""
    from tokenizers import Tokenizer, models, pre_tokenizers

    tok = Tokenizer(models.WordLevel({f"w{i}": i for i in range(259)},
                                     unk_token="w0"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.save(os.path.join(path, "tokenizer.json"))
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast"}, f)
    return path


async def _one_start(out: dict, extra_args=()) -> None:
    """What ``cli.main`` -> ``_serve_once`` does up to the tunnel, then
    the serve loop over a loopback channel with the first session's
    ``tunnel_t0``."""
    global_compile_watch.reset()
    cli_mod._BACKEND = None
    cli_mod._ENGINES.clear()
    global_compile_watch.process_began(time.monotonic())
    args = cli_mod.build_parser().parse_args(ARGS + list(extra_args))
    backend = await cli_mod._engine_backend(args)
    engine = cli_mod._ENGINES[0]
    out["plan"] = [cli_mod_key(kind, shape)
                   for kind, shape in engine.warmup_plan()]
    out["has_pool"] = engine._prefix is not None
    tunnel_t0 = time.monotonic()
    serve_ch, client_ch = loopback_pair()
    serve_task = asyncio.create_task(
        run_serve(serve_ch, backend=backend, tunnel_t0=tunnel_t0))
    client = FrameClient(client_ch)
    try:
        await client.handshake(timeout=10.0)
        # one request through the engine: tracing is off, so the request
        # path must leave the span rings as they were
        r = await client.wait(await client.request(
            "POST", "/api/generate",
            {"model": "tiny", "prompt": "hello there", "stream": False,
             "options": {"num_predict": 4}}), 60.0)
        out["generate_status"] = r.status
        out["ring_after_request"] = len(global_tracer.records())
        h = await client.wait(await client.request("GET", "/healthz"), 10.0)
        out["healthz"] = json.loads(h.text)
        t = await client.wait(
            await client.request("GET", "/healthz?trace=1"), 10.0)
        out["trace"] = json.loads(t.text)
    finally:
        client.close()
        serve_task.cancel()
        serve_ch.close()
        await asyncio.gather(serve_task, return_exceptions=True)
        await engine.stop()
    out["records"] = global_compile_watch.startup_records()
    out["section"] = global_compile_watch.startup_section()
    out["events"] = global_compile_watch.events()
    out["cold"] = global_metrics.counter("engine_cold_compiles_total")


def cli_mod_key(kind, shape) -> str:
    from p2p_llm_tunnel_tpu.engine.engine import _program_key

    return _program_key(kind, shape)


@pytest.fixture(scope="module")
def starts(tmp_path_factory):
    """{"first": ..., "second": ...}: two starts of one configuration in
    one process against one compile-cache directory that begins empty; the
    second with a ``--tokenizer`` directory of the byte tokenizer's size."""
    import jax
    from jax._src import compilation_cache

    cache = str(tmp_path_factory.mktemp("compile-cache"))
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    patch = pytest.MonkeyPatch()
    patch.setenv("TUNNEL_WARMUP_PAR", "2")
    patch.setenv("TUNNEL_WARMUP_VIEW_CAP", "256")
    out = {"first": {}, "second": {}, "cache": cache}
    global_tracer.configure(enabled=False)
    global_tracer.clear()
    try:
        asyncio.run(_one_start(out["first"]))
        out["cache_files"] = len(os.listdir(cache))
        tok_dir = _write_tokenizer(str(tmp_path_factory.mktemp("tokenizer")))
        asyncio.run(_one_start(out["second"], ["--tokenizer", tok_dir]))
    finally:
        patch.undo()
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min)
        compilation_cache.reset_cache()
        cli_mod._BACKEND = None
        cli_mod._ENGINES.clear()
        global_compile_watch.reset()
    return out


def spans(start, name):
    return [r for r in start["records"] if r["name"] == name]


def one(start, name):
    (rec,) = spans(start, name)
    return rec


@pytest.mark.parametrize("which", ["first", "second"])
def test_the_phases_tile_the_process(starts, which):
    """In order, no overlap, gaps under 50 ms in sum: what the four
    setup_*_s metrics add up to is the whole of setup_to_ready_s."""
    start = starts[which]
    process = one(start, "startup.process")
    ready = one(start, "startup.ready")
    assert process["attrs"]["clock"] in ("proc", "cli.main")
    assert ready["dur"] is None
    assert ready["ts"] == pytest.approx(process["ts"] + process["dur"])
    if which == "first":
        # no tokenizer was given: that phase is absent, not zero
        tiles = [one(start, n) for n in STARTUP_PHASES
                 if n != "startup.tokenizer"]
        assert spans(start, "startup.tokenizer") == []
    else:
        tiles = [one(start, n) for n in STARTUP_PHASES]
    assert tiles[0]["ts"] == process["ts"]
    gaps = 0.0
    for before, after in zip(tiles, tiles[1:]):
        gap = after["ts"] - (before["ts"] + before["dur"])
        assert gap >= -1e-6, (before["name"], after["name"], gap)
        gaps += gap
    gaps += ready["ts"] - (tiles[-1]["ts"] + tiles[-1]["dur"])
    assert 0.0 <= gaps < 0.05
    assert sum(r["dur"] for r in tiles) >= 0.97 * process["dur"]


@pytest.mark.parametrize("parent,children", [
    ("startup.engine_build", ["startup.params", "startup.cache_alloc"]),
    ("startup.warmup", ["startup.aot", "startup.execute",
                        "startup.prefix_warm"]),
])
def test_children_lie_inside_their_parent_in_order(starts, parent, children):
    start = starts["first"]
    outer = one(start, parent)
    inner = [one(start, n) for n in children]
    assert inner[0]["ts"] >= outer["ts"] - 1e-6
    for before, after in zip(inner, inner[1:]):
        assert after["ts"] >= before["ts"] + before["dur"] - 1e-6
    assert (inner[-1]["ts"] + inner[-1]["dur"]
            <= outer["ts"] + outer["dur"] + 1e-6)


def test_the_phases_carry_their_attrs(starts):
    start = starts["first"]
    backend = one(start, "startup.backend")["attrs"]
    assert backend == {"platform": "cpu", "device_kind": "cpu",
                       "devices": backend["devices"]}
    params = one(start, "startup.params")["attrs"]
    assert params["source"] == "random" and params["quant"] == "none"
    assert params["bytes"] > 100_000
    cache = one(start, "startup.cache_alloc")["attrs"]
    assert cache["bytes"] > 100_000
    assert one(start, "startup.aot")["attrs"] == {"threads": 2}
    assert one(start, "startup.execute")["attrs"] == {}
    for rec in start["records"]:
        assert rec["name"] in SPAN_CATALOG
        assert set(rec["attrs"]) <= set(STARTUP_SCHEMA)


def test_the_tokenizer_span_names_the_loader_that_engaged(starts):
    """ISSUE 41: a directory with tokenizer.json is read with the tokenizers
    library alone, and the span says so beside the entries."""
    rec = one(starts["second"], "startup.tokenizer")
    assert rec["attrs"] == {"entries": 259, "loader": "tokenizers"}
    assert rec["dur"] > 0.0
    section = starts["second"]["healthz"]["startup"]
    assert section["phases_s"]["startup.tokenizer"] == pytest.approx(
        rec["dur"], abs=1e-3)


@pytest.mark.parametrize("phase,parent", [("aot", "startup.aot"),
                                          ("warmup", "startup.warmup")])
def test_one_program_record_a_planned_key_and_copy_program(starts, phase,
                                                           parent):
    """setup_programs' rule: the plan's keys once each under the AOT phase
    and again under the serial pass, whose records the two copy programs'
    are (the AOT phase's ``_one`` stays what it was before the journal)."""
    start = starts["first"]
    assert start["has_pool"]
    recs = [r for r in spans(start, "startup.program")
            if r["attrs"]["phase"] == phase]
    keys = [r["attrs"]["key"] for r in recs]
    copies = ["copy_in", "copy_out"] if phase == "warmup" else []
    assert sorted(keys) == sorted(start["plan"] + copies)
    outer = one(start, parent)
    for r in recs:
        assert r["ts"] >= outer["ts"] - 1e-3
        assert r["ts"] + r["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert start["section"]["programs"] == len(start["plan"]) + 2


def test_a_programs_two_parts_add_up_to_its_record(starts):
    """``thunk()`` and ``.compile()`` timed apart: Python's part and
    XLA's, within 5 % of the record's duration; two threads compiled."""
    recs = [r for r in spans(starts["first"], "startup.program")
            if r["attrs"]["phase"] == "aot"]
    for r in recs:
        a = r["attrs"]
        assert a["trace_lower_s"] > 0 and a["compile_s"] > 0
        assert a["trace_lower_s"] + a["compile_s"] == pytest.approx(
            r["dur"], rel=0.05, abs=0.002), a["key"]
        assert a["aot_hit"] is False and a["cold"] is False
    assert {r["attrs"]["thread"] for r in recs} == {"warm-aot_0",
                                                    "warm-aot_1"}
    # the serial pass found every planned key compiled by this process
    serial = [r["attrs"] for r in spans(starts["first"], "startup.program")
              if r["attrs"]["phase"] == "warmup"
              and r["attrs"]["program"] != "copy"]
    assert serial and all(a["aot_hit"] for a in serial)


def test_a_second_start_reads_the_compile_cache_where_the_first_missed(
        starts):
    first, second = starts["first"]["section"], starts["second"]["section"]
    if not starts["cache_files"]:
        pytest.skip("this backend wrote nothing to the compile cache")
    planned = first["programs"] - 2      # the AOT phase's records
    assert first["persistent_misses"] == planned > 0
    assert first["persistent_hits"] == 0
    assert second["persistent_misses"] == 0
    assert second["persistent_hits"] == planned
    assert second["programs"] == first["programs"]


def test_the_journal_costs_a_start_under_a_tenth_of_a_second(starts):
    """Its own records say so: about sixty of them, each a clock read and
    a list append under a lock; timed here at a hundred times that."""
    from p2p_llm_tunnel_tpu.utils import flight
    from p2p_llm_tunnel_tpu.utils.flight import CompileWatch

    n = len(starts["first"]["records"])
    assert 30 <= n <= 100
    cw = CompileWatch()
    cw.listen(True)
    t0 = time.perf_counter()
    for _ in range(50):
        for i in range(n):
            for _event in range(100):   # a program's worth of JAX's events
                flight._on_jax_event(flight._EV_TRACE, 0.0005)
            flight._on_jax_event(flight._EV_COMPILE, 0.05)
            cw.note(program="decode", key=f"decode[{i}]", shape=[i],
                    seconds=0.1, phase="aot")
        cw.reset()
    cw.listen(False)
    assert (time.perf_counter() - t0) / 50 < 0.1


def test_with_tracing_off_the_request_path_records_nothing(starts):
    for which in ("first", "second"):
        assert starts[which]["generate_status"] == 200
        assert starts[which]["ring_after_request"] == 0
        assert starts[which]["cold"] == starts["first"]["cold"]
        assert not any(e["cold"] for e in starts[which]["events"])


def test_healthz_has_the_startup_section_without_trace(starts):
    section = starts["second"]["healthz"]["startup"]
    assert section["ready"] is True
    assert set(section) == {"ready", "to_ready_s", "phases_s", "programs",
                            "persistent_hits", "persistent_misses",
                            "slowest_program"}
    assert section["programs"] == len(starts["second"]["plan"]) + 2
    assert section["to_ready_s"] == section["phases_s"]["startup.process"]
    for name in ("startup.imports", "startup.backend",
                 "startup.engine_build", "startup.warmup", "startup.aot",
                 "startup.execute"):
        assert section["phases_s"][name] >= 0.0
    assert set(section["slowest_program"]) == {"key", "seconds"}
    # the first session's handshake was timed, after ready
    tunnel = one(starts["second"], "startup.tunnel")
    ready = one(starts["second"], "startup.ready")
    assert tunnel["ts"] >= ready["ts"] and 0.0 < tunnel["dur"] < 10.0
    # the warm-up's wall total keeps its gauge and its /healthz key
    assert starts["second"]["healthz"]["warmup_compile_s"] >= 0.0


def test_healthz_trace_export_carries_the_startup_lane(starts):
    """As run.py's fetch_spans keeps it: ``ph`` X or i, ``ts`` / ``dur``
    in µs of the monotonic clock, attrs under ``args``."""
    trace = starts["second"]["trace"]
    assert validate_chrome_trace(trace)
    lanes = [e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "thread_name"]
    assert "startup" in lanes
    kept = [e for e in trace["traceEvents"] if e.get("ph") in ("X", "i")
            and e["name"].startswith("startup.")]
    names = {e["name"] for e in kept}
    assert {"startup.process", "startup.ready", "startup.imports",
            "startup.backend", "startup.engine_build", "startup.warmup",
            "startup.program", "startup.tunnel"} <= names
    process = next(e for e in kept if e["name"] == "startup.process")
    rec = one(starts["second"], "startup.process")
    assert process["ts"] == int(rec["ts"] * 1e6)
    assert process["dur"] == int(rec["dur"] * 1e6)
    assert process["args"]["clock"] == rec["attrs"]["clock"]
