"""Tunnel-wide request tracing (ISSUE 6): context propagation, the span
journal, Chrome-trace export, /metrics exposition, and tail percentiles.

Three layers, matching where the machinery lives:
- pure recorder/registry logic (utils/tracing.py, utils/metrics.py) — no
  asyncio, no JAX;
- serve-endpoint surfaces over a loopback channel with a FAKE backend
  (/metrics exposition, /healthz?trace=1, span parenting across the
  header rewrite) — fast;
- engine-backed behavior: a 32-client mux herd whose every request's
  spans chain proxy -> serve -> engine under one propagated trace id, and
  a seeded-chaos topology-determinism run — JAX compiles, slow.
"""

from __future__ import annotations

import asyncio
import contextlib
import importlib.util
import json
import os
from pathlib import Path

import pytest

from p2p_llm_tunnel_tpu.endpoints.serve import run_serve
from p2p_llm_tunnel_tpu.testing.frame_client import FrameClient
from p2p_llm_tunnel_tpu.transport import loopback_pair
from p2p_llm_tunnel_tpu.utils.metrics import (
    METRICS_CATALOG,
    Metrics,
    _Percentiles,
    global_metrics,
)
from p2p_llm_tunnel_tpu.utils.tracing import (
    SPAN_CATALOG,
    TRACE_HEADER,
    TraceContext,
    TraceRecorder,
    global_tracer,
    mint_trace_id,
    new_span_id,
    parse_trace_context,
    validate_chrome_trace,
)

REPO_ROOT = Path(__file__).resolve().parents[1]
TID = "deadbeef" * 4


@contextlib.contextmanager
def tracing_on(sample: float = 1.0, capacity: int = 16384):
    """Enable the process-wide recorder for one test, restore after."""
    global_tracer.clear()
    global_tracer.configure(enabled=True, sample=sample, capacity=capacity)
    try:
        yield global_tracer
    finally:
        global_tracer.configure(enabled=False, sample=1.0)
        global_tracer.clear()


# ---------------------------------------------------------------------------
# trace context: header contract
# ---------------------------------------------------------------------------

def test_header_roundtrip():
    tid = mint_trace_id()
    ctx = TraceContext(tid, "00ab")
    parsed = parse_trace_context({TRACE_HEADER: ctx.header_value()})
    assert parsed == ctx
    # Case-insensitive header key, like the deadline header.
    assert parse_trace_context({"X-Tunnel-Trace": f"{tid}/1"}).trace_id == tid


@pytest.mark.parametrize("bad", [
    "", "no-slash", "/orphan", "GHIJ/1", "spaces here/1",
])
def test_malformed_header_is_ignored(bad):
    assert parse_trace_context({TRACE_HEADER: bad}) is None


def test_mint_trace_id_unique_and_hex():
    ids = {mint_trace_id() for _ in range(64)}
    assert len(ids) == 64
    assert all(len(t) == 32 and int(t, 16) >= 0 for t in ids)


# ---------------------------------------------------------------------------
# recorder: off by default, bounded, sampled
# ---------------------------------------------------------------------------

def test_recorder_disabled_by_default_records_nothing():
    rec = TraceRecorder()
    assert rec.add_span("engine.request", trace_id=TID, t0=0.0) is None
    rec.add_event("engine.first_token", trace_id=TID)
    assert rec.records() == []
    # The process-wide default is off too (production default).
    assert not global_tracer.enabled


def test_ring_buffer_stays_bounded():
    rec = TraceRecorder(capacity=8, enabled=True)
    for i in range(40):
        rec.add_span("engine.request", trace_id=TID, t0=float(i),
                     t1=float(i) + 0.5)
    recs = rec.records()
    assert len(recs) == 8
    assert recs[0].ts == 32.0  # oldest half dropped, recency kept


def test_engine_scope_firehose_cannot_evict_request_chains():
    """Engine-scope records (trace_id=None) ignore the sampling knob and
    fire every loop iteration; they get their own quarter-sized ring so a
    rare sampled request chain survives the unsampled firehose."""
    rec = TraceRecorder(capacity=64, enabled=True)
    rec.add_span("engine.request", trace_id=TID, t0=0.0, t1=1.0)
    for i in range(10_000):
        rec.add_span("engine.decode_burst", trace_id=None, t0=float(i),
                     t1=float(i) + 0.1, track="engine-loop")
    recs = rec.records()
    assert any(r.trace_id == TID for r in recs)
    assert sum(1 for r in recs if r.trace_id is None) <= 16  # cap // 4


def test_sampling_is_deterministic_per_trace_id():
    full = TraceRecorder(enabled=True, sample=1.0)
    none = TraceRecorder(enabled=True, sample=0.0)
    half_a = TraceRecorder(enabled=True, sample=0.5)
    half_b = TraceRecorder(enabled=True, sample=0.5)
    ids = [mint_trace_id() for _ in range(64)]
    assert all(full.on(t) for t in ids)
    assert not any(none.on(t) for t in ids)
    picks = [half_a.on(t) for t in ids]
    assert picks == [half_b.on(t) for t in ids]  # layer-independent verdict
    assert 0 < sum(picks) < len(ids)
    # Engine-scope records follow `enabled` only.
    assert none.on(None)


def test_chrome_trace_validates_and_carries_track_metadata():
    rec = TraceRecorder(enabled=True)
    root = rec.add_span("proxy.request", trace_id=TID, t0=1.0, t1=2.0,
                        track="proxy", attrs={"status": 200})
    rec.add_span("serve.dispatch", trace_id=TID, parent_id=root, t0=1.1,
                 t1=1.9, track="serve")
    rec.add_event("engine.first_token", trace_id=TID, t=1.5)
    rec.add_span("engine.decode_burst", trace_id=None, t0=1.2, t1=1.3,
                 track="engine-loop")
    obj = rec.chrome_trace()
    assert validate_chrome_trace(obj)
    names = {e["name"] for e in obj["traceEvents"] if e["ph"] != "M"}
    assert names == {"proxy.request", "serve.dispatch",
                     "engine.first_token", "engine.decode_burst"}
    threads = {e["args"]["name"] for e in obj["traceEvents"]
               if e["ph"] == "M" and e["name"] == "thread_name"}
    assert threads == {"proxy", "serve", "engine", "engine-loop"}
    # Parent links survive export.
    serve = next(e for e in obj["traceEvents"]
                 if e["name"] == "serve.dispatch")
    assert serve["args"]["parent_id"] == root


def test_validator_rejects_malformed_traces():
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": "nope"})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "X",
                                                "pid": 1, "tid": 1}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "Q", "pid": 1, "tid": 1, "ts": 0}
        ]})


def test_span_catalog_names_are_layer_dotted():
    for name in SPAN_CATALOG:
        layer, _, what = name.partition(".")
        # (startup: the start-up journal's spans, ISSUE 40; process: the
        # collector's pauses, ISSUE 57)
        assert layer in ("proxy", "serve", "engine", "startup",
                         "process") and what, name


# ---------------------------------------------------------------------------
# metrics registry: tails, reservoir cap, exposition, windowed rate
# ---------------------------------------------------------------------------

def test_snapshot_carries_tail_percentiles():
    m = Metrics(hist_cap=20000)
    for i in range(10000):
        m.observe("engine_ttft_ms", float(i))
    snap = m.snapshot()
    assert snap["engine_ttft_ms_p50"] == pytest.approx(5000, abs=10)
    assert snap["engine_ttft_ms_p99"] == pytest.approx(9900, abs=15)
    assert snap["engine_ttft_ms_p999"] == pytest.approx(9990, abs=15)
    assert snap["engine_ttft_ms_count"] == 10000


def test_bad_reservoir_cap_fails_at_construction():
    """A bad TUNNEL_METRICS_RESERVOIR must fail when the registry is
    built, not at the first observe() deep inside the serving path."""
    with pytest.raises(ValueError):
        Metrics(hist_cap=1)


def test_reservoir_cap_is_configurable():
    p = _Percentiles(cap=8)
    for i in range(100):
        p.observe(float(i))
    assert p.count <= 8
    m = Metrics(hist_cap=32)
    for i in range(1000):
        m.observe("proxy_ttfb_ms", float(i))
    assert m.snapshot()["proxy_ttfb_ms_count"] <= 32


def test_prometheus_text_covers_the_full_catalog():
    m = Metrics(hist_cap=4096)
    m.inc("engine_tokens_total", 7)
    m.set_gauge("engine_queue_depth", 3)
    for i in range(100):
        m.observe("engine_ttft_ms", float(i))
    text = m.prometheus_text()
    for name in METRICS_CATALOG:
        assert f"# HELP {name} " in text, name
        assert f"# TYPE {name} " in text, name
    assert "# TYPE engine_tokens_total counter" in text
    assert "engine_tokens_total 7" in text
    assert "# TYPE engine_queue_depth gauge" in text
    assert "# TYPE engine_ttft_ms summary" in text
    for q in ("0.5", "0.95", "0.99", "0.999"):
        assert f'engine_ttft_ms{{quantile="{q}"}}' in text  # tunnelcheck: disable=TC12  read-side assertion against the registry's OWN rendering; no series is produced here
    assert "engine_ttft_ms_count 100" in text
    # Never-written series still expose zeros (schema-complete scrape).
    assert "serve_shed_total 0" in text


def test_rate_uses_a_sliding_window_and_survives_reset():
    m = Metrics()
    m.inc("engine_tokens_total", 100)
    first = m.rate("engine_tokens_total")  # lifetime fallback
    assert first >= 0
    m.inc("engine_tokens_total", 50)
    again = m.rate("engine_tokens_total", window_s=60.0)
    assert again >= 0
    # reset() drops the sample history with the counters: the next read
    # must not divide a fresh count by a stale anchor (can't go negative,
    # can't explode).
    m.reset()
    m.inc("engine_tokens_total", 10)
    post = m.rate("engine_tokens_total")
    assert post >= 0
    # Reads spaced wider than the window keep ONE out-of-window anchor:
    # the rate stays a recent-delta estimate rather than silently falling
    # back to the lifetime average every read.
    m2 = Metrics()
    m2.inc("engine_tokens_total", 5)
    m2.rate("engine_tokens_total", window_s=0.0)  # seeds the anchor
    m2.inc("engine_tokens_total", 5)
    r = m2.rate("engine_tokens_total", window_s=0.0)  # anchor is "stale"
    assert r > 0
    # The out-of-window anchor was RETAINED (old + new sample), not
    # pruned into the lifetime fallback.
    assert len(m2._rate_hist["engine_tokens_total"]) == 2


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


def test_serve_metrics_endpoint_is_prometheus_text():
    async def main():
        serve_task, ch, client = await _stack(_echo_backend())
        try:
            r = await client.wait(
                await client.request("GET", "/metrics"), 10.0
            )
            assert r.status == 200
            assert r.headers["content-type"].startswith("text/plain")
            assert "# TYPE engine_tokens_total counter" in r.text
            assert "# TYPE proxy_ttfb_ms summary" in r.text
        finally:
            await _teardown(serve_task, ch, client)

    asyncio.run(main())


def test_healthz_trace_export_and_pool_accounting():
    async def main():
        with tracing_on():
            serve_task, ch, client = await _stack(_echo_backend())
            try:
                tid = mint_trace_id()
                r = await client.wait(await client.request(
                    "GET", "/work",
                    headers={TRACE_HEADER: f"{tid}/0001"},
                ), 10.0)
                assert r.status == 200
                capture = await client.wait(await client.request(
                    "GET", "/healthz?trace=1"), 10.0)
                assert capture.status == 200
                obj = json.loads(capture.text)
                assert validate_chrome_trace(obj)
                spans = {e["name"]: e for e in obj["traceEvents"]
                         if e["ph"] == "X"}
                assert spans["serve.dispatch"]["args"]["trace_id"] == tid
                # The client-sent span id is the dispatch span's parent.
                assert spans["serve.dispatch"]["args"]["parent_id"] == "0001"
                # Plain /healthz still answers, with the new tail +
                # pool-accounting sections.
                h = await client.wait(await client.request(
                    "GET", "/healthz"), 10.0)
                payload = json.loads(h.text)
                assert "ttft_p999_ms" in payload["tails"]
                assert set(payload["prefix_pool"]) == {
                    "blocks_used", "blocks_free", "kv_bytes",
                    # ISSUE 14: reservation/eviction accounting + the
                    # conversation cache's reuse counters.
                    "pages_reserved", "evictions_total", "conversation",
                    # ISSUE 16: host-RAM spill tier + the memory
                    # degradation contract's live reason.
                    "spill", "degraded_reason",
                    # ISSUE 44: snapshots of recurrent state, counted apart
                    # from the rows
                    "state_snapshots", "state_bytes",
                }
                assert set(payload["prefix_pool"]["conversation"]) == {
                    "saved_pages_total", "hits_total", "hit_tokens_total",
                }
                assert set(payload["prefix_pool"]["spill"]) == {
                    "pages", "bytes", "inflight", "pageouts_total",
                    "pageins_total", "pagein_failures_total",
                    "memory_sheds_total", "thrash_trips_total",
                }
                # The composition-fence registry rides /healthz too: a
                # list (empty unless an engine auto-disabled something).
                assert isinstance(payload["config"]["fences"], list)
                # ... and the precision the engine was built with (what
                # an engine publishes at construction; null without one).
                assert set(payload["config"]) == {
                    "fences", "attention", "quant", "kv_quant", "model"}
                global_metrics.set_info("config_quant", "int8")
                global_metrics.set_info("config_kv_quant", "none")
                try:
                    h = await client.wait(await client.request(
                        "GET", "/healthz"), 10.0)
                    config = json.loads(h.text)["config"]
                    assert (config["quant"], config["kv_quant"]) == (
                        "int8", "none")
                finally:
                    global_metrics.set_info("config_quant", None)
                    global_metrics.set_info("config_kv_quant", None)
            finally:
                await _teardown(serve_task, ch, client)

    asyncio.run(main())


def test_untraced_request_records_nothing_even_when_enabled():
    """No x-tunnel-trace header and no proxy in front: the serve layer has
    no context to record under — the journal stays empty (no orphan
    spans), and sampling=0 drops a present header's trace too."""
    async def main():
        with tracing_on():
            serve_task, ch, client = await _stack(_echo_backend())
            try:
                await client.wait(await client.request("GET", "/x"), 10.0)
                assert [r for r in global_tracer.records()
                        if r.trace_id is not None] == []
            finally:
                await _teardown(serve_task, ch, client)
        with tracing_on(sample=0.0):
            serve_task, ch, client = await _stack(_echo_backend())
            try:
                await client.wait(await client.request(
                    "GET", "/x",
                    headers={TRACE_HEADER: f"{mint_trace_id()}/1"},
                ), 10.0)
                assert global_tracer.records() == []
            finally:
                await _teardown(serve_task, ch, client)

    asyncio.run(main())


def test_proxy_metrics_tunnels_through_and_local_answers_locally():
    """Bare /metrics through the proxy reaches the SERVE loop (in the
    deployed two-process topology that registry holds the engine_*/serve_*
    series — a local answer would render them as silent zeros), while
    /metrics?local=1 answers from the proxy process even tunnel-down."""
    from p2p_llm_tunnel_tpu.endpoints import http11
    from p2p_llm_tunnel_tpu.endpoints.proxy import run_proxy

    async def main():
        serve_ch, proxy_ch = loopback_pair()
        serve_task = asyncio.create_task(
            run_serve(serve_ch, backend=_echo_backend())
        )
        ready: asyncio.Future = asyncio.get_running_loop().create_future()
        proxy_task = asyncio.create_task(
            run_proxy(proxy_ch, "127.0.0.1", 0, ready=ready)
        )
        port = await asyncio.wait_for(ready, 10.0)
        base = f"http://127.0.0.1:{port}"
        try:
            before = global_metrics.counter("serve_requests_total")
            r = await http11.http_request("GET", f"{base}/metrics")
            body = (await r.read_all()).decode()
            assert r.status == 200
            assert "# TYPE engine_tokens_total counter" in body
            # The scrape crossed the tunnel and the serve loop answered
            # (loop-served routes don't count as backend dispatches).
            assert global_metrics.counter("serve_requests_total") == before
            rl = await http11.http_request("GET", f"{base}/metrics?local=1")
            assert rl.status == 200
            assert "# TYPE proxy_ttfb_ms summary" in (
                await rl.read_all()
            ).decode()
            # The proxy's OWN span journal is exportable too (the ingress
            # spans live in this process in the two-process topology).
            rt = await http11.http_request(
                "GET", f"{base}/healthz?trace=1&local=1"
            )
            assert rt.status == 200
            assert validate_chrome_trace(json.loads(await rt.read_all()))
        finally:
            serve_task.cancel()
            proxy_task.cancel()
            serve_ch.close()
            await asyncio.gather(serve_task, proxy_task,
                                 return_exceptions=True)

    asyncio.run(main())


# ---------------------------------------------------------------------------
# traceview summarizer
# ---------------------------------------------------------------------------

def _load_traceview():
    path = REPO_ROOT / "scripts" / "traceview.py"
    spec = importlib.util.spec_from_file_location("traceview", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_traceview_reconstructs_the_ttft_split():
    rec = TraceRecorder(enabled=True)
    root = rec.add_span("proxy.request", trace_id=TID, t0=1.0, t1=3.0,
                        track="proxy",
                        attrs={"path": "/v1/chat/completions",
                               "status": 200})
    eng = rec.add_span("engine.request", trace_id=TID, parent_id=root,
                       t0=1.1, t1=2.9, attrs={"finish": "stop"})
    rec.add_span("engine.queue_wait", trace_id=TID, parent_id=eng,
                 t0=1.1, t1=1.4)
    rec.add_span("engine.prefill_exec", trace_id=TID, parent_id=eng,
                 t0=1.4, t1=1.6)
    rec.add_event("engine.first_token", trace_id=TID, parent_id=eng, t=1.6)
    rec.add_span("engine.decode_burst", trace_id=None, t0=1.6, t1=1.8,
                 track="engine-loop")
    tv = _load_traceview()
    out = tv.summarize(rec.chrome_trace())
    (req,) = out["requests"]
    assert req["ttft_ms"] == pytest.approx(500, abs=1)
    assert req["queue_wait_ms"] == pytest.approx(300, abs=1)
    assert req["prefill_exec_ms"] == pytest.approx(200, abs=1)
    # The split tiles TTFT exactly — the reconstruction the ISSUE asks for.
    assert req["queue_wait_ms"] + req["prefill_exec_ms"] == pytest.approx(
        req["ttft_ms"], abs=1
    )
    assert out["aggregate"]["ttft_p50_ms"] == pytest.approx(500, abs=1)
    assert out["engine_scope"]["engine.decode_burst"]["count"] == 1


def test_traceview_multi_generation_trace_pairs_by_parent():
    """n>1 / prompt-list requests run several engine generations under ONE
    propagated trace id: the rollup must pair children with THEIR
    generation by parent linkage, never by span name (which would compute
    a bogus — even negative — TTFT from generation B's first token and
    generation A's span)."""
    rec = TraceRecorder(enabled=True)
    root = rec.add_span("proxy.request", trace_id=TID, t0=1.0, t1=9.0,
                        track="proxy", attrs={"status": 200})
    a = rec.add_span("engine.request", trace_id=TID, parent_id=root,
                     t0=1.0, t1=4.0)
    rec.add_span("engine.queue_wait", trace_id=TID, parent_id=a,
                 t0=1.0, t1=1.2)
    rec.add_event("engine.first_token", trace_id=TID, parent_id=a, t=1.5)
    b = rec.add_span("engine.request", trace_id=TID, parent_id=root,
                     t0=2.0, t1=9.0)
    rec.add_span("engine.queue_wait", trace_id=TID, parent_id=b,
                 t0=2.0, t1=6.0)
    rec.add_event("engine.first_token", trace_id=TID, parent_id=b, t=7.0)
    tv = _load_traceview()
    (req,) = tv.summarize(rec.chrome_trace())["requests"]
    assert req["generations"] == 2
    # First generation's numbers, not a cross-generation mixture.
    assert req["ttft_ms"] == pytest.approx(500, abs=1)
    assert req["queue_wait_ms"] == pytest.approx(200, abs=1)
    assert req["total_ms"] == pytest.approx(8000, abs=1)


def test_traceview_per_peer_attribution():
    """Fabric captures carry serve.dispatch peer attrs (ISSUE 9): the
    rollup attributes each request's TTFT to the peer whose dispatch
    parented the engine generation, lists every peer a failover touched,
    and rolls up a by_peer aggregate with a failover count."""
    rec = TraceRecorder(enabled=True)
    root = rec.add_span("proxy.request", trace_id=TID, t0=1.0, t1=3.0,
                        track="proxy", attrs={"status": 200,
                                              "peer": "peer-b"})
    rec.add_span("serve.dispatch", trace_id=TID, parent_id=root,
                 track="serve", t0=1.0, t1=1.2,
                 attrs={"peer": "peer-a", "path": "/gen"})
    d2 = rec.add_span("serve.dispatch", trace_id=TID, parent_id=root,
                      track="serve", t0=1.3, t1=2.9,
                      attrs={"peer": "peer-b", "path": "/gen"})
    eng = rec.add_span("engine.request", trace_id=TID, parent_id=d2,
                       t0=1.4, t1=2.8)
    rec.add_event("engine.first_token", trace_id=TID, parent_id=eng, t=1.9)
    tv = _load_traceview()
    out = tv.summarize(rec.chrome_trace())
    (req,) = out["requests"]
    # TTFT belongs to the peer that actually served the generation...
    assert req["peer"] == "peer-b"
    # ...while the failover trail lists both peers it touched.
    assert req["peers"] == ["peer-a", "peer-b"]
    by_peer = out["aggregate"]["by_peer"]
    assert by_peer["peer-b"]["requests"] == 1
    assert by_peer["peer-b"]["failovers"] == 1
    assert by_peer["peer-b"]["ttft_p50_ms"] == pytest.approx(500, abs=1)


# ---------------------------------------------------------------------------
# cross-peer trace stitching (ISSUE 9, stitch_chrome_traces)
# ---------------------------------------------------------------------------

def _capture(build) -> dict:
    rec = TraceRecorder(enabled=True)
    build(rec)
    return rec.chrome_trace()


def test_stitch_assigns_lanes_and_dedupes_shared_journals():
    """Single-process fabrics share one recorder: the same records pulled
    via three journals must appear ONCE, with serve-track spans landing on
    the lane their peer attr names and engine spans inheriting their
    parent dispatch's lane."""
    from p2p_llm_tunnel_tpu.utils.tracing import stitch_chrome_traces

    rec = TraceRecorder(enabled=True)
    root = rec.add_span("proxy.request", trace_id=TID, t0=1.0, t1=3.0,
                        track="proxy", attrs={"status": 200})
    d = rec.add_span("serve.dispatch", trace_id=TID, parent_id=root,
                     track="serve", t0=1.1, t1=2.9,
                     attrs={"peer": "p1", "path": "/g"})
    rec.add_span("engine.request", trace_id=TID, parent_id=d,
                 t0=1.2, t1=2.8)
    shared = rec.chrome_trace()
    out = stitch_chrome_traces(
        {"proxy": shared, "p1": shared, "p2": shared})
    validate_chrome_trace(out)
    events = [e for e in out["traceEvents"] if e["ph"] != "M"]
    assert len(events) == 3  # deduped across the three identical pulls
    by_name = {e["name"]: e for e in events}
    # proxy-track events pin to the proxy lane even when pulled from a
    # peer journal; the dispatch and its engine child share p1's lane.
    assert by_name["proxy.request"]["pid"] != by_name["serve.dispatch"]["pid"]
    assert by_name["engine.request"]["pid"] == \
        by_name["serve.dispatch"]["pid"]
    assert out["stitch"]["sources"] == ["proxy", "p1", "p2"]
    assert out["stitch"]["stale"] == []
    assert out["stitch"]["partial_traces"] == []


def test_stitch_flags_evicted_journal_as_partial_not_crash():
    """A peer whose ring buffer evicted the sampled trace (or that died
    before its journal could be pulled) yields a PARTIAL chain: flagged in
    the stitch summary, never an exception (the federation-failure-mode
    satellite)."""
    from p2p_llm_tunnel_tpu.utils.tracing import stitch_chrome_traces

    def proxy_only(rec):
        rec.add_span("proxy.request", trace_id=TID, t0=1.0, t1=2.0,
                     track="proxy", attrs={"status": 200, "peer": "p1"})

    # Case 1: the serving peer's journal is empty (evicted) — the
    # proxy.request names p1 but no span of the trace sits on p1's lane.
    out = stitch_chrome_traces({
        "proxy": _capture(proxy_only),
        "p1": {"traceEvents": []},
    })
    validate_chrome_trace(out)
    assert out["stitch"]["partial_traces"] == [TID]
    assert out["stitch"]["stale"] == []

    # Case 2: the peer was unpullable entirely (dead/slow): stale AND the
    # chain is partial.
    out = stitch_chrome_traces({
        "proxy": _capture(proxy_only), "p1": None,
    })
    validate_chrome_trace(out)
    assert out["stitch"]["stale"] == ["p1"]
    assert out["stitch"]["partial_traces"] == [TID]

    # Case 3: an orphaned parent_id (the dispatch span evicted under the
    # engine span) is also partial — and still renders.
    def orphaned(rec):
        rec.add_span("engine.request", trace_id=TID,
                     parent_id="feedfeedfeed", t0=1.0, t1=2.0)

    out = stitch_chrome_traces({"proxy": _capture(orphaned)})
    validate_chrome_trace(out)
    assert out["stitch"]["partial_traces"] == [TID]


def test_stitch_keeps_colliding_cross_process_span_ids_distinct():
    """Counter-allocated span ids collide ACROSS processes: two peers'
    journals reusing span id 1 at different timestamps are different
    spans and must both survive the dedupe."""
    from p2p_llm_tunnel_tpu.utils.tracing import stitch_chrome_traces

    def peer_at(t0):
        def build(rec):
            rec.add_span("serve.dispatch", trace_id=TID, span_id="000001",
                         track="serve", t0=t0, t1=t0 + 1.0,
                         attrs={"peer": ""})
        return build

    # Distinct ts -> distinct records, each on its source journal's lane
    # (no peer attr, no parent: source fallback).
    out = stitch_chrome_traces({
        "p1": _capture(peer_at(1.0)), "p2": _capture(peer_at(5.0)),
    })
    events = [e for e in out["traceEvents"] if e["ph"] != "M"]
    assert len(events) == 2
    assert {e["pid"] for e in events} == {1, 2}


# ---------------------------------------------------------------------------
# engine-backed: herd chains + chaos topology (JAX; slow)
# ---------------------------------------------------------------------------

def _topology(records):
    """Per-trace span/event topology as a comparable value: the multiset
    of per-trace (name, parent-name) edge sets — trace and span IDS differ
    across runs, the STRUCTURE must not."""
    by_trace = {}
    for r in records:
        if r.trace_id is not None:
            by_trace.setdefault(r.trace_id, []).append(r)
    shapes = []
    for recs in by_trace.values():
        name_of = {r.span_id: r.name for r in recs}
        shapes.append(tuple(sorted(
            (r.name, name_of.get(r.parent_id)) for r in recs
        )))
    return tuple(sorted(shapes))


@pytest.mark.slow
def test_mux_herd_traces_chain_proxy_serve_engine():
    """ISSUE 6 acceptance: a 32-client mux herd emits, per request, one
    span chain crossing proxy -> serve -> engine under one propagated
    trace id, with the queue-wait + prefill-exec spans tiling the
    submit -> first-token window exactly; the capture validates against
    the trace-event schema."""
    from p2p_llm_tunnel_tpu.endpoints import http11
    from p2p_llm_tunnel_tpu.endpoints.proxy import run_proxy
    from p2p_llm_tunnel_tpu.engine.api import engine_backend
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    n = 32
    shared = "You are a helpful tunnel assistant; answer briefly. "

    async def main():
        engine = InferenceEngine(engine_cfg=EngineConfig(
            model="tiny", num_slots=8, max_seq=256, dtype="float32",
            mux=True, prefix_cache=True,
        ))
        await engine.start()
        serve_ch, proxy_ch = loopback_pair()
        serve_task = asyncio.create_task(
            run_serve(serve_ch, backend=engine_backend(engine, "tiny"))
        )
        ready: asyncio.Future = asyncio.get_running_loop().create_future()
        proxy_task = asyncio.create_task(
            run_proxy(proxy_ch, "127.0.0.1", 0, ready=ready)
        )
        port = await asyncio.wait_for(ready, 10.0)

        async def one(i):
            payload = json.dumps({
                "messages": [{"role": "user",
                              "content": f"{shared}q{i}"}],
                "max_tokens": 4, "stream": True,
            }).encode()
            resp = await http11.http_request(
                "POST", f"http://127.0.0.1:{port}/v1/chat/completions",
                {"content-type": "application/json"}, payload, timeout=120.0,
            )
            body = await resp.read_all()
            assert resp.status == 200
            assert body.strip().endswith(b"data: [DONE]")

        try:
            await asyncio.gather(*(one(i) for i in range(n)))
        finally:
            serve_task.cancel()
            proxy_task.cancel()
            serve_ch.close()
            await asyncio.gather(serve_task, proxy_task,
                                 return_exceptions=True)
            await engine.stop()

    with tracing_on(capacity=65536):
        asyncio.run(main())
        recs = global_tracer.records()
        by_trace = {}
        for r in recs:
            if r.trace_id is not None:
                by_trace.setdefault(r.trace_id, []).append(r)
        roots = [r for r in recs if r.name == "proxy.request"]
        assert len(roots) == n
        assert len(by_trace) == n  # one trace id per request, minted once
        for tid, trs in by_trace.items():
            spans = {r.name: r for r in trs if r.dur is not None}
            events = {r.name: r for r in trs if r.dur is None}
            for required in ("proxy.request", "proxy.frame_send",
                             "serve.dispatch", "engine.request",
                             "engine.queue_wait", "engine.prefill_exec"):
                assert required in spans, (tid, sorted(spans))
            for required in ("serve.frame_recv", "engine.first_token",
                             "engine.stream_end", "proxy.first_byte"):
                assert required in events, (tid, sorted(events))
            # The chain: serve.dispatch under proxy.request, engine.request
            # under serve.dispatch, the split under engine.request.
            assert (spans["serve.dispatch"].parent_id
                    == spans["proxy.request"].span_id)
            assert (spans["engine.request"].parent_id
                    == spans["serve.dispatch"].span_id)
            assert (spans["engine.queue_wait"].parent_id
                    == spans["engine.request"].span_id)
            # TTFT reconstruction: the two spans tile submit->first-token.
            qw, pf = spans["engine.queue_wait"], spans["engine.prefill_exec"]
            ft = events["engine.first_token"]
            assert qw.ts == pytest.approx(spans["engine.request"].ts,
                                          abs=1e-6)
            assert qw.ts + qw.dur == pytest.approx(pf.ts, abs=1e-6)
            assert pf.ts + pf.dur == pytest.approx(ft.ts, abs=1e-6)
            assert spans["engine.request"].attrs["finish"] in (
                "stop", "length"
            )
        # The shared template exercised the prefix-group machinery.
        assert any(r.name == "engine.prefix_own" for r in recs)
        # Engine-scope timeline rows recorded alongside.
        assert any(r.name == "engine.decode_burst" for r in recs)
        # And the export is schema-valid end to end.
        assert validate_chrome_trace(global_tracer.chrome_trace())


@pytest.mark.slow
def test_chaos_span_topology_deterministic():
    """Seeded drop/dup/stall on the client->serve path: two runs yield the
    SAME span topology — tracing is part of the `make chaos` determinism
    contract, not an exception to it."""
    from p2p_llm_tunnel_tpu.engine.api import engine_backend
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.transport.chaos import ChaosChannel, ChaosSpec

    seed = int(os.environ.get("CHAOS_TEST_SEED", "5"))

    async def scenario():
        engine = InferenceEngine(engine_cfg=EngineConfig(
            model="tiny", num_slots=2, max_seq=256, dtype="float32",
            decode_steps=4, mux=True,
        ))
        await engine.start()
        serve_ch, client_ch = loopback_pair()
        chaos = ChaosChannel(client_ch, ChaosSpec.parse(
            f"seed={seed},drop=0.06,dup=0.05,stall=0.25:0.04"
        ))
        serve_task = asyncio.create_task(
            run_serve(serve_ch, backend=engine_backend(engine, "tiny"))
        )
        client = FrameClient(chaos, pad_pings=True, reply_pings=False)
        try:
            await client.handshake(timeout=30.0)
            results = []
            for i in range(4):
                r = await client.request(
                    "POST", "/v1/chat/completions",
                    body={"messages": [{"role": "user",
                                        "content": f"chaos {i}"}],
                          "stream": True, "max_tokens": 3,
                          "ignore_eos": True},
                    headers={TRACE_HEADER: f"{'%032x' % (i + 1)}/c{i}"},
                )
                results.append(r)
            for r in results:
                await client.wait(r, timeout=120.0)
            return tuple(chaos.faults)
        finally:
            client.close()
            serve_task.cancel()
            serve_ch.close()
            await asyncio.gather(serve_task, return_exceptions=True)
            await engine.stop()

    def run_once():
        with tracing_on(capacity=65536):
            faults = asyncio.run(scenario())
            return faults, _topology(global_tracer.records())

    f1, t1 = run_once()
    f2, t2 = run_once()
    assert f1 == f2, "fault schedule must be seed-deterministic"
    assert f1, "schedule fired no faults at these rates — spec broken"
    assert t1 == t2, "span topology must be identical across seeded runs"
    assert len(t1) == 4  # one topology per request trace
    for shape in t1:
        names = [name for name, _parent in shape]
        assert "serve.dispatch" in names and "engine.request" in names
