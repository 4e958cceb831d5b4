#!/usr/bin/env python
"""Out-of-process SSE ingress load generator (ISSUE 7, stdlib-only).

Drives hundreds-to-1000 concurrent ``/v1/chat/completions`` SSE streams
against a tunnel proxy from a SEPARATE process — client-side HTTP parsing
must never share an interpreter with the server under test (the same
reason the reference drives load from curl, scripts/test-tunnel.sh:88-96).
It speaks raw HTTP/1.1 + chunked transfer over asyncio sockets and
imports nothing of the package, so it also runs against a deployed proxy
with nothing installed.

Per-tenant mixes model the hot-tenant-aggressor-vs-victim-herd scenario:
each ``--tenant name:clients[:requests]`` spec contributes ``clients``
concurrent clients issuing ``requests`` sequential generations tagged with
``x-tunnel-tenant: name`` (the explicit label, so server-side series and
``--tenant-weights`` match the spec names; ``x-api-key`` identities are
fingerprinted server-side); the report aggregates p50/p99/p999 TTFT/TTFB
rows per tenant, plus ok/shed/error/stuck counts.

Usage:
    # against a running proxy
    python scripts/loadgen.py --port 8000 --tenant herd:500

    # self-contained: spawn the loopback stack in a subprocess first
    python scripts/loadgen.py --spawn --tenant victim:400 --tenant hot:100:8

Exit code 1 when any stream got stuck (no completion within --timeout,
or its client task crashed) OR the post-run /healthz leak check finds
nonzero in-flight/queue/occupancy — the "zero stuck streams or leaked
slots" acceptance gate is the exit code.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import select
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple


def nearest_rank(values: List[float], p: float) -> Optional[float]:
    """Nearest-rank percentile — the same estimator utils.metrics uses,
    re-stated here because this script must not import the package."""
    if not values:
        return None
    xs = sorted(values)
    idx = min(len(xs) - 1, max(0, int(round(p / 100.0 * (len(xs) - 1)))))
    return xs[idx]


# ---------------------------------------------------------------------------
# minimal HTTP/1.1 client (chunked-aware) over asyncio sockets
# ---------------------------------------------------------------------------

async def _read_headers(reader) -> Tuple[int, Dict[str, str]]:
    status_line = await reader.readline()
    if not status_line:
        raise ConnectionError("empty response")
    parts = status_line.decode("latin-1").split(" ", 2)
    status = int(parts[1])
    headers: Dict[str, str] = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        k, _, v = line.decode("latin-1").partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers


async def _iter_body(reader, headers):
    """Yield body chunks for chunked or content-length responses."""
    if "chunked" in headers.get("transfer-encoding", "").lower():
        while True:
            size_line = await reader.readline()
            size = int(size_line.strip().split(b";")[0], 16)
            if size == 0:
                await reader.readline()  # trailing CRLF
                return
            data = await reader.readexactly(size)
            await reader.readexactly(2)  # CRLF
            yield data
    else:
        n = int(headers.get("content-length", "0") or "0")
        if n:
            yield await reader.readexactly(n)


class ReqResult:
    __slots__ = ("status", "ttfb_ms", "ttft_ms", "tokens", "wall_ms",
                 "outcome", "finish", "retry_after", "text")

    def __init__(self):
        self.status = 0
        self.ttfb_ms = None
        self.ttft_ms = None
        self.tokens = 0
        self.wall_ms = 0.0
        self.outcome = "error"  # ok | shed | error | stuck
        self.finish = None  # finish_reason of the last SSE chunk, if any
        self.retry_after = None
        # Concatenated SSE content deltas — only captured in --turns mode,
        # where each client replays its own growing conversation history.
        self.text = ""


#: finish_reason values that mean the server SHED the stream after the 200
#: was already on the wire (mid-queue displacement, drain) — protocol
#: contract from p2p_llm_tunnel_tpu.protocol.frames.ERROR_CODES, spelled
#: out here because this script must stay stdlib-only.  Classifying these
#: as "ok" would let a fairness regression that displaces victim streams
#: read as "victim N/N ok" and pass the gate.
SHED_FINISH_REASONS = frozenset({"tenant_overlimit", "busy", "draining"})

#: Typed TERMINAL error events a stream can end with (ISSUE 13: the proxy
#: emits data: {"error": {code, ...}} when a mid-stream peer loss could
#: not be resumed inside the grace window).  These are failures, not
#: clean completions — and note what is absent: a stream that RESUMED
#: mid-run completes byte-identically with no marker at all, so it
#: counts "ok" (and never "stuck": the only stuck criteria are the
#: whole-run --timeout and client crashes, so a stream parked in the
#: grace window is simply a slower success).
TERMINAL_ERROR_CODES = frozenset({"peer_lost", "tunnel_reset"})


async def one_request(host: str, port: int, tenant: str, rid: str,
                      prompt: str, max_tokens: int,
                      capture_text: bool = False,
                      messages: Optional[List[dict]] = None,
                      logit_bias: Optional[Dict[str, float]] = None
                      ) -> ReqResult:
    out = ReqResult()
    t0 = time.monotonic()
    payload = {
        "model": "loadgen",
        "messages": (messages if messages is not None
                     else [{"role": "user", "content": prompt}]),
        "max_tokens": max_tokens,
        "stream": True,
        "temperature": 0.0,
        "ignore_eos": True,
    }
    if logit_bias:
        payload["logit_bias"] = logit_bias
    body = json.dumps(payload).encode()
    req = (
        f"POST /v1/chat/completions HTTP/1.1\r\n"
        f"host: {host}:{port}\r\n"
        f"x-tunnel-tenant: {tenant}\r\n"
        f"x-request-tag: {rid}\r\n"
        f"content-type: application/json\r\n"
        f"content-length: {len(body)}\r\n"
        f"connection: close\r\n\r\n"
    ).encode() + body
    reader = writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(req)
        await writer.drain()
        status, headers = await _read_headers(reader)
        out.status = status
        out.retry_after = headers.get("retry-after")
        buf = b""
        async for chunk in _iter_body(reader, headers):
            if out.ttfb_ms is None:
                out.ttfb_ms = (time.monotonic() - t0) * 1000.0
            if status != 200:
                continue  # drain the error body
            buf += chunk
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                if not event.startswith(b"data: "):
                    continue
                data = event[6:]
                if data == b"[DONE]":
                    continue
                payload = json.loads(data)
                err = payload.get("error")
                if isinstance(err, dict) and err.get("code"):
                    # Typed terminal event: the stream is over, failed.
                    out.finish = str(err["code"])
                    continue
                choices = payload.get("choices") or []
                if not choices:
                    continue
                delta = choices[0].get("delta", {})
                if choices[0].get("finish_reason"):
                    out.finish = choices[0]["finish_reason"]
                if out.ttft_ms is None and delta:
                    out.ttft_ms = (time.monotonic() - t0) * 1000.0
                if delta.get("content"):
                    out.tokens += 1
                    if capture_text:
                        out.text += delta["content"]
        if status == 200:
            # A 200 is not automatically a success: a stream displaced
            # after admission ends with a typed shed finish_reason on an
            # otherwise-clean SSE body, and an unresumable mid-stream
            # peer loss ends with a typed terminal error event.
            if out.finish in SHED_FINISH_REASONS:
                out.outcome = "shed"
            elif out.finish in TERMINAL_ERROR_CODES:
                out.outcome = "error"
            else:
                out.outcome = "ok"
        elif status == 429:
            out.outcome = "shed"
        else:
            out.outcome = "error"
    except (ConnectionError, asyncio.IncompleteReadError, OSError,
            ValueError):
        out.outcome = "error"
    finally:
        if writer is not None:
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionError, OSError):
                pass
        out.wall_ms = (time.monotonic() - t0) * 1000.0
    return out


async def one_client(host: str, port: int, tenant: str, idx: int,
                     requests: int, prompt_pad: int, max_tokens: int,
                     delay: float, results: List[ReqResult]) -> None:
    if delay > 0:
        await asyncio.sleep(delay)
    for r in range(requests):
        # Unique per (tenant, client, round) so prefix dedup cannot
        # collapse the herd into one prefill.
        prompt = f"load {tenant} {idx} {r} ".ljust(prompt_pad, "x")
        results.append(await one_request(
            host, port, tenant, f"{tenant}-{idx}-{r}", prompt, max_tokens
        ))


#: Turns-mode logit bias banning the byte tokenizers' special ids
#: (PAD/BOS/EOS) from being SAMPLED: they decode to "" — invisible in the
#: replayed text while present in the server's KV chain — so one sampled
#: special would silently break the conversation-cache byte-exactness the
#: experiment measures.  Random weights sample them ~1% of tokens;
#: real-checkpoint tokenizers frame specials via their chat template and
#: don't need this (--ban-ids "" disables).
DEFAULT_BAN_IDS = "256,257,258"


async def one_turn(host: str, port: int, tenant: str, idx: int, turn: int,
                   histories: Dict, prompt_pad: int, max_tokens: int,
                   delay: float, results: List[ReqResult],
                   logit_bias: Optional[Dict[str, float]] = None) -> int:
    """One conversation TURN (ISSUE 14 --turns mode): the client resends
    its ENTIRE message history — every prior user line and assistant
    response, the way real chat clients replay conversations — plus a
    fresh user message, then appends the response to its history.
    Returns the rendered-prompt length sent (bytes ~ tokens under the
    byte tokenizer), so the per-turn report can show resent-history
    volume next to the prefill tokens the server ACTUALLY computed."""
    if delay > 0:
        await asyncio.sleep(delay)
    msgs = histories[(tenant, idx)]
    user = f"turn {turn} {tenant} {idx} ".ljust(prompt_pad, "y")
    msgs = msgs + [{"role": "user", "content": user}]
    r = await one_request(
        host, port, tenant, f"{tenant}-{idx}-t{turn}", user, max_tokens,
        capture_text=True, messages=msgs, logit_bias=logit_bias,
    )
    histories[(tenant, idx)] = msgs + [
        {"role": "assistant", "content": r.text}
    ]
    results.append(r)
    # The server renders "role: content\n..." + the assistant cue; this
    # mirrors engine.api.render_chat_prompt's arithmetic closely enough
    # for the sent-volume column (exact prefill counts come from the
    # server's own metrics delta).
    return sum(len(m["content"]) + len(m["role"]) + 3 for m in msgs) + 10


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------

def tenant_rows(per_tenant: Dict[str, List[ReqResult]]) -> List[dict]:
    rows = []
    for tenant, rs in sorted(per_tenant.items()):
        ttfts = [r.ttft_ms for r in rs if r.ttft_ms is not None]
        ttfbs = [r.ttfb_ms for r in rs if r.ttfb_ms is not None]
        n = lambda v: round(v, 1) if v is not None else None  # noqa: E731
        rows.append({
            "tenant": tenant,
            "requests": len(rs),
            "ok": sum(1 for r in rs if r.outcome == "ok"),
            "shed_429": sum(1 for r in rs if r.outcome == "shed"),
            "errors": sum(1 for r in rs if r.outcome == "error"),
            "stuck": sum(1 for r in rs if r.outcome == "stuck"),
            "tokens": sum(r.tokens for r in rs),
            "ttft_p50_ms": n(nearest_rank(ttfts, 50)),
            "ttft_p99_ms": n(nearest_rank(ttfts, 99)),
            "ttft_p999_ms": n(nearest_rank(ttfts, 99.9)),
            "ttfb_p50_ms": n(nearest_rank(ttfbs, 50)),
            "ttfb_p99_ms": n(nearest_rank(ttfbs, 99)),
            "ttfb_p999_ms": n(nearest_rank(ttfbs, 99.9)),
        })
    return rows


#: Unlabeled families sampled by --metrics-poll (sheds, queue pressure,
#: token throughput, ingress volume), plus summary quantiles from
#: POLL_QUANTILES — picked so a PERF.md round can plot sheds/TTFT over the
#: run instead of only the end-state row.
POLL_KEYS = (
    "engine_tokens_total",
    "serve_shed_total",
    "engine_tenant_sheds_total",
    "engine_queue_depth",
    "engine_batch_occupancy",
    "proxy_requests_total",
    "serve_stream_resumes_total",
    "serve_streams_detached",
    "serve_replay_buffer_bytes",
    # Block-paged pool + conversation cache (ISSUE 14): pool occupancy,
    # reservation level (the leak-gate gauge), and the per-turn prefill /
    # conversation-reuse counters the --turns report differences.
    "engine_prefill_tokens_total",
    "engine_prefix_pool_blocks_used",
    "engine_prefix_pool_pages_reserved",
    "engine_conv_hit_tokens_total",
    "engine_conv_hits_total",
    # Host-RAM spill tier (ISSUE 16): residency + tier-I/O ledger over
    # the run, so a capacity-cliff timeline shows WHEN the pool started
    # migrating pages and whether page-ins kept up with returning turns.
    "engine_spill_pages",
    "engine_spill_inflight",
    "engine_spill_pageouts_total",
    "engine_spill_pageins_total",
    # Disaggregated prefill/decode (ISSUE 20): handoff volume and the
    # transfer in-flight gauge over the run — a timeline shows whether
    # page shipping kept pace with admission or the proxy fell back.
    "engine_pages_shipped_total",
    "engine_pages_spliced_total",
    "engine_page_xfer_bytes_total",
    "engine_kv_xfer_inflight",
    "proxy_disagg_handoffs_total",
    "proxy_disagg_fallbacks_total",
    "proxy_affinity_hits_total",
)

#: Disagg counters reported as RUN DELTAS in the summary row (ISSUE 20):
#: the A/B evidence that the handoff path ran (or fell back) this run.
DISAGG_DELTA_KEYS = (
    "engine_pages_shipped_total",
    "engine_pages_spliced_total",
    "engine_page_xfer_bytes_total",
    "proxy_disagg_handoffs_total",
    "proxy_disagg_fallbacks_total",
    "proxy_affinity_hits_total",
)
POLL_QUANTILES = {
    "engine_ttft_ms": ("0.5", "0.99"),
    "proxy_ttfb_ms": ("0.5", "0.99"),
    # The prefill-EXECUTION half of the TTFT split (ISSUE 15): per-turn
    # rows sample it from the poll timeline so conversation-cache
    # re-prefill cost and ragged-prefill gains read from one run.
    "engine_prefill_exec_ms": ("0.5",),
}


def parse_metrics_sample(text: str) -> Dict[str, float]:
    """Pull the POLL_KEYS/POLL_QUANTILES samples out of one Prometheus
    text exposition (quantile keys land as ``<name>_q<q>``)."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, rest = line.partition(" ")
        base, _, label = name.partition("{")
        try:
            value = float(rest.strip())
        except ValueError:
            continue
        if not label and base in POLL_KEYS:
            out[base] = value
        elif label and base in POLL_QUANTILES:
            for q in POLL_QUANTILES[base]:
                if f'quantile="{q}"' in label:
                    out[f"{base}_q{q}"] = value
    return out


async def fetch_metrics(host: str, port: int,
                        path: str = "/metrics",
                        timeout: float = 5.0) -> Optional[str]:
    """One GET ``path`` as raw text, bounded by ``timeout``; None when
    unreachable OR when the server accepts but never finishes the
    response — a wedged stack (exactly what the stuck-task accounting
    exists to surface) must yield an error row, not freeze the poller."""

    async def inner() -> str:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write((f"GET {path} HTTP/1.1\r\nhost: {host}\r\n"
                      "connection: close\r\n\r\n").encode())
        await writer.drain()
        _status, headers = await _read_headers(reader)
        body = b""
        async for chunk in _iter_body(reader, headers):
            body += chunk
        writer.close()
        return body.decode("utf-8", "replace")

    try:
        return await asyncio.wait_for(inner(), timeout)
    except (ConnectionError, OSError, ValueError,
            asyncio.IncompleteReadError, asyncio.TimeoutError):
        return None


async def metrics_poller(host: str, port: int, interval: float,
                         t0: float, rows: List[dict]) -> None:
    """Sample the stack's metrics every ``interval`` seconds for the
    duration of the herd (--metrics-poll); each row is timestamped
    relative to the run start.  TWO scrapes per tick: bare ``/metrics``
    tunnels to the SERVE peer's registry (the engine_*/serve_* keys),
    while ``/metrics?local=1`` answers from the PROXY process — the only
    place the proxy_* families are real; the tunneled exposition renders
    them as full-catalog zeros (the TC06 silent-zero class).  A failed
    scrape records an error row — a gap in the timeline should be
    visible, not silent."""
    scrape_timeout = max(1.0, interval)
    while True:
        serve_text = await fetch_metrics(
            host, port, "/metrics", scrape_timeout)
        proxy_text = await fetch_metrics(
            host, port, "/metrics?local=1", scrape_timeout)
        row: Dict[str, object] = {"t": round(time.monotonic() - t0, 1)}
        if serve_text is None and proxy_text is None:
            row["error"] = "unreachable"
        else:
            if serve_text is not None:
                row.update({
                    k: v
                    for k, v in parse_metrics_sample(serve_text).items()
                    if not k.startswith("proxy_")
                })
            if proxy_text is not None:
                row.update({
                    k: v
                    for k, v in parse_metrics_sample(proxy_text).items()
                    if k.startswith("proxy_")
                })
        rows.append(row)
        await asyncio.sleep(interval)


async def fetch_healthz(host: str, port: int) -> Optional[dict]:
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write((f"GET /healthz HTTP/1.1\r\nhost: {host}\r\n"
                      "connection: close\r\n\r\n").encode())
        await writer.drain()
        _status, headers = await _read_headers(reader)
        body = b""
        async for chunk in _iter_body(reader, headers):
            body += chunk
        writer.close()
        return json.loads(body)
    except (ConnectionError, OSError, ValueError,
            asyncio.IncompleteReadError):
        return None


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def parse_tenant_spec(spec: str) -> Tuple[str, int, int]:
    parts = spec.split(":")
    if not 2 <= len(parts) <= 3 or not parts[0]:
        raise SystemExit(
            f"--tenant must be name:clients[:requests], got {spec!r}"
        )
    return parts[0], int(parts[1]), int(parts[2]) if len(parts) == 3 else 1


async def run_load(args) -> dict:
    per_tenant: Dict[str, List[ReqResult]] = {}
    tasks = []
    t0 = time.monotonic()
    timeline: List[dict] = []
    poller = None
    # Streams that resume mid-run complete byte-identically with no
    # client-visible marker — the serve-side counter is the only honest
    # source for the `resumed` summary column (ISSUE 13).
    resumes0 = None
    # Disagg transfer counters (ISSUE 20): deltas over the run, same
    # server-side-only honesty argument — a spliced page is invisible in
    # the client stream BY CONTRACT (byte identity), so only the
    # counters can say the handoff path actually ran.
    disagg0: Dict[str, float] = {}
    pre_text = await fetch_metrics(args.host, args.port, "/metrics", 5.0)
    if pre_text is not None:
        sample = parse_metrics_sample(pre_text)
        resumes0 = sample.get("serve_stream_resumes_total")
        disagg0 = {k: sample.get(k) or 0.0 for k in DISAGG_DELTA_KEYS}
    if args.metrics_poll > 0:
        poller = asyncio.create_task(metrics_poller(
            args.host, args.port, args.metrics_poll, t0, timeline,
        ))
    pending: set = set()
    turn_rows: List[dict] = []
    if args.turns > 1:
        # Multi-turn conversation mode (ISSUE 14): the herd advances in
        # LOCKSTEP turn phases — every client completes turn T before any
        # starts T+1 — so the /metrics deltas between phases attribute
        # prefill tokens and conversation-cache hits to exactly one turn.
        # With the conversation cache on, turn-2+ prefill_tokens should
        # collapse to ~the new tail per client while prompt_tokens_sent
        # keeps growing with the resent history.
        histories: Dict = {
            (name, i): []
            for name, clients, _r in args.tenants for i in range(clients)
        }
        ban = {
            tid.strip(): -100.0
            for tid in (args.ban_ids or "").split(",") if tid.strip()
        } or None
        deadline = t0 + args.timeout
        for turn in range(args.turns):
            t_turn0 = time.monotonic() - t0
            pre_text = await fetch_metrics(
                args.host, args.port, "/metrics", 5.0)
            pre_s = (parse_metrics_sample(pre_text)
                     if pre_text is not None else {})
            turn_tasks = []
            for name, clients, _requests in args.tenants:
                results = per_tenant.setdefault(name, [])
                for i in range(clients):
                    delay = (args.ramp * i / max(1, clients)
                             if turn == 0 else 0.0)
                    turn_tasks.append(asyncio.create_task(one_turn(
                        args.host, args.port, name, i, turn, histories,
                        args.prompt_pad, args.max_tokens, delay, results,
                        logit_bias=ban,
                    )))
            done, pend = await asyncio.wait(
                turn_tasks, timeout=max(0.1, deadline - time.monotonic())
            )
            for t in pend:
                t.cancel()
            tasks.extend(turn_tasks)
            pending |= pend
            post_text = await fetch_metrics(
                args.host, args.port, "/metrics", 5.0)
            post_s = (parse_metrics_sample(post_text)
                      if post_text is not None else {})

            def _delta(key):
                if key in pre_s and key in post_s:
                    return int(post_s[key] - pre_s[key])
                return None

            turn_rows.append({
                "turn": turn,
                # Window bounds (run-relative seconds): the post-run pass
                # below resolves each turn's prefill-exec split from the
                # --metrics-poll timeline samples inside this window.
                "t0_s": round(t_turn0, 1),
                "t1_s": round(time.monotonic() - t0, 1),
                "prompt_tokens_sent": sum(
                    t.result() for t in done
                    if not t.cancelled() and t.exception() is None
                ),
                "prefill_tokens": _delta("engine_prefill_tokens_total"),
                "conv_hit_tokens": _delta("engine_conv_hit_tokens_total"),
                "conv_hits": _delta("engine_conv_hits_total"),
                "pool_pages_used": post_s.get(
                    "engine_prefix_pool_blocks_used"),
                # Tier traffic attributed to this turn (ISSUE 16): how
                # many pages the drain migrated out and how many a
                # returning client's history spliced back in.
                "spill_pageouts": _delta("engine_spill_pageouts_total"),
                "spill_pageins": _delta("engine_spill_pageins_total"),
                "spill_resident": post_s.get("engine_spill_pages"),
                # Inline fallback when no poller runs: the live quantile
                # at turn end (sliding reservoir, so dominated by this
                # turn's own prefills in lockstep mode).
                "prefill_exec_p50_ms": post_s.get(
                    "engine_prefill_exec_ms_q0.5"),
            })
            if pend:
                break  # stuck clients: stop advancing turns
    else:
        for name, clients, requests in args.tenants:
            results = per_tenant.setdefault(name, [])
            for i in range(clients):
                # Stagger connection starts across the ramp so the connect
                # storm itself is not the experiment.
                delay = args.ramp * i / max(1, clients)
                tasks.append(asyncio.create_task(one_client(
                    args.host, args.port, name, i, requests,
                    args.prompt_pad, args.max_tokens, delay, results,
                )))
        done, pending = await asyncio.wait(tasks, timeout=args.timeout)
        for t in pending:
            t.cancel()
    if poller is not None:
        poller.cancel()
        await asyncio.gather(poller, return_exceptions=True)
        # Per-turn prefill-exec split from the poll timeline (ISSUE 15):
        # the LAST in-window sample wins — by lockstep construction it
        # reflects the turn's own prefills; the inline end-of-turn scrape
        # above stays as the no-poller fallback.
        for tr in turn_rows:
            samples = [
                row["engine_prefill_exec_ms_q0.5"] for row in timeline
                if "engine_prefill_exec_ms_q0.5" in row
                and tr["t0_s"] <= row["t"] <= tr["t1_s"]
            ]
            if samples:
                tr["prefill_exec_p50_ms"] = samples[-1]
    # Retrieve every task's outcome: cancelled stragglers AND tasks that
    # died with an uncaught exception (whose remaining requests would
    # otherwise vanish from the report with the exit code still 0).
    settled = await asyncio.gather(*tasks, return_exceptions=True)
    crashed = sum(1 for t, r in zip(tasks, settled)
                  if t not in pending and isinstance(r, BaseException))
    stuck = len(pending) + crashed
    for name, clients, requests in args.tenants:
        got = len(per_tenant[name])
        # Tasks cancelled or crashed mid-flight under-report; every
        # planned request must land in some bucket — mark the gap stuck.
        # (--turns mode plans one request per client per COMPLETED-or-
        # attempted turn phase.)
        expect = clients * (len(turn_rows) if args.turns > 1 else requests)
        for _ in range(expect - got):
            r = ReqResult()
            r.outcome = "stuck"
            per_tenant[name].append(r)
    wall = time.monotonic() - t0
    healthz = None
    if not args.no_healthz:
        await asyncio.sleep(0.5)  # let the server settle before leak check
        healthz = await fetch_healthz(args.host, args.port)
    resumed = None
    post_text = await fetch_metrics(args.host, args.port, "/metrics", 5.0)
    if post_text is not None and resumes0 is not None:
        resumes1 = parse_metrics_sample(post_text).get(
            "serve_stream_resumes_total")
        if resumes1 is not None:
            resumed = int(resumes1 - resumes0)
    streams_hz = (healthz or {}).get("streams") or {}
    pool_hz = (healthz or {}).get("prefix_pool") or {}
    spill_hz = pool_hz.get("spill") or {}
    spec_hz = (healthz or {}).get("spec") or {}
    disagg_hz = (healthz or {}).get("disagg") or {}
    disagg_row = None
    if post_text is not None and disagg0:
        post_sample = parse_metrics_sample(post_text)
        deltas = {
            k.replace("_total", ""): int(
                (post_sample.get(k) or 0.0) - disagg0[k]
            )
            for k in DISAGG_DELTA_KEYS
        }
        # Only report the row when the stack is actually disaggregated
        # (healthz advertises a role) or the counters moved — a plain
        # single-engine run keeps its summary schema unchanged.
        if disagg_hz or any(deltas.values()):
            disagg_row = dict(deltas, role=disagg_hz.get("role"))
    out = {
        "clients": sum(c for _n, c, _r in args.tenants),
        "wall_s": round(wall, 2),
        "stuck_tasks": stuck,
        # Streams that reattached mid-run after a tunnel reset (ISSUE
        # 13): byte-identical to the client, so only the server counter
        # can report them; None = the scrape was unavailable.
        "resumed": resumed,
        # ISSUE 17: the run's speculative-decode yield — lifetime verify
        # acceptance over the whole run (None when spec was off or the
        # scrape unavailable); the adaptive-K controller's input.
        "spec_accept_rate": (
            None if not spec_hz.get("proposed_total")
            else round(spec_hz["accepted_total"]
                       / spec_hz["proposed_total"], 3)
        ),
        # Disaggregated prefill/decode (ISSUE 20): pages shipped/spliced
        # and handoff/fallback/affinity deltas over the run; None when
        # the stack is not disaggregated (schema stays stable).
        "disagg": disagg_row,
        "tenants": tenant_rows(per_tenant),
        # Leak check: in-flight, occupancy, AND the detached-stream
        # registry must be back to zero once every client is done — a
        # nonzero value here is a leaked slot or a leaked replay journal.
        "healthz_after": None if healthz is None else {
            "status": healthz.get("status"),
            "inflight_requests": healthz.get("inflight_requests"),
            "queue_depth": healthz.get("queue_depth"),
            "slot_occupancy": healthz.get("slot_occupancy"),
            "streams_detached": streams_hz.get("detached"),
            "replay_buffer_bytes": streams_hz.get("replay_buffer_bytes"),
            # ISSUE 14 leak gate: page reservations must return to zero
            # once every stream finished — a leftover grant pins pool
            # pressure forever (the deadline/cancel/owner-death paths the
            # engine's generate() finally releases).
            "pool_pages_reserved": pool_hz.get("pages_reserved"),
            # ISSUE 16 leak gate: the spill tier's in-flight I/O ledger
            # must drain to zero — a stuck counter is a page-out/page-in
            # whose executor copy never committed or aborted.  Resident
            # shadow pages/bytes are recorded for the report but NOT
            # gated: the tier is a cache, residency persists by design.
            "pool_spill_inflight": spill_hz.get("inflight"),
            "pool_spill_pages": spill_hz.get("pages"),
            "pool_spill_bytes": spill_hz.get("bytes"),
            # ISSUE 17 leak gate: the per-slot draft-history registry
            # must be empty once every stream finished — an entry left
            # behind by a cancel/eviction path pins stale proposals (and
            # their EMA) to whatever request lands in the slot next.
            "spec_hist_entries": spec_hz.get("hist_entries"),
            # ISSUE 20 leak gate: the KV-transfer in-flight ledger must
            # be zero at rest — a stuck value is an export/splice whose
            # executor hop never finished (its finally never ran).
            "kv_xfer_inflight": disagg_hz.get("xfer_inflight"),
            "tenants": healthz.get("tenants"),
            "retry_after_s": healthz.get("retry_after_s"),
        },
    }
    if args.turns > 1:
        out["turns"] = turn_rows
    if args.metrics_poll > 0:
        # The in-run timeline next to the summary row (--metrics-poll):
        # sheds/TTFT/queue depth sampled every poll interval, so a PERF
        # round plots the run's shape instead of its end state.
        out["metrics_timeline"] = timeline
    return out


def spawn_stack(args) -> Tuple[subprocess.Popen, int]:
    # One process for each chip: this load generator stays off JAX, and
    # the stack it spawns takes its platform from the caller's environment
    # (`make loadgen` passes JAX_PLATFORMS=cpu) and logs the device it got.
    env = dict(os.environ)
    cmd = [
        sys.executable, "-m", "p2p_llm_tunnel_tpu.testing.local_stack",
        "--port", "0", "--slots", str(args.stack_slots),
        "--max-seq", str(args.stack_max_seq),
        "--max-waiting", str(args.stack_max_waiting),
    ]
    if args.stack_tenant_weights:
        cmd += ["--tenant-weights", args.stack_tenant_weights]
    if args.stack_no_fair:
        cmd += ["--no-fair-admission"]
    if args.turns > 1:
        # The conversation-cache experiment needs the pool server-side.
        cmd += ["--prefix-cache"]
    if args.stack_pool_blocks:
        cmd += ["--prefix-pool-blocks", str(args.stack_pool_blocks)]
    if args.stack_spill_pages:
        # Memory-pressure experiment (ISSUE 16): host-RAM spill tier on
        # the server side, sized from the CLI so the capacity-cliff run
        # can shrink the pool and still keep returning turns warm.
        cmd += ["--spill-pages", str(args.stack_spill_pages)]
    if args.stack_disagg:
        # Disaggregated A/B (ISSUE 20): prefill-role + decode-role
        # engines behind one fabric proxy with KV-page handoff.
        cmd += ["--disagg"]
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
    )
    deadline = time.monotonic() + args.spawn_timeout
    port = None
    assert proc.stdout is not None
    while time.monotonic() < deadline:
        # readline() on a silent pipe blocks forever — a stack wedged in
        # warmup that prints NOTHING would hang loadgen (and the chaos
        # gate) past --spawn-timeout.  select() bounds each wait, so the
        # deadline is enforced even with zero output.
        ready, _, _ = select.select(
            [proc.stdout], [], [], max(0.1, deadline - time.monotonic())
        )
        if not ready:
            break
        line = proc.stdout.readline()
        if not line:
            break
        if line.startswith("LOADGEN_STACK_PORT="):
            port = int(line.strip().split("=", 1)[1])
            break
    if port is None:
        proc.terminate()
        raise SystemExit("stack never reported a port (warmup failure?)")
    return proc, port


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="loadgen", description=__doc__.splitlines()[0],
    )
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--tenant", action="append", default=[],
                    help="name:clients[:requests] (repeatable; default "
                         "herd:500)")
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--ban-ids", default=DEFAULT_BAN_IDS,
                    help="turns mode: comma-separated token ids biased out "
                         "of sampling (-100) so invisible specials can't "
                         "break the replayed conversation's byte chain; "
                         "'' disables (real-checkpoint deployments)")
    ap.add_argument("--turns", type=int, default=1,
                    help="multi-turn conversation mode (ISSUE 14): each "
                         "client replays its full growing history per "
                         "turn, N lockstep turn phases; the report gains "
                         "a per-turn 'turns' table (prompt tokens resent "
                         "vs prefill tokens computed vs conversation-"
                         "cache hits) — the out-of-process driver for "
                         "the conversation cache (1 = classic mode)")
    ap.add_argument("--prompt-pad", type=int, default=24,
                    help="prompt length in bytes (byte tokenizer: ~tokens)")
    ap.add_argument("--ramp", type=float, default=2.0,
                    help="seconds over which each tenant's connects stagger")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="whole-run budget; clients past it count as STUCK")
    ap.add_argument("--no-healthz", action="store_true",
                    help="skip the post-run /healthz leak check")
    ap.add_argument("--metrics-poll", type=float, default=0.0,
                    help="sample the stack's /metrics every S seconds "
                         "during the herd and emit the rows as a "
                         "'metrics_timeline' key next to the summary "
                         "(sheds, queue depth, token counters, TTFT/TTFB "
                         "quantiles; 0 = off)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output only")
    ap.add_argument("--spawn", action="store_true",
                    help="spawn p2p_llm_tunnel_tpu.testing.local_stack in "
                         "a subprocess and aim at it")
    ap.add_argument("--spawn-timeout", type=float, default=900.0)
    ap.add_argument("--stack-slots", type=int, default=32)
    ap.add_argument("--stack-max-seq", type=int, default=256)
    ap.add_argument("--stack-max-waiting", type=int, default=600)
    ap.add_argument("--stack-tenant-weights", default="")
    ap.add_argument("--stack-no-fair", action="store_true")
    ap.add_argument("--stack-pool-blocks", type=int, default=0,
                    help="override the spawned stack's prefix pool "
                         "capacity in KV blocks (0 = stack default)")
    ap.add_argument("--stack-spill-pages", type=int, default=0,
                    help="host-RAM spill tier pages on the spawned stack "
                         "(0 = off)")
    ap.add_argument("--stack-disagg", action="store_true",
                    default=os.environ.get("TUNNEL_DISAGG") == "1",
                    help="spawn the TWO-engine disaggregated stack "
                         "(prefill-role + decode-role peers behind one "
                         "fabric proxy, ISSUE 20) instead of the "
                         "single-engine mux stack")
    args = ap.parse_args(argv)
    args.tenants = [parse_tenant_spec(s) for s in (args.tenant or
                                                   ["herd:500"])]

    proc = None
    try:
        if args.spawn:
            proc, args.port = spawn_stack(args)
            if not args.json:
                print(f"stack up on port {args.port}", file=sys.stderr)
        out = asyncio.run(run_load(args))
    finally:
        if proc is not None:
            proc.terminate()
            try:
                proc.wait(10)
            except subprocess.TimeoutExpired:
                proc.kill()
    print(json.dumps(out, indent=None if args.json else 2), flush=True)
    total_stuck = sum(r["stuck"] for r in out["tenants"])
    # The leak check is part of the gate, not advisory: "zero stuck
    # streams or leaked slots" means a nonzero post-run in-flight/queue/
    # occupancy (or an unreachable /healthz when the check was requested)
    # must fail the run the same way a stuck stream does.
    leaked = False
    if not args.no_healthz:
        hz = out.get("healthz_after")
        leaked = hz is None or any(
            hz.get(k) or 0
            for k in ("inflight_requests", "queue_depth", "slot_occupancy",
                      "streams_detached", "replay_buffer_bytes",
                      "pool_pages_reserved", "pool_spill_inflight",
                      "spec_hist_entries", "kv_xfer_inflight")
        )
        if leaked:
            detail = ("unreachable" if hz is None
                      else f"not clean: {hz!r}")
            print(f"# LEAK: post-run /healthz {detail}", file=sys.stderr)
    if not args.json:
        for r in out["tenants"]:
            print(
                f"# {r['tenant']}: {r['ok']}/{r['requests']} ok, "
                f"{r['shed_429']} shed, {r['errors']} errors, "
                f"{r['stuck']} stuck; ttft p50/p99/p999 = "
                f"{r['ttft_p50_ms']}/{r['ttft_p99_ms']}/"
                f"{r['ttft_p999_ms']} ms",
                file=sys.stderr,
            )
        if out.get("resumed") is not None:
            print(f"# resumed mid-run (tunnel resets survived): "
                  f"{out['resumed']}", file=sys.stderr)
        if out.get("disagg"):
            d = out["disagg"]
            print(
                f"# disagg: {d.get('engine_pages_shipped')} pages "
                f"shipped / {d.get('engine_pages_spliced')} spliced "
                f"({d.get('engine_page_xfer_bytes')} B); handoffs "
                f"{d.get('proxy_disagg_handoffs')}, fallbacks "
                f"{d.get('proxy_disagg_fallbacks')}, affinity hits "
                f"{d.get('proxy_affinity_hits')}",
                file=sys.stderr,
            )
        for tr in out.get("turns", []):
            pf = tr.get("prefill_exec_p50_ms")
            print(
                f"# turn {tr['turn']}: sent {tr['prompt_tokens_sent']} "
                f"prompt tokens, prefilled {tr['prefill_tokens']}, "
                f"conversation hits {tr['conv_hits']} "
                f"({tr['conv_hit_tokens']} tokens reused), "
                f"prefill-exec p50 "
                f"{'-' if pf is None else f'{pf:.1f}'} ms",
                file=sys.stderr,
            )
    return 1 if (total_stuck or leaked) else 0


if __name__ == "__main__":
    sys.exit(main())
