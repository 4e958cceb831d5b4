#!/usr/bin/env python
"""Summarize a Chrome trace-event capture from ``GET /healthz?trace=1``.

Stdlib-only companion to ``utils/tracing.py``: groups the journal's spans
by propagated trace id and prints one line per request — total span, the
TTFT decomposition (queue-wait + prefill-exec), park time, outcome — plus
aggregate tail percentiles across the capture.  The same JSON loads in
``chrome://tracing`` / Perfetto for the visual timeline; this is the
terminal-sized view.

Usage:
    curl -s 'http://127.0.0.1:8000/healthz?trace=1' > trace.json   # via proxy
    python scripts/traceview.py trace.json
    python scripts/traceview.py trace.json --json     # machine-readable
    python scripts/traceview.py trace.json --flight   # the engine loop
    python scripts/traceview.py trace.json --startup  # process start -> ready
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

# Runnable as `python scripts/traceview.py` from anywhere: put the repo
# root ahead of scripts/ so the package import below resolves.
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _pct(xs: List[float], p: float) -> Optional[float]:
    """The registry's shared nearest-rank estimator, so traceview tails
    can never diverge from /metrics quantiles over the same data."""
    from p2p_llm_tunnel_tpu.utils.metrics import nearest_rank

    return nearest_rank(xs, p) if xs else None


def summarize(trace: dict) -> dict:
    """Per-request rollup of a Chrome trace-event object.

    Returns ``{"requests": [...], "aggregate": {...}, "engine_scope":
    {...}}`` where each request entry carries ms durations keyed off the
    span names in utils.tracing.SPAN_CATALOG."""
    from p2p_llm_tunnel_tpu.utils.tracing import validate_chrome_trace

    validate_chrome_trace(trace)
    by_trace: Dict[str, List[dict]] = {}
    engine_scope: Dict[str, List[float]] = {}
    for ev in trace["traceEvents"]:
        if ev.get("ph") == "M":
            continue
        args = ev.get("args", {})
        tid = args.get("trace_id")
        if tid is None:
            # (the start-up journal's lane has a view of its own)
            if ev.get("ph") == "X" and ev.get("cat") != "startup":
                engine_scope.setdefault(ev["name"], []).append(
                    ev["dur"] / 1000.0
                )
            continue
        by_trace.setdefault(tid, []).append(ev)

    requests = []
    for tid, evs in sorted(
        by_trace.items(), key=lambda kv: min(e["ts"] for e in kv[1])
    ):
        spans: Dict[str, List[dict]] = {}
        events: Dict[str, List[dict]] = {}
        for e in evs:
            (spans if e["ph"] == "X" else events).setdefault(
                e["name"], []
            ).append(e)

        def earliest(name: str) -> Optional[dict]:
            lst = spans.get(name)
            return min(lst, key=lambda e: e["ts"]) if lst else None

        # One HTTP request per trace at the proxy, but one trace can hold
        # SEVERAL engine generations (n>1 / prompt lists share the
        # propagated context): children are matched to their generation by
        # parent linkage — never by name, which would pair generation B's
        # first token with generation A's span — and the row reports the
        # first generation plus a generation count.
        gens = sorted(spans.get("engine.request", ()),
                      key=lambda e: e["ts"])
        eng = gens[0] if gens else None

        def child_dur(name: str) -> Optional[float]:
            if eng is None:
                return None
            for e in spans.get(name, ()):
                if e["args"].get("parent_id") == eng["args"]["span_id"]:
                    return e["dur"] / 1000.0
            return None

        ttft = None
        if eng is not None:
            for e in events.get("engine.first_token", ()):
                if e["args"].get("parent_id") == eng["args"]["span_id"]:
                    ttft = (e["ts"] - eng["ts"]) / 1000.0
                    break
        parks = spans.get("engine.prefix_park", ())
        prx = earliest("proxy.request")
        top = prx or earliest("serve.dispatch") or eng
        # Tenant identity (ISSUE 7): stamped on proxy.request and
        # engine.request span attrs when the ingress derived one.
        tenant = None
        for e in (prx, eng):
            if e is not None and e["args"].get("tenant"):
                tenant = e["args"]["tenant"]
                break
        # Per-peer attribution (ISSUE 9): serve.dispatch spans carry the
        # fabric peer id the serve side learned at handshake.  `peers`
        # lists every peer that touched the request — a failover shows
        # two — and `peer` is the one whose dispatch parented the first
        # engine generation (i.e. the peer that actually SERVED it),
        # falling back to proxy.request's own peer attr (the peer that
        # completed the relay) for captures without engine spans.
        dispatches = spans.get("serve.dispatch", ())
        peers = sorted({
            e["args"]["peer"] for e in dispatches if e["args"].get("peer")
        })
        peer = None
        if eng is not None:
            eng_parent = eng["args"].get("parent_id")
            for e in dispatches:
                if (eng_parent and e["args"].get("span_id") == eng_parent
                        and e["args"].get("peer")):
                    peer = e["args"]["peer"]
                    break
        if peer is None and prx is not None:
            peer = prx["args"].get("peer")
        if peer is None and len(peers) == 1:
            peer = peers[0]
        requests.append({
            "trace_id": tid,
            "tenant": tenant,
            "peer": peer,
            "peers": peers,
            "path": (top or {}).get("args", {}).get("path"),
            "status": (prx or {}).get("args", {}).get("status"),
            "finish": (eng or {}).get("args", {}).get("finish"),
            "total_ms": top["dur"] / 1000.0 if top is not None else None,
            "ttft_ms": ttft,
            "queue_wait_ms": child_dur("engine.queue_wait"),
            "prefill_exec_ms": child_dur("engine.prefill_exec"),
            "park_ms": (sum(e["dur"] for e in parks) / 1000.0
                        if parks else None),
            "generations": len(gens),
            "layers": sorted({e["cat"] for e in evs}),
            "spans": len(evs),
        })

    ttfts = [r["ttft_ms"] for r in requests if r["ttft_ms"] is not None]
    aggregate = {
        "requests": len(requests),
        "ttft_p50_ms": _pct(ttfts, 50),
        "ttft_p99_ms": _pct(ttfts, 99),
        "ttft_p999_ms": _pct(ttfts, 99.9),
    }
    # Per-tenant TTFT rollup (ISSUE 7) — present only when the capture
    # carries tenant identities, so untenanted traces render unchanged.
    if any(r["tenant"] for r in requests):
        by_tenant: Dict[str, List[float]] = {}
        counts: Dict[str, int] = {}
        for r in requests:
            t = r["tenant"] or "-"
            counts[t] = counts.get(t, 0) + 1
            if r["ttft_ms"] is not None:
                by_tenant.setdefault(t, []).append(r["ttft_ms"])
        aggregate["by_tenant"] = {
            t: {
                "requests": counts[t],
                "ttft_p50_ms": _pct(by_tenant.get(t, []), 50),
                "ttft_p99_ms": _pct(by_tenant.get(t, []), 99),
                "ttft_p999_ms": _pct(by_tenant.get(t, []), 99.9),
            }
            for t in sorted(counts)
        }
    # Per-peer TTFT rollup (ISSUE 9) — present only when the capture
    # carries fabric peer identities (stitched fleet traces, fabric
    # peers), so single-peer captures render unchanged.  `failovers`
    # counts requests that touched more than one peer: their TTFT
    # attributes to the peer that finally served them, and the count says
    # how much of a peer's tail is failover recovery rather than its own
    # serving latency.
    if any(r["peer"] or r["peers"] for r in requests):
        by_peer: Dict[str, List[float]] = {}
        pcounts: Dict[str, int] = {}
        pfail: Dict[str, int] = {}
        for r in requests:
            p = r["peer"] or "-"
            pcounts[p] = pcounts.get(p, 0) + 1
            if len(r["peers"]) > 1:
                pfail[p] = pfail.get(p, 0) + 1
            if r["ttft_ms"] is not None:
                by_peer.setdefault(p, []).append(r["ttft_ms"])
        aggregate["by_peer"] = {
            p: {
                "requests": pcounts[p],
                "failovers": pfail.get(p, 0),
                "ttft_p50_ms": _pct(by_peer.get(p, []), 50),
                "ttft_p99_ms": _pct(by_peer.get(p, []), 99),
                "ttft_p999_ms": _pct(by_peer.get(p, []), 99.9),
            }
            for p in sorted(pcounts)
        }
    scope = {
        name: {"count": len(xs), "p50_ms": _pct(xs, 50)}
        for name, xs in sorted(engine_scope.items())
    }
    return {"requests": requests, "aggregate": aggregate,
            "engine_scope": scope}


def summarize_flight(trace: dict, tail: int = 12) -> dict:
    """Rollup of the engine flight-recorder tracks in a capture (ISSUE
    12): per-iteration scheduler decisions (``engine.flight`` slices on
    the ``engine-flight`` lane, exported by ``/healthz?trace=1``).

    Returns aggregates over every iteration in the capture — totals of
    admitted/prefill/decode work, budget and queue-depth distribution,
    cold-compile count, where the iterations' wall went (``split``, ISSUE
    57) — plus the last ``tail`` raw records (the part a postmortem reader
    scans first)."""
    from p2p_llm_tunnel_tpu.utils.flight import LOOP_PARTS
    from p2p_llm_tunnel_tpu.utils.tracing import validate_chrome_trace

    parts = tuple(LOOP_PARTS.values())
    validate_chrome_trace(trace)
    rows = sorted(
        (
            ev for ev in trace["traceEvents"]
            if ev.get("ph") == "X" and ev.get("name") == "engine.flight"
        ),
        key=lambda e: e["ts"],
    )
    args = [r.get("args", {}) for r in rows]

    def col(key):
        return [a.get(key) for a in args if a.get(key) is not None]

    budgets = col("budget_tokens")
    queue = col("queue_depth")
    # Where the iterations' wall went (ISSUE 57): the seven parts that
    # tile an iteration, and what lies across them.  Records from before
    # the split (no wait_ms) give no such section.
    split = None
    timed = [a for a in args if "wait_ms" in a]
    if timed:
        def total(key):
            return round(sum(float(a.get(key) or 0.0) for a in timed), 3)

        dur, wait = total("dur_ms"), total("wait_ms")
        longest = max(timed, key=lambda a: (float(a["dur_ms"])
                                            - float(a["wait_ms"])))
        split = {
            "iterations": len(timed),
            "dur_ms": dur,
            "host_ms": round(dur - wait, 3),
            "host_share_pct": 100.0 * (dur - wait) / dur if dur else None,
            "parts_ms": {part: total(part) for part in parts},
            **{key: total(key) for key in
               ("wait_ms", "exec_ms", "lag_ms", "evict_ms", "gc_ms")},
            "evicted_pages": int(total("evicted_pages")),
            "gc_full": int(total("gc_full")),
            "longest_hold": {k: longest.get(k) for k in
                             ("iter", "dur_ms", "wait_ms", "lag_ms",
                              "evict_ms", "gc_ms", *parts)},
        }
    return {
        "split": split,
        "iterations": len(rows),
        "admitted_total": sum(col("admitted")),
        "prefill_rows_total": sum(col("prefill_rows")),
        "decode_steps_total": sum(col("decode_steps")),
        "cold_compiles": sum(col("cold_compiles")),
        "queue_depth_max": max(queue) if queue else 0,
        "budget_tokens_p50": _pct([float(b) for b in budgets], 50),
        "active_slots_max": max(col("active_slots") or [0]),
        "tail": [dict(a) for a in args[-tail:]],
    }


def _print_flight(out: dict) -> None:
    print(
        f"flight: {out['iterations']} iteration(s); admitted "
        f"{out['admitted_total']}, prefill rows "
        f"{out['prefill_rows_total']}, decode steps "
        f"{out['decode_steps_total']}, cold compiles "
        f"{out['cold_compiles']}; queue depth max "
        f"{out['queue_depth_max']}, budget p50 "
        f"{out['budget_tokens_p50']}, active slots max "
        f"{out['active_slots_max']}"
    )
    split = out.get("split")
    if split:
        share = split["host_share_pct"]
        print(
            f"  wall {split['dur_ms']:.1f} ms over {split['iterations']} "
            f"iteration(s): host {split['host_ms']:.1f} ms"
            + (f" ({share:.1f} %)" if share is not None else "")
            + f", waiting for the chip {split['wait_ms']:.1f} ms")
        print("  parts: " + ", ".join(
            f"{part[:-3]} {ms:.1f}" for part, ms in split["parts_ms"].items()))
        print(
            f"  executor calls {split['exec_ms']:.1f} ms, event-loop lag "
            f"{split['lag_ms']:.1f} ms, eviction {split['evict_ms']:.1f} ms "
            f"({split['evicted_pages']} pages), collector "
            f"{split['gc_ms']:.1f} ms ({split['gc_full']} full)")
        hold = split["longest_hold"]
        print(
            f"  longest hold: iteration {hold['iter']}, "
            f"{hold['dur_ms'] - hold['wait_ms']:.1f} ms of host time ("
            + ", ".join(f"{part[:-3]} {hold[part]}"
                        for part in split["parts_ms"])
            + f"; lag {hold['lag_ms']}, evict {hold['evict_ms']}, gc "
            f"{hold['gc_ms']})")
    if not out["tail"]:
        return
    cols = ("iter", "queue_depth", "backlog_rows", "budget_tokens",
            "admitted", "prefill_rows", "decode_steps", "active_slots",
            "cold_compiles")
    print("  ".join(f"{c:>13}" for c in cols))
    for rec in out["tail"]:
        print("  ".join(f"{rec.get(c, '-')!s:>13}" for c in cols))


def summarize_startup(trace: dict, slowest: int = 5) -> dict:
    """Rollup of the start-up journal's lane in a capture (ISSUE 40): the
    ``startup.*`` spans of ``/healthz?trace=1``.  Seconds by phase in the
    order they began (``startup.process`` is the whole, from the kernel's
    start of the process to ``startup.ready``), the warmed programs with
    Python's part and XLA's apart, how many the compile cache on disk
    held, and the ``slowest`` programs."""
    from p2p_llm_tunnel_tpu.utils.tracing import validate_chrome_trace

    validate_chrome_trace(trace)
    evs = sorted(
        (ev for ev in trace["traceEvents"]
         if ev.get("ph") == "X" and ev.get("cat") == "startup"),
        key=lambda e: e["ts"],
    )
    phases = [
        {"span": ev["name"], "seconds": ev["dur"] / 1e6,
         "attrs": ev.get("args", {})}
        for ev in evs if ev["name"] != "startup.program"
    ]
    from p2p_llm_tunnel_tpu.utils.flight import program_rollup

    programs = [dict(ev.get("args", {}), seconds=ev["dur"] / 1e6)
                for ev in evs if ev["name"] == "startup.program"]
    return {"phases": phases, **program_rollup(programs, slowest)}


def _print_startup(out: dict) -> None:
    if not out["phases"]:
        print("startup: no startup.* spans in this capture")
        return
    whole = next((p["seconds"] for p in out["phases"]
                  if p["span"] == "startup.process"), None)
    print(f"{'span':24} {'seconds':>9} {'share':>6}  attrs")
    for p in out["phases"]:
        share = (f"{100.0 * p['seconds'] / whole:5.1f}%"
                 if whole and p["span"] != "startup.tunnel" else "     -")
        attrs = " ".join(f"{k}={v}" for k, v in p["attrs"].items())
        print(f"{p['span']:24} {p['seconds']:9.3f} {share}  {attrs}")
    per = out["trace_lower_s_per_program"]
    print(f"-- {out['programs']} program(s); compile cache on disk: "
          f"{out['persistent_hits']} hit(s), {out['persistent_misses']} "
          f"miss(es); tracing + lowering "
          f"{'-' if per is None else f'{per:.3f}'} s a program")
    for p in out["slowest"]:
        print(f"-- {p.get('key', '?'):24} {p['seconds']:7.3f}s = lower "
              f"{p.get('trace_lower_s')} + compile {p.get('compile_s')} "
              f"(persistent_hit {p.get('persistent_hit')}, thread "
              f"{p.get('thread')})")


def _fmt(v: Optional[float]) -> str:
    return f"{v:8.1f}" if v is not None else "       -"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="traceview",
        description="Summarize a /healthz?trace=1 Chrome trace capture.",
    )
    ap.add_argument("path", help="trace JSON file ('-' = stdin)")
    ap.add_argument("--json", action="store_true",
                    help="emit the rollup as JSON instead of a table")
    ap.add_argument("--flight", action="store_true",
                    help="summarize the engine flight-recorder tracks "
                         "(per-iteration scheduler decisions) instead of "
                         "the per-request view")
    ap.add_argument("--startup", action="store_true",
                    help="summarize the start-up journal's lane: seconds "
                         "by phase from process start to ready, and the "
                         "five slowest warmed programs")
    args = ap.parse_args(argv)
    raw = (sys.stdin.read() if args.path == "-"
           else open(args.path).read())
    for wanted, rollup, show in (
            (args.flight, summarize_flight, _print_flight),
            (args.startup, summarize_startup, _print_startup)):
        if wanted:
            out = rollup(json.loads(raw))
            if args.json:
                print(json.dumps(out, indent=2))
            else:
                show(out)
            return 0
    out = summarize(json.loads(raw))
    if args.json:
        print(json.dumps(out, indent=2))
        return 0
    print(f"{'trace':12} {'total':>8} {'ttft':>8} {'queue':>8} "
          f"{'prefill':>8} {'park':>8}  layers / finish")
    for r in out["requests"]:
        layers = "->".join(
            t for t in ("proxy", "serve", "engine") if t in r["layers"]
        )
        where = f" @ {'+'.join(r['peers'])}" if r["peers"] else ""
        print(f"{r['trace_id'][:12]:12} {_fmt(r['total_ms'])} "
              f"{_fmt(r['ttft_ms'])} {_fmt(r['queue_wait_ms'])} "
              f"{_fmt(r['prefill_exec_ms'])} {_fmt(r['park_ms'])}  "
              f"{layers} / {r['finish'] or '-'}{where}")
    agg = out["aggregate"]
    print(f"-- {agg['requests']} request(s); engine TTFT ms "
          f"p50={agg['ttft_p50_ms']} p99={agg['ttft_p99_ms']} "
          f"p999={agg['ttft_p999_ms']}")
    for t, row in (agg.get("by_tenant") or {}).items():
        print(f"-- tenant {t}: n={row['requests']} TTFT ms "
              f"p50={row['ttft_p50_ms']} p99={row['ttft_p99_ms']} "
              f"p999={row['ttft_p999_ms']}")
    for p, row in (agg.get("by_peer") or {}).items():
        print(f"-- peer {p}: n={row['requests']} "
              f"failovers={row['failovers']} TTFT ms "
              f"p50={row['ttft_p50_ms']} p99={row['ttft_p99_ms']} "
              f"p999={row['ttft_p999_ms']}")
    for name, s in out["engine_scope"].items():
        print(f"-- {name}: n={s['count']} p50={s['p50_ms']:.1f} ms")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:
        # `traceview … | head` is a normal way to skim a big capture.
        sys.exit(0)
