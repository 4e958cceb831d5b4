"""How long a request held a slot with nothing of its own in flight, before
its first token (ms): its ``engine.prefill_exec`` span less the union of its
``engine.prefill_part`` spans (one for each prefill dispatch that carried
its rows, over that dispatch's interval), matched by trace id; a percentile
over the requests whose span started inside the timed window.  The guide's
self time, for each request."""

from benchmarks.dispatch_trace import request_parts
from benchmarks.stats import percentile
from benchmarks.trace_reduce import merge


def waits_ms(ctx):
    t0, t1 = ctx.load.t0 * 1e6, ctx.load.t1 * 1e6
    parts = request_parts(ctx.spans)
    out = []
    for ev in ctx.spans:
        if not (ev.get("name") == "engine.prefill_exec"
                and ev.get("ph") == "X" and t0 <= ev["ts"] < t1):
            continue
        args = ev.get("args") or {}
        own = parts.get(args.get("trace_id"))
        # a program from before the ledger names no parts: nothing to read
        if "parts" not in args or not own or len(own) != args["parts"]:
            continue
        lo, hi = ev["ts"], ev["ts"] + ev["dur"]
        covered = sum(
            min(e, hi) - max(s, lo)
            for s, e in merge([(p["ts"], p["ts"] + p["dur"]) for p in own])
            if e > lo and s < hi)
        out.append((ev["dur"] - covered) / 1000.0)
    return out


def read(ctx, percentile_of: float):
    waits = waits_ms(ctx)
    if not waits:
        return None
    return percentile(waits, percentile_of)
