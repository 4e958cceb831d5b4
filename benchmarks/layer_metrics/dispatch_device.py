"""Device time of the runs that the program's own dispatch records account
for, from the device trace joined to the journal (benchmarks/
dispatch_trace.py: clock fit, pairing of runs with records, scopes).

``what="step"``: device time of the decode runs that lie inside
``bench_window`` and are paired with a dispatch record, over the steps
those records say they carried (ms a step).  No layer count and no count of
loop-body passes: the program says how many steps each burst ran.

``what="own"``: for each request whose ``engine.prefill_exec`` lies inside
``bench_window``, the device seconds of the prefill runs paired with its
``engine.prefill_part`` records over the span's length (%); the median.
A run carries several requests' rows and counts whole for each of them.
Prints its sample count, and is nothing under ``least`` requests.

``what="scopes"``: the share (%) of the window's device self-time spent in
operations under the named scopes."""

from benchmarks import dispatch_trace
from benchmarks.stack import say
from benchmarks.stats import percentile


def step_ms(summary):
    runs = [r for r in dispatch_trace.in_window(
                summary, summary["pairs"].get("engine.decode_burst", []))
            if r["annotation"]]
    steps = sum(r["annotation"]["steps"] for r in runs)
    if not steps:
        return None
    say(f"decode by the ledger: {len(runs)} paired runs, {steps} steps")
    return 1000.0 * sum(r["end"] - r["start"] for r in runs) / steps


def own_pct(ctx, summary, least: int):
    by_seq = {r["annotation"]["seq"]: r["end"] - r["start"]
              for r in summary["pairs"].get("engine.prefill_segment", [])
              if r["annotation"]}
    offset = summary["fit"]["offset_s"]
    w0, w1 = summary["window"]
    parts = dispatch_trace.request_parts(ctx.spans)
    shares = []
    for ev in ctx.spans:
        if ev.get("name") != "engine.prefill_exec" or ev.get("ph") != "X":
            continue
        start = ev["ts"] / 1e6 + offset
        seqs = [p["args"]["seq"] for p in
                parts.get((ev.get("args") or {}).get("trace_id"), [])]
        if (start < w0 or start + ev["dur"] / 1e6 > w1 or not ev["dur"]
                or not seqs or any(seq not in by_seq for seq in seqs)):
            continue
        shares.append(100.0 * sum(by_seq[seq] for seq in seqs)
                      / (ev["dur"] / 1e6))
    say(f"prefill_own_dev_pct: {len(shares)} requests with their "
        f"prefill_exec inside the traced window and every part paired "
        f"(least {least})")
    if len(shares) < least:
        return None
    return percentile(shares, 50)


def read(ctx, what: str, scopes=(), least: int = 5):
    summary = dispatch_trace.of(ctx)
    if summary is None:
        return None
    if what == "scopes":
        own = summary["scopes"]
        if not own:
            return None
        return 100.0 * sum(own.get(s, 0.0) for s in scopes) / sum(own.values())
    if summary["fit"] is None or not summary["pairs"]:
        return None
    if what == "step":
        return step_ms(summary)
    if what == "own":
        return own_pct(ctx, summary, least)
    raise ValueError(f"unknown quantity {what!r}")
