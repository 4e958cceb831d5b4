"""Roofline shares (%) of the latent-attention, routed-expert family, from
the device trace joined to the program's dispatch ledger.

``what="decode_step"``: the least time the chip could take for a decode
step (benchmarks/mla_moe_roofline.py: the configuration's sizes, the live
rows and tokens the client had in flight during the traced window, and the
held experts each step's routing touched and the assignments it made to
them, as the window's ``engine.decode_burst`` records count them) over the
step's measured device time, that of the decode runs the records account
for (``dispatch_device``'s ``step``).

``what="experts"``: the grouped expert products alone.  Over the device
runs that lie inside the traced window and are paired with a dispatch
record: the sum, run by run, of the least time for that dispatch's grouped
products (the touched experts' weights read once, its held assignments'
arithmetic), over the device self-time of the operations under the scope
``moe_experts`` that start inside those runs.  The scope also holds the
gather of the sorted rows and the scatter of the results back to their
tokens, so the share is of the whole routed product as the program runs it.

Nothing to read without a device trace, without the ledger, or of a program
whose records carry no counts.
"""

import os

from benchmarks import dispatch_trace, mla_moe_roofline
from benchmarks.correctness import load_module
from benchmarks.stack import say

HERE = os.path.dirname(os.path.abspath(__file__))


def _sibling(name: str):
    """Another reader of this directory."""
    return load_module(os.path.join(HERE, name + ".py"))


def _records_by_seq(ctx):
    ledger = _sibling("moe_ledger")
    return {ev["args"]["seq"]: ev["args"] for ev in ctx.spans
            if ev.get("name") in ledger.SPANS and ev.get("ph") == "X"
            and all(k in (ev.get("args") or {}) for k in ledger.KEYS)}


def _paired_in_window(summary, span, by_seq):
    """[(run, record)] of the device runs inside the window whose dispatch
    record carries the counts."""
    runs = dispatch_trace.in_window(summary, summary["pairs"].get(span, []))
    return [(r, by_seq[r["annotation"]["seq"]]) for r in runs
            if r["annotation"] and r["annotation"]["seq"] in by_seq]


def decode_step(ctx, summary, by_seq):
    step_ms = _sibling("dispatch_device").read(ctx, "step")
    bursts = _paired_in_window(summary, "engine.decode_burst", by_seq)
    steps = sum(rec["steps"] for _run, rec in bursts)
    if not step_ms or not steps:
        return None
    touched = sum(rec["moe_experts_touched"] for _r, rec in bursts) / steps
    held = sum(rec["moe_held"] for _r, rec in bursts) / steps
    rows, tokens = _sibling("roofline_share").live_rows_and_tokens(ctx)
    least = mla_moe_roofline.least_step_seconds(
        ctx.config, ctx.peaks, rows, tokens, touched, held)
    say(f"mla-moe roofline: {rows:.1f} rows, {tokens:.0f} live tokens, "
        f"{touched:.1f} held experts touched and {held:.1f} held "
        f"assignments a step; least step {least['seconds'] * 1000:.3f} ms, "
        f"bound by {least['bound']} (bytes {least['by_bytes_s'] * 1000:.3f} "
        f"ms, flops {least['by_flops_s'] * 1000:.3f} ms); measured "
        f"{step_ms:.3f} ms")
    return 100.0 * least["seconds"] * 1000.0 / step_ms


def experts(ctx, summary, by_seq):
    paired = (_paired_in_window(summary, "engine.decode_burst", by_seq)
              + _paired_in_window(summary, "engine.prefill_segment", by_seq))
    if not paired:
        return None
    own = _sibling("scope_share").self_time_by_scope(
        ctx, within=[(r["start"], r["end"]) for r, _rec in paired])
    spent = (own or {}).get("moe_experts")
    if not spent:
        return None
    least = by_bytes = by_flops = 0.0
    for _run, rec in paired:
        one = mla_moe_roofline.experts_least_seconds(
            ctx.config, ctx.peaks, rec["moe_experts_touched"],
            rec["moe_held"])
        least += one["seconds"]
        by_bytes += one["by_bytes_s"]
        by_flops += one["by_flops_s"]
    say(f"mla-moe roofline: grouped expert products of {len(paired)} paired "
        f"runs: least {least * 1000:.2f} ms (bytes {by_bytes * 1000:.2f} ms, "
        f"flops {by_flops * 1000:.2f} ms), device self-time under "
        f"moe_experts {spent * 1000:.2f} ms")
    return 100.0 * least / spent


def read(ctx, what: str):
    if ctx.peaks is None or ctx.trace_span is None:
        return None
    summary = dispatch_trace.of(ctx)
    if summary is None or summary["fit"] is None or not summary["pairs"]:
        return None
    by_seq = _records_by_seq(ctx)
    if not by_seq:
        return None
    if what == "decode_step":
        return decode_step(ctx, summary, by_seq)
    if what == "experts":
        return experts(ctx, summary, by_seq)
    raise ValueError(f"unknown quantity {what!r}")
