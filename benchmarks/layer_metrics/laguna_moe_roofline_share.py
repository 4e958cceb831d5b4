"""Roofline shares (%) of ``Laguna-S-2.1``'s family, from the device trace
joined to the program's dispatch ledger.

``what="decode_step"``: the share of the WHOLE decode step.  The least time
the chip could take for a step (benchmarks/laguna_moe_roofline.py: the
configuration's sizes, and what the window's ``engine.decode_burst`` records
count a step: live rows, cache positions x layers its attention had to read
by layer kind, the held experts its routing touched and the assignments it
made to them) over the step's measured device time, that of the decode runs
the same records account for (``dispatch_device``'s ``step``).

``what="experts"``: the grouped expert products alone, as
``swa_moe_roofline_share`` reads them: over the device runs inside the
traced window that are paired with a dispatch record, the sum of the least
time for each dispatch's grouped products over the device self-time under
the scope ``moe_experts`` in those runs.

``what="window_attn"``: the window layers' attention in decode: over the
decode runs paired with a record, the least time for the cached positions
their window layers had to read (``kv_rows_window``: ``min(position + 1,
window)`` a live row and layer) and the gates' weights, over the device
self-time under the scope ``attn_window`` in those runs (the innermost of
the known scopes owns an operation: ``attn_gate`` inside it is its).  While
a window layer reads its whole ring under a mask, the share cannot pass
``window / ring``.

Nothing to read without a device trace, without the ledger, or of a program
whose records carry no counts or that names no such scope (the parent of the
PR that added the family cannot run its cell at all).
"""

import os

from benchmarks import (dispatch_trace, laguna_moe_roofline, stack,
                        trace_reduce, xplane_read)
from benchmarks.correctness import load_module
from benchmarks.stack import say

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = ("engine.decode_burst", "engine.prefill_segment")
KEYS = ("moe_held", "moe_experts_touched", "kv_rows_full", "kv_rows_window")
WINDOW_SCOPE = "attn_window"


def _sibling(name: str):
    """Another reader of this directory."""
    return load_module(os.path.join(HERE, name + ".py"))


def _records_by_seq(ctx):
    return {ev["args"]["seq"]: ev["args"] for ev in ctx.spans
            if ev.get("name") in SPANS and ev.get("ph") == "X"
            and all(k in (ev.get("args") or {}) for k in KEYS)}


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

    def a_step(key):
        return sum(rec[key] for _run, rec in bursts) / steps

    rows = sum(rec["live_rows"] * rec["steps"] for _r, rec in bursts) / steps
    least = laguna_moe_roofline.least_step_seconds(
        ctx.config, ctx.peaks, rows, a_step("kv_rows_full"),
        a_step("kv_rows_window"), a_step("moe_experts_touched"),
        a_step("moe_held"))
    say(f"laguna-moe roofline: a step of {rows:.1f} rows reads "
        f"{a_step('kv_rows_full'):.0f} full and "
        f"{a_step('kv_rows_window'):.0f} window positions x layers, touches "
        f"{a_step('moe_experts_touched'):.1f} held experts with "
        f"{a_step('moe_held'):.1f} assignments; least step "
        f"{least['seconds'] * 1000:.3f} ms, bound by {least['bound']} (bytes "
        f"{least['by_bytes_s'] * 1000:.3f} ms, flops "
        f"{least['by_flops_s'] * 1000:.3f} ms); measured {step_ms:.3f} ms")
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
        one = laguna_moe_roofline.experts_least_seconds(
            ctx.config, ctx.peaks, rec["moe_experts_touched"],
            rec["moe_held"])
        least += one["seconds"]
        by_bytes += one["by_bytes_s"]
        by_flops += one["by_flops_s"]
    say(f"laguna-moe roofline: grouped expert products of {len(paired)} "
        f"paired runs: least {least * 1000:.2f} ms (bytes "
        f"{by_bytes * 1000:.2f} ms, flops {by_flops * 1000:.2f} ms), device "
        f"self-time under moe_experts {spent * 1000:.2f} ms")
    return 100.0 * least / spent


def kind_ops(ctx):
    """(scope, start, end) of the first device's operations, the attention
    kinds' scopes among the known ones (``kind_scope_share``'s reading), or
    None."""
    kinds = _sibling("kind_scope_share")
    share = _sibling("scope_share")
    known = share.SCOPES + kinds.KINDS
    trace_dir = os.path.join(stack.work_dir(ctx.cell), "trace")
    planes = xplane_read.read(trace_reduce.newest_xplane(trace_dir))
    devices = [p for p in planes
               if p.name.startswith(trace_reduce.DEVICE_PREFIX)
               and any(len(ln) for ln in p.line(trace_reduce.OPS_LINE))]
    if not devices:
        return None
    return [(kinds.scope_of(stats.get("tf_op"), known)
             or share.kernel_scope(name) or "unscoped", start, end)
            for line in devices[0].line(trace_reduce.OPS_LINE)
            for name, start, end, stats in line.events(stats=False)]


def self_time_within(ops, spans):
    """Device self-seconds by scope of the operations that start inside one
    of the (start, end) ``spans``."""
    spans = sorted(spans)
    kept, i = [], 0
    for op in sorted(ops, key=lambda o: o[1]):
        while i < len(spans) and spans[i][1] <= op[1]:
            i += 1
        if i < len(spans) and spans[i][0] <= op[1]:
            kept.append(op)
    return dict(trace_reduce.self_times(kept))


def window_attn(ctx, summary, by_seq):
    bursts = _paired_in_window(summary, "engine.decode_burst", by_seq)
    if not bursts or summary["window"] is None:
        return None
    ops = kind_ops(ctx)
    if not ops:
        return None
    own = self_time_within(trace_reduce.clip(ops, summary["window"]),
                           [(r["start"], r["end"]) for r, _rec in bursts])
    spent = own.get(WINDOW_SCOPE)
    if not spent:
        return None
    steps = sum(rec["steps"] for _run, rec in bursts)
    rows = sum(rec["kv_rows_window"] for _run, rec in bursts)
    least = laguna_moe_roofline.window_attention_least_seconds(
        ctx.config, ctx.peaks, steps, rows)
    say(f"laguna-moe roofline: window layers' attention of {steps} decode "
        f"steps in {len(bursts)} paired runs: {rows} positions x layers by "
        f"need, least {least['seconds'] * 1000:.2f} ms (bytes "
        f"{least['by_bytes_s'] * 1000:.2f} ms, flops "
        f"{least['by_flops_s'] * 1000:.2f} ms), device self-time under "
        f"{WINDOW_SCOPE} {spent * 1000:.2f} ms")
    return 100.0 * least["seconds"] / spent


QUANTITIES = {"decode_step": decode_step, "experts": experts,
              "window_attn": window_attn}


def read(ctx, what: str):
    if what not in QUANTITIES:
        raise ValueError(f"unknown quantity {what!r}")
    if ctx.peaks is None or ctx.trace_span is None:
        return None
    summary = dispatch_trace.of(ctx)
    if summary is None or summary["fit"] is None or not summary["pairs"]:
        return None
    by_seq = _records_by_seq(ctx)
    if not by_seq:
        return None
    return QUANTITIES[what](ctx, summary, by_seq)
