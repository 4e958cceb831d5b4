"""Roofline shares (%) of the family of one mixer a layer (Mamba-2
state-space layers, routed experts of two products, attention), from the
device trace joined to the program's dispatch ledger.

``what="decode_step"``: the share of the WHOLE decode step.  The least time
the chip could take for a step (benchmarks/ssm_moe_roofline.py: the
configuration's sizes, and what the window's ``engine.decode_burst`` records
count a step: live rows, whose state is read and written, cache positions x
attention layers, the held experts its routing touched and the assignments
it made to them) over the step's measured device time, that of the decode
runs the same records account for (``dispatch_device``'s ``step``).

``what="ssm_step"``: the state updates alone.  Over the decode runs inside
the traced window that are paired with a dispatch record: the sum of the
least time for each burst's state traffic (its ``state_rows``: live rows x
steps, each the state and the convolution's tail of every Mamba-2 layer in
and out) over the device self-time under the scopes ``ssm_step``,
``ssm_conv``, ``state_read`` and ``state_write`` in those runs.

``what="ssm_scan"``: the prefill scans alone: over the paired prefill runs,
the least time for each dispatch's scans (its real rows' state in and out,
its real positions' inputs and outputs, the recurrence's arithmetic) over
the self-time under ``ssm_scan``.

``what="experts"``: the grouped expert products (two an expert), as
``swa_moe_roofline_share`` reads them.

Nothing to read without a device trace, without the ledger, or of a program
whose records carry no counts (the parent of the PR that added them).
"""

import os

from benchmarks import dispatch_trace, ssm_moe_roofline
from benchmarks.correctness import load_module
from benchmarks.stack import say

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = ("engine.decode_burst", "engine.prefill_segment")
KEYS = ("moe_held", "moe_experts_touched", "kv_rows_full", "state_rows")


def _sibling(name: str):
    """Another reader of this directory."""
    return load_module(os.path.join(HERE, name + ".py"))


def _records_by_seq(ctx):
    return {ev["args"]["seq"]: ev["args"] for ev in ctx.spans
            if ev.get("name") in SPANS and ev.get("ph") == "X"
            and all(k in (ev.get("args") or {}) for k in KEYS)}


def _paired_in_window(summary, span, by_seq):
    return _sibling("mla_moe_roofline_share")._paired_in_window(
        summary, span, by_seq)


def _spent(ctx, paired, scopes):
    own = _sibling("ssm_scope_share").self_time_by_scope(
        ctx, within=[(r["start"], r["end"]) for r, _rec in paired])
    return sum((own or {}).get(s, 0.0) for s in scopes)


def decode_step(ctx, summary, by_seq):
    step_ms = _sibling("dispatch_device").read(ctx, "step")
    bursts = _paired_in_window(summary, "engine.decode_burst", by_seq)
    steps = sum(rec["steps"] for _run, rec in bursts)
    if not step_ms or not steps:
        return None

    def a_step(key):
        return sum(rec[key] for _run, rec in bursts) / steps

    rows = a_step("state_rows")
    least = ssm_moe_roofline.least_step_seconds(
        ctx.config, ctx.peaks, rows, a_step("kv_rows_full"),
        a_step("moe_experts_touched"), a_step("moe_held"))
    say(f"ssm-moe roofline: a step of {rows:.1f} live rows reads and writes "
        f"{ssm_moe_roofline.state_bytes(ctx.config, rows) / 1e9:.3f} GB of "
        f"state, reads {a_step('kv_rows_full'):.0f} positions x layers, "
        f"touches {a_step('moe_experts_touched'):.1f} held experts with "
        f"{a_step('moe_held'):.1f} assignments; least step "
        f"{least['seconds'] * 1000:.3f} ms, bound by {least['bound']} (bytes "
        f"{least['by_bytes_s'] * 1000:.3f} ms, flops "
        f"{least['by_flops_s'] * 1000:.3f} ms); measured {step_ms:.3f} ms")
    return 100.0 * least["seconds"] * 1000.0 / step_ms


def ssm_step(ctx, summary, by_seq):
    bursts = _paired_in_window(summary, "engine.decode_burst", by_seq)
    if not bursts:
        return None
    spent = _spent(ctx, bursts, ("ssm_step", "ssm_conv", "state_read",
                                 "state_write"))
    if not spent:
        return None
    row_steps = sum(rec["state_rows"] for _run, rec in bursts)
    least = ssm_moe_roofline.state_step_least_seconds(
        ctx.config, ctx.peaks, row_steps)
    say(f"ssm-moe roofline: state updates of {len(bursts)} paired bursts, "
        f"{row_steps} live rows x steps: least {least['seconds'] * 1000:.2f} "
        f"ms (bytes {least['by_bytes_s'] * 1000:.2f} ms, flops "
        f"{least['by_flops_s'] * 1000:.2f} ms), device self-time under "
        f"ssm_step + ssm_conv + state_* {spent * 1000:.2f} ms")
    return 100.0 * least["seconds"] / spent


def ssm_scan(ctx, summary, by_seq):
    segments = _paired_in_window(summary, "engine.prefill_segment", by_seq)
    if not segments:
        return None
    spent = _spent(ctx, segments, ("ssm_scan",))
    if not spent:
        return None
    least = by_bytes = by_flops = 0.0
    for _run, rec in segments:
        one = ssm_moe_roofline.scan_least_seconds(
            ctx.config, ctx.peaks, rec["state_rows"], rec["tokens"])
        least += one["seconds"]
        by_bytes += one["by_bytes_s"]
        by_flops += one["by_flops_s"]
    say(f"ssm-moe roofline: scans of {len(segments)} paired prefill runs: "
        f"least {least * 1000:.2f} ms (bytes {by_bytes * 1000:.2f} ms, flops "
        f"{by_flops * 1000:.2f} ms), device self-time under ssm_scan "
        f"{spent * 1000:.2f} ms")
    return 100.0 * least / spent


def experts(ctx, summary, by_seq):
    paired = (_paired_in_window(summary, "engine.decode_burst", by_seq)
              + _paired_in_window(summary, "engine.prefill_segment", by_seq))
    if not paired:
        return None
    spent = _spent(ctx, paired, ("moe_experts",))
    if not spent:
        return None
    least = by_bytes = by_flops = 0.0
    for _run, rec in paired:
        one = ssm_moe_roofline.experts_least_seconds(
            ctx.config, ctx.peaks, rec["moe_experts_touched"],
            rec["moe_held"])
        least += one["seconds"]
        by_bytes += one["by_bytes_s"]
        by_flops += one["by_flops_s"]
    say(f"ssm-moe roofline: grouped expert products of {len(paired)} paired "
        f"runs: least {least * 1000:.2f} ms (bytes {by_bytes * 1000:.2f} ms, "
        f"flops {by_flops * 1000:.2f} ms), device self-time under "
        f"moe_experts {spent * 1000:.2f} ms")
    return 100.0 * least / spent


QUANTITIES = {"decode_step": decode_step, "ssm_step": ssm_step,
              "ssm_scan": ssm_scan, "experts": experts}


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
