"""Roofline shares (%) of the family that generates by masked denoising over
blocks, from the device trace joined to the program's dispatch ledger.

``what="decode_step"``: the share of the whole decode work PER DELIVERED
BLOCK.  The least time the chip could take for a pass
(benchmarks/block_diffusion_roofline.py: the configuration's sizes, and what
the window's ``engine.decode_burst`` records count a pass: live rows, those
of them that carried a pending block and wrote it (``row_commits_fused``),
cache positions x layers the current blocks' queries saw, the experts the
routing touched and the assignments it made, the rows that committed in a
pass of their own), times the passes that the tokens those records DECIDED
need (``denoising_steps`` passes a block of ``block_length`` tokens, so
``tokens_decided`` over ``block_length / denoising_steps`` row-passes, at the
window's mean live rows a pass), over the device time of the decode passes
that ran (``dispatch_device``'s ``step`` times the records' ``steps``: a
burst's ``steps`` are its passes).  Where every row-pass decides its group
of tokens it is the share of one pass, which the metric was until ISSUE 50;
the program before PR 48, three passes a block of four, would read two
thirds of its per-pass share.  ``tokens_decided`` leaves out forced prompt
tokens (an ``echo`` request's) and tokens past a request's end, and a row
that finished inside a burst still rides its last passes: the share can read
low by those and never high.

``what="experts"``: the grouped expert products alone, as
``swa_moe_roofline_share`` reads them: over the device runs inside the
traced window that are paired with a dispatch record, the sum of the least
time for each dispatch's grouped products over the device self-time under
the scope ``moe_experts`` in those runs.

Nothing to read without a device trace, without the ledger, or of a program
whose records carry no counts.
"""

import os

from benchmarks import block_diffusion_roofline as roofline
from benchmarks import dispatch_trace
from benchmarks.correctness import load_module
from benchmarks.stack import say

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = ("engine.decode_burst", "engine.prefill_segment")
KEYS = ("moe_held", "moe_experts_touched", "kv_rows_full")


def _sibling(name: str):
    """Another reader of this directory."""
    return load_module(os.path.join(HERE, name + ".py"))


def _records_by_seq(ctx):
    return {ev["args"]["seq"]: ev["args"] for ev in ctx.spans
            if ev.get("name") in SPANS and ev.get("ph") == "X"
            and all(k in (ev.get("args") or {}) for k in KEYS)}


def _paired_in_window(summary, span, by_seq):
    """[(run, record)] of the device runs inside the window whose dispatch
    record carries the counts (the latent family's reader pairs alike)."""
    return _sibling("mla_moe_roofline_share")._paired_in_window(
        summary, span, by_seq)


def paired_bursts(summary, by_seq):
    """[(run, record)] of the window's paired decode runs of a model that
    generates by blocks: their records count passes and decided tokens."""
    return [(run, rec) for run, rec in _paired_in_window(
        summary, "engine.decode_burst", by_seq)
        if "row_passes_commit" in rec and "tokens_decided" in rec]


def decode_step(ctx, summary, by_seq):
    step_ms = _sibling("dispatch_device").read(ctx, "step")
    bursts = paired_bursts(summary, by_seq)
    steps = sum(rec["steps"] for _run, rec in bursts)
    if not step_ms or not steps:
        return None

    def a_pass(key):
        return sum(rec[key] for _run, rec in bursts) / steps

    rows = sum(rec["live_rows"] * rec["steps"] for _r, rec in bursts) / steps
    # a program from before PR 48 has no fused commits: its records lack
    # the key, and none of its rows carried a second block
    fused = sum(rec.get("row_commits_fused", 0) for _r, rec in bursts) / steps
    least = roofline.least_pass_seconds(
        ctx.config, ctx.peaks, rows, a_pass("kv_rows_full"),
        a_pass("moe_experts_touched"), a_pass("moe_held"),
        a_pass("row_passes_commit") + fused, fused)
    decided = sum(rec["tokens_decided"] for _run, rec in bursts)
    share = roofline.block_share(
        ctx.config, least["seconds"], step_ms / 1000.0, decided,
        rows * steps)
    say(f"block-diffusion roofline: a pass of {rows:.1f} rows, {fused:.1f} "
        f"with the block before beside the current one, sees "
        f"{a_pass('kv_rows_full'):.0f} positions x layers, touches "
        f"{a_pass('moe_experts_touched'):.1f} experts with "
        f"{a_pass('moe_held'):.1f} assignments, commits "
        f"{a_pass('row_passes_commit'):.1f} rows alone; least pass "
        f"{least['seconds'] * 1000:.3f} ms, bound by {least['bound']} (bytes "
        f"{least['by_bytes_s'] * 1000:.3f} ms, flops "
        f"{least['by_flops_s'] * 1000:.3f} ms); measured {step_ms:.3f} ms: "
        f"{100.0 * least['seconds'] * 1000.0 / step_ms:.2f} % a pass; "
        f"{decided} tokens decided in {rows * steps:.0f} row-passes need "
        f"{roofline.row_passes_needed(ctx.config, decided):.0f}: "
        f"{share:.2f} % a delivered block")
    return share


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
        one = roofline.experts_least_seconds(
            ctx.config, ctx.peaks, rec["moe_experts_touched"],
            rec["moe_held"])
        least += one["seconds"]
        by_bytes += one["by_bytes_s"]
        by_flops += one["by_flops_s"]
    say(f"block-diffusion roofline: grouped expert products of "
        f"{len(paired)} paired runs: least {least * 1000:.2f} ms (bytes "
        f"{by_bytes * 1000:.2f} ms, flops {by_flops * 1000:.2f} ms), device "
        f"self-time under moe_experts {spent * 1000:.2f} ms")
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
