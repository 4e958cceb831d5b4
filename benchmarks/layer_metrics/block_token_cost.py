"""Device time a decided token (us) of a model that generates by blocks,
from the device trace joined to the program's dispatch ledger: the device
time of the decode runs that lie inside the traced window and are paired
with a dispatch record (``block_diffusion_roofline_share``'s pairs), over
the tokens those records say their passes decided (``tokens_decided``:
tokens that reached a request, never a forced prompt token, never one past
a request's end).

What a pass costs and what it yields in one number, whatever the schedule:
``decode_step_ctr_dev_ms.closed`` is per PASS, and a change that makes a
pass carry more (PR 48: two blocks a row, 21.6 -> 22.1 ms) reads worse there
while a token got cheaper (363 -> 261 us).  It falls with more live rows a
pass, so read it beside ``decode_fill_pct.closed``.

Nothing to read without a device trace, without the ledger, or of a program
whose records count no decided tokens (any other model).
"""

import os

from benchmarks import dispatch_trace
from benchmarks.correctness import load_module
from benchmarks.stack import say

HERE = os.path.dirname(os.path.abspath(__file__))


def totals(bursts):
    """[(run, record)] -> (device seconds, tokens decided)."""
    return (sum(run["end"] - run["start"] for run, _rec in bursts),
            sum(rec["tokens_decided"] for _run, rec in bursts))


def cost_us(bursts):
    """[(run, record)] -> device microseconds a decided token, or None."""
    spent, decided = totals(bursts)
    return 1e6 * spent / decided if decided else None


def read(ctx):
    if ctx.trace_span is None:
        return None
    summary = dispatch_trace.of(ctx)
    if summary is None or summary["fit"] is None or not summary["pairs"]:
        return None
    share = load_module(os.path.join(
        HERE, "block_diffusion_roofline_share.py"))
    bursts = share.paired_bursts(summary, share._records_by_seq(ctx))
    spent, decided = totals(bursts)
    if not decided:
        return None
    say(f"block token cost: {len(bursts)} paired decode runs, "
        f"{spent * 1000:.2f} ms on the device, {decided} tokens decided")
    return cost_us(bursts)
