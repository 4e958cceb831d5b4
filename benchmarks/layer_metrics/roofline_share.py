"""A decode step's share (%) of its roofline: the least time the chip
could take for the step (benchmarks/roofline.py, from the configuration's
sizes and the live tokens the client had in flight during the traced
window) over the step's measured device time: that of the decode runs the
program's own dispatch records account for (``dispatch_device``)."""

import importlib.util
import os

from benchmarks import roofline


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location("benchmarks_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def live_rows_and_tokens(ctx):
    """Mean number of requests decoding, and of tokens in their contexts,
    over the traced window, from what the client saw."""
    t0, t1 = ctx.trace_span
    by_index = {r.index: r for r in ctx.plan.all_requests()}
    samples = 20
    rows = tokens = 0.0
    for k in range(samples):
        t = t0 + (t1 - t0) * (k + 0.5) / samples
        for o in ctx.load.outcomes:
            if o.first_token is None or o.first_token > t:
                continue
            if o.last_token is None or (o.tokens_seen >= o.asked
                                        and o.last_token < t):
                continue
            rows += 1
            tokens += by_index[o.index].prompt_words + sum(
                n for when, n in o.token_times if when <= t)
    return rows / samples, tokens / samples


def read(ctx):
    if ctx.peaks is None or ctx.trace_span is None:
        return None
    step_ms = _sibling("dispatch_device").read(ctx, "step")
    if not step_ms:
        return None
    rows, tokens = live_rows_and_tokens(ctx)
    least = roofline.least_step_seconds(ctx.config, ctx.peaks, rows, tokens)
    from benchmarks.stack import say

    say(f"roofline: {rows:.1f} rows and {tokens:.0f} live tokens; least "
        f"step {least['seconds'] * 1000:.3f} ms, bound by {least['bound']} "
        f"(bytes {least['by_bytes_s'] * 1000:.3f} ms, flops "
        f"{least['by_flops_s'] * 1000:.3f} ms); measured {step_ms:.3f} ms")
    return 100.0 * least["seconds"] * 1000.0 / step_ms
