"""Share (%) of the traced window's device self-time spent in operations
under the named scopes, where the scopes are the block decode pass's own
(``attn_block``: the block's own scores and their merge with the prefix's,
inside ``attn``; ``denoise_select``: the choice between a sampled and a
forced token, inside ``head_sample``; models/block_decode.py,
engine/block_engine.py): ``kind_scope_share`` with these names in its list
in place of the layer kinds, so the innermost of them on an operation's
path owns it.  (That reader's own list is fixed, and to it these operations
are ``attn``'s and ``head_sample``'s.)

On a trace without device planes (a CPU rehearsal), or of a program that
names none of the scopes asked for (any other model; the parent of the PR
that added them), there is nothing to read.
"""

import os

from benchmarks import dispatch_trace, stack, trace_reduce, xplane_read
from benchmarks.correctness import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
OWN = ("attn_block", "denoise_select")


def _sibling(name):
    return load_module(os.path.join(HERE, name + ".py"))


def self_time_by_scope(ctx):
    """Device self-seconds by scope inside the window, or None; read once a
    run and kept on ``ctx``."""
    if hasattr(ctx, "block_scoped"):
        return ctx.block_scoped
    ctx.block_scoped = None
    summary = dispatch_trace.of(ctx)  # None: the run recorded no trace
    if summary is None or summary["window"] is None:
        return None
    share, scope_of = _sibling("scope_share"), _sibling(
        "kind_scope_share").scope_of
    known = share.SCOPES + OWN
    trace_dir = os.path.join(stack.work_dir(ctx.cell), "trace")
    planes = xplane_read.read(trace_reduce.newest_xplane(trace_dir))
    devices = [p for p in planes
               if p.name.startswith(trace_reduce.DEVICE_PREFIX)
               and any(len(ln) for ln in p.line(trace_reduce.OPS_LINE))]
    if not devices:
        return None
    ops = [(scope_of(stats.get("tf_op"), known) or share.kernel_scope(name)
            or "unscoped", start, end)
           for line in devices[0].line(trace_reduce.OPS_LINE)
           for name, start, end, stats in line.events(stats=False)]
    ctx.block_scoped = dict(trace_reduce.self_times(
        trace_reduce.clip(ops, summary["window"])))
    return ctx.block_scoped


def read(ctx, scopes):
    own = self_time_by_scope(ctx)
    if not own or not any(s in own for s in scopes):
        return None
    shares = ", ".join(f"{name} {100.0 * secs / sum(own.values()):.1f} %"
                       for name, secs in sorted(own.items(),
                                                key=lambda kv: -kv[1]))
    stack.say(f"block_scope_share: device self-time by scope: {shares}")
    return 100.0 * sum(own.get(s, 0.0) for s in scopes) / sum(own.values())
