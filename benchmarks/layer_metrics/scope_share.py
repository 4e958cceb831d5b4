"""Share (%) of the traced window's device self-time spent in operations
under the named scopes, for scopes that ``benchmarks/dispatch_trace.py``'s
fixed list does not know: the innermost of ``SCOPES`` on an operation's
path (``tf_op``) owns it, so ``ffn/moe_experts/...`` is ``moe_experts``'s
and the decompression under ``mla_up`` is not ``attn``'s.  An operation
named ``ragged-dot...`` (the chip compiler's grouped-matmul kernel, which
keeps no scope) is ``moe_experts``'s too.

On a trace without device planes (a CPU rehearsal), or of a program that
names none of the scopes asked for (the parent of the PR that added them),
there is nothing to read.
"""

import os

from benchmarks import dispatch_trace, stack, trace_reduce, xplane_read

#: dispatch_trace's scopes and the latent / routed family's own
#: (models/mla.py, models/moe.py)
SCOPES = dispatch_trace.SCOPES + ("moe_route", "moe_experts", "moe_shared",
                                  "mla_up")


def scope_of(tf_op):
    if not tf_op:
        return None
    for part in reversed(str(tf_op).rstrip(":").split("/")[:-1]):
        if part in SCOPES:
            return part
    return None


def kernel_scope(name):
    """The compiler's grouped-matmul kernel carries no scope of the program
    (its operation is named ``ragged-dot...`` whatever scope traced it);
    the routed layer's grouped products are the program's only ones."""
    if str(name).lstrip("%").startswith("ragged-dot"):
        return "moe_experts"
    return None


def device_ops(ctx):
    """(scope or 'unscoped', start, end) of the first device's operations
    and the window, or None; read once a run and kept on ``ctx``."""
    if hasattr(ctx, "scoped_ops"):
        return ctx.scoped_ops
    ctx.scoped_ops = None
    summary = dispatch_trace.of(ctx)  # None: the run recorded no trace
    if summary is None or summary["window"] is None:
        return None
    trace_dir = os.path.join(stack.work_dir(ctx.cell), "trace")
    planes = xplane_read.read(trace_reduce.newest_xplane(trace_dir))
    devices = [p for p in planes
               if p.name.startswith(trace_reduce.DEVICE_PREFIX)
               and any(len(ln) for ln in p.line(trace_reduce.OPS_LINE))]
    if not devices:
        return None
    ops = [(scope_of(stats.get("tf_op")) or kernel_scope(name) or "unscoped",
            start, end)
           for line in devices[0].line(trace_reduce.OPS_LINE)
           for name, start, end, stats in line.events(stats=False)]
    ctx.scoped_ops = (ops, summary["window"])
    return ctx.scoped_ops


def self_time_by_scope(ctx, within=None):
    """Device self-seconds by scope inside the window; ``within``: only of
    operations that start inside one of these (start, end) intervals."""
    found = device_ops(ctx)
    if found is None:
        return None
    ops, window = found
    if within is not None:
        spans = sorted(within)
        kept, i = [], 0
        for op in sorted(ops, key=lambda o: o[1]):
            while i < len(spans) and spans[i][1] <= op[1]:
                i += 1
            if i < len(spans) and spans[i][0] <= op[1]:
                kept.append(op)
        ops = kept
    return dict(trace_reduce.self_times(trace_reduce.clip(ops, window)))


def read(ctx, scopes):
    own = self_time_by_scope(ctx)
    if not own or not any(s in own for s in scopes):
        return None
    shares = ", ".join(f"{name} {100.0 * secs / sum(own.values()):.1f} %"
                       for name, secs in sorted(own.items(),
                                                key=lambda kv: -kv[1]))
    stack.say(f"scope_share: device self-time by scope: {shares}")
    return 100.0 * sum(own.get(s, 0.0) for s in scopes) / sum(own.values())
