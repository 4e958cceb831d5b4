"""Share (%) of the traced window's device self-time spent in operations
under the named scopes, where the scopes are the state-space family's
(``ssm_proj``, ``ssm_conv``, ``ssm_scan``, ``ssm_step``, ``state_read``,
``state_write``: models/ssm_moe.py, engine/prefix_cache.py): ``scope_share``
with six more names in its list, so the innermost of them on an operation's
path owns it.  (That reader's own list is fixed, and to it these operations
are unscoped.)

On a trace without device planes (a CPU rehearsal), or of a program that
names none of the scopes asked for (the parent of the PR that added them),
there is nothing to read.
"""

import os

from benchmarks import dispatch_trace, stack, trace_reduce, xplane_read
from benchmarks.correctness import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
SSM = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_step", "state_read",
       "state_write")


def _scopes():
    return load_module(os.path.join(HERE, "scope_share.py"))


def scope_of(tf_op, known):
    if not tf_op:
        return None
    for part in reversed(str(tf_op).rstrip(":").split("/")[:-1]):
        if part in known:
            return part
    return None


def device_ops(ctx):
    """(scope or 'unscoped', start, end) of the first device's operations
    and the window, or None; read once a run and kept on ``ctx``."""
    if hasattr(ctx, "ssm_scoped_ops"):
        return ctx.ssm_scoped_ops
    ctx.ssm_scoped_ops = None
    summary = dispatch_trace.of(ctx)  # None: the run recorded no trace
    if summary is None or summary["window"] is None:
        return None
    share = _scopes()
    known = share.SCOPES + SSM
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
    ctx.ssm_scoped_ops = (ops, summary["window"])
    return ctx.ssm_scoped_ops


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
    stack.say(f"ssm_scope_share: device self-time by scope: {shares}")
    return 100.0 * sum(own.get(s, 0.0) for s in scopes) / sum(own.values())
