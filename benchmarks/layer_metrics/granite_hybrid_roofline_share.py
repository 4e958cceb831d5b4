"""Roofline shares (%) of the family of a mixer and then a dense gated MLP a
layer (Mamba-2 state-space layers and attention, a tied head), from the
device trace joined to the program's dispatch ledger: what
``ssm_moe_roofline_share`` reads for the family of one mixer a layer, by
``benchmarks/granite_hybrid_roofline.py``'s count (that reader asks its
records for routed layers' counts, which a model without a router has not,
and its count for keys this family's configuration has not).

``what="decode_step"``: the share of the WHOLE decode step: the least time
the chip could take for a step (the weights once, the live rows' state in
and out, the cache positions x attention layers the step's records count)
over the step's measured device time (``dispatch_device``'s ``step``).

``what="ssm_step"``: the state updates alone, over the decode runs of the
traced window that are paired with a dispatch record: the least time for
each burst's state traffic (its ``state_rows``) over the device self-time
under the scopes ``ssm_step``, ``ssm_conv``, ``state_read`` and
``state_write`` in those runs, AND of the operations no scope owns whose
result is a whole state leaf (``state_leaf``, below).

``what="ssm_share"``: the share of the window's device self-time that the
state-space layers take: ``ssm_scope_share``'s six scopes and
``state_leaf``.

**``state_leaf``.**  An operation on no scope's path whose result has the
shape of the ``conv`` or the ``ssm`` leaf as the program lays them
(``[Mamba-2 layers, rows, (K - 1) x channels]``: the convolution's tail as
lanes of a slot's row, since PR 47; ``[Mamba-2 layers, rows, H, P, N]``;
the rows are the engine's, so any count) is the state update's own work:
the compiler's copies of a leaf around a layer's write carry the name it
gave them and no scope (72 ``bitcast_dynamic-update-slice_fusion.N
.remat_compressed`` / ``.remat_uncompressed = bf16[36,65,3,4352]`` a step,
7.5 of 31.7 ms, in this family's first traced runs, when the tail lay as
``[.., K - 1, channels]``: ISSUE 46; PR 47's layout left none, and the rule
stays for the change that brings one back).  ``ssm_scope_share`` reads
scopes alone and calls them unscoped.
``tests/benchmarks/test_bm_granite_roofline.py`` holds ``leaf_shapes`` to
the leaves ``models/ssm_moe.py`` ``init_kv_cache`` makes, so that the next
change of layout fails a test and not, in silence, this rule.

``what="ssm_scan"``: the prefill scans alone: over the paired prefill runs,
the least time for each dispatch's scans over the self-time under
``ssm_scan``.

Nothing to read without a device trace, without the ledger, or of a program
whose records carry no counts of state (the parent of the PR that added this
family cannot serve it at all).
"""

import os
import re

from benchmarks import dispatch_trace, granite_hybrid_roofline as count
from benchmarks import stack, trace_reduce, xplane_read
from benchmarks.correctness import load_module
from benchmarks.stack import say

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = ("engine.decode_burst", "engine.prefill_segment")
KEYS = ("kv_rows_full", "state_rows")


def _sibling(name: str):
    """Another reader of this directory."""
    return load_module(os.path.join(HERE, name + ".py"))


def _paired(ctx, summary, span):
    by_seq = {ev["args"]["seq"]: ev["args"] for ev in ctx.spans
              if ev.get("name") in SPANS and ev.get("ph") == "X"
              and all(k in (ev.get("args") or {}) for k in KEYS)}
    if not by_seq:
        return []
    return _sibling("mla_moe_roofline_share")._paired_in_window(
        summary, span, by_seq)


STATE_LEAF = "state_leaf"


def leaf_shapes(config, rows):
    """The shapes of the two state leaves at ``rows`` rows, from the
    configuration's keys: ``conv`` ``[Lm, rows, (K - 1) x channels]`` and
    ``ssm`` ``[Lm, rows, H, P, N]``."""
    n, k = int(config["mamba_d_state"]), int(config["mamba_d_conv"])
    heads, width = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    channels = heads * width + 2 * int(config["mamba_n_groups"]) * n
    layers = count.sizes(config)["mamba_layers"]
    return {"conv": (layers, rows, (k - 1) * channels),
            "ssm": (layers, rows, heads, width, n)}


def leaf_pattern(config):
    """What the name of an operation whose result is a whole state leaf
    holds: ``= bf16[36,<rows>,13056]`` or ``= f32[36,<rows>,64,64,128]``
    (a loop's result is a tuple that holds the leaves: not this)."""
    rows = r"\d+"
    either = "|".join(",".join(str(d) for d in shape)
                      for shape in leaf_shapes(config, rows).values())
    return re.compile(r"= \w+\[(%s)\]" % either)


def owner_of(config):
    """(name, tf_op) -> the scope that owns a device operation: the
    innermost known scope on its path (``ssm_scope_share``'s rule), else
    ``state_leaf`` for a whole state leaf's copy, else ``unscoped``."""
    share, ssm = _sibling("scope_share"), _sibling("ssm_scope_share")
    known, leaf = share.SCOPES + ssm.SSM, leaf_pattern(config)

    def owner(name, tf_op):
        return (ssm.scope_of(tf_op, known) or share.kernel_scope(name)
                or (STATE_LEAF if leaf.search(name) else "unscoped"))
    return owner


def _device_ops(ctx):
    """(owner, start, end) of the first device's operations, or None;
    ``ssm_scope_share.device_ops`` with the operations' names kept long
    enough to find the state leaves' copies; read once a run."""
    if hasattr(ctx, "granite_owned_ops"):
        return ctx.granite_owned_ops
    ctx.granite_owned_ops = None
    trace_dir = os.path.join(stack.work_dir(ctx.cell), "trace")
    planes = xplane_read.read(trace_reduce.newest_xplane(trace_dir))
    devices = [p for p in planes
               if p.name.startswith(trace_reduce.DEVICE_PREFIX)
               and any(len(ln) for ln in p.line(trace_reduce.OPS_LINE))]
    if devices:
        owner = owner_of(ctx.config)
        ctx.granite_owned_ops = [
            (owner(name, stats.get("tf_op")), start, end)
            for line in devices[0].line(trace_reduce.OPS_LINE)
            for name, start, end, stats in line.events(stats=False)]
    return ctx.granite_owned_ops


def self_time_by_owner(ops, window, within=None):
    """Device self-seconds by owner inside ``window``; ``within``: only of
    operations that start inside one of these (start, end) intervals."""
    if within is not None:
        spans, kept, i = sorted(within), [], 0
        for op in sorted(ops, key=lambda o: o[1]):
            while i < len(spans) and spans[i][1] <= op[1]:
                i += 1
            if i < len(spans) and spans[i][0] <= op[1]:
                kept.append(op)
        ops = kept
    return dict(trace_reduce.self_times(trace_reduce.clip(ops, window)))


STATE_SCOPES = ("ssm_step", "ssm_conv", "state_read", "state_write",
                STATE_LEAF)


def _spent(ctx, summary, paired, owners):
    ops = _device_ops(ctx)
    if not ops:
        return 0.0
    own = self_time_by_owner(
        ops, summary["window"],
        within=[(r["start"], r["end"]) for r, _rec in paired])
    return sum(own.get(s, 0.0) for s in owners)


def decode_step(ctx, summary):
    step_ms = _sibling("dispatch_device").read(ctx, "step")
    bursts = _paired(ctx, summary, "engine.decode_burst")
    steps = sum(rec["steps"] for _run, rec in bursts)
    if not step_ms or not steps:
        return None
    rows = sum(rec["state_rows"] for _run, rec in bursts) / steps
    kv_rows = sum(rec["kv_rows_full"] for _run, rec in bursts) / steps
    least = count.least_step_seconds(ctx.config, ctx.peaks, rows, kv_rows)
    say(f"granite-hybrid roofline: a step of {rows:.1f} live rows reads "
        f"{count.parameters(ctx.config) / 1e9:.3f} G weights, reads and "
        f"writes {count.state_bytes(ctx.config, rows) / 1e9:.3f} GB of "
        f"state, reads {kv_rows:.0f} positions x layers; least step "
        f"{least['seconds'] * 1000:.3f} ms, bound by {least['bound']} (bytes "
        f"{least['by_bytes_s'] * 1000:.3f} ms, flops "
        f"{least['by_flops_s'] * 1000:.3f} ms); measured {step_ms:.3f} ms")
    return 100.0 * least["seconds"] * 1000.0 / step_ms


def ssm_step(ctx, summary):
    bursts = _paired(ctx, summary, "engine.decode_burst")
    spent = bursts and _spent(ctx, summary, bursts, STATE_SCOPES)
    if not spent:
        return None
    row_steps = sum(rec["state_rows"] for _run, rec in bursts)
    least = count.state_step_least_seconds(ctx.config, ctx.peaks, row_steps)
    say(f"granite-hybrid roofline: state updates of {len(bursts)} paired "
        f"bursts, {row_steps} live rows x steps: least "
        f"{least['seconds'] * 1000:.2f} ms (bytes "
        f"{least['by_bytes_s'] * 1000:.2f} ms, flops "
        f"{least['by_flops_s'] * 1000:.2f} ms), device self-time under "
        f"ssm_step + ssm_conv + state_* and of the leaves' unscoped copies "
        f"{spent * 1000:.2f} ms")
    return 100.0 * least["seconds"] / spent


def ssm_scan(ctx, summary):
    segments = _paired(ctx, summary, "engine.prefill_segment")
    spent = segments and _spent(ctx, summary, segments, ("ssm_scan",))
    if not spent:
        return None
    least = sum(count.scan_least_seconds(
        ctx.config, ctx.peaks, rec["state_rows"], rec["tokens"])["seconds"]
        for _run, rec in segments)
    say(f"granite-hybrid roofline: scans of {len(segments)} paired prefill "
        f"runs: least {least * 1000:.2f} ms, device self-time under "
        f"ssm_scan {spent * 1000:.2f} ms")
    return 100.0 * least / spent


def ssm_share(ctx, summary):
    ops = _device_ops(ctx)
    own = ops and self_time_by_owner(ops, summary["window"])
    mine = _sibling("ssm_scope_share").SSM + (STATE_LEAF,)
    if not own or not any(s in own for s in mine):
        return None
    say("granite-hybrid share: device self-time by owner: " + ", ".join(
        f"{name} {100.0 * secs / sum(own.values()):.1f} %"
        for name, secs in sorted(own.items(), key=lambda kv: -kv[1])))
    return 100.0 * sum(own.get(s, 0.0) for s in mine) / sum(own.values())


QUANTITIES = {"decode_step": decode_step, "ssm_step": ssm_step,
              "ssm_scan": ssm_scan, "ssm_share": ssm_share}


def read(ctx, what: str):
    if what not in QUANTITIES:
        raise ValueError(f"unknown quantity {what!r}")
    if ctx.peaks is None or ctx.trace_span is None:
        return None
    summary = dispatch_trace.of(ctx)
    if summary is None or summary["fit"] is None or not summary["pairs"]:
        return None
    return QUANTITIES[what](ctx, summary)
