"""Roofline shares and device shares (%) of the family of gated delta-rule
layers beside full attention, an MLP a layer, from the device trace joined to
the program's dispatch ledger: what ``granite_hybrid_roofline_share`` reads
for the Mamba-2 family, by ``benchmarks/olmo_hybrid_roofline.py``'s count and
this family's scopes (that reader's count asks keys this family's
configuration has not, and its scopes are ``ssm_*``).

An operation is owned by the innermost known scope on its path: the program's
``delta_proj`` (the six projections, the gate and its norm), ``delta_conv``,
``delta_scan`` (prefill), ``delta_step`` (decode), ``state_read`` /
``state_write`` (models/ssm_moe.py, engine/prefix_cache.py) beside
``scope_share``'s own (``attn``, ``ffn``, ``head_sample``, ``kv_read``,
``kv_write`` ...); an operation on no scope's path whose result is a whole
state leaf as the program lays it (``[delta layers, rows, H, Dk / f, f x
Dv]`` float32, ``[delta layers, rows, (K - 1) x channels]``) is
``state_leaf``: the compiler's copy of a leaf around a layer's write carries
no scope (``granite_hybrid_roofline_share``'s rule; none stood in this
family's first traced runs, and the rule stays for the change that brings
one).  ``tests/benchmarks/test_bm_olmo_roofline.py`` holds ``leaf_shapes``
to the leaves ``models/ssm_moe.py`` ``init_kv_cache`` makes.

``what="decode_step"``: the least time the chip could take for a step (the
weights once, the live rows' state in and out, the cache positions x
attention layers the step's records count) over the step's measured device
time (``dispatch_device``'s ``step``).

``what="delta_step"``: the state updates alone, over the decode runs of the
traced window that are paired with a dispatch record: the least time for each
burst's state traffic (its ``state_rows``) over the device self-time under
``delta_step``, ``delta_conv``, ``state_read``, ``state_write`` and
``state_leaf`` in those runs.

``what="delta_scan"``: the prefill scans alone: over the paired prefill runs,
the least time for each dispatch's scans over the self-time under
``delta_scan``.

``what="delta_share"`` / ``what="attn_share"``: the share of the window's
device self-time that the delta layers' mixers take (the six scopes and
``state_leaf``), and that the full-attention layers' mixers take (``attn``,
``kv_read``, ``kv_write``: every attention layer of this family is full).

Nothing to read without a device trace, without the ledger, or of a program
whose records carry no counts of state (the parent of the PR that added this
family cannot serve it at all).
"""

import os
import re

from benchmarks import dispatch_trace, olmo_hybrid_roofline as count
from benchmarks import stack, trace_reduce, xplane_read
from benchmarks.correctness import load_module
from benchmarks.stack import say

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = ("engine.decode_burst", "engine.prefill_segment")
KEYS = ("kv_rows_full", "state_rows")
DELTA = ("delta_proj", "delta_conv", "delta_scan", "delta_step",
         "state_read", "state_write")
STATE_LEAF = "state_leaf"
STEP_SCOPES = ("delta_step", "delta_conv", "state_read", "state_write",
               STATE_LEAF)
ATTENTION = ("attn", "kv_read", "kv_write")


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


def leaf_shapes(config, rows):
    """The shapes of the two state leaves at ``rows`` rows, from the
    configuration's keys: ``dconv`` ``[Ld, rows, (K - 1) x channels]`` and
    ``delta`` ``[Ld, rows, H, Dk / f, f x Dv]``."""
    heads = int(config["linear_num_value_heads"])
    channels = heads * (2 * int(config["linear_key_head_dim"])
                        + int(config["linear_value_head_dim"]))
    layers = count.sizes(config)["delta_layers"]
    k = int(config["linear_conv_kernel_dim"])
    return {"dconv": (layers, rows, (k - 1) * channels),
            "delta": (layers, rows, heads) + count.held_as(config)}


def leaf_pattern(config):
    """What the name of an operation whose result is a whole state leaf
    holds: ``= bf16[12,<rows>,34560]`` or ``= f32[12,<rows>,30,48,384]`` (a
    loop's result is a tuple that holds the leaves: not this)."""
    either = "|".join(",".join(str(d) for d in shape)
                      for shape in leaf_shapes(config, r"\d+").values())
    return re.compile(r"= \w+\[(%s)\]" % either)


def owner_of(config):
    """(name, tf_op) -> the scope that owns a device operation: the
    innermost known scope on its path, else ``state_leaf`` for a whole state
    leaf's copy, else ``unscoped``."""
    share, ssm = _sibling("scope_share"), _sibling("ssm_scope_share")
    known, leaf = share.SCOPES + DELTA, leaf_pattern(config)

    def owner(name, tf_op):
        return (ssm.scope_of(tf_op, known) or share.kernel_scope(name)
                or (STATE_LEAF if leaf.search(name) else "unscoped"))
    return owner


def _device_ops(ctx):
    """(owner, start, end) of the first device's operations, or None; read
    once a run."""
    if hasattr(ctx, "olmo_owned_ops"):
        return ctx.olmo_owned_ops
    ctx.olmo_owned_ops = None
    trace_dir = os.path.join(stack.work_dir(ctx.cell), "trace")
    planes = xplane_read.read(trace_reduce.newest_xplane(trace_dir))
    devices = [p for p in planes
               if p.name.startswith(trace_reduce.DEVICE_PREFIX)
               and any(len(ln) for ln in p.line(trace_reduce.OPS_LINE))]
    if devices:
        owner = owner_of(ctx.config)
        ctx.olmo_owned_ops = [
            (owner(name, stats.get("tf_op")), start, end)
            for line in devices[0].line(trace_reduce.OPS_LINE)
            for name, start, end, stats in line.events(stats=False)]
    return ctx.olmo_owned_ops


def self_time_by_owner(ops, window, within=None):
    """Device self-seconds by owner inside ``window``; ``within``: only of
    operations that start inside one of these (start, end) intervals."""
    return _sibling("granite_hybrid_roofline_share").self_time_by_owner(
        ops, window, within)


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
    say(f"olmo-hybrid roofline: a step of {rows:.1f} live rows reads "
        f"{count.parameters(ctx.config) / 1e9:.3f} G weights, reads and "
        f"writes {count.state_bytes(ctx.config, rows) / 1e9:.3f} GB of "
        f"state, reads {kv_rows:.0f} positions x layers; least step "
        f"{least['seconds'] * 1000:.3f} ms, bound by {least['bound']} (bytes "
        f"{least['by_bytes_s'] * 1000:.3f} ms, flops "
        f"{least['by_flops_s'] * 1000:.3f} ms); measured {step_ms:.3f} ms")
    return 100.0 * least["seconds"] * 1000.0 / step_ms


def delta_step(ctx, summary):
    bursts = _paired(ctx, summary, "engine.decode_burst")
    spent = bursts and _spent(ctx, summary, bursts, STEP_SCOPES)
    if not spent:
        return None
    row_steps = sum(rec["state_rows"] for _run, rec in bursts)
    least = count.state_step_least_seconds(ctx.config, ctx.peaks, row_steps)
    say(f"olmo-hybrid roofline: state updates of {len(bursts)} paired "
        f"bursts, {row_steps} live rows x steps: least "
        f"{least['seconds'] * 1000:.2f} ms (bytes "
        f"{least['by_bytes_s'] * 1000:.2f} ms, flops "
        f"{least['by_flops_s'] * 1000:.2f} ms), device self-time under "
        f"delta_step + delta_conv + state_* and of the leaves' unscoped "
        f"copies {spent * 1000:.2f} ms")
    return 100.0 * least["seconds"] / spent


def delta_scan(ctx, summary):
    segments = _paired(ctx, summary, "engine.prefill_segment")
    spent = segments and _spent(ctx, summary, segments, ("delta_scan",))
    if not spent:
        return None
    least = sum(count.scan_least_seconds(
        ctx.config, ctx.peaks, rec["state_rows"], rec["tokens"])["seconds"]
        for _run, rec in segments)
    say(f"olmo-hybrid roofline: scans of {len(segments)} paired prefill "
        f"runs: least {least * 1000:.2f} ms, device self-time under "
        f"delta_scan {spent * 1000:.2f} ms")
    return 100.0 * least / spent


def _share(ctx, summary, mine):
    ops = _device_ops(ctx)
    own = ops and self_time_by_owner(ops, summary["window"])
    if not own or not any(s in own for s in mine):
        return None
    say("olmo-hybrid share: device self-time by owner: " + ", ".join(
        f"{name} {100.0 * secs / sum(own.values()):.1f} %"
        for name, secs in sorted(own.items(), key=lambda kv: -kv[1])))
    return 100.0 * sum(own.get(s, 0.0) for s in mine) / sum(own.values())


def delta_share(ctx, summary):
    return _share(ctx, summary, DELTA + (STATE_LEAF,))


def attn_share(ctx, summary):
    return _share(ctx, summary, ATTENTION)


QUANTITIES = {"decode_step": decode_step, "delta_step": delta_step,
              "delta_scan": delta_scan, "delta_share": delta_share,
              "attn_share": attn_share}


def read(ctx, what: str):
    if what not in QUANTITIES:
        raise ValueError(f"unknown quantity {what!r}")
    if ctx.peaks is None or ctx.trace_span is None:
        return None
    summary = dispatch_trace.of(ctx)
    if summary is None or summary["fit"] is None or not summary["pairs"]:
        return None
    return QUANTITIES[what](ctx, summary)
