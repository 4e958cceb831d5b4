"""The program's dispatch records joined to the device trace.

While its span journal is on, the engine wraps every dispatch call in a
``jax.profiler.TraceAnnotation`` named as the dispatch's journal span
(``engine.decode_burst``, ``engine.prefill_segment``, ``engine.pool_copy``)
with ``seq`` (the join key to the journal), ``steps`` or ``tokens``, and
``mono_us``: the process's monotonic clock at the dispatch.  A device trace
taken meanwhile holds those annotations on its host plane, on its own clock.
From one traced run this module gives:

- the clock offset: the median of ``annotation start - mono_us`` over all
  annotations, and the residuals around it.  With it every journal span and
  client timestamp (the same monotonic clock) is on the trace's clock;
- the pairing of device runs with dispatch records, family by family.  The
  device runs programs in dispatch order, so the runs of a family and its
  annotations are the same sequence, shifted by the ``h`` runs at the trace's
  head that were dispatched before the profile began.  A record closes when
  the host has fetched the run's result, so no run ends after its own
  record's end; a run paired with an earlier dispatch's record would.
  ``h`` is therefore the largest shift under which every pair still has
  ``run end <= record end``.  Head runs stay unpaired and are left out;
- device self-time inside ``bench_window`` by name scope (``tf_op`` of an
  operation's metadata: the innermost of the scopes the program names).

It knows nothing of the model.  On a trace without device planes (a CPU
rehearsal), without annotations (a program from before the ledger) or
without scopes, the part concerned is None or empty and the readers return
nothing.
"""

from __future__ import annotations

import os
import statistics
from typing import Dict, List, Optional

from benchmarks import stack, trace_reduce, xplane_read

#: journal span / annotation name -> substrings of the XLA programs it
#: dispatches (the program families that are paired).
FAMILIES = {
    "engine.decode_burst": ("_decode_fn",),
    "engine.prefill_segment": ("_chunk_prefill_fn", "_prefill_fn",
                               "_ragged_prefill_fn"),
}
#: every annotation of the ledger (the clock fit uses them all)
ANNOTATIONS = tuple(FAMILIES) + ("engine.pool_copy",)
#: the scopes the program names (models/transformer.py, engine/engine.py,
#: engine/prefix_cache.py)
SCOPES = ("kv_read", "kv_write", "attn", "ffn", "head_sample", "pool_copy")
#: slack on ``run end <= record end``: the clock fit's residual and the
#: journal's whole microseconds
END_SLACK_S = 0.002


def scope_of(tf_op: Optional[str]) -> Optional[str]:
    """The innermost named scope on an operation's path, or None."""
    if not tf_op:
        return None
    for part in reversed(str(tf_op).rstrip(":").split("/")[:-1]):
        if part in SCOPES:
            return part
    return None


def clock_fit(annotations: List[dict]) -> Optional[dict]:
    """``trace clock = monotonic clock + offset_s``."""
    deltas = [a["start"] - a["mono_us"] / 1e6 for a in annotations]
    if not deltas:
        return None
    offset = statistics.median(deltas)
    residuals = sorted(abs(d - offset) for d in deltas)
    return {"offset_s": offset, "annotations": len(deltas),
            "residual_p50_us": residuals[len(residuals) // 2] * 1e6,
            "residual_max_us": residuals[-1] * 1e6}


def pair_family(runs: List[tuple], annotations: List[dict],
                record_end: Dict[int, float]) -> List[Optional[dict]]:
    """For each run (start, end), in start order, the annotation it
    executes, or None for a head run dispatched before the profile began.

    ``record_end`` maps ``seq`` to the end of that dispatch's journal record
    on the trace's clock; an annotation without one constrains nothing, and
    with nothing to hold the shift against every run stays unpaired."""
    def causal(shift: int) -> bool:
        """No run starts before its dispatch or ends after its record."""
        for run, ann in zip(runs[shift:], annotations):
            end = record_end.get(ann["seq"])
            if run[0] < ann["start"] or (
                    end is not None and run[1] > end + END_SLACK_S):
                return False
        return True

    # the shifts that hold are a range (too small: a run before its
    # dispatch; too large: a run after its record's end); its largest
    shift = next(h for h in range(len(runs) + 1) if causal(h))
    while shift < len(runs) and causal(shift + 1):
        shift += 1
    return [None] * shift + [
        annotations[i] if i < len(annotations) else None
        for i in range(len(runs) - shift)]


def request_parts(spans: List[dict]) -> Dict[str, List[dict]]:
    """trace id -> the request's ``engine.prefill_part`` spans."""
    parts: Dict[str, List[dict]] = {}
    for ev in spans:
        if ev.get("name") == "engine.prefill_part" and ev.get("ph") == "X":
            parts.setdefault(ev["args"].get("trace_id"), []).append(ev)
    return parts


def journal_records(spans: List[dict], offset_s: float) -> Dict[str, dict]:
    """span name -> seq -> (start, end) on the trace's clock, from the
    journal's dispatch records."""
    out: Dict[str, dict] = {name: {} for name in ANNOTATIONS}
    for ev in spans:
        args = ev.get("args") or {}
        if ev.get("name") in out and ev.get("ph") == "X" and "seq" in args:
            start = ev["ts"] / 1e6 + offset_s
            out[ev["name"]][args["seq"]] = (start, start + ev["dur"] / 1e6)
    return out


def summarize(planes, spans: List[dict]) -> dict:
    """Everything the readers need of one trace, see the module's text."""
    window = None
    annotations: List[dict] = []
    for plane in planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for name, start, end, stats in line.events():
                if name == trace_reduce.WINDOW_NAME:
                    window = (start, end)
                elif (name in ANNOTATIONS and "seq" in stats
                      and "mono_us" in stats):
                    annotations.append({
                        "name": name, "start": start, "end": end,
                        "seq": int(stats["seq"]),
                        "mono_us": int(stats["mono_us"]),
                        "steps": int(stats.get("steps", 0)),
                        "tokens": int(stats.get("tokens", 0))})
    annotations.sort(key=lambda a: a["start"])
    fit = clock_fit(annotations)
    out = {"window": window, "fit": fit, "pairs": {}, "scopes": None,
           "records": None}
    devices = [p for p in planes
               if p.name.startswith(trace_reduce.DEVICE_PREFIX)
               and any(len(ln) for ln in p.line(trace_reduce.OPS_LINE))]
    if window is None or not devices:
        return out
    device = devices[0]  # one chip a cell; a replica's twin runs the same

    if fit is not None:
        records = journal_records(spans, fit["offset_s"])
        out["records"] = records
        modules = sorted(
            (start, end, name)
            for line in device.line(trace_reduce.MODULES_LINE)
            for name, start, end, _stats in line.events(stats=False))
        for span, programs in FAMILIES.items():
            runs = [(s, e) for s, e, name in modules
                    if any(p in name for p in programs)]
            anns = [a for a in annotations if a["name"] == span]
            ends = {seq: se[1] for seq, se in records[span].items()}
            paired = pair_family(runs, anns, ends)
            out["pairs"][span] = [
                {"start": s, "end": e, "annotation": ann}
                for (s, e), ann in zip(runs, paired)]

    ops = [(scope_of(stats.get("tf_op")) or "unscoped", start, end)
           for line in device.line(trace_reduce.OPS_LINE)
           for _name, start, end, stats in line.events(stats=False)]
    own = trace_reduce.self_times(trace_reduce.clip(ops, window))
    if any(scope in own for scope in SCOPES):
        out["scopes"] = dict(own)
    return out


def of(ctx) -> Optional[dict]:
    """The summary of the run's trace, made once and kept on ``ctx``; None
    when the run recorded no trace."""
    if hasattr(ctx, "dispatch_trace"):
        return ctx.dispatch_trace
    ctx.dispatch_trace = None
    trace_dir = os.path.join(stack.work_dir(ctx.cell), "trace")
    try:
        path = trace_reduce.newest_xplane(trace_dir)
    except FileNotFoundError:
        return None
    summary = summarize(xplane_read.read(path), ctx.spans)
    ctx.dispatch_trace = summary
    fit = summary["fit"]
    if fit is not None:
        stack.say(
            f"dispatch ledger: {fit['annotations']} annotations on the "
            f"trace; journal clock + {fit['offset_s']:.6f} s = trace clock, "
            f"residual median {fit['residual_p50_us']:.1f} us, largest "
            f"{fit['residual_max_us']:.1f} us")
    for span, runs in summary["pairs"].items():
        inside = in_window(summary, runs)
        stack.say(f"dispatch ledger: {span}: {len(runs)} device runs, "
                  f"{len(inside)} inside the window, "
                  f"{sum(1 for r in inside if r['annotation'])} of those "
                  f"paired with a dispatch record")
    if summary["scopes"]:
        total = sum(summary["scopes"].values())
        shares = ", ".join(
            f"{name} {100.0 * secs / total:.1f} %" for name, secs in
            sorted(summary["scopes"].items(), key=lambda kv: -kv[1]))
        stack.say(f"dispatch ledger: device self-time by scope: {shares}")
    return summary


def in_window(summary: dict, runs: List[dict]) -> List[dict]:
    """The runs that lie whole inside ``bench_window``."""
    w0, w1 = summary["window"]
    return [r for r in runs if r["start"] >= w0 and r["end"] <= w1]
