"""The device's idle time inside ``bench_window``, put down to what the
engine loop was doing meanwhile (ISSUE 57): the table the breakdown's
``idle_gaps`` lack, and the share of the idle time that is the host's.

Idle time is the complement of the merged ``XLA Ops`` intervals inside the
window, as ``trace_reduce.reduce_device`` takes it.  What the host was doing
comes from the loop's own flight records (the ``engine.flight`` slices of
the journal; ``loop_host.py``): each gives its iteration's start and length
on the monotonic clock, the first start of each of its phases (``at_ms``)
and the fetches in which it waited for the chip (``waits_ms``).  They come
onto the trace's clock by the dispatch ledger's fit
(``dispatch_trace.of(ctx)``): a phase runs from its start to the next
phase's, the last to the iteration's end; between two records the loop was
parked.  The collector's pauses (``process.gc_pause`` spans) lie across
them.

Every idle instant is then one of: a **phase** (``admit``,
``prefill_dispatch``, ``decode_dispatch``, ``decode_fetch``, ``process``,
``segments``, ``drain``) outside a fetch, which is the host at WORK while
the chip has nothing; **wait** (inside a fetch: the host is waiting for a
result, burst n + 1 was queued before it asked, so the chip idles there only
if there was nothing to dispatch); **park** (no iteration: nothing to do);
or **unrecorded** (before the oldest record the ring still held).  ``gc``
stands beside them: idle time under a pause of the collector, whatever else
it lies in.

``read`` prints that table, the ten longest gaps (iteration, phase, and the
record's ``lag_ms``, ``evict_ms``, ``gc_ms``) and the fit's largest
residual, and returns the share (%) of the idle seconds that overlap host
work: a phase outside its fetches, or a pause of the collector.  0.0 where
the window idles under a millisecond in all; None without flight records
that carry the split, without a device plane or without a fit."""

import bisect
import os

from benchmarks import dispatch_trace, stack, trace_reduce, xplane_read
from benchmarks.correctness import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
#: the least idle time there is anything to attribute in
LEAST_IDLE_S = 0.001
LONGEST = 10


def busy_intervals(planes):
    """The merged intervals in which the first device that ran anything ran
    an operation; None without such a plane."""
    for plane in planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        ops = [(start, end) for line in plane.line(trace_reduce.OPS_LINE)
               for _name, start, end, _stats in line.events(stats=False)]
        if ops:
            return trace_reduce.merge(ops)
    return None


def busy_of(ctx):
    """``busy_intervals`` of the run's trace, read once and kept on
    ``ctx``."""
    if not hasattr(ctx, "device_busy"):
        ctx.device_busy = None
        trace_dir = os.path.join(stack.work_dir(ctx.cell), "trace")
        try:
            path = trace_reduce.newest_xplane(trace_dir)
        except FileNotFoundError:
            return None
        ctx.device_busy = busy_intervals(xplane_read.read(path))
    return ctx.device_busy


def idle_gaps(busy, window):
    """The gaps between the busy intervals inside ``window``, its edges
    included."""
    w0, w1 = window
    edges = [w0]
    for start, end in busy:
        if end > w0 and start < w1:
            edges += [max(start, w0), min(end, w1)]
    edges.append(w1)
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def lay(record, offset_s):
    """One flight record on the trace's clock: (start, end, [(phase,
    start, end)], [(wait start, wait end)])."""
    start = float(record["t"]) + offset_s
    end = start + float(record["dur_ms"]) / 1e3
    marks = sorted((at / 1e3 + start, phase)
                   for phase, at in (record.get("at_ms") or {}).items())
    if marks:
        marks[0] = (start, marks[0][1])
    phases = [(phase, t, marks[i + 1][0] if i + 1 < len(marks) else end)
              for i, (t, phase) in enumerate(marks)]
    waits = [(start + at / 1e3, start + (at + length) / 1e3)
             for at, length in record.get("waits_ms") or []]
    return start, end, phases, waits


def overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def touching(intervals, ends, g0, g1):
    """Those of ``intervals`` (in order, apart; ``ends`` their ends) that
    overlap ``(g0, g1)``."""
    i = bisect.bisect_right(ends, g0)
    while i < len(intervals) and intervals[i][0] < g1:
        yield intervals[i]
        i += 1


def attribute(gaps, records, pauses, offset_s):
    """``(by, rows)``: idle seconds by ``phase`` / ``wait`` / ``park`` /
    ``unrecorded`` and, beside them, ``gc``; and for each gap ``(length,
    start, iteration, what, record)``: where most of it lies."""
    laid = [lay(r, offset_s) + (r,) for r in records]
    ends = [it[1] for it in laid]
    evicted_before = bool(records) and int(records[0].get("iter", 1)) > 1
    first = laid[0][0] if laid else float("inf")
    by = {"gc": 0.0}
    rows = []
    for g0, g1 in gaps:
        mine = {}  # (iteration, what) -> (seconds of this gap, record)

        def add(what, seconds, rec=None):
            if seconds > 1e-9:  # (a rounding's sliver is no reading)
                by[what] = by.get(what, 0.0) + seconds
                key = (rec.get("iter") if rec else None, what)
                mine[key] = (mine.get(key, (0.0, rec))[0] + seconds, rec)

        covered = 0.0
        for start, end, phases, waits, rec in touching(laid, ends, g0, g1):
            covered += overlap(g0, g1, start, end)
            for phase, p0, p1 in phases:
                inside = overlap(g0, g1, p0, p1)
                waited = sum(overlap(max(g0, p0), min(g1, p1), w0, w1)
                             for w0, w1 in waits) if inside else 0.0
                add(phase, inside - waited, rec)
                add("wait", waited, rec)
        outside = (g1 - g0) - covered
        before = min(outside, overlap(g0, g1, float("-inf"), first)) \
            if evicted_before else 0.0
        add("unrecorded", before)
        add("park", outside - before)
        by["gc"] += sum(overlap(g0, g1, p0 + offset_s, p1 + offset_s)
                        for p0, p1 in pauses)
        (iteration, what), (_secs, rec) = max(
            mine.items(), key=lambda kv: kv[1][0],
            default=((None, "park"), (0.0, None)))
        rows.append((g1 - g0, g0, iteration, what, rec))
    return by, rows


def host_seconds(gaps, records, pauses, offset_s):
    """Idle seconds that overlap host work: an iteration outside its
    fetches, or a pause of the collector (each instant once)."""
    work = []
    for rec in records:
        start, end, _phases, waits = lay(rec, offset_s)
        t = start
        for w0, w1 in sorted(waits):
            work.append((t, max(t, w0)))
            t = max(t, w1)
        work.append((t, max(t, end)))
    work += [(p0 + offset_s, p1 + offset_s) for p0, p1 in pauses]
    work = trace_reduce.merge([iv for iv in work if iv[1] > iv[0]])
    ends = [iv[1] for iv in work]
    return sum(overlap(g0, g1, w0, w1) for g0, g1 in gaps
               for w0, w1 in touching(work, ends, g0, g1))


def read(ctx):
    journal = load_module(os.path.join(HERE, "loop_host.py"))
    records = journal.flight_records(ctx)
    summary = dispatch_trace.of(ctx)
    if not records or summary is None or summary["window"] is None \
            or summary["fit"] is None:
        return None
    busy = busy_of(ctx)
    if busy is None:
        return None
    fit, window = summary["fit"], summary["window"]
    offset = fit["offset_s"]
    gaps = idle_gaps(busy, window)
    idle = sum(g1 - g0 for g0, g1 in gaps)
    pauses = journal.gc_pauses(ctx)
    by, rows = attribute(gaps, records, pauses, offset)
    held = [r for r in records
            if window[0] <= float(r["t"]) + offset < window[1]]
    stack.say(
        f"idle by phase: {idle:.6f} s idle of {window[1] - window[0]:.3f} s "
        f"in {len(gaps)} gaps; {len(held)} iterations start inside the "
        f"window (records {records[0].get('iter')}..{records[-1].get('iter')}"
        f" held); the clock fit's largest residual "
        f"{fit['residual_max_us']:.1f} us")
    if idle < LEAST_IDLE_S:
        return 0.0
    gc_s = by.pop("gc")
    for what, secs in sorted(by.items(), key=lambda kv: -kv[1]):
        stack.say(f"idle by phase: {what:>16} {secs:.6f} s "
                  f"{100.0 * secs / idle:5.1f} %")
    stack.say(f"idle by phase: {'gc (beside them)':>16} {gc_s:.6f} s "
              f"{100.0 * gc_s / idle:5.1f} %")
    for length, start, iteration, what, rec in sorted(
            rows, key=lambda r: -r[0])[:LONGEST]:
        rec = rec or {}
        stack.say(
            f"idle by phase: gap {1e3 * length:9.3f} ms at "
            f"{start - window[0]:.4f} s: "
            + (f"iteration {iteration} " if iteration is not None else "")
            + what
            + (f" (lag_ms {rec.get('lag_ms')}, evict_ms "
               f"{rec.get('evict_ms')}, gc_ms {rec.get('gc_ms')}, dur_ms "
               f"{rec.get('dur_ms')})" if rec else ""))
    return 100.0 * host_seconds(gaps, records, pauses, offset) / idle
