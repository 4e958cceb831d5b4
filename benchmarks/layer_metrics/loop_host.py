"""What the engine loop's own records say of its host time (ISSUE 57).

The serve process keeps one flight record for every iteration of its loop
that did work (``p2p_llm_tunnel_tpu/utils/flight.py``), always on, and
exports them as ``engine.flight`` slices of ``/healthz?trace=1`` with the
whole record under ``args``: ``t`` and ``dur_ms`` on the monotonic clock,
``wait_ms`` the wall the iteration spent inside blocking device->host
fetches (the host waited for the chip).  ``benchmarks/LOOP_HOST.md`` reads
the rest of the record.

``what="host"``: over the slices that start inside the timed window, the sum
of ``dur_ms - wait_ms`` over the sum of ``dur_ms`` (%): the share of an
iteration the host needs for itself.  At 100 the chip waits for the host.

``what="gc"``: the durations of the ``process.gc_pause`` spans that start
inside the timed window, over its seconds (ms/s).  0.0 where the collector
made no pause worth a span there.

A journal whose flight slices carry no ``wait_ms`` is a program's from
before the split: both are None, and the metric is left out."""


def flight_records(ctx, window=None):
    """The flight records that carry the split, oldest first; with
    ``window`` (monotonic seconds) those that start inside it."""
    out = []
    for ev in ctx.spans:
        args = ev.get("args") or {}
        if (ev.get("name") != "engine.flight" or ev.get("ph") != "X"
                or "wait_ms" not in args):
            continue
        if window is None or window[0] <= float(args["t"]) < window[1]:
            out.append(args)
    return sorted(out, key=lambda a: a["t"])


def gc_pauses(ctx):
    """(start, end) of the collector's recorded pauses, monotonic
    seconds."""
    return [(ev["ts"] / 1e6, (ev["ts"] + ev["dur"]) / 1e6)
            for ev in ctx.spans
            if ev.get("name") == "process.gc_pause" and ev.get("ph") == "X"]


def read(ctx, what: str):
    window = (ctx.load.t0, ctx.load.t1)
    if what == "host":
        found = flight_records(ctx, window)
        total = sum(float(a["dur_ms"]) for a in found)
        if not total:
            return None
        return 100.0 * sum(float(a["dur_ms"]) - float(a["wait_ms"])
                           for a in found) / total
    if what == "gc":
        if not flight_records(ctx):
            return None
        return 1000.0 * sum(end - start for start, end in gc_pauses(ctx)
                            if window[0] <= start < window[1]) \
            / (window[1] - window[0])
    raise ValueError(f"unknown quantity {what!r}")
