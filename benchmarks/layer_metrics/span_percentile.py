"""A percentile of the durations (ms) of one of the program's spans, over
the spans that started inside the window.  Spans come from the serve
process's journal (``/healthz?trace=1``); their clock is the machine's
monotonic clock, the same as the client's."""

from benchmarks.stats import percentile


def durations_ms(ctx, span: str):
    t0, t1 = ctx.load.t0 * 1e6, ctx.load.t1 * 1e6
    return [ev["dur"] / 1000.0 for ev in ctx.spans
            if ev.get("name") == span and ev.get("ph") == "X"
            and t0 <= ev["ts"] < t1]


def read(ctx, span: str, percentile_of: float):
    durs = durations_ms(ctx, span)
    if not durs:
        return None
    return percentile(durs, percentile_of)
