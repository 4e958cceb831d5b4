"""Fill (%) of the programs the engine dispatched: real work over
dispatched work, summed over the dispatch records of one kind (the
engine-scope spans of the program's journal that carry ``seq``) that
started inside the timed window.  ``real`` and ``dispatched`` each name the
attrs whose product is a record's work: ``tokens`` over ``positions`` for
prefill (real prompt tokens over rows x width, padding included),
``live_rows x steps`` over ``slots x steps`` for decode."""


def records(ctx, span: str):
    t0, t1 = ctx.load.t0 * 1e6, ctx.load.t1 * 1e6
    return [ev["args"] for ev in ctx.spans
            if ev.get("name") == span and ev.get("ph") == "X"
            and t0 <= ev["ts"] < t1 and "seq" in (ev.get("args") or {})]


def work(args: dict, names) -> float:
    out = 1.0
    for name in names:
        out *= args[name]
    return out


def read(ctx, span: str, real, dispatched):
    found = records(ctx, span)
    total = sum(work(a, dispatched) for a in found)
    if not total:
        return None
    return 100.0 * sum(work(a, real) for a in found) / total
