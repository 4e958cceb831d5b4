"""What attention had to read of the cache, by layer kind, from the
program's dispatch ledger: the ``engine.decode_burst`` records (by default)
that started inside the timed window carry ``kv_rows_full`` and
``kv_rows_window``: cache positions x layers that the dispatch's attention
had to read in full-attention and in window layers, counted on the host from
the rows' positions (a query at position p sees p + 1 positions in a full
layer, ``min(p + 1, window)`` in a window layer).  The same numbers grow
``engine_kv_rows_{full,window}_total``.

``what="window_share"``: window over both (%): the share of attention's
reads that the windows bound.  With 5 of 7 layers windowed at 128 and
contexts of thousands it is a few per cent; under a uniform cache read by
mask every layer would read the context.

A program that counts nothing (the parent of the PR that added the counts)
gives nothing to read.
"""

KEYS = ("kv_rows_full", "kv_rows_window")


def read(ctx, what: str, spans=("engine.decode_burst",)):
    t0, t1 = ctx.load.t0 * 1e6, ctx.load.t1 * 1e6
    found = [ev["args"] for ev in ctx.spans
             if ev.get("name") in spans and ev.get("ph") == "X"
             and t0 <= ev["ts"] < t1
             and all(k in (ev.get("args") or {}) for k in KEYS)]
    full = sum(a["kv_rows_full"] for a in found)
    window = sum(a["kv_rows_window"] for a in found)
    if not full + window:
        return None
    if what == "window_share":
        return 100.0 * window / (full + window)
    raise ValueError(f"unknown quantity {what!r}")
