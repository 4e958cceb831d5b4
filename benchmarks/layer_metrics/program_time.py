"""Device time of a family of the program's XLA programs inside the traced
window, from the device trace, divided by the work they did there.

``per="ktok"``: by thousands of prompt tokens prefilled between the trace's
two edges, read off the ``engine_prefill_tokens_total`` polls taken around
it.  (A decode step's time is ``dispatch_device``'s: the program's own
dispatch records say how many steps each run carried.)"""


def matching(ctx, modules):
    programs = (ctx.trace or {}).get("programs") or {}
    return [p for name, p in programs.items()
            if any(m in name for m in modules)]


def counter_between(ctx, name: str):
    """Growth of a polled counter between the trace window's edges, by
    linear interpolation between the polls on either side."""
    if not ctx.polls or ctx.trace_span is None:
        return None

    def at(t):
        before = [p for p in ctx.polls if p[0] <= t]
        after = [p for p in ctx.polls if p[0] > t]
        if not before or not after:
            return None
        (ta, a), (tb, b) = before[-1], after[0]
        if name not in a or name not in b:
            return None
        return a[name] + (b[name] - a[name]) * (t - ta) / (tb - ta)

    lo, hi = at(ctx.trace_span[0]), at(ctx.trace_span[1])
    if lo is None or hi is None:
        return None
    return hi - lo


def read(ctx, modules, per: str):
    found = matching(ctx, modules)
    if not found:
        return None
    if per != "ktok":
        raise ValueError(f"unknown divisor {per!r}")
    device_ms = sum(p["device_s"] for p in found) * 1000.0
    tokens = counter_between(ctx, "engine_prefill_tokens_total")
    return device_ms / (tokens / 1000.0) if tokens else None
