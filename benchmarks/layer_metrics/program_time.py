"""Device time of a family of the program's XLA programs inside the traced
window, from the device trace, divided by the work they did there.

``per="step"``: by the decode steps those programs ran: the trace shows
how often each program's innermost loop body ran, which is once for each
layer of each step.  That count follows the program's loop structure, so
it is held against something the program publishes: a step emits at most
one token a slot, so the steps counted may not be fewer than the
``engine_tokens_total`` emitted between the trace's edges over the slots
(less a quarter, for the polls' coarseness).  A program whose layer loop
was unrolled or fused would read too few steps, and fails here instead of
reporting a step that looks faster.  ``per="ktok"``: by thousands of
prompt tokens prefilled between the trace's two edges, read off the
``engine_prefill_tokens_total`` polls taken around it."""


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
        return a[name] + (b[name] - a[name]) * (t - ta) / (tb - ta)

    lo, hi = at(ctx.trace_span[0]), at(ctx.trace_span[1])
    if lo is None or hi is None:
        return None
    return hi - lo


def _slots(config):
    args = (config.get("serve") or {}).get("args") or []
    return int(args[args.index("--slots") + 1]) if "--slots" in args else None


def read(ctx, modules, per: str):
    found = matching(ctx, modules)
    if not found:
        return None
    device_ms = sum(p["device_s"] for p in found) * 1000.0
    if per == "step":
        layers = int(ctx.config["num_hidden_layers"])
        passes = sum(p["body_runs"] for p in found)
        if passes % layers:
            raise ValueError(f"{passes} loop-body passes in {modules} are "
                             f"not whole steps of {layers} layers")
        if not passes:
            return None
        steps = passes // layers
        tokens = counter_between(ctx, "engine_tokens_total")
        slots = _slots(ctx.config)
        if tokens is not None and slots and steps * slots < 0.75 * tokens:
            raise ValueError(
                f"{steps} decode steps counted in the trace cannot have "
                f"emitted the {tokens:.0f} tokens the program counted in "
                f"that time on {slots} slots: the step count is wrong")
        return device_ms / steps
    if per == "ktok":
        tokens = counter_between(ctx, "engine_prefill_tokens_total")
        return device_ms / (tokens / 1000.0) if tokens else None
    raise ValueError(f"unknown divisor {per!r}")
