"""Where ``setup_s`` goes, from the program's start-up journal: the
``startup.*`` spans that the serve process writes once, from the kernel's
start of the process to ``startup.ready``, and that ride
``/healthz?trace=1`` on a lane of their own whatever the request rings have
turned over since.  Their clock is the machine's monotonic clock, the
client's too, so ``ctx.load.t0`` (the window's start) is on it.

``what="span"``: seconds of the named ``spans``, summed (the first record
of each name: a process starts once).  ``startup.process`` is the whole;
``startup.imports`` + ``startup.tokenizer``, ``startup.backend``,
``startup.engine_build`` and ``startup.warmup`` tile it.
``what="after_ready"``: the window's start less the instant
``startup.ready``: what the benchmark itself adds to ``setup_s`` after the
engine is warm (the tunnel's handshake, the harness's polls, ``/healthz``,
the warm requests, the mix's lead-in).

``what="programs"``: distinct ``key``s among the ``startup.program``
records: the plan's length plus the copy programs (whose records are the
serial pass's).  The other two read the records of the AOT phase (``phase:
aot``: each carries a planned program's lowering and its compile, or the
compile cache's load, apart); a start that ran no AOT phase compiled in its
serial pass, and those records are read instead.
``what="lower_per_program"``: mean ``trace_lower_s``, Python's tracing and
lowering as a WALL inside its thread: the phase's threads share one
interpreter lock, so it holds what a thread waited for the others too and
reads about ``threads`` times the lowering's CPU seconds on a warm start
(every thread lowers at once) and less on a cold one (the others sit in
XLA's compile, the lock free).  Hold it only against a run of the same
``TUNNEL_WARMUP_PAR`` and the same cache state; times ``programs`` it is
at most ``threads`` times the AOT phase, not seconds a cure could save
(that is the phase itself, ``startup.aot``).
``what="cache_misses"``: records whose ``persistent_hit`` is false (compiled
and written to the compile cache on disk): 0 on a warm start; on a cold one
``programs`` less the few that compile faster than JAX's threshold for
caching and are never written (their ``persistent_hit`` is null at every
start); whether a "warm" run was warm.

A program that writes no such span or field (the parent of the PR that
added them) gives nothing to read: None, never 0.
"""


def _first(ctx, name: str, ph: str):
    for ev in ctx.spans:
        if ev.get("name") == name and ev.get("ph") == ph:
            return ev
    return None


def programs(ctx, aot=True):
    recs = [ev.get("args") or {} for ev in ctx.spans
            if ev.get("name") == "startup.program" and ev.get("ph") == "X"]
    return aot and [a for a in recs if a.get("phase") == "aot"] or recs


def read(ctx, what: str, spans=()):
    if what == "span":
        found = [_first(ctx, name, "X") for name in spans]
        if not found or found[0] is None:
            return None
        return sum(ev["dur"] for ev in found if ev is not None) / 1e6
    if what == "after_ready":
        ready = _first(ctx, "startup.ready", "i")
        if ready is None:
            return None
        return ctx.load.t0 - ready["ts"] / 1e6
    if what == "programs":
        return len({a.get("key") for a in programs(ctx, aot=False)}) or None
    recs = programs(ctx)
    if what == "lower_per_program":
        lower = [a["trace_lower_s"] for a in recs
                 if a.get("trace_lower_s") is not None]
        return sum(lower) / len(lower) if lower else None
    if what == "cache_misses":
        told = [a["persistent_hit"] for a in recs
                if a.get("persistent_hit") is not None]
        return sum(1 for hit in told if not hit) if told else None
    raise ValueError(f"unknown quantity {what!r}")
