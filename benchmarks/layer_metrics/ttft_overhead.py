"""What the tunnel adds to the first token: for each request of the
window, the client's time from send to first content delta minus the
engine's time from submit to first token (its ``engine.queue_wait`` and
``engine.prefill_exec`` spans, which tile that interval; matched to the
request by the trace id the client sent); the median of the differences."""

from benchmarks.client import trace_id_of
from benchmarks.stats import percentile


def read(ctx):
    engine = {}
    for ev in ctx.spans:
        if (ev.get("name") in ("engine.queue_wait", "engine.prefill_exec")
                and ev.get("ph") == "X"):
            tid = (ev.get("args") or {}).get("trace_id")
            if tid is not None:
                engine.setdefault(tid, {})[ev["name"]] = ev["dur"] / 1000.0
    added = []
    for o in ctx.load.sample():
        pair = engine.get(trace_id_of(o.index))
        if o.failed() or not pair or len(pair) != 2:
            continue
        added.append((o.first_token - o.sent) * 1000.0 - sum(pair.values()))
    if not added:
        return None
    return percentile(added, 50)
