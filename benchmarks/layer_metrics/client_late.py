"""How late the load generator sent: actual send minus due time, over the
window's sample.  A starved generator must not read as a fast server."""

from benchmarks.stats import percentile


def read(ctx, percentile_of: float):
    late = [o.late_ms() for o in ctx.load.sample() if o.sent is not None]
    if not late:
        return None
    return percentile(late, percentile_of)
