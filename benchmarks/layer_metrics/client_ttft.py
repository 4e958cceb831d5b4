"""A percentile of the time to first token as the client saw it (from the
due time, failed requests at the timeout), over the window's sample: the
end-to-end definition, reported beside the end-to-end metrics where a tail
is too unsteady from run to run to be held to a bound."""

from benchmarks.stats import percentile, supported, ttft_values


def read(ctx, percentile_of: float):
    values = ttft_values(ctx.load.sample(), ctx.plan.timeout_s)
    if not values or not supported(len(values), percentile_of):
        return None
    return percentile(values, percentile_of)
