"""Device time per answered query: the union of the device op intervals
over the window (averaged over the chips used) over the queries the window
answered, in ms."""

from bench import trace as trace_lib


def read(ctx):
    if ctx.trace is None:
        return None
    answered = ctx.outcome.attempted - ctx.outcome.failed
    if not answered:
        return None
    busy, _ = trace_lib.busy_share(ctx.trace)
    return 1e3 * busy / answered
