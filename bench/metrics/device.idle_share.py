"""Share of the window in which no operation ran on the device: 1 − the
union of the device op intervals over the window, in percent, averaged over
the chips used."""

from bench import trace as trace_lib


def read(ctx):
    if ctx.trace is None:
        return None
    busy, window = trace_lib.busy_share(ctx.trace)
    return 100.0 * (1.0 - busy / window)
