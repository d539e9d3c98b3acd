"""Device time of one serving wave's search: the mean over the window's
runs of the XLA module ``jit_search`` (one run a wave), in ms."""

from bench import trace as trace_lib

MODULE = "jit_search"


def read(ctx):
    if ctx.trace is None:
        return None
    runs = trace_lib.module_events(ctx.trace, MODULE)  # KeyError when absent
    return sum(e.dur for e in runs) / len(runs) / 1e6
