"""Host time of one serving wave: the mean ``bench.step`` host span (a
``ServingLoop.step``, which waits for its wave's answers) less the mean
device run of the wave's search (``jit_search``, one a wave), in ms: the
queue, padding, transfer, result pull, eager slices and stats fold."""

from bench import trace as trace_lib

SPAN = "bench.step"
MODULE = "jit_search"


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = trace_lib.window(ctx.trace)
    steps = [e for e in ctx.trace.host if e.name == SPAN and e.start >= lo and e.end <= hi]
    if not steps:
        raise KeyError(f"no {SPAN!r} host span in the window")
    runs = trace_lib.module_events(ctx.trace, MODULE)  # KeyError when absent
    return (sum(e.dur for e in steps) / len(steps) - sum(e.dur for e in runs) / len(runs)) / 1e6
