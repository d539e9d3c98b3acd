"""Device time per run of the jitted wave step (search + commit of one
wave), from the trace's XLA module events named ``jit_wave_core``."""

from bench import trace as trace_lib

MODULE = "jit_wave_core"


def read(ctx):
    if ctx.trace is None:
        return None
    runs = trace_lib.module_events(ctx.trace, MODULE)  # KeyError when absent
    return sum(e.dur for e in runs) / len(runs) / 1e6
