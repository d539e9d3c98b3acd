"""Device time per wave of the insertion searches: the self time of the ops
under the ``wave_search`` scope of ``construct.wave_core``, inside the
trace's ``jit_wave_core`` module runs, over the number of runs (the
denominator of ``build.wave_device_ms``)."""

from bench import scopes

MODULE, SCOPE = "jit_wave_core", "wave_search"


def read(ctx):
    if ctx.trace is None:
        return None
    runs = scopes.scope_runs(scopes.scoped(ctx), MODULE, SCOPE)  # KeyError when absent
    return sum(secs for _, secs in runs) / len(runs) * 1e3
