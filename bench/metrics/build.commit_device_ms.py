"""Device time per wave of the wave commit: the self time of the ops under
the ``wave_commit`` scope of ``construct.wave_core`` (``commit_wave``, the
stats fold and the coarse level's ``note_inserted``), inside the trace's
``jit_wave_core`` module runs, over the number of runs."""

from bench import scopes

MODULE, SCOPE = "jit_wave_core", "wave_commit"


def read(ctx):
    if ctx.trace is None:
        return None
    runs = scopes.scope_runs(scopes.scoped(ctx), MODULE, SCOPE)  # KeyError when absent
    return sum(secs for _, secs in runs) / len(runs) * 1e3
