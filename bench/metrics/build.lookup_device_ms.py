"""Device time per wave of the LGD commit's D lookup: the self time of the
ops under the ``d_lookup`` scope of ``construct.commit_wave`` (nested in
``wave_commit``), inside the trace's ``jit_wave_core`` module runs, over the
number of runs."""

from bench import scopes

MODULE, SCOPE = "jit_wave_core", "d_lookup"


def read(ctx):
    if ctx.trace is None:
        return None
    runs = scopes.scope_runs(scopes.scoped(ctx), MODULE, SCOPE)  # KeyError when absent
    return sum(secs for _, secs in runs) / len(runs) * 1e3
