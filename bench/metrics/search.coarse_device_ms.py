"""Device time per search call of the coarse pass: the self time of the ops
under the ``coarse_pass`` scope of ``search.init_state`` (the EHC pass over
the landmark graph, its own expansion loop included), inside the trace's
``jit_search`` module runs, over the number of runs."""

from bench import scopes

MODULE, SCOPE = "jit_search", "coarse_pass"


def read(ctx):
    if ctx.trace is None:
        return None
    runs = scopes.scope_runs(scopes.scoped(ctx), MODULE, SCOPE)  # KeyError when absent
    return sum(secs for _, secs in runs) / len(runs) * 1e3
