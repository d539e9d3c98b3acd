"""Device time of one expansion iteration of a search call: the self time of
the ops under the ``expand`` scope of ``search.search`` and not under
``coarse_pass`` (the fine graph's ``while_loop``), inside one ``jit_search``
module run, over the iterations that call ran (its largest ``n_iters``, from
``bench/drive.py``'s ``readings["searches"]``), as a mean over the window's calls."""

import numpy as np

from bench import scopes

MODULE, SCOPE, NOT = "jit_search", "expand", ("coarse_pass",)


def read(ctx):
    if ctx.trace is None:
        return None
    runs = scopes.scope_runs(scopes.scoped(ctx), MODULE, SCOPE, NOT)  # KeyError when absent
    iters = [int(it.max()) for it in ctx.readings.get("searches", []) if it.size]
    if len(iters) != len(runs):
        raise KeyError(f"{len(runs)} {MODULE} runs in the window against "
                       f"{len(iters)} search calls")
    per_iter = [secs / n for (_, secs), n in zip(runs, iters) if n > 0]
    return 1e3 * float(np.mean(per_iter)) if per_iter else None
