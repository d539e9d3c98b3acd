"""Share of a search call's lanes that were still climbing, per iteration.

Σ ``n_iters`` / (batch × the call's largest ``n_iters``), from each window
call's ``SearchResult`` (exact counts), as a mean over calls in percent.  A
call runs as long as its slowest lane; this says how much of that the other
lanes spent masked.
"""

import numpy as np


def read(ctx):
    occ = [float(it.sum()) / (it.size * it.max()) for it in ctx.readings.get("searches", [])
           if it.size and it.max() > 0]
    return 100.0 * float(np.mean(occ)) if occ else None
