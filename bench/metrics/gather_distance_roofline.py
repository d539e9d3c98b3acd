"""Share of its roofline that the Pallas ``gather_distance`` kernel reaches.

The least time the chip needs for the bytes and operations of every call in
the window (``bench/roofline.py``, from each call's (B, C_pad) shape in the
trace and the table width ``d`` of the configuration) over the kernel's
summed device time.  The kernel is memory-bound.  No call in the window (a
table the kernel does not take) reads nothing.
"""

from bench import roofline
from bench import trace as trace_lib


def read(ctx):
    if ctx.trace is None:
        return None
    calls = trace_lib.kernel_events(ctx.trace, "gather_distance")
    d = ctx.config["d"]
    ops = nbytes = secs = 0.0
    for e in calls:
        b, c_pad = trace_lib.kernel_shape(e)
        o, n = roofline.gather_distance_cost(b, c_pad, d)
        ops, nbytes, secs = ops + o, nbytes + n, secs + e.dur / 1e9
    share, _bound = roofline.roofline_share(ops, nbytes, secs, ctx.device_kind)
    return share
