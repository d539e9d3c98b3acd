"""Record the small TPU trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out dir>    # on one TPU chip

Inside a ``bench.window`` host span: a 10 ms ``bench.lead`` host span (the
host's and the device's clocks in a trace differ by a millisecond or so, so
the window opens well before the first device op), two calls of the Pallas
``gather_distance`` kernel on a (8, 16) candidate table of 128-wide rows,
three runs of a jitted ``tiny_step``, and a 50 ms ``bench.wait`` host span
in which the device idles.  The ``.xplane.pb`` it writes is kept as
``bench/tests/data/tiny.xplane.pb``.
"""

import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from bench import trace as trace_lib
    from repro.kernels import gather_dist

    x = jax.random.normal(jax.random.PRNGKey(0), (256, 128), jnp.float32)
    q = x[:8]
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 256)

    @jax.jit
    def tiny_step(a):
        return jnp.tanh(a @ a.T).sum(0)

    def kernel():
        return gather_dist.gather_distance(q, x, idx, metric="l2")

    jax.block_until_ready((kernel(), tiny_step(x)))  # compile outside the trace
    tmp = os.path.join(out, "raw")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.lead"):
            time.sleep(0.01)
        for _ in range(2):
            jax.block_until_ready(kernel())
        for _ in range(3):
            jax.block_until_ready(tiny_step(x))
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.05)
        jax.block_until_ready(tiny_step(x))
    jax.profiler.stop_trace()
    src = trace_lib.find_xplane(tmp)
    shutil.copy(src, os.path.join(out, "tiny.xplane.pb"))
    shutil.rmtree(tmp)
    print(trace_lib.summary(trace_lib.load(os.path.join(out, "tiny.xplane.pb"))))


if __name__ == "__main__":
    main(sys.argv[1])
