"""Record the small TPU trace that ``test_scopes.py`` reduces.

    python3 bench/tests/record_scoped_trace.py <out dir>    # on one TPU chip

What ``record_trace.py`` records, with a ``jax.named_scope`` (``tiny``)
inside ``tiny_step``, and then, still inside the ``bench.window`` host span:

* an ``index/search`` program span (``repro.obs.NOOP``, a profiler
  TraceAnnotation) around two runs of a jitted ``scoped_step`` whose scopes
  nest as the search's do: a ``coarse_pass`` scope around a nested jitted
  function with an ``expand`` scope and a loop of its own, then an
  ``expand`` scope around a loop;
* a 30 ms ``build/wave`` program span in which the device idles.

The ``.xplane.pb`` it writes is kept as
``bench/tests/data/scoped.xplane.pb``.
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

    from bench import scopes
    from bench import trace as trace_lib
    from repro.kernels import gather_dist
    from repro.obs import NOOP

    x = jax.random.normal(jax.random.PRNGKey(0), (256, 128), jnp.float32)
    q = x[:8]
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 256)

    @jax.jit
    def tiny_step(a):
        with jax.named_scope("tiny"):
            return jnp.tanh(a @ a.T).sum(0)

    def loop(n, body, init):
        """``n`` runs of ``body``, a trip count the compiler cannot see, so
        the loop stays one ``while`` op around its body's ops."""
        trips = jnp.where(init[0, 0] > -1e30, n, 0)
        return jax.lax.while_loop(lambda s: s[0] < trips,
                                  lambda s: (s[0] + 1, body(s[1])), (0, init))[1]

    @jax.jit
    def inner(b):
        with jax.named_scope("expand"):
            return loop(3, lambda c: jnp.tanh(c @ b), b)

    @jax.jit
    def scoped_step(a):
        with jax.named_scope("coarse_pass"):
            b = inner(a[:128])
        with jax.named_scope("expand"):
            c = loop(4, lambda c: jnp.tanh(c @ b), a)
        return c.sum(0)

    def kernel():
        return gather_dist.gather_distance(q, x, idx, metric="l2")

    # compile outside the trace
    jax.block_until_ready((kernel(), tiny_step(x), scoped_step(x)))
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
        with NOOP.span("index/search"):
            for _ in range(2):
                jax.block_until_ready(scoped_step(x))
        with NOOP.span("build/wave"):
            time.sleep(0.03)
    jax.profiler.stop_trace()
    src = trace_lib.find_xplane(tmp)
    dst = os.path.join(out, "scoped.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    tr = scopes.attach(trace_lib.load(dst), dst)
    print(trace_lib.summary(tr))
    print(scopes.summary(tr))
    for e in tr.ops[tr.devices[0]]:
        print(f"  {e.stats.get('tf_op')}  {trace_lib.op_label(e.name)}  {e.dur}")


if __name__ == "__main__":
    main(sys.argv[1])
