"""GloVe-like rows: a copy of ``repro.data.synthetic.heavy_tailed``, kept
with the benchmark so that a change to the program's generators cannot
change the data a cell runs on."""

import jax
import jax.numpy as jnp


def heavy_tailed(key, n, d, *, alpha=1.1):
    """Directions with power-law coordinate scales (high intrinsic dimension
    under cosine) and Pareto norms."""
    kg, ks = jax.random.split(key)
    g = jax.random.normal(kg, (n, d))
    scales = jnp.arange(1, d + 1, dtype=jnp.float32) ** (-alpha / 2.0)
    x = g * scales[None, :]
    norms = jax.random.pareto(ks, 3.0, (n, 1)) + 1.0
    return (x * norms).astype(jnp.float32)
