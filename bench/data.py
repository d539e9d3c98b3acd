"""Seeded catalogs and query pools, made on the device in one jitted call.

The generator is a copy of ``repro.data.synthetic.clustered`` (the benchmark
keeps its own yardstick: a later change to the program's generators cannot
change the data a cell runs on).  A configuration names its generator,
parameters and data seed; ``make`` draws ``n_rows`` rows from one key, so the
catalog and its held-out queries share one distribution.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def clustered(key, n, d, *, n_clusters=256, intrinsic_dim=16, noise=0.05):
    """SIFT-like: Gaussian clusters on a low-dimensional linear manifold."""
    kc, kb, kz, kn = jax.random.split(key, 4)
    basis = jax.random.normal(kb, (intrinsic_dim, d)) / jnp.sqrt(d)
    centers_z = jax.random.normal(kc, (n_clusters, intrinsic_dim))
    assign = jax.random.randint(kz, (n,), 0, n_clusters)
    local = jax.random.normal(kn, (n, intrinsic_dim)) * 0.15
    z = centers_z[assign] + local
    x = z @ basis + noise * jax.random.normal(jax.random.fold_in(kn, 1), (n, d))
    return x.astype(jnp.float32)


GENERATORS = {"clustered": clustered}


def seed_key(seed: int, stream: int = 0):
    """A PRNG key for any non-negative seed, also past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed % (1 << 31))
    key = jax.random.fold_in(key, seed >> 31)
    return jax.random.fold_in(key, stream)


@functools.partial(jax.jit, static_argnames=("kind", "n", "d", "params"))
def _make(key, kind, n, d, params):
    return GENERATORS[kind](key, n, d, **dict(params))


def make(data_cfg: dict, key, n_rows: int, d: int):
    """``n_rows`` rows of the configuration's distribution, on the device."""
    params = tuple(sorted(data_cfg.get("params", {}).items()))
    return _make(key, data_cfg["generator"], n_rows, d, params)
