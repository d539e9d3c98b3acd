"""Distance metrics for k-NN graph construction and search.

The paper's central "generic" claim is that OLG/LGD make no assumption about
the metric beyond it being computable pairwise.  Everything in ``repro.core``
is therefore written against this registry; adding a metric here makes it
available to brute force, EHC search, OLG/LGD construction, NN-Descent and the
benchmarks alike.

Conventions
-----------
* Smaller distance == closer (the paper's convention, footnote 1).
* ``l2`` is the *squared* euclidean distance.  Squaring is monotone, so every
  ordering-based quantity (k-NN lists, recalls, occlusion comparisons between
  distances) is unchanged while the MXU-friendly ``|q|^2 + |x|^2 - 2 q.x``
  expansion stays a single matmul.  Benchmarks that report raw distances
  sqrt() at the edge.
* ``chi2`` assumes non-negative inputs (BoVW histograms, the paper's NUSW
  setting).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

Array = jax.Array

# Every matmul below contracts at full fp32 precision.  On a TPU the default
# precision contracts f32 in bf16 passes, which the norms expansion turns into
# errors on exactly the small distances that rank nearest neighbours — and
# this registry is the exact reference every recall audit compares against.
# On the CPU HIGHEST is what the default already computes.
_HIGHEST = jax.lax.Precision.HIGHEST

# metric name -> (pairwise_fn, needs_matmul)
_REGISTRY: Dict[str, Callable[[Array, Array], Array]] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn

    return deco


def names():
    return sorted(_REGISTRY)


def pairwise(metric: str, q: Array, x: Array) -> Array:
    """All-pairs distances.

    Args:
      metric: registry key ("l2", "l1", "cosine", "chi2", "ip").
      q: (m, d) queries.
      x: (n, d) points.

    Returns:
      (m, n) distances, float32.
    """
    if metric not in _REGISTRY:
        raise KeyError(f"unknown metric {metric!r}; have {names()}")
    return _REGISTRY[metric](q, x)


def one_to_many(metric: str, q: Array, x: Array) -> Array:
    """(d,) query vs (n, d) points -> (n,) distances."""
    return pairwise(metric, q[None, :], x)[0]


@register("l2")
def _l2(q: Array, x: Array) -> Array:
    # Squared euclidean via the matmul expansion: hits the MXU on TPU and is
    # the form the Pallas kernel implements.  max(., 0) guards the tiny
    # negative residue of the expansion in low precision.
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=-1, keepdims=True)  # (m, 1)
    xn = jnp.sum(x * x, axis=-1, keepdims=True).T  # (1, n)
    d = qn + xn - 2.0 * jnp.matmul(q, x.T, precision=_HIGHEST)
    return jnp.maximum(d, 0.0)


@register("ip")
def _ip(q: Array, x: Array) -> Array:
    # Negative inner product (so that smaller == closer holds).
    return -jnp.matmul(
        q.astype(jnp.float32), x.astype(jnp.float32).T, precision=_HIGHEST
    )


@register("cosine")
def _cosine(q: Array, x: Array) -> Array:
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    return 1.0 - jnp.matmul(qn, xn.T, precision=_HIGHEST)


@register("l1")
def _l1(q: Array, x: Array) -> Array:
    # VPU-bound: no matmul form exists.  Blocked over the feature axis to keep
    # the (m, n, d_block) broadcast bounded; XLA fuses the abs/sum.
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    m, d = q.shape
    n = x.shape[0]
    block = 128 if d > 128 else d
    nblk = -(-d // block)
    pad = nblk * block - d
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad)))
        x = jnp.pad(x, ((0, 0), (0, pad)))
    qb = q.reshape(m, nblk, block)
    xb = x.reshape(n, nblk, block)

    def body(c, i):
        c = c + jnp.sum(
            jnp.abs(qb[:, i, None, :] - xb[None, :, i, :]), axis=-1
        )
        return c, None

    out, _ = jax.lax.scan(body, jnp.zeros((m, n), jnp.float32), jnp.arange(nblk))
    return out


@register("chi2")
def _chi2(q: Array, x: Array) -> Array:
    # chi^2 distance for histograms: sum (q - x)^2 / (q + x), with the usual
    # 0/0 -> 0 convention.
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    m, d = q.shape
    n = x.shape[0]
    block = 128 if d > 128 else d
    nblk = -(-d // block)
    pad = nblk * block - d
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad)))
        x = jnp.pad(x, ((0, 0), (0, pad)))
    qb = q.reshape(m, nblk, block)
    xb = x.reshape(n, nblk, block)

    def body(c, i):
        qq = qb[:, i, None, :]
        xx = xb[None, :, i, :]
        num = (qq - xx) ** 2
        den = qq + xx
        c = c + jnp.sum(jnp.where(den > 1e-12, num / jnp.maximum(den, 1e-12), 0.0), axis=-1)
        return c, None

    out, _ = jax.lax.scan(body, jnp.zeros((m, n), jnp.float32), jnp.arange(nblk))
    return out


@functools.lru_cache(maxsize=None)
def is_matmul_metric(metric: str) -> bool:
    """True when the metric reduces to a GEMM (MXU-eligible on TPU)."""
    return metric in ("l2", "ip", "cosine")
