"""The plain reference every cell is judged against, and its control.

``exact_topk`` is a float64 exact scan on the host (after ``chip_smoke.f64_topk``
and ``recall``, rewritten to take a live-row mask, exclude a query's own row
and support the cosine metric).  It imports nothing of the program and reads
only the rows the benchmark generated.

``ControlSearch`` and ``control_graph`` are the control of the comparison:
the same exact answers computed on the device with the distance contraction
at ``high`` precision (three bf16 passes), the step below the ``highest``
precision the configurations state.  They are written out in bf16 pieces, so
they compute the same numbers on the CPU as on the chip.  The benchmark runs
never use them; ``bench/control.py`` and the tests do.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# float64 exact scan (host)
# ---------------------------------------------------------------------------


def f64_dist(metric: str, q: np.ndarray, x: np.ndarray) -> np.ndarray:
    """All-pairs distances in float64, smaller is closer."""
    q = np.asarray(q, np.float64)
    x = np.asarray(x, np.float64)
    dots = q @ x.T
    if metric == "l2":
        return np.sum(q * q, 1)[:, None] + np.sum(x * x, 1)[None, :] - 2.0 * dots
    if metric == "cosine":
        qn = np.maximum(np.linalg.norm(q, axis=1), 1e-300)
        xn = np.maximum(np.linalg.norm(x, axis=1), 1e-300)
        return 1.0 - dots / qn[:, None] / xn[None, :]
    raise KeyError(metric)


def pair_dist(metric: str, q: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Row-wise float64 distances: q (..., d) against x (..., d)."""
    q = np.asarray(q, np.float64)
    x = np.asarray(x, np.float64)
    if metric == "l2":
        return np.sum((q - x) ** 2, -1)
    if metric == "cosine":
        qn = np.maximum(np.linalg.norm(q, axis=-1), 1e-300)
        xn = np.maximum(np.linalg.norm(x, axis=-1), 1e-300)
        return 1.0 - np.sum(q * x, -1) / qn / xn
    raise KeyError(metric)


def dist_scale(metric: str, q: np.ndarray, x: np.ndarray) -> np.ndarray:
    """What a distance error is measured against: ‖q‖² + ‖x‖² for l2 (the
    norms decomposition loses precision in proportion to them), 1 for cosine
    (a distance in [0, 2])."""
    if metric == "l2":
        q = np.asarray(q, np.float64)
        x = np.asarray(x, np.float64)
        return np.sum(q * q, -1) + np.sum(x * x, -1)
    return np.ones(np.broadcast_shapes(q.shape[:-1], x.shape[:-1]))


def exact_topk(
    metric: str,
    q: np.ndarray,
    x: np.ndarray,
    k: int,
    *,
    self_ids: np.ndarray | None = None,
    chunk: int = 128,
) -> np.ndarray:
    """Exact top-k row ids of ``x`` for each query, in float64.

    ``self_ids[i]`` is a row that query i may not return (a graph row's own
    id).
    """
    out = np.empty((q.shape[0], k), np.int64)
    for lo in range(0, q.shape[0], chunk):
        d = f64_dist(metric, q[lo : lo + chunk], x)
        if self_ids is not None:
            d[np.arange(d.shape[0]), self_ids[lo : lo + chunk]] = np.inf
        part = np.argpartition(d, k - 1, axis=1)[:, :k]
        pd = np.take_along_axis(d, part, 1)
        order = np.lexsort((part, pd), axis=1)
        out[lo : lo + chunk] = np.take_along_axis(part, order, 1)
    return out


def recall(pred: np.ndarray, true: np.ndarray, k: int) -> float:
    """Mean share of the true top-k found among the first k predictions."""
    hits = sum(len(set(p[:k].tolist()) & set(t[:k].tolist())) for p, t in zip(pred, true))
    return hits / (len(true) * k)


def bad_ids(ids: np.ndarray, n: int, self_ids: np.ndarray | None = None) -> int:
    """Answers that may never appear: ids out of range, a row's own id, and a
    row repeated within one answer.  -1 marks an empty slot: a missed
    neighbour, which recall counts, not a wrong answer."""
    ids = np.asarray(ids, np.int64)
    real = ids >= 0
    bad = (ids < -1) | (ids >= n)
    if self_ids is not None:
        bad |= ids == np.asarray(self_ids)[:, None]
    srt = np.sort(np.where(real, ids, -np.arange(1, ids.shape[1] + 1) - 1), axis=1)
    dup = int(np.sum(srt[:, 1:] == srt[:, :-1]))
    return int(np.sum(bad)) + dup


def holes(ids: np.ndarray) -> int:
    """Empty (-1) slots among the answers."""
    return int(np.sum(np.asarray(ids) == -1))


def max_dist_err(metric: str, q: np.ndarray, x: np.ndarray, ids: np.ndarray,
                 dists: np.ndarray) -> float:
    """Widest gap between a returned distance and the float64 distance of the
    returned row, over the scale of ``dist_scale``.  Invalid ids are skipped
    (``bad_ids`` counts them)."""
    ids = np.asarray(ids, np.int64)
    ok = (ids >= 0) & (ids < x.shape[0])
    rows = x[np.clip(ids, 0, x.shape[0] - 1)]  # (S, k, d)
    qq = np.broadcast_to(np.asarray(q)[:, None, :], rows.shape)
    want = pair_dist(metric, qq, rows)
    err = np.abs(np.asarray(dists, np.float64) - want) / dist_scale(metric, qq, rows)
    err = np.where(ok, err, 0.0)
    return float(np.max(err)) if err.size else 0.0


# ---------------------------------------------------------------------------
# Control: the exact answers on the device at ``high`` precision
# ---------------------------------------------------------------------------


def _dot_high(a, b):
    """a @ b.T in three bf16 passes (hi·hi + hi·lo + lo·hi) with f32 sums:
    what ``Precision.HIGH`` computes on the MXU.  The pieces are rounded
    with ``reduce_precision`` (which no compiler pass elides) and
    multiplied exactly, so the CPU and the chip compute the same thing."""

    def split(v):
        hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(v - hi, exponent_bits=8, mantissa_bits=7)
        return hi, lo

    (ah, al), (bh, bl) = split(a), split(b)
    dot = lambda u, v: jnp.einsum("id,jd->ij", u, v, precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


@functools.partial(jax.jit, static_argnames=("metric", "k"))
def _control_topk(q, x, self_ids, metric, k):
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    if metric == "l2":
        d = (jnp.sum(q * q, 1)[:, None] + jnp.sum(x * x, 1)[None, :]
             - 2.0 * _dot_high(q, x))
        d = jnp.maximum(d, 0.0)
    elif metric == "cosine":
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        xn = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-12)
        d = 1.0 - _dot_high(qn, xn)
    else:
        raise KeyError(metric)
    d = jnp.where(jnp.arange(x.shape[0])[None, :] != self_ids[:, None], d, jnp.inf)
    neg, ids = jax.lax.top_k(-d, k)
    return ids.astype(jnp.int32), -neg


def control_topk(q, x, k, metric, *, self_ids=None, chunk=1024):
    """Exact top-k on the device at ``high`` precision: (ids, dists)."""
    B = q.shape[0]
    self_ids = (jnp.full((B,), -1, jnp.int32) if self_ids is None
                else jnp.asarray(self_ids, jnp.int32))
    ids, dists = [], []
    for lo in range(0, B, chunk):
        i, d = _control_topk(q[lo : lo + chunk], x, self_ids[lo : lo + chunk], metric, k)
        ids.append(i)
        dists.append(d)
    return jnp.concatenate(ids), jnp.concatenate(dists)


class ControlResult(NamedTuple):
    """The fields of a search result that the batch driver reads."""

    ids: jax.Array
    dists: jax.Array
    n_iters: jax.Array


class ControlIndex:
    """The reference in the index's place: ``search`` answered by the exact
    scan at ``high``."""

    def __init__(self, items, metric: str):
        self.items = items
        self.metric = metric

    def search(self, queries, top_k, *, beam=None):
        ids, dists = control_topk(queries, self.items, top_k, self.metric)
        return ControlResult(ids, dists, jnp.ones((ids.shape[0],), jnp.int32))


def control_graph(x, k: int, metric: str):
    """Exact k-NN graph of ``x`` at ``high``: (nbr_ids, nbr_dist)."""
    n = x.shape[0]
    return control_topk(x, x, k, metric, self_ids=jnp.arange(n, dtype=jnp.int32))
