"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references the kernel tests sweep against
(``tests/test_kernels.py``) and the CPU execution path used by the rest of
the framework when no TPU is present.  The gather-distance oracle implements
the SAME norms-decomposed blocked formula as the Pallas engine
(``kernels.gather_dist.block_distance``) — ``‖q‖² + ‖x‖² − 2·q·x`` with the
``‖x‖²`` term served from the graph-resident cache when the caller has one —
so the CPU production path and the TPU kernel agree to float tolerance and
neither recomputes norms per iteration.  Matmuls contract at the engine's
``gather_dist.PRECISION`` (HIGHEST), so these oracles stay exact on a TPU.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import metrics
from repro.kernels import precision as _precision
from repro.kernels.gather_dist import PRECISION as _PRECISION

Array = jax.Array

# C-block width of the compressed gather's chunked contraction — matches the
# Pallas engine's _MAX_BLOCK_C, and keeps each dequantized f32 chunk
# L2-resident on CPU (the full-tile cast/dot was measured ~1.7x slower at
# the wave shape, see the precision microbench).
_CHUNK_C = 128


def _chunked_dots(qf: Array, cand: Array) -> Array:
    """Batched ``q·cand`` contraction over C-chunks of a gathered tile.

    ``cand`` is (B, C, d) in its *storage* dtype (bf16/int8); each chunk is
    cast to fp32 right before its dot so at most (B, _CHUNK_C, d) fp32 ever
    materializes.  Returns (B, C) float32.
    """
    B, C, d = cand.shape
    dn = (((1,), (2,)), ((0,), (0,)))
    if C <= _CHUNK_C or C % _CHUNK_C:
        return jax.lax.dot_general(
            qf, cand.astype(jnp.float32), dn,
            precision=_PRECISION, preferred_element_type=jnp.float32,
        )
    blocks = jnp.moveaxis(cand.reshape(B, C // _CHUNK_C, _CHUNK_C, d), 1, 0)

    def body(carry, blk):
        return carry, jax.lax.dot_general(
            qf, blk.astype(jnp.float32), dn,
            precision=_PRECISION, preferred_element_type=jnp.float32,
        )

    _, out = jax.lax.scan(body, 0, blocks)
    return jnp.moveaxis(out, 0, 1).reshape(B, C)


def pairwise_distance(
    q: Array,
    x: Array,
    metric: str = "l2",
    *,
    x_sq_norms: Optional[Array] = None,
    enc: Optional[_precision.EncodedData] = None,
    precision: str = "fp32",
) -> Array:
    """(m, d) x (n, d) -> (m, n) distances.  Oracle for kernels.distance.

    ``x_sq_norms`` is the cached ``‖x‖²`` of the x side; when provided (l2)
    the decomposition consumes it instead of re-reducing x.  ``enc`` /
    ``precision`` select a compressed x-side representation
    (``kernels.precision``); fp32 (or no ``enc``) is byte-identical to the
    pre-precision path.
    """
    if enc is not None and precision != "fp32":
        return _pairwise_distance_compressed(
            q, x, metric, x_sq_norms=x_sq_norms, enc=enc, precision=precision
        )
    if x_sq_norms is not None and metric == "l2":
        qf = q.astype(jnp.float32)
        xf = x.astype(jnp.float32)
        qn = jnp.sum(qf * qf, axis=-1, keepdims=True)  # (m, 1)
        return jnp.maximum(
            qn + x_sq_norms.astype(jnp.float32)[None, :]
            - 2.0 * jnp.matmul(qf, xf.T, precision=_PRECISION),
            0.0,
        )
    return metrics.pairwise(metric, q, x)


def _pairwise_distance_compressed(
    q: Array,
    x: Array,
    metric: str,
    *,
    x_sq_norms: Optional[Array],
    enc: _precision.EncodedData,
    precision: str,
) -> Array:
    """All-pairs distances against a compressed x side (bf16/int8/PQ-ADC)."""
    _precision.validate_precision(precision)
    qf = q.astype(jnp.float32)
    if x_sq_norms is None:
        from repro.core.graph import squared_norms  # lazy: no cycle

        x_sq_norms = squared_norms(x)
    xn = x_sq_norms.astype(jnp.float32)[None, :]  # (1, n)
    if precision == "pq":
        if metric == "cosine":
            qf = qf / jnp.maximum(
                jnp.linalg.norm(qf, axis=-1, keepdims=True), 1e-12
            )
        lut = _precision.adc_tables(qf, enc.codebook, metric)  # (m, M, K)
        lutm = jnp.moveaxis(lut, 1, 0)  # (M, m, K)
        terms = jax.vmap(lambda l, c: l[:, c])(lutm, enc.codes.T)  # (M, m, n)
        d = jnp.sum(terms, axis=0)
        if metric == "cosine":
            d = 1.0 - d / jnp.maximum(jnp.sqrt(xn), 1e-12)
        return d.astype(jnp.float32)
    if metric in ("l2", "ip", "dot", "cosine"):
        if metric == "cosine":
            qf = qf / jnp.maximum(
                jnp.linalg.norm(qf, axis=-1, keepdims=True), 1e-12
            )
        dots = jnp.matmul(
            qf, enc.data.astype(jnp.float32).T, precision=_PRECISION
        )
        if precision == "int8":
            s = enc.scale.astype(jnp.float32)
            dots = dots * jnp.where(s > 0, s, 1.0)[None, :]
        if metric == "l2":
            qn = jnp.sum(qf * qf, axis=-1, keepdims=True)
            return jnp.maximum(qn + xn - 2.0 * dots, 0.0)
        if metric == "cosine":
            return 1.0 - dots / jnp.maximum(jnp.sqrt(xn), 1e-12)
        return -dots if metric == "ip" else dots
    # VPU metrics: dequantize once, reuse the exact pairwise reduction.
    xf = enc.data.astype(jnp.float32)
    if precision == "int8":
        s = enc.scale.astype(jnp.float32)
        xf = xf * jnp.where(s > 0, s, 1.0)[:, None]
    return metrics.pairwise(metric, q, xf)


def gather_distance(
    q: Array,
    x: Array,
    idx: Array,
    metric: str = "l2",
    *,
    sq_norms: Optional[Array] = None,
    enc: Optional[_precision.EncodedData] = None,
    precision: str = "fp32",
) -> Array:
    """Blocked gather + distance oracle (decomposed formula).

    Args:
      q:   (b, d)  queries.
      x:   (n, d)  dataset.
      idx: (b, c)  int32 candidate ids per query; id < 0 means padding.
      sq_norms: optional (n,) cached ``‖x‖²`` (the graph-resident cache);
        derived once per call when absent.
      enc / precision: compressed companion table + which representation to
        fetch candidates from (``kernels.precision``).  ``"fp32"`` (or no
        ``enc``) takes the exact path below, byte-identical to before the
        precision API existed.  ``"pq"`` here is the pure ADC rank — the
        exact re-rank composes in ``kernels.ops.expand_step``.

    Returns:
      (b, c) float32 distances; +inf at padded slots.
    """
    if enc is not None and precision != "fp32":
        return _gather_distance_compressed(
            q, x, idx, metric, sq_norms=sq_norms, enc=enc, precision=precision
        )
    safe = jnp.clip(idx, 0, x.shape[0] - 1)
    if metric in ("l2", "ip", "dot", "cosine", "cos"):
        qf = q.astype(jnp.float32)
        if metric in ("cosine", "cos"):
            qf = qf / jnp.maximum(
                jnp.linalg.norm(qf, axis=-1, keepdims=True), 1e-12
            )
        cand = x[safe].astype(jnp.float32)  # (b, c, d)
        # broadcast-multiply + reduce rather than einsum: XLA:CPU fuses this
        # into one pass over the gathered tile, while the einsum/dot_general
        # lowering becomes a loop of (1, d) matvecs that is measurably slower
        # at the large-C shapes the engine targets (see the gather-engine
        # microbench); on TPU the Pallas kernel owns this computation anyway.
        dots = jnp.sum(qf[:, None, :] * cand, axis=-1)
        if metric in ("l2", "cosine", "cos"):
            if sq_norms is None:
                from repro.core.graph import squared_norms  # lazy: no cycle

                xn = squared_norms(cand)
            else:
                xn = sq_norms[safe].astype(jnp.float32)
            if metric == "l2":
                qn = jnp.sum(qf * qf, axis=-1, keepdims=True)
                d = jnp.maximum(qn + xn - 2.0 * dots, 0.0)
            else:
                d = 1.0 - dots / jnp.maximum(jnp.sqrt(xn), 1e-12)
        elif metric == "ip":
            d = -dots
        else:  # dot
            d = dots
    else:
        # VPU metrics (l1 / chi2): no matmul form — broadcast reduction.
        cand = x[safe]

        def per_query(qi, ci):
            return metrics.pairwise(metric, qi[None, :], ci)[0]

        d = jax.vmap(per_query)(q, cand)
    return jnp.where(idx >= 0, d.astype(jnp.float32), jnp.inf)


def _gather_distance_compressed(
    q: Array,
    x: Array,
    idx: Array,
    metric: str,
    *,
    sq_norms: Optional[Array],
    enc: _precision.EncodedData,
    precision: str,
) -> Array:
    """Reduced-precision candidate fetch + distance (bf16 / int8 / PQ-ADC).

    The structural twin of the fp32 path above: same decomposition, same
    masking convention, but the gathered tile is the 2-byte/1-byte encoded
    table — 2–4x fewer random-access bytes, the point of the compressed
    engine — and for the matmul metrics the contraction runs in
    ``_CHUNK_C``-wide chunks so the dequantized fp32 chunk stays cache
    resident.  The ``‖x‖²`` term always comes from the exact cache: only the
    ``q·x`` term carries quantization error (int8 rel err ~2e-3 at d=256).
    """
    _precision.validate_precision(precision)
    qf = q.astype(jnp.float32)
    if sq_norms is None:
        from repro.core.graph import squared_norms  # lazy: no cycle

        sq_norms = squared_norms(x)
    if precision == "pq":
        if metric in ("cosine", "cos"):
            qf = qf / jnp.maximum(
                jnp.linalg.norm(qf, axis=-1, keepdims=True), 1e-12
            )
        lut = _precision.adc_tables(qf, enc.codebook, metric)
        return _precision.adc_gather(lut, enc.codes, idx, metric, sq_norms)
    safe = jnp.clip(idx, 0, x.shape[0] - 1)
    cand = enc.data[safe]  # (b, c, d) bf16/int8 — the compressed fetch
    if metric in ("l2", "ip", "dot", "cosine", "cos"):
        if metric in ("cosine", "cos"):
            qf = qf / jnp.maximum(
                jnp.linalg.norm(qf, axis=-1, keepdims=True), 1e-12
            )
        dots = _chunked_dots(qf, cand)
        if precision == "int8":
            s = enc.scale[safe].astype(jnp.float32)
            dots = dots * jnp.where(s > 0, s, 1.0)
        xn = sq_norms[safe].astype(jnp.float32)
        if metric == "l2":
            qn = jnp.sum(qf * qf, axis=-1, keepdims=True)
            d = jnp.maximum(qn + xn - 2.0 * dots, 0.0)
        elif metric in ("cosine", "cos"):
            d = 1.0 - dots / jnp.maximum(jnp.sqrt(xn), 1e-12)
        elif metric == "ip":
            d = -dots
        else:  # dot
            d = dots
    else:
        # VPU metrics: dequantize the gathered tile, then the broadcast
        # reduction (same shape as the fp32 path's per-query vmap).
        candf = cand.astype(jnp.float32)
        if precision == "int8":
            s = enc.scale[safe].astype(jnp.float32)
            candf = candf * jnp.where(s > 0, s, 1.0)[..., None]

        def per_query(qi, ci):
            return metrics.pairwise(metric, qi[None, :], ci)[0]

        d = jax.vmap(per_query)(q, candf)
    return jnp.where(idx >= 0, d.astype(jnp.float32), jnp.inf)


def topk_smallest(dists: Array, ids: Array, k: int):
    """Row-wise smallest-k (distance, id) selection.  Oracle for merge ops.

    Args:
      dists: (m, c) distances (inf = padding).
      ids:   (m, c) ids aligned with dists.
      k:     number to keep.

    Returns:
      (m, k) dists sorted ascending, (m, k) ids.
    """
    neg, sel = jax.lax.top_k(-dists, k)
    return -neg, jnp.take_along_axis(ids, sel, axis=1)
