"""Tiled pairwise-distance Pallas TPU kernel.

This is the compute hot-spot of everything in the paper: brute-force k-NN
(ground truth + the seed graph), NN-Descent local joins, refinement passes and
the intra-wave tiles of the online construction all reduce to "distances
between a block of queries and a block of points".

TPU mapping
-----------
For MXU-eligible metrics (l2 / ip / cosine) the kernel accumulates the
``q @ x^T`` GEMM over feature tiles on the MXU and folds the norm terms in on
the last reduction step (``|q|^2 + |x|^2 - 2 q.x`` expansion).  For VPU
metrics (l1 / chi2) the kernel walks the x-block row-tiles with a fori_loop of
broadcasted absolute-difference reductions — no matmul form exists.

Grid: ``(m_tiles, n_tiles, d_tiles)`` with the reduction axis innermost
("arbitrary" semantics) so each output tile sees its partial sums
consecutively; partials live in VMEM scratch, the HBM output is written once.

Block shapes are multiples of (8, 128) so fp32 tiles are register-aligned and
the MXU sees 128x128-aligned operands.  The GEMM contracts at HIGHEST
precision, as every engine matmul does (``gather_dist.block_distance``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat
from repro.kernels.gather_dist import PRECISION as _PRECISION

Array = jax.Array

MXU_METRICS = ("l2", "ip", "dot")
VPU_METRICS = ("l1", "chi2")


def _dist_kernel_mxu(q_ref, x_ref, o_ref, acc_ref, qsq_ref, xsq_ref, *, metric: str, nd: int):
    """One (bm, bn) output tile; reduction step k over feature tiles."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        qsq_ref[...] = jnp.zeros_like(qsq_ref)
        xsq_ref[...] = jnp.zeros_like(xsq_ref)

    q = q_ref[...].astype(jnp.float32)  # (bm, bd)
    x = x_ref[...].astype(jnp.float32)  # (bn, bd)
    acc_ref[...] += jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=_PRECISION, preferred_element_type=jnp.float32,
    )
    if metric == "l2":
        qsq_ref[...] += jnp.sum(q * q, axis=1, keepdims=True)
        xsq_ref[...] += jnp.sum(x * x, axis=1, keepdims=True).T

    @pl.when(k == nd - 1)
    def _done():
        if metric == "l2":
            o_ref[...] = jnp.maximum(qsq_ref[...] + xsq_ref[...] - 2.0 * acc_ref[...], 0.0)
        elif metric == "ip":
            o_ref[...] = -acc_ref[...]
        else:  # "dot": raw dot product (cosine handled by the wrapper)
            o_ref[...] = acc_ref[...]


def _dist_kernel_mxu_cached(q_ref, x_ref, xn_ref, o_ref, acc_ref, qsq_ref, *, nd: int):
    """l2 tile with the graph-resident ``‖x‖²`` cache: the x-side norm
    accumulation is skipped entirely — the cached (1, bn) row supplies the
    norm term on the last reduction step."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        qsq_ref[...] = jnp.zeros_like(qsq_ref)

    q = q_ref[...].astype(jnp.float32)  # (bm, bd)
    x = x_ref[...].astype(jnp.float32)  # (bn, bd)
    acc_ref[...] += jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=_PRECISION, preferred_element_type=jnp.float32,
    )
    qsq_ref[...] += jnp.sum(q * q, axis=1, keepdims=True)

    @pl.when(k == nd - 1)
    def _done():
        o_ref[...] = jnp.maximum(
            qsq_ref[...] + xn_ref[...] - 2.0 * acc_ref[...], 0.0
        )


def _dist_kernel_vpu(q_ref, x_ref, o_ref, acc_ref, *, metric: str, nd: int, rows_per_step: int):
    """VPU path: accumulate sum-reductions of |q - x| / chi2 over d tiles.

    The (bm, bn, bd) broadcast is walked in row-strips of the q block so the
    VMEM-resident intermediate stays at (rows_per_step, bn, bd).  Strips are
    sliced from the refs at sublane-aligned offsets: the TPU compiler takes a
    dynamic slice of a ref, never of a value.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)  # (bn, bd)
    bm = q_ref.shape[0]

    def body(i, _):
        rows = pl.ds(pl.multiple_of(i * rows_per_step, rows_per_step), rows_per_step)
        qs = q_ref[rows, :].astype(jnp.float32)  # (rps, bd)
        diff = qs[:, None, :] - x[None, :, :]  # (rps, bn, bd)
        if metric == "l1":
            part = jnp.sum(jnp.abs(diff), axis=-1)
        else:  # chi2
            den = qs[:, None, :] + x[None, :, :]
            part = jnp.sum(
                jnp.where(den > 1e-12, diff * diff / jnp.maximum(den, 1e-12), 0.0),
                axis=-1,
            )
        acc_ref[rows, :] += part
        return ()

    jax.lax.fori_loop(0, bm // rows_per_step, body, ())

    @pl.when(k == nd - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def _pad_to(a: Array, m0: int, m1: int) -> Array:
    p0 = -a.shape[0] % m0
    p1 = -a.shape[1] % m1
    if p0 or p1:
        a = jnp.pad(a, ((0, p0), (0, p1)))
    return a


@functools.partial(
    jax.jit,
    static_argnames=("metric", "bm", "bn", "bd", "interpret"),
)
def pairwise_distance(
    q: Array,
    x: Array,
    *,
    metric: str = "l2",
    x_sq_norms: Optional[Array] = None,
    bm: int = 128,
    bn: int = 128,
    bd: int = 128,
    interpret: Optional[bool] = None,
) -> Array:
    """Pallas tiled pairwise distances: (m, d) x (n, d) -> (m, n) float32.

    ``x_sq_norms`` is the cached ``‖x‖²`` of the x side (the graph-resident
    norm cache); for l2 the kernel then skips the x-norm accumulation
    entirely.  ``interpret=None`` resolves to compiled on TPU and interpret
    mode elsewhere — the kernel-vs-reference *choice* belongs to
    ``kernels.ops`` dispatch, not here.
    """
    if interpret is None:
        interpret = compat.default_interpret()
    kernel_metric = metric
    if metric == "cosine":
        # Normalize outside the kernel; cosine == 1 - dot on unit vectors.
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
        kernel_metric = "dot"

    m, d = q.shape
    n = x.shape[0]

    def _round8(v):
        return -(-v // 8) * 8

    bm = _round8(min(bm, m))
    bn = _round8(min(bn, n))
    bd = min(bd, d) if d >= 128 else d
    qp = _pad_to(q, bm, bd)
    xp = _pad_to(x, bn, bd)
    mp, dp = qp.shape
    np_ = xp.shape[0]
    grid = (mp // bm, np_ // bn, dp // bd)

    cached_xn = x_sq_norms is not None and kernel_metric == "l2"
    in_specs = [
        pl.BlockSpec((bm, bd), lambda i, j, k: (i, k)),
        pl.BlockSpec((bn, bd), lambda i, j, k: (j, k)),
    ]
    operands = [qp, xp]
    if cached_xn:
        kern = functools.partial(_dist_kernel_mxu_cached, nd=grid[2])
        scratch = [
            compat.VMEM((bm, bn), jnp.float32),
            compat.VMEM((bm, 1), jnp.float32),
        ]
        xnp = x_sq_norms.astype(jnp.float32)
        if np_ != n:
            xnp = jnp.pad(xnp, (0, np_ - n))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        operands.append(xnp[None, :])
    elif kernel_metric in MXU_METRICS:
        kern = functools.partial(_dist_kernel_mxu, metric=kernel_metric, nd=grid[2])
        scratch = [
            compat.VMEM((bm, bn), jnp.float32),
            compat.VMEM((bm, 1), jnp.float32),
            compat.VMEM((1, bn), jnp.float32),
        ]
    elif kernel_metric in VPU_METRICS:
        rows = 8  # bm is a multiple of 8
        kern = functools.partial(
            _dist_kernel_vpu, metric=kernel_metric, nd=grid[2], rows_per_step=rows
        )
        scratch = [compat.VMEM((bm, bn), jnp.float32)]
    else:
        raise KeyError(f"metric {metric!r} has no Pallas path")

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=compat.compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="pairwise_distance",
    )(*operands)
    out = out[:m, :n]
    if metric == "cosine":
        out = 1.0 - out
    return out
