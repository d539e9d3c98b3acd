"""Blocked gather + distance Pallas TPU kernel — the MXU distance engine.

The inner loop of EHC hill-climbing is: take the candidate ids produced by
expanding a beam vertex, fetch those rows of the dataset, and compute their
distance to the query.  The first-generation kernel here streamed one (1, d)
candidate row per DMA and reduced it on the VPU — ~0.05 flops/byte, idle
MXUs.  This version is *blocked*: candidate ids (riding in scalar-prefetch
SMEM) drive double-buffered HBM->VMEM row gathers into a (C_blk, d) tile,
and each landed tile is reduced against the VMEM-resident query in ONE shot:

  * l2 / ip / cos ride the norms decomposition ``‖q‖² + ‖x‖² − 2·q·x`` — the
    ``q·x`` term is a single (1, d) x (C_blk, d)ᵀ MXU pass per block and the
    ``‖x‖²`` term comes from the graph-resident norm cache
    (``KNNGraph.sq_norms``), so nothing recomputes norms per iteration;
  * l1 / chi2 keep the VPU broadcast reduction (no matmul form exists) over
    the same (C_blk, d) tile — the block analogue of ``kernels.distance``'s
    row-strip walk.

``blocked_gather_phase`` is the whole phase — DMA discipline, block
reduction, and padding-lane masking — and is shared *verbatim* with the
fused expansion kernel (``kernels.expand``), which is what keeps the two
bit-identical per comparison (pinned by the expansion parity suite).

Layout
------
* grid = (B,): one grid step per query; Pallas pipelines steps.
* ``idx`` (B, C_pad) int32: one (1, C_pad) SMEM block per grid step drives
  the DMAs (a scalar prefetch of the whole (B, C_pad) table would not fit
  the 1 MiB SMEM at wave sizes); the same ids ride again as a VMEM operand
  for vector-phase masking.
* ``x`` (n, d): stays in HBM/ANY; rows are moved with
  ``pltpu.make_async_copy`` into a 2-slot (C_blk, d) VMEM scratch (block
  j+1 is in flight while block j is reduced).
* ``xn`` (B, C_pad) float32: gathered squared norms of the candidate rows.
* out block (1, C_pad) float32; the wrapper slices back to C.
* Per-query operands travel as (B, 1, w) arrays blocked ``(None, 1, w)``
  (``lane_spec``): the TPU compiler requires a block's last two dimensions
  to be (8, 128)-divisible or whole, which a (1, w) block of a (B, w) array
  is not.  The kernel still sees (1, w) refs.

The TPU compiler accepts the single-row HBM slices of the row DMAs only for
fp32 tables with d a multiple of 128 (bf16/int8 rows are sub-tile along the
sublane axis; other widths are lane-misaligned).  ``kernel_fits`` states that
rule; ``kernels.ops`` routes every other table to the XLA engine on TPU.

Candidate lists are padded to a multiple of the block width with -1; negative
ids are padding and their lanes are forced to +inf (the convention the search
layer uses for masked candidates).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat

Array = jax.Array

# Widest candidate block one tile reduction covers: matches the MXU's 128
# systolic rows; shorter candidate lists use one exact-width block.
_MAX_BLOCK_C = 128

# Contraction precision of every engine matmul (see ``block_distance``).
PRECISION = jax.lax.Precision.HIGHEST


def kernel_fits(dtype, d: int) -> bool:
    """Whether the compiled row-DMA gather takes an (n, d) table of ``dtype``.

    Only fp32 tables whose width is a multiple of 128 lanes: a one-row HBM
    slice of a bf16/int8 table is not aligned to its (16|32, 128) tiling, and
    a row of any other width is not aligned to 128 lanes.
    """
    return jnp.dtype(dtype) == jnp.float32 and d % 128 == 0


def lane_spec(w: int, memory_space=None) -> pl.BlockSpec:
    """(None, 1, w) block of a (B, 1, w) per-query operand at grid step i."""
    kw = {} if memory_space is None else {"memory_space": memory_space}
    return pl.BlockSpec((None, 1, w), lambda i: (i, 0, 0), **kw)


def block_c(n_cand: int) -> int:
    """Candidate-block width used for a C-wide candidate list."""
    return min(_MAX_BLOCK_C, max(n_cand, 1))


def padded_c(n_cand: int) -> int:
    """C padded up to a whole number of blocks."""
    cb = block_c(n_cand)
    return -(-n_cand // cb) * cb


def block_distance(
    q: Array, tile: Array, xn: Array, metric: str,
    xscale: Optional[Array] = None,
) -> Array:
    """Distances between one query and one block of candidate rows.

    The single in-kernel distance formula, shared by this kernel and the
    fused expansion kernel — keeping it in one place is what makes the two
    bit-identical, which the expansion parity suite pins.

    Args:
      q: (1, d) query.
      tile: (C_blk, d) candidate rows — fp32, or a reduced-precision tile
        (bf16/int8) cast to fp32 on read; accumulation is always fp32.
      xn: (1, C_blk) cached ``‖x‖²`` per row (consumed by l2 and cos;
        ignored by ip/dot/l1/chi2).
      xscale: optional (1, C_blk) per-row int8 dequant scales
        (``KNNGraph.row_scale`` gathered; 1 at padding).  Applied to the
        *dot* term for the matmul metrics — the norm term stays exact from
        the cache — and to the tile for l1/chi2.  None (fp32/bf16) leaves
        the formula untouched, so the fp32 jaxpr is unchanged.

    Returns (1, C_blk) float32 distances.  ``"dot"`` is the raw inner
    product; ``"cos"`` expects a pre-normalized query and *raw* data rows —
    the cached norm supplies the denominator.

    Precision: the ``q·x`` contraction runs at ``PRECISION`` (HIGHEST, full
    fp32), like every engine matmul in ``kernels`` and ``core.metrics``.  On
    a TPU the default precision contracts f32 in bf16 passes, and the norms
    decomposition then loses the small distances that rank the nearest
    neighbours.  On the CPU HIGHEST is what the default already computes, so
    fp32 results there are unchanged bit for bit.
    """
    q = q.astype(jnp.float32)
    tile = tile.astype(jnp.float32)
    if xscale is not None and metric in ("l1", "chi2"):
        tile = tile * xscale.reshape(-1, 1)
    if metric in ("l2", "ip", "dot", "cos"):
        dots = jax.lax.dot_general(
            q, tile, (((1,), (1,)), ((), ())),
            precision=PRECISION, preferred_element_type=jnp.float32,
        )  # (1, C_blk) — one MXU pass covers the whole block
        if xscale is not None:
            dots = dots * xscale
        if metric == "l2":
            qn = jnp.sum(q * q, axis=1, keepdims=True)
            return jnp.maximum(qn + xn - 2.0 * dots, 0.0)
        if metric == "ip":
            return -dots
        if metric == "dot":
            return dots
        return 1.0 - dots / jnp.maximum(jnp.sqrt(xn), 1e-12)  # cos
    if metric == "l1":
        return jnp.sum(jnp.abs(tile - q), axis=1, keepdims=True).T
    if metric == "chi2":
        num = (tile - q) ** 2
        den = tile + q
        return jnp.sum(
            jnp.where(den > 1e-12, num / jnp.maximum(den, 1e-12), 0.0),
            axis=1,
            keepdims=True,
        ).T
    raise KeyError(metric)


def gathered_sq_norms(x: Array, idx: Array, sq_norms: Optional[Array]) -> Array:
    """(B, C) candidate ids -> (B, C) float32 ``‖x_idx‖²``; 0 at padding.

    ``sq_norms`` is the graph-resident cache (``KNNGraph.sq_norms``).  When a
    caller has none (direct kernel use, tests) the norms are derived from
    ``x`` once per call — never per candidate row, and never inside the
    search iteration — through ``graph.squared_norms``, the cache contents'
    single definition.
    """
    if sq_norms is None:
        from repro.core.graph import squared_norms  # lazy: kernels load first

        sq_norms = squared_norms(x)
    safe = jnp.clip(idx, 0, x.shape[0] - 1)
    return jnp.where(idx >= 0, sq_norms[safe].astype(jnp.float32), 0.0)


def gathered_row_scales(idx: Array, row_scale: Array) -> Array:
    """(B, C) candidate ids -> (B, C) float32 dequant scales; 1 at padding.

    ``row_scale`` is the graph-resident int8 scale table
    (``KNNGraph.row_scale``).  Zero entries (unallocated/removed rows, the
    all-zero vector) map to 1 — mirroring ``precision.quantize_int8``'s
    guard — so the engine never divides or multiplies by 0 into NaN lanes.
    """
    safe = jnp.clip(idx, 0, row_scale.shape[0] - 1)
    s = row_scale[safe].astype(jnp.float32)
    return jnp.where((idx >= 0) & (s > 0), s, 1.0)


def blocked_gather_phase(
    idx_ref,  # (1, C_pad) int32 SMEM — this lane's ids, drive the DMAs
    ids_ref,  # (1, C_pad) int32 VMEM — same ids, vector-phase masking
    q,  # (1, d) float32 (already read from its ref)
    xn_ref,  # (1, C_pad) float32 VMEM — gathered ‖x‖² per candidate
    x_ref,  # (n, d) ANY (HBM)
    out_ref,  # (1, C_pad) float32 VMEM — distances out (+inf at padding)
    tile_buf,  # (2, C_blk, d) VMEM scratch (block double buffer)
    sems,  # (2, C_blk) DMA semaphores
    *,
    n_blocks: int,
    c_blk: int,
    metric: str,
    xs_ref=None,  # (1, C_pad) f32 VMEM — int8 dequant scales (None: fp32/bf16)
):
    """The blocked candidate-distance phase, shared verbatim by the
    gather-distance kernel and the fused expansion kernel's phase 1 — one
    body, two execution sites, zero drift.

    Block j+1's row DMAs are in flight while block j reduces on the
    MXU/VPU.  Padding lanes (id < 0) fetch row 0 and are masked to +inf.

    Reduced precision rides the same discipline: ``x_ref``/``tile_buf`` may
    be bf16 or int8 (cast-on-DMA — the tile lands in its storage dtype and
    is cast to fp32 at the reduction), and ``xs_ref`` carries the gathered
    int8 dequant scales.  With ``xs_ref=None`` and fp32 operands the body
    traces to exactly the pre-precision jaxpr.
    """

    def row_copy(blk, r, slot):
        rid = jnp.maximum(idx_ref[0, blk * c_blk + r], 0)
        return compat.make_async_copy(
            x_ref.at[pl.ds(rid, 1)], tile_buf.at[slot, pl.ds(r, 1)],
            sems.at[slot, r],
        )

    def start_block(blk, slot):
        def start_row(r, _):
            row_copy(blk, r, slot).start()
            return ()

        jax.lax.fori_loop(0, c_blk, start_row, (), unroll=False)

    def wait_block(blk, slot):
        def wait_row(r, _):
            row_copy(blk, r, slot).wait()
            return ()

        jax.lax.fori_loop(0, c_blk, wait_row, (), unroll=False)

    start_block(0, 0)

    def body(blk, _):
        slot = jax.lax.rem(blk, 2)

        @pl.when(blk + 1 < n_blocks)
        def _prefetch_next():
            start_block(blk + 1, jax.lax.rem(blk + 1, 2))

        wait_block(blk, slot)
        off = blk * c_blk
        tile = tile_buf[slot].astype(jnp.float32)  # (C_blk, d)
        ids_blk = ids_ref[0:1, pl.ds(off, c_blk)]  # (1, C_blk)
        xn_blk = xn_ref[0:1, pl.ds(off, c_blk)]
        xs_blk = None if xs_ref is None else xs_ref[0:1, pl.ds(off, c_blk)]
        dist = block_distance(q, tile, xn_blk, metric, xscale=xs_blk)
        out_ref[0:1, pl.ds(off, c_blk)] = jnp.where(ids_blk >= 0, dist, jnp.inf)
        return ()

    jax.lax.fori_loop(0, n_blocks, body, (), unroll=False)


def _gather_dist_kernel(
    idx_ref,  # (1, C_pad) int32 SMEM
    ids_ref,  # (1, C_pad) int32 VMEM
    q_ref,  # (1, d) VMEM
    xn_ref,  # (1, C_pad) VMEM
    *rest,  # [xs_ref (1, C_pad) — int8 only], x_ref ANY, o_ref, tile_buf, sems
    n_blocks: int,
    c_blk: int,
    metric: str,
    quantized: bool = False,
):
    if quantized:
        xs_ref, x_ref, o_ref, tile_buf, sems = rest
    else:
        x_ref, o_ref, tile_buf, sems = rest
        xs_ref = None
    q = q_ref[...].astype(jnp.float32)  # (1, d)
    blocked_gather_phase(
        idx_ref, ids_ref, q, xn_ref, x_ref, o_ref, tile_buf, sems,
        n_blocks=n_blocks, c_blk=c_blk, metric=metric, xs_ref=xs_ref,
    )


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def gather_distance(
    q: Array,
    x: Array,
    idx: Array,
    *,
    metric: str = "l2",
    sq_norms: Optional[Array] = None,
    row_scale: Optional[Array] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """(b, d) queries, (n, d) data, (b, c) int32 ids -> (b, c) f32 distances.

    ``sq_norms`` is the graph-resident ``‖x‖²`` cache; omit it and the norms
    are derived once per call.  ``interpret=None`` resolves to compiled on
    TPU and interpret mode elsewhere — the execution-path *choice* (kernel vs
    pure-JAX reference) belongs to ``kernels.ops`` dispatch, not here.

    Reduced precision: pass ``x`` as the *encoded* table (bf16 or int8 —
    ``precision.EncodedData.data``) and, for int8, ``row_scale`` as the
    graph-resident scale table.  The candidate blocks then move as 2- or
    1-byte rows (cast-on-DMA) and dequantize at the block reduction; fp32
    callers pass raw ``x`` and the kernel is unchanged.  PQ never reaches
    this kernel — the ADC first-pass rank lives in ``kernels.ops``.
    """
    if interpret is None:
        interpret = compat.default_interpret()
    kernel_metric = metric
    if metric == "cosine":
        # Normalize the query once; the cached ‖x‖² supplies the data-side
        # denominator in-kernel (no O(n·d) dataset normalization per call).
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        kernel_metric = "cos"

    b, d = q.shape
    c = idx.shape[1]
    cb = block_c(c)
    cp = padded_c(c)
    idx = idx.astype(jnp.int32)
    if cp != c:
        idx = jnp.pad(idx, ((0, 0), (0, cp - c)), constant_values=-1)
    if x.dtype == jnp.int8 and sq_norms is None:
        raise ValueError("int8 tables need the exact sq_norms cache")
    xn = gathered_sq_norms(x, idx, sq_norms)  # (b, cp)
    quantized = x.dtype == jnp.int8
    lanes = lambda a: a[:, None, :]  # (b, w) -> (b, 1, w): see lane_spec
    operands = [lanes(idx), lanes(idx), lanes(q), lanes(xn)]
    in_specs = [
        lane_spec(cp, compat.SMEM),  # ids (DMA addresses)
        lane_spec(cp),  # ids (vector phase masking)
        lane_spec(d),  # q
        lane_spec(cp),  # xn
    ]
    if quantized:
        if row_scale is None:
            raise ValueError("int8 tables need the row_scale table")
        xs = gathered_row_scales(idx, row_scale)  # (b, cp)
        operands.append(lanes(xs))
        in_specs.append(lane_spec(cp))
    operands.append(x)
    in_specs.append(pl.BlockSpec(memory_space=compat.ANY))  # x

    kern = functools.partial(
        _gather_dist_kernel, n_blocks=cp // cb, c_blk=cb,
        metric=kernel_metric, quantized=quantized,
    )
    out = pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=in_specs,
        out_specs=lane_spec(cp),
        scratch_shapes=[
            compat.VMEM((2, cb, d), x.dtype),  # tile lands in storage dtype
            compat.SemaphoreType.DMA((2, cb)),
        ],
        out_shape=jax.ShapeDtypeStruct((b, 1, cp), jnp.float32),
        interpret=interpret,
        name="gather_distance",
    )(*operands)
    return out[:, 0, :c]
