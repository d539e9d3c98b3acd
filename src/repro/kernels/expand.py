"""Fused EHC expansion step — the hot loop of Alg. 1/3 as ONE Pallas kernel.

One EHC iteration per query lane is: take the candidate ids produced by
expanding the best unexpanded beam vertex r (``G[r] ∪ Ḡ[r]`` after LGD/alive
masking), drop the ones the per-query open-addressing hash table (the paper's
D array) has already seen, compute distances to the survivors, record them
into the hash, and merge them into the beam top-k.  Unfused, that is ~6
separate XLA ops per iteration with every intermediate round-tripping HBM;
here the whole chain runs per query inside one kernel:

  * candidate data rows are moved HBM->VMEM in double-buffered *blocks* of
    (C_blk, d), each reduced against the query in one MXU/VPU pass via the
    norms decomposition (``kernels.gather_dist.blocked_gather_phase`` — the
    phase is shared verbatim with the gather-distance kernel, so the two are
    bit-identical per comparison); the ``‖x‖²`` term comes from the
    graph-resident norm cache, never recomputed per iteration;
  * the (1, H) visited-hash rows and the (1, e) beam rows live in VMEM for
    the whole step — probe, insert, and top-k merge never touch HBM;
  * one (1, 1) scalar output returns the lane's comparison count (the
    scanning-rate numerator, Eq. 2).

This module also hosts the *pure-jnp expansion primitives* (probe-slot
computation, hash probe/lookup, beam dedupe) and ``expand_reference`` — the
unfused op chain.  Both implementations consume the same helpers; the parity
suite (``tests/test_expand_parity.py``) pins them bit-identical in interpret
mode.  Dispatch between them is ``kernels.ops.expand_step``:

  * ``dispatch="auto"`` on TPU: ``expand_reference`` with the compiled
    ``gather_dist`` kernel as its distance phase (for fp32 tables with d a
    multiple of 128; the XLA gather otherwise);
  * ``"pallas"``: this fused kernel — compiled on TPU (where it does not
    compile yet, see below), interpret mode elsewhere;
  * ``"interpret"``: the fused kernel in interpret mode everywhere (the
    correctness net the tests sweep);
  * ``"reference"``, and ``"auto"`` off-TPU: ``expand_reference`` with the
    XLA gather (XLA fuses the whole step into the jitted search loop).

Candidate *generation* (graph-row gathers + λ/alive masking,
``core.search._candidates_from_expansion``) stays outside the kernel: it is a
handful of dense row gathers XLA already handles well, and keeping it shared
between both paths means the kernel boundary is exactly the memory-bound
probe/distance/merge chain the ROADMAP's scanning-rate numbers depend on.

What lowers for a TPU today (JAX 0.9, checked against a described v5e by
``tests/test_tpu_compile.py``): the operand blocking and phase 1, the
blocked row-DMA distance phase, which is the ``gather_dist`` kernel's own
body.  Phase 2 does not: its first hash gather (``take_along_axis`` in
``hash_probe_state``) is refused ("Shape mismatch in input, indices and
output"), and ``lax.top_k`` and the hash-record scatter are primitives the
Mosaic lowering does not implement.  A compiled fused step needs phase 2
rewritten for the vector unit (scalar-indexed probes, a merge network in
place of ``top_k``); until then ``ops.expand_step`` keeps ``"auto"`` off it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import compat
from repro.kernels import gather_dist as _gather_dist

# NOTE: kernels.ref is imported lazily inside expand_reference — ref pulls in
# core.metrics, and core.search imports this module at class-body time, so a
# module-level import would close an import cycle through repro.core.

Array = jax.Array

# numpy scalars, not jnp: probe_slots runs inside the fused kernel's trace,
# where module-level jax Arrays would be captured constants (rejected by
# pallas_call); numpy scalars fold into the jaxpr as literals.
_KNUTH = np.uint32(2654435761)
_SHIFT = np.uint32(16)


# ---------------------------------------------------------------------------
# Pure-jnp expansion primitives (shared by the kernel and the reference)
# ---------------------------------------------------------------------------


def probe_slots(ids: Array, hash_slots: int, probes: int) -> Array:
    """(...,) ids -> (..., P) linear-probe slot sequence (Knuth hash)."""
    h = (ids.astype(jnp.uint32) * _KNUTH) >> _SHIFT
    h = h.astype(jnp.int32) & (hash_slots - 1)
    return (h[..., None] + jnp.arange(probes, dtype=jnp.int32)) & (hash_slots - 1)


def hash_lookup(
    vis_ids: Array, vis_dist: Array, ids: Array, probes: int
) -> tuple[Array, Array]:
    """Batch lookup ids (B, C) in per-lane tables (B, H).

    Returns (found (B, C) bool, dist (B, C) f32 — +inf where not found).
    The paper's D[i] with default ∞ (Alg. 3 line 3) is exactly this.
    """
    B, H = vis_ids.shape
    C = ids.shape[1]
    slots = probe_slots(ids, H, probes)  # (B, C, P)
    flat = slots.reshape(B, C * probes)
    got_ids = jnp.take_along_axis(vis_ids, flat, axis=1).reshape(B, C, probes)
    got_dist = jnp.take_along_axis(vis_dist, flat, axis=1).reshape(B, C, probes)
    hit = got_ids == ids[..., None]
    found = jnp.any(hit, axis=-1)
    dist = jnp.min(jnp.where(hit, got_dist, jnp.inf), axis=-1)
    return found, dist


def hash_probe_state(vis_ids: Array, ids: Array, probes: int):
    """Classify ids against tables: (present, insert_ok, insert_slot)."""
    B, H = vis_ids.shape
    C = ids.shape[1]
    slots = probe_slots(ids, H, probes)
    flat = slots.reshape(B, C * probes)
    got = jnp.take_along_axis(vis_ids, flat, axis=1).reshape(B, C, probes)
    is_hit = got == ids[..., None]
    is_empty = got == -1
    pidx = jnp.arange(probes, dtype=jnp.int32)
    first_hit = jnp.min(jnp.where(is_hit, pidx, probes), axis=-1)
    first_empty = jnp.min(jnp.where(is_empty, pidx, probes), axis=-1)
    present = first_hit < first_empty
    insert_ok = (~present) & (first_empty < probes)
    insert_slot = jnp.take_along_axis(
        slots, jnp.minimum(first_empty, probes - 1)[..., None], axis=-1
    )[..., 0]
    return present, insert_ok, insert_slot


def dedupe_beam(ids: Array, dist: Array, exp: Array):
    """Mask later copies of duplicate beam ids (rows sorted by distance).

    Duplicates are rare — they only arise when a hash insert failed (probe
    exhaustion) and the same vertex was re-compared later — but they must not
    survive into results/new graph rows.
    """
    dup = jnp.triu((ids[:, None, :] == ids[:, :, None]) & (ids[:, None, :] >= 0), k=1)
    dup = jnp.any(dup, axis=1)
    return (
        jnp.where(dup, -1, ids),
        jnp.where(dup, jnp.inf, dist),
        exp | dup,
    )


def _probe_mask_record_merge(
    cands: Array,  # (B, C) candidate ids, -1 masked
    dists_all: Array,  # (B, C) m(q, cand) for every id >= 0 (rest: anything)
    beam_ids: Array,  # (B, e)
    beam_dist: Array,  # (B, e)
    beam_exp: Array,  # (B, e) bool (r already marked expanded)
    vis_ids: Array,  # (B, H)
    vis_dist: Array,  # (B, H)
    probes: int,
):
    """The op chain downstream of the distance gather, shared verbatim by the
    kernel's vector phase (B=1 blocks) and ``expand_reference`` — one body,
    two execution sites, zero drift."""
    B, e = beam_ids.shape
    H = vis_ids.shape[1]
    present, insert_ok, insert_slot = hash_probe_state(vis_ids, cands, probes)
    fresh = (cands >= 0) & ~present  # compare these (probe-full: compare anyway)
    cand_ids = jnp.where(fresh, cands, -1)
    dists = jnp.where(fresh, dists_all, jnp.inf)
    comps = jnp.sum(fresh, axis=1).astype(jnp.int32)
    # -- record into the hash (the D array) ----------------------------------
    do_ins = fresh & insert_ok
    B_idx = jnp.broadcast_to(jnp.arange(B)[:, None], cand_ids.shape)
    slot = jnp.where(do_ins, insert_slot, H)  # OOB -> dropped
    vis_ids = vis_ids.at[B_idx, slot].set(
        jnp.where(do_ins, cand_ids, -1), mode="drop"
    )
    vis_dist = vis_dist.at[B_idx, slot].set(
        jnp.where(do_ins, dists, jnp.inf), mode="drop"
    )
    # -- beam merge ----------------------------------------------------------
    cat_ids = jnp.concatenate([beam_ids, cand_ids], axis=1)
    cat_dist = jnp.concatenate([beam_dist, dists], axis=1)
    cat_exp = jnp.concatenate(
        [beam_exp, jnp.zeros_like(cand_ids, bool) | (cand_ids < 0)], axis=1
    )
    neg, sel = jax.lax.top_k(-cat_dist, e)
    beam_ids = jnp.take_along_axis(cat_ids, sel, axis=1)
    beam_dist = -neg
    beam_exp = jnp.take_along_axis(cat_exp, sel, axis=1)
    beam_ids, beam_dist, beam_exp = dedupe_beam(beam_ids, beam_dist, beam_exp)
    return beam_ids, beam_dist, beam_exp, vis_ids, vis_dist, comps


# ---------------------------------------------------------------------------
# Unfused reference (the pre-fusion op chain)
# ---------------------------------------------------------------------------


def expand_reference(
    q: Array,  # (B, d) queries
    x: Array,  # (n, d) dataset
    cands: Array,  # (B, C) masked candidate ids (-1 = skip)
    beam_ids: Array,  # (B, e)
    beam_dist: Array,  # (B, e) f32
    beam_exp: Array,  # (B, e) bool
    vis_ids: Array,  # (B, H)
    vis_dist: Array,  # (B, H) f32
    *,
    metric: str = "l2",
    probes: int = 8,
    sq_norms: Optional[Array] = None,
    enc=None,
    precision: str = "fp32",
    pallas_distances: bool = False,
    interpret: Optional[bool] = None,
):
    """Unfused EHC expansion: probe -> gather-distance -> record -> merge.

    With ``pallas_distances=False`` (default) this is the pure-JAX execution
    path — XLA fuses it into the surrounding jitted search loop; its distance
    gather is the same blocked/decomposed formula as the kernels
    (``kernels.ref.gather_distance``).  With ``pallas_distances=True`` the
    distance gather runs the ``kernels.gather_dist`` Pallas kernel instead,
    giving the exact per-block numerics of the fused kernel — that variant
    is what the parity suite diffs ``fused_expand`` against bit-for-bit.
    ``sq_norms`` is the graph-resident ``‖x‖²`` cache (derived once per call
    when absent).  ``enc``/``precision`` select the compressed candidate
    representation (``kernels.precision``) the distance gather fetches from;
    fp32 leaves both paths untouched.
    """
    if precision == "pq":
        # PQ is a *rank*, not a distance: only exact distances may enter the
        # visited hash / beam.  The ADC prerank + fp32 re-rank composition
        # lives one layer up, in kernels.ops.expand_step.
        raise ValueError("expansion kernels take fp32/bf16/int8; pq is an ops-level prerank")
    present, _, _ = hash_probe_state(vis_ids, cands, probes)
    fresh = (cands >= 0) & ~present
    cand_ids = jnp.where(fresh, cands, -1)
    if pallas_distances:
        x_eng = x if enc is None or precision == "fp32" else enc.data
        row_scale = enc.scale if enc is not None and precision == "int8" else None
        dists = _gather_dist.gather_distance(
            q, x_eng, cand_ids, metric=metric, sq_norms=sq_norms,
            row_scale=row_scale, interpret=interpret,
        )
    else:
        from repro.kernels import ref as _ref  # lazy: see module note

        dists = _ref.gather_distance(
            q, x, cand_ids, metric, sq_norms=sq_norms,
            enc=enc, precision=precision,
        )
    return _probe_mask_record_merge(
        cands, dists, beam_ids, beam_dist, beam_exp, vis_ids, vis_dist, probes
    )


# ---------------------------------------------------------------------------
# Fused Pallas kernel
# ---------------------------------------------------------------------------


def _fused_expand_kernel(
    idx_ref,  # (1, C_pad) int32 SMEM — drives the DMAs
    cand_ref,  # (1, C_pad) int32 VMEM — same ids, vector phase operand
    q_ref,  # (1, d) VMEM
    xn_ref,  # (1, C_pad) f32 VMEM — gathered ‖x‖² (the norm cache)
    bi_ref,  # (1, e) int32 beam ids
    bd_ref,  # (1, e) f32 beam dists
    be_ref,  # (1, e) int32 beam expanded flags (bool cast at the boundary)
    vi_ref,  # (1, H) int32 visited-hash ids
    vd_ref,  # (1, H) f32 visited-hash dists
    *rest,  # [xs_ref (1, C_pad) — int8 only], x_ref ANY, outs, scratch
    n_cand: int,
    n_blocks: int,
    c_blk: int,
    metric: str,
    probes: int,
    quantized: bool = False,
):
    if quantized:
        (xs_ref, x_ref, obi_ref, obd_ref, obe_ref, ovi_ref, ovd_ref, oc_ref,
         dist_buf, tile_buf, sems) = rest
    else:
        (x_ref, obi_ref, obd_ref, obe_ref, ovi_ref, ovd_ref, oc_ref,
         dist_buf, tile_buf, sems) = rest
        xs_ref = None
    q = q_ref[...].astype(jnp.float32)  # (1, d)

    # -- phase 1: blocked candidate gather + one-shot tile reductions --------
    # The exact body of kernels.gather_dist (blocked_gather_phase): block
    # j+1's row DMAs are in flight while block j reduces against q on the
    # MXU (l2/ip/cos norms decomposition, ‖x‖² from the cache) or VPU
    # (l1/chi2 broadcast).  Distances land for every id >= 0 and are masked
    # against the hash in phase 2 — trading a few discarded reductions for a
    # DMA loop with no data-dependent control flow.  Counted comps (phase 2)
    # only charge fresh candidates, matching the unfused path.
    _gather_dist.blocked_gather_phase(
        idx_ref, cand_ref, q, xn_ref, x_ref, dist_buf, tile_buf, sems,
        n_blocks=n_blocks, c_blk=c_blk, metric=metric, xs_ref=xs_ref,
    )

    # -- phase 2: probe / record / merge, all VMEM-resident ------------------
    beam_ids, beam_dist, beam_exp, vis_ids, vis_dist, comps = (
        _probe_mask_record_merge(
            cand_ref[0:1, 0:n_cand],
            dist_buf[0:1, 0:n_cand],
            bi_ref[...],
            bd_ref[...],
            be_ref[...] > 0,
            vi_ref[...],
            vd_ref[...],
            probes,
        )
    )
    obi_ref[...] = beam_ids
    obd_ref[...] = beam_dist
    obe_ref[...] = beam_exp.astype(jnp.int32)
    ovi_ref[...] = vis_ids
    ovd_ref[...] = vis_dist
    oc_ref[0, 0] = comps[0]


@functools.partial(
    jax.jit, static_argnames=("metric", "probes", "interpret", "precision")
)
def fused_expand(
    q: Array,
    x: Array,
    cands: Array,
    beam_ids: Array,
    beam_dist: Array,
    beam_exp: Array,
    vis_ids: Array,
    vis_dist: Array,
    *,
    metric: str = "l2",
    probes: int = 8,
    sq_norms: Optional[Array] = None,
    enc=None,
    precision: str = "fp32",
    interpret: Optional[bool] = None,
):
    """One fused EHC expansion step for a batch of queries.

    Same signature and return contract as ``expand_reference``:
    (beam_ids, beam_dist, beam_exp, vis_ids, vis_dist, comps (B,) int32).
    ``sq_norms`` is the graph-resident ``‖x‖²`` cache backing the blocked
    distance engine (derived once per call when absent).  With
    ``precision="bf16"``/``"int8"`` (and ``enc`` the matching
    ``precision.EncodedData``) phase 1 DMAs the compressed table instead —
    2-/1-byte candidate rows, cast at the block reduction; int8 also rides a
    gathered scale operand.  fp32 keeps the exact pre-precision operands.
    """
    if precision == "pq":
        raise ValueError("expansion kernels take fp32/bf16/int8; pq is an ops-level prerank")
    if interpret is None:
        interpret = compat.default_interpret()
    kernel_metric = metric
    if metric == "cosine":
        # Normalize the query once (exactly as kernels.gather_dist does); the
        # cached ‖x‖² supplies the data-side denominator in-kernel.
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        kernel_metric = "cos"

    B, d = q.shape
    C = cands.shape[1]
    e = beam_ids.shape[1]
    H = vis_ids.shape[1]
    cb = _gather_dist.block_c(C)
    cp = _gather_dist.padded_c(C)
    cands_p = cands.astype(jnp.int32)
    if cp != C:
        cands_p = jnp.pad(cands_p, ((0, 0), (0, cp - C)), constant_values=-1)
    xn = _gather_dist.gathered_sq_norms(x, cands_p, sq_norms)  # (B, cp)

    x_eng = x if (enc is None or precision == "fp32") else enc.data
    quantized = enc is not None and precision == "int8"

    kern = functools.partial(
        _fused_expand_kernel, n_cand=C, n_blocks=cp // cb, c_blk=cb,
        metric=kernel_metric, probes=probes, quantized=quantized,
    )
    row = _gather_dist.lane_spec
    lanes = lambda a: a[:, None, :]  # (B, w) -> (B, 1, w): see lane_spec
    in_specs = [
        row(cp, compat.SMEM),  # cands (DMA addresses)
        row(cp),  # cands (vector phase; first C entries are the originals)
        row(d),  # q
        row(cp),  # xn
        row(e),  # beam_ids
        row(e),  # beam_dist
        row(e),  # beam_exp
        row(H),  # vis_ids
        row(H),  # vis_dist
    ]
    operands = [
        cands_p,
        cands_p,
        q,
        xn,
        beam_ids,
        beam_dist,
        beam_exp.astype(jnp.int32),
        vis_ids,
        vis_dist,
    ]
    if quantized:
        in_specs.append(row(cp))  # xs (gathered int8 dequant scales)
        operands.append(_gather_dist.gathered_row_scales(cands_p, enc.scale))
    operands = [lanes(a) for a in operands]
    in_specs.append(pl.BlockSpec(memory_space=compat.ANY))  # x
    operands.append(x_eng)
    outs = pl.pallas_call(
        kern,
        grid=(B,),
        in_specs=in_specs,
        out_specs=[row(e), row(e), row(e), row(H), row(H), row(1)],
        scratch_shapes=[
            compat.VMEM((1, cp), jnp.float32),
            compat.VMEM((2, cb, d), x_eng.dtype),  # tile in storage dtype
            compat.SemaphoreType.DMA((2, cb)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, e), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, e), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, e), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, H), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ],
        interpret=interpret,
        name="fused_expand",
    )(*operands)
    bi, bd, be, vi, vd, comps = (o[:, 0, :] for o in outs)
    return bi, bd, be > 0, vi, vd, comps[:, 0]
