"""Dispatching wrappers over the Pallas kernels.

Every call site in ``repro.core`` goes through these functions, and the two
execution-surface policies of the framework are resolved HERE and only here:

* **dispatch** — which engine implementation runs.  One enum replaces the old
  tri-state ``use_pallas`` flag:

    - ``"auto"``      a compiled Pallas kernel on TPU where the kernel compiles
                      for the operands (rules below), the pure-JAX reference
                      elsewhere (the old ``use_pallas=None``);
    - ``"pallas"``    the Pallas kernel — compiled on TPU, interpret mode
                      elsewhere (the old ``use_pallas=True``).  On TPU it
                      raises where the chip's compiler refuses the kernel;
    - ``"interpret"`` the Pallas kernel in interpret mode everywhere (what
                      kernel-correctness tests sweep, even on TPU);
    - ``"reference"`` the pure-JAX reference path everywhere (the old
                      ``use_pallas=False``).

  What ``"auto"`` runs on TPU is decided by static predicates on shapes and
  dtypes, never by catching a compile error:

    - ``pairwise_distance``: the tiled kernel (fp32 tiles).
    - ``gather_distance``: the blocked row-DMA kernel when
      ``gather_dist.kernel_fits(table dtype, d)`` — fp32 tables with d a
      multiple of 128 — and the XLA reference for bf16/int8 tables or any
      other width, whose one-row HBM slices the compiler refuses.
    - ``expand_step``: never the fused kernel, whose vector phase (hash
      gathers, scatter, ``lax.top_k``) does not lower to Mosaic.  It runs
      ``expand.expand_reference`` — the XLA probe/record/merge — with
      ``pallas_distances`` set by the same ``gather_distance`` predicate.
    - ``visited_lookup``: the dense per-lane compare kernel when
      ``visited_lookup.kernel_fits(H)`` — visited tables of up to
      ``visited_lookup.MAX_SLOTS`` slots — and the probe gathers of
      ``expand.hash_lookup`` (the reference) for larger tables.

  ``engines`` reports these choices for a table, so callers can print what
  ran.  The legacy ``use_pallas`` keyword is still accepted (None/True/False
  map to auto/pallas/reference); config-level deprecation lives in
  ``core.search.SearchConfig``.

* **precision** — which candidate representation the engine fetches
  (``"fp32"|"bf16"|"int8"|"pq"``, see ``kernels.precision``).  Callers pass
  the raw dataset ``x`` plus the compressed companion ``enc``; no call site
  ever handles dtypes itself.  ``"pq"`` composes as rank-then-rerank inside
  ``expand_step``: ADC first-pass rank on the fresh candidates, exact fp32
  distances for the surviving top ``rerank_keep`` — only exact distances
  enter the visited hash or the beam.

``sq_norms`` / ``x_sq_norms`` thread the graph-resident ``‖x‖²`` cache
(``KNNGraph.sq_norms``) into the blocked distance engine so no path — brute
force, seed gathers, or the expansion hot loop — recomputes norms per
iteration.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import compat
from repro.kernels import distance as _distance
from repro.kernels import expand as _expand
from repro.kernels import gather_dist as _gather_dist
from repro.kernels import precision as _precision
from repro.kernels import ref as _ref
from repro.kernels import visited_lookup as _visited_lookup

Array = jax.Array

_on_tpu = compat.on_tpu

DISPATCHES = ("auto", "pallas", "interpret", "reference")


def resolve_dispatch(
    dispatch: Optional[str] = None,
    use_pallas: Optional[bool] = None,
    *,
    fits: bool = True,
) -> tuple[bool, bool]:
    """The one resolution point for the execution-path enum.

    Returns ``(use_kernel, interpret)``.  ``fits`` is the op's static
    predicate that its kernel compiles on TPU for these operands; only
    ``"auto"`` consults it (see the module docstring for each op's rule).
    ``dispatch=None`` falls back to the legacy ``use_pallas`` tri-state
    (None -> auto, True -> pallas, False -> reference) so old callers and
    old snapshots keep working.
    """
    if dispatch is None:
        if use_pallas is None:
            dispatch = "auto"
        else:
            dispatch = "pallas" if use_pallas else "reference"
    if dispatch == "auto":
        return _on_tpu() and fits, False
    if dispatch == "pallas":
        return True, not _on_tpu()
    if dispatch == "interpret":
        return True, True
    if dispatch == "reference":
        return False, False
    raise ValueError(
        f"unknown dispatch {dispatch!r}; expected one of {DISPATCHES}"
    )


def engines(
    dispatch: Optional[str], dtype, d: int,
    hash_slots: int = _visited_lookup.MAX_SLOTS,
) -> dict[str, str]:
    """Which engine each op runs for an (n, d) table of ``dtype`` and, for
    ``visited_lookup``, visited tables of ``hash_slots`` slots.

    Values: ``"pallas"`` (compiled kernel), ``"pallas-interpret"``,
    ``"xla"`` (pure-JAX reference), and for ``expand_step`` also
    ``"xla+pallas-gather"`` (the XLA probe/merge around the compiled gather
    kernel).  Computed by the same predicates the dispatchers use.
    """

    def name(use_kernel, interpret):
        if not use_kernel:
            return "xla"
        return "pallas-interpret" if interpret else "pallas"

    gather = _gather_engine(dispatch, None, dtype, d)
    fused = _fused_expand_engine(dispatch, None)
    if fused[0]:
        expand = name(*fused)
    else:
        expand = "xla+pallas-gather" if gather[0] else "xla"
    return {
        "pairwise_distance": name(*resolve_dispatch(dispatch)),
        "gather_distance": name(*gather),
        "expand_step": expand,
        "visited_lookup": name(*_lookup_engine(dispatch, hash_slots)),
    }


def _gather_engine(
    dispatch: Optional[str], use_pallas: Optional[bool], dtype, d: int
) -> tuple[bool, bool]:
    """``resolve_dispatch`` for the gather kernel over an (n, d) table of
    ``dtype``: its one-row HBM slices compile only where
    ``gather_dist.kernel_fits``."""
    return resolve_dispatch(
        dispatch, use_pallas, fits=_gather_dist.kernel_fits(dtype, d)
    )


def _lookup_engine(dispatch: Optional[str], hash_slots: int) -> tuple[bool, bool]:
    """``resolve_dispatch`` for the visited-lookup kernel over tables of
    ``hash_slots`` slots: compiled where ``visited_lookup.kernel_fits``."""
    return resolve_dispatch(
        dispatch, fits=_visited_lookup.kernel_fits(hash_slots)
    )


def _fused_expand_engine(
    dispatch: Optional[str], use_pallas: Optional[bool]
) -> tuple[bool, bool]:
    """``resolve_dispatch`` for the fused expansion kernel: ``"auto"`` never
    selects it, since its vector phase does not lower to Mosaic."""
    return resolve_dispatch(dispatch, use_pallas, fits=False)


def pairwise_distance(
    q: Array,
    x: Array,
    metric: str = "l2",
    *,
    use_pallas: Optional[bool] = None,
    dispatch: Optional[str] = None,
    x_sq_norms: Optional[Array] = None,
    enc: Optional[_precision.EncodedData] = None,
    precision: str = "fp32",
    bm: int = 128,
    bn: int = 128,
    bd: int = 128,
) -> Array:
    """(m, d) x (n, d) -> (m, n) float32 distances.

    ``x_sq_norms``: optional cached ``‖x‖²`` of the x side (l2 consumes it;
    other metrics ignore it).  Compressed precisions run the reference
    engine regardless of dispatch — pairwise feeds seeding/brute-force
    tiles, not the expansion hot loop, and the Pallas pairwise kernel stays
    fp32-only.
    """
    use_kernel, interpret = resolve_dispatch(dispatch, use_pallas)
    if enc is not None and precision != "fp32":
        return _ref.pairwise_distance(
            q, x, metric, x_sq_norms=x_sq_norms, enc=enc, precision=precision
        )
    if use_kernel:
        return _distance.pairwise_distance(
            q, x, metric=metric, x_sq_norms=x_sq_norms,
            bm=bm, bn=bn, bd=bd, interpret=interpret,
        )
    return _ref.pairwise_distance(q, x, metric, x_sq_norms=x_sq_norms)


def gather_distance(
    q: Array,
    x: Array,
    idx: Array,
    metric: str = "l2",
    *,
    use_pallas: Optional[bool] = None,
    dispatch: Optional[str] = None,
    sq_norms: Optional[Array] = None,
    enc: Optional[_precision.EncodedData] = None,
    precision: str = "fp32",
) -> Array:
    """(b, d) queries vs rows x[idx] -> (b, c) float32; inf at idx < 0.

    ``sq_norms``: optional (n,) graph-resident ``‖x‖²`` cache feeding the
    blocked engine's norms decomposition.  ``enc``/``precision`` select the
    candidate representation: bf16/int8 ride the kernel *or* reference
    engine (per dispatch); ``"pq"`` is always the reference ADC rank — the
    in-kernel tile path has no code-table form, and the exact re-rank
    composes in ``expand_step``.
    """
    compressed = enc is not None and precision != "fp32"
    if compressed and precision == "pq":
        return _ref.gather_distance(
            q, x, idx, metric, sq_norms=sq_norms, enc=enc, precision=precision
        )
    x_eng = enc.data if compressed else x
    use_kernel, interpret = _gather_engine(
        dispatch, use_pallas, x_eng.dtype, x_eng.shape[1]
    )
    if use_kernel:
        row_scale = enc.scale if compressed and precision == "int8" else None
        return _gather_dist.gather_distance(
            q, x_eng, idx, metric=metric, sq_norms=sq_norms,
            row_scale=row_scale, interpret=interpret,
        )
    return _ref.gather_distance(
        q, x, idx, metric, sq_norms=sq_norms,
        enc=enc if compressed else None,
        precision=precision if compressed else "fp32",
    )


def merge_proposals(
    q: Array,
    xt: Array,
    hit_ids: Array,
    t_nbr_ids: Array,
    t_alive: Array,
    metric: str = "l2",
    *,
    dispatch: Optional[str] = None,
    sq_norms: Optional[Array] = None,
    hop_top: Optional[int] = None,
) -> tuple[Array, Array, Array]:
    """Second-hop merge candidates through the blocked distance engine.

    For each query row with cross-search hits ``hit_ids`` (target-LOCAL ids,
    -1 pad) against a target sub-graph, propose the hits' own neighbor lists
    (``t_nbr_ids[hit]``) as additional candidates — the 1908.00814 move that
    turns one EHC walk per query into a k²-wide neighborhood sample.  All
    candidate distances run through ``gather_distance`` (the one blocked
    engine), so proposal assembly stays on-device; dead targets are masked.

    Args:
      q: (B, d) query vectors (the searching side's points).
      xt: (n_t, d) target side's data.
      hit_ids: (B, k) target-LOCAL hit ids from the cross search.
      t_nbr_ids: (n_t, k_t) target graph forward lists (LOCAL ids).
      t_alive: (n_t,) target liveness.
      metric/dispatch/sq_norms: distance-engine routing (``sq_norms`` =
        target side's graph-resident norm cache).
      hop_top: expand only the nearest ``hop_top`` hits per query (hit
        lists arrive distance-sorted from the search).  The full k² fan-out
        is quadratic in candidate volume but the recall lives in the first
        few hits' neighborhoods; ``None`` expands every hit.

    Returns (cand_ids (B, h*k_t) LOCAL, cand_dist (B, h*k_t) with inf at
    masked lanes, n_comps () int32 — every evaluated lane charged), where
    ``h = min(hop_top, k)``.
    """
    B, k = hit_ids.shape
    if hop_top is not None and hop_top < k:
        hit_ids = hit_ids[:, :hop_top]
    hop = t_nbr_ids[jnp.maximum(hit_ids, 0)]  # (B, h, k_t)
    hop = jnp.where(hit_ids[:, :, None] >= 0, hop, -1).reshape(B, -1)
    hop = jnp.where((hop >= 0) & t_alive[jnp.maximum(hop, 0)], hop, -1)
    d = gather_distance(
        q, xt, hop, metric, dispatch=dispatch, sq_norms=sq_norms
    )
    live = hop >= 0
    return hop, jnp.where(live, d, jnp.inf), jnp.sum(live, dtype=jnp.int32)


def visited_lookup(
    vis_ids: Array,
    vis_dist: Array,
    ids: Array,
    probes: int,
    *,
    dispatch: Optional[str] = None,
) -> Array:
    """D(q_w, ids[w, m]) from per-lane visited tables: (W, H) tables and
    (W, M) ids -> (W, M) float32, the distance lane w's search recorded for
    the id within its ``probes``-slot window, +inf where it recorded none.

    The compiled kernel (``kernels.visited_lookup``, a dense compare against
    each lane's whole table) returns the reference's float for every id >= 0;
    the reference is ``expand.hash_lookup``'s probe gathers.
    """
    use_kernel, interpret = _lookup_engine(dispatch, vis_ids.shape[1])
    if use_kernel:
        return _visited_lookup.visited_lookup(
            vis_ids, vis_dist, ids, probes, interpret=interpret
        )
    return _expand.hash_lookup(vis_ids, vis_dist, ids, probes)[1]


def topk_smallest(dists: Array, ids: Array, k: int):
    """Row-wise smallest-k selection; see ref.topk_smallest."""
    return _ref.topk_smallest(dists, ids, k)


def expand_step(
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
    hash_probes: int = 8,
    sq_norms: Optional[Array] = None,
    use_pallas: Optional[bool] = None,
    dispatch: Optional[str] = None,
    enc: Optional[_precision.EncodedData] = None,
    precision: str = "fp32",
    rerank_keep: int = 0,
):
    """One EHC expansion step (Alg. 1/3 inner loop) for a batch of queries.

    Given masked candidate ids (``core.search._candidates_from_expansion``
    output), dedups them against the per-query visited hash, computes the
    surviving distances with the blocked MXU engine (``sq_norms`` = the
    graph-resident norm cache), records them into the hash, and merges them
    into the beam top-k.  Returns
    ``(beam_ids, beam_dist, beam_exp, vis_ids, vis_dist, comps)``.

    Precision: ``"bf16"``/``"int8"`` fetch candidate rows from the
    compressed table inside whichever engine dispatch selects.  ``"pq"``
    runs rank-then-rerank: the fresh candidates get an ADC first-pass rank
    from the code table, only the best ``rerank_keep`` go through the exact
    fp32 expansion, and the ADC-scanned-but-dropped candidates still charge
    ``comps`` (scanning-rate honesty — every fresh candidate was evaluated
    once).  Only exact distances ever enter the visited hash or the beam.
    """
    if enc is not None and precision == "pq":
        if rerank_keep <= 0:
            raise ValueError("pq expansion needs rerank_keep > 0")
        return _pq_rank_then_rerank(
            q, x, cands, beam_ids, beam_dist, beam_exp, vis_ids, vis_dist,
            metric=metric, hash_probes=hash_probes, sq_norms=sq_norms,
            use_pallas=use_pallas, dispatch=dispatch, enc=enc,
            rerank_keep=rerank_keep,
        )
    compressed = enc is not None and precision != "fp32"
    # where the fused kernel is not selected (always under "auto"), the XLA
    # op chain runs around the gather engine
    use_kernel, interpret = _fused_expand_engine(dispatch, use_pallas)
    if use_kernel:
        return _expand.fused_expand(
            q, x, cands, beam_ids, beam_dist, beam_exp, vis_ids, vis_dist,
            metric=metric, probes=hash_probes, sq_norms=sq_norms,
            enc=enc if compressed else None,
            precision=precision if compressed else "fp32",
            interpret=interpret,
        )
    x_eng = enc.data if compressed else x
    gather_kernel, gather_interpret = _gather_engine(
        dispatch, use_pallas, x_eng.dtype, x_eng.shape[1]
    )
    return _expand.expand_reference(
        q, x, cands, beam_ids, beam_dist, beam_exp, vis_ids, vis_dist,
        metric=metric, probes=hash_probes, sq_norms=sq_norms,
        enc=enc if compressed else None,
        precision=precision if compressed else "fp32",
        pallas_distances=gather_kernel, interpret=gather_interpret,
    )


def _pq_rank_then_rerank(
    q, x, cands, beam_ids, beam_dist, beam_exp, vis_ids, vis_dist,
    *, metric, hash_probes, sq_norms, use_pallas, dispatch, enc, rerank_keep
):
    """ADC first-pass rank -> exact fp32 re-rank of the survivors.

    The prerank never touches the visited hash: the same ``hash_probe_state``
    the inner expansion will run classifies fresh candidates, the ADC ranks
    them, and everything below the top ``rerank_keep`` is masked to -1 before
    the (unchanged, exact) expansion step executes.  Dropped candidates are
    *not* recorded anywhere — they may be rediscovered by a later expansion,
    which re-charges them; that is the price of keeping the hash exact.
    """
    C = cands.shape[1]
    keep = min(rerank_keep, C)
    present, _, _ = _expand.hash_probe_state(vis_ids, cands, hash_probes)
    fresh = (cands >= 0) & ~present
    cand_ids = jnp.where(fresh, cands, -1)
    adc = _ref.gather_distance(
        q, x, cand_ids, metric, sq_norms=sq_norms, enc=enc, precision="pq"
    )  # (B, C); +inf at masked
    # survivors: the `keep` smallest ADC scores per row
    _, sel = jax.lax.top_k(-adc, keep)  # (B, keep)
    B_idx = jnp.broadcast_to(jnp.arange(q.shape[0])[:, None], sel.shape)
    survive = jnp.zeros(cands.shape, bool).at[B_idx, sel].set(True)
    cands_kept = jnp.where(survive, cands, -1)
    out = expand_step(
        q, x, cands_kept, beam_ids, beam_dist, beam_exp, vis_ids, vis_dist,
        metric=metric, hash_probes=hash_probes, sq_norms=sq_norms,
        use_pallas=use_pallas, dispatch=dispatch, enc=None, precision="fp32",
    )
    bi, bd, be, vi, vd, _comps_exact = out
    # scanning-rate honesty: every fresh candidate cost one (ADC) evaluation;
    # the exact re-ranks are a subset, not an addition.
    comps = jnp.sum(fresh, axis=1).astype(jnp.int32)
    return bi, bd, be, vi, vd, comps
