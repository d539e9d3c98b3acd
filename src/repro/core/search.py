"""Batched Enhanced Hill-Climbing (EHC) — Alg. 1, TPU-native.

The paper's Alg. 1 is a best-first walk: repeatedly take the closest
not-yet-expanded vertex r from a sorted list Q, compare the query against
G[r] ∪ Ḡ[r], and stop when no unexpanded vertex can improve the result.

TPU adaptation (DESIGN.md §2):
  * a whole wave of B queries climbs simultaneously (leading batch axis, not
    vmap, so the gathers/distance kernels see batched shapes);
  * Q becomes a fixed-width beam (ids, dists, expanded-flags) maintained by
    top-k merges;
  * the O(n) Flag array becomes a per-query open-addressing hash table that
    doubles as the paper's D array of Alg. 3 (id -> computed distance), which
    is exactly what the LGD commit needs later;
  * ``while updated`` becomes a lax.while_loop over a convergence mask: a
    lane is done when its best unexpanded beam entry cannot enter its current
    top-k (the paper's "no closer sample identified"), with a hard
    ``max_iters`` cap as straggler mitigation — one pathological query cannot
    stall the wave (converged lanes are masked, SIMT style).

LGD-aware expansion (Alg. 3 lines 15/19): neighbors whose occlusion factor λ
exceeds the mean λ of the expanded row are skipped; for reverse edges the λ
of the forward twin (r's slot inside G[j]) is looked up.  ``hard_diversify``
gives the FANNG/DPG-style λ>0 ablation the paper argues against.

The per-iteration hot path (hash probe → candidate-row gather + distance →
hash record → beam top-k merge) is one fused call, ``kernels.ops
.expand_step``: a single Pallas kernel on TPU, the XLA-fused pure-JAX
reference elsewhere — see ``SearchConfig.use_pallas`` for the dispatch.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import segments
from repro.core.graph import KNNGraph
from repro.kernels import expand as expand_lib
from repro.kernels import ops
from repro.kernels import precision as precision_lib

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    """Static EHC search configuration.

    ``dispatch`` selects the execution path of the fused expansion step
    (``kernels.ops.expand_step`` — one call per EHC iteration covering hash
    probe, candidate-row gather + distance, hash record, and beam top-k
    merge) and of the seed-distance gather.  One enum, resolved only in
    ``kernels.ops``:

      * ``"auto"`` (default): the compiled fused Pallas kernel on TPU, the
        pure-JAX reference elsewhere (XLA fuses it into the jitted search
        loop; the fast CPU path);
      * ``"pallas"``: always the kernel — compiled on TPU, interpret mode
        off-TPU (slow, but bit-identical to compiled semantics);
      * ``"interpret"``: the kernel in interpret mode everywhere (what the
        parity/correctness tests sweep);
      * ``"reference"``: always the pure-JAX reference path.

    ``use_pallas`` is the DEPRECATED tri-state ancestor of ``dispatch``
    (None/True/False = auto/pallas/reference).  Setting it still works —
    it is mapped onto ``dispatch`` with a ``DeprecationWarning`` — so old
    callers and old snapshots keep loading.

    ``precision`` selects the candidate representation the distance engine
    fetches (``kernels.precision``): ``"fp32"`` (exact, the default —
    bit-identical to the pre-precision engine), ``"bf16"``/``"int8"``
    (compressed tiles, fp32 accumulation, tolerance-suite accuracy), or
    ``"pq"`` (ADC first-pass rank + exact fp32 re-rank of the top
    ``rerank_factor * k`` fresh candidates per expansion; only exact
    distances enter the visited hash or beam).  The compressed companion
    table rides as the ``enc`` operand of ``search`` and is derived from
    the dataset (and the graph-resident ``row_scale`` table) when absent.

    ``seed_mode`` selects the Alg. 1 line-5 entry points: ``"random"`` is the
    paper's p uniform draws over [0, n); ``"coarse"`` first runs a short EHC
    pass on a coarse landmark graph (``core.hierarchy.CoarseLevel``, passed
    as the ``coarse`` operand of ``search``/``init_state``) and seeds the
    full-graph beam from the winning landmarks' rows plus their assigned
    member cells — the EFANNA-style hierarchical initialization that drops
    the scanning rate from O(n) territory to polylog.
    """

    k: int = 10  # result size; also the improvement-termination horizon
    beam: int = 64  # beam width e >= k
    n_seeds: int = 8  # p random entry points
    # H, power of two.  None auto-sizes from beam/max_iters (see
    # __post_init__); explicit values are respected — the hash_full flag in
    # SearchResult reports per-lane saturation either way.
    hash_slots: Optional[int] = None
    hash_probes: int = 8  # linear-probe depth
    max_iters: int = 64  # straggler cap on expansions
    metric: str = "l2"
    use_reverse: bool = True  # False = plain HC (Fig. 5 ablation: no Ḡ[r])
    use_lgd_mask: bool = False  # λ <= mean-λ expansion filter (Alg. 3)
    lgd_rev_lambda: bool = True  # look up λ of the forward twin for rev edges
    hard_diversify: bool = False  # ablation: skip any λ > 0 (DPG/FANNG style)
    use_pallas: Optional[bool] = None  # DEPRECATED -> dispatch
    dispatch: Optional[str] = None  # None -> "auto" (post-init)
    precision: str = "fp32"  # "fp32" | "bf16" | "int8" | "pq"
    rerank_factor: int = 4  # pq: exact re-rank width = rerank_factor * k
    seed_mode: str = "random"  # "random" | "coarse"
    coarse_top: int = 4  # T winning landmarks whose cells seed the beam
    coarse_beam: int = 16  # beam width of the coarse EHC pass
    coarse_iters: int = 16  # max_iters of the coarse EHC pass

    def __post_init__(self):
        assert self.beam >= self.k, "beam must be >= k"
        assert self.seed_mode in ("random", "coarse"), self.seed_mode
        if self.use_pallas is not None:
            warnings.warn(
                "SearchConfig.use_pallas is deprecated; use dispatch="
                "'auto'|'pallas'|'interpret'|'reference' instead",
                DeprecationWarning,
                stacklevel=2,
            )
            if self.dispatch is None:
                object.__setattr__(
                    self, "dispatch",
                    "pallas" if self.use_pallas else "reference",
                )
            # normalize so dataclasses.replace round trips don't re-warn and
            # configs differing only in the legacy spelling compare equal
            object.__setattr__(self, "use_pallas", None)
        if self.dispatch is None:
            object.__setattr__(self, "dispatch", "auto")
        assert self.dispatch in ops.DISPATCHES, self.dispatch
        precision_lib.validate_precision(self.precision)
        assert self.rerank_factor >= 1, "rerank_factor must be >= 1"
        if self.hash_slots is None:
            object.__setattr__(
                self, "hash_slots", auto_hash_slots(self.beam, self.max_iters)
            )
        assert self.hash_slots & (self.hash_slots - 1) == 0, "hash_slots must be 2^h"


def auto_hash_slots(beam: int, max_iters: int) -> int:
    """Default H for a (beam, max_iters) search shape: the next power of two
    above ``beam * max_iters / 2`` (a per-row candidate width is beam-scale
    and masking/convergence roughly halve the recorded entries), clamped to
    [1024, 65536].  A heuristic, not a guarantee — ``SearchResult.hash_full``
    is the ground truth for saturation."""
    est = (beam * max_iters) // 2
    H = 1024
    while H < est and H < (1 << 16):
        H <<= 1
    return H


class SearchResult(NamedTuple):
    ids: Array  # (B, k) int32 top-k ids, ascending distance
    dists: Array  # (B, k) float32
    vis_ids: Array  # (B, H) int32 — every vertex compared (the D array keys)
    vis_dist: Array  # (B, H) float32 — m(q, vertex) (the D array values)
    n_comps: Array  # (B,) int32 — distance computations (scanning rate)
    n_iters: Array  # (B,) int32 — expansions until convergence
    converged: Array  # (B,) bool — False = stopped by max_iters cap
    hash_full: Array  # (B,) bool — True = some computed distance was NOT
    #   recorded in the D array (insert failed: table saturated or slot
    #   collision); n_comps may then overcount unique evaluations
    seed_cell: Array  # (B,) int32 — winning coarse landmark (seed_mode=
    #   "coarse"; -1 under random seeding).  Lets callers assign freshly
    #   inserted rows to their cell without a separate brute pass.


# The hash/beam primitives live next to the fused kernel that consumes them
# (kernels.expand); these aliases keep the established core-layer surface.
_probe_slots = expand_lib.probe_slots
hash_lookup = expand_lib.hash_lookup
_hash_probe_state = expand_lib.hash_probe_state
_dedupe_beam = expand_lib.dedupe_beam


def _row_mean_lambda(lam_row: Array, ids_row: Array) -> Array:
    """Mean λ over valid entries of a k-NN list: λ̄(r)."""
    valid = ids_row >= 0
    cnt = jnp.maximum(jnp.sum(valid, axis=-1), 1)
    return jnp.sum(jnp.where(valid, lam_row, 0), axis=-1) / cnt


class _LoopState(NamedTuple):
    beam_ids: Array
    beam_dist: Array
    beam_exp: Array
    vis_ids: Array
    vis_dist: Array
    n_comps: Array
    n_iters: Array
    done: Array
    it: Array
    hash_full: Array
    seed_cell: Array


def _candidates_from_expansion(
    g: KNNGraph, r_id: Array, has_r: Array, cfg: SearchConfig
) -> Array:
    """Expand r: G[r] ∪ Ḡ[r] with LGD masking. Returns (B, k+R) ids, -1 masked."""
    B = r_id.shape[0]
    safe_r = jnp.maximum(r_id, 0)
    fwd_ids = g.nbr_ids[safe_r]  # (B, kg)
    rev_ids = g.rev_ids[safe_r]  # (B, R)
    if not cfg.use_reverse:  # plain hill-climbing (Hajebi'11): G[r] only
        rev_ids = jnp.full_like(rev_ids, -1)
    if cfg.use_lgd_mask or cfg.hard_diversify:
        fwd_lam = g.nbr_lam[safe_r]  # (B, kg)
        mean_lam = _row_mean_lambda(fwd_lam, fwd_ids)[:, None]
        if cfg.hard_diversify:
            fwd_keep = fwd_lam <= 0
        else:
            fwd_keep = fwd_lam.astype(jnp.float32) <= mean_lam  # Alg.3 line 15 (≤)
        fwd_ids = jnp.where(fwd_keep, fwd_ids, -1)
        if cfg.lgd_rev_lambda:
            # λ of the forward twin, from the graph-resident rev_lam table —
            # a flat (B, R) gather.  The table snapshots λ at append/rebuild
            # time (the old live (B, R, kg) twin-row gather per iteration is
            # gone); staleness only perturbs this expansion *filter*, exactly
            # like stale rev_ids entries, never distances or results.
            rev_lam = g.rev_lam[safe_r].astype(jnp.float32)  # (B, R)
            if cfg.hard_diversify:
                rev_keep = rev_lam <= 0
            else:
                rev_keep = rev_lam < mean_lam  # Alg.3 line 19 (<)
            rev_ids = jnp.where(rev_keep, rev_ids, -1)
    cands = jnp.concatenate([fwd_ids, rev_ids], axis=1)  # (B, C0)
    cands = jnp.where(has_r[:, None], cands, -1)
    # mask ids beyond allocation / dead rows
    in_range = (cands >= 0) & (cands < g.n_valid)
    alive = jnp.where(in_range, g.alive[jnp.maximum(cands, 0)], False)
    cands = jnp.where(in_range & alive, cands, -1)
    # in-step dedupe (G[r] and Ḡ[r] overlap, per the paper's Fig. 1 remark) —
    # sort-based segmented idiom, not the old O(C²) pairwise matrix
    cands = jnp.where(segments.mask_row_duplicates(cands), -1, cands)
    return cands


def _prepare_expansion(
    g: KNNGraph, st: _LoopState, cfg: SearchConfig
) -> tuple[Array, Array]:
    """Select r (closest unexpanded beam entry per lane), mark it expanded,
    and emit its masked candidate ids.  Returns (cands (B, C), beam_exp)."""
    B = st.beam_ids.shape[0]
    sel_dist = jnp.where(st.beam_exp, jnp.inf, st.beam_dist)
    r_slot = jnp.argmin(sel_dist, axis=1)
    r_best = jnp.take_along_axis(sel_dist, r_slot[:, None], axis=1)[:, 0]
    has_r = jnp.isfinite(r_best) & ~st.done
    r_id = jnp.where(
        has_r, jnp.take_along_axis(st.beam_ids, r_slot[:, None], axis=1)[:, 0], -1
    )
    beam_exp = st.beam_exp.at[jnp.arange(B), r_slot].set(
        st.beam_exp[jnp.arange(B), r_slot] | has_r
    )
    cands = _candidates_from_expansion(g, r_id, has_r, cfg)
    return cands, beam_exp


def _expand(
    g: KNNGraph, x: Array, q: Array, cands: Array, beam_exp: Array,
    st: _LoopState, cfg: SearchConfig, enc=None,
):
    """The fused expansion: probe the visited hash, compute surviving
    distances (blocked MXU engine fed by the graph-resident norm cache),
    record them, merge into the beam.  One ``ops.expand_step`` call —
    engine per ``cfg.dispatch``, candidate representation per
    ``cfg.precision`` (``enc`` is the compressed companion table)."""
    return ops.expand_step(
        q, x, cands, st.beam_ids, st.beam_dist, beam_exp,
        st.vis_ids, st.vis_dist,
        metric=cfg.metric, hash_probes=cfg.hash_probes,
        sq_norms=g.sq_norms, dispatch=cfg.dispatch,
        enc=enc, precision=cfg.precision,
        rerank_keep=cfg.rerank_factor * cfg.k,
    )


def _hash_fill(vis_ids: Array) -> Array:
    """Occupied D-array slots per lane."""
    return jnp.sum(vis_ids >= 0, axis=1).astype(jnp.int32)


def _make_step(g: KNNGraph, x: Array, q: Array, cfg: SearchConfig, enc=None):
    def step(st: _LoopState) -> _LoopState:
        cands, beam_exp = _prepare_expansion(g, st, cfg)
        fill_before = _hash_fill(st.vis_ids)
        beam_ids, beam_dist, beam_exp, vis_ids, vis_dist, comps = _expand(
            g, x, q, cands, beam_exp, st, cfg, enc
        )
        n_comps = st.n_comps + comps
        # every computed distance must land in the D array; a fill delta below
        # the comparison count means an insert was dropped (probe depth
        # exhausted on a saturated table, or a same-slot scatter collision)
        hash_full = st.hash_full | (_hash_fill(vis_ids) - fill_before < comps)
        # -- convergence: best unexpanded cannot improve current top-k --------
        best_unexp = jnp.min(jnp.where(beam_exp, jnp.inf, beam_dist), axis=1)
        kth = beam_dist[:, cfg.k - 1]
        newly_done = ~(best_unexp < kth)
        n_iters = st.n_iters + (~st.done).astype(jnp.int32)
        return _LoopState(
            beam_ids,
            beam_dist,
            beam_exp,
            vis_ids,
            vis_dist,
            n_comps,
            n_iters,
            st.done | newly_done,
            st.it + 1,
            hash_full,
            st.seed_cell,
        )

    return step


def coarse_config(cfg: SearchConfig) -> SearchConfig:
    """The config of the short coarse-graph EHC pass implied by a
    ``seed_mode="coarse"`` config: top-``coarse_top`` over a small beam and
    few iterations, random seeding (so the recursion terminates), LGD
    filtering off (the landmark graph is tiny and routing-only), and exact
    fp32 distances (the landmark table is tiny — compressing it buys nothing
    and would demand a second enc table for the coarse points)."""
    return dataclasses.replace(
        cfg,
        k=cfg.coarse_top,
        beam=max(cfg.coarse_beam, cfg.coarse_top),
        hash_slots=None,  # re-auto-size for the coarse shape
        max_iters=cfg.coarse_iters,
        use_lgd_mask=False,
        hard_diversify=False,
        seed_mode="random",
        precision="fp32",
    )


def init_state(
    g: KNNGraph,
    x: Array,
    q: Array,
    key: Array,
    cfg: SearchConfig,
    coarse=None,
    enc=None,
) -> _LoopState:
    """Pre-loop search state: entry points scored, hashed, and merged into
    an otherwise-empty beam (Alg. 1 line 5).  Public so benchmarks and the
    expansion parity suite can drive single EHC iterations directly.

    ``seed_mode="random"`` draws p uniform seeds.  ``seed_mode="coarse"``
    additionally runs a short EHC pass over ``coarse`` (a
    ``core.hierarchy.CoarseLevel``) and seeds from the winning landmarks'
    full-graph rows plus their assigned member cells; the coarse pass's
    comparisons are pre-charged into ``n_comps`` so the scanning rate stays
    honest, and its top-1 winner is carried out as ``seed_cell``."""
    B = q.shape[0]
    e, H = cfg.beam, cfg.hash_slots

    # -- entry points (Alg. 1 line 5) ----------------------------------------
    if cfg.seed_mode == "coarse":
        if coarse is None:
            raise ValueError(
                "seed_mode='coarse' needs a coarse level (core.hierarchy."
                "CoarseLevel) passed as the `coarse` operand"
            )
        key_c, key_r = jax.random.split(key)
        # device scope read by the benchmark as the coarse pass's time; the
        # nested search's own ``expand`` falls under it
        with jax.named_scope("coarse_pass"):
            cres = search(
                coarse.graph, coarse.points, q, key_c, coarse_config(cfg)
            )
            win = cres.ids  # (B, T) landmark indices, -1 padded
            safe_win = jnp.maximum(win, 0)
            lm_rows = jnp.where(win >= 0, coarse.landmark_rows[safe_win], -1)
            members = jnp.where(
                win[:, :, None] >= 0, coarse.members[safe_win], -1
            ).reshape(B, -1)
        rand = jax.random.randint(
            key_r, (B, cfg.n_seeds), 0, jnp.maximum(g.n_valid, 1),
            dtype=jnp.int32,
        )
        seeds = jnp.concatenate([lm_rows, members, rand], axis=1)
        seed_cell = win[:, 0]
        pre_comps = cres.n_comps
        pre_full = cres.hash_full
    else:
        seeds = jax.random.randint(
            key, (B, cfg.n_seeds), 0, jnp.maximum(g.n_valid, 1),
            dtype=jnp.int32,
        )
        seed_cell = jnp.full((B,), -1, jnp.int32)
        pre_comps = jnp.zeros((B,), jnp.int32)
        pre_full = jnp.zeros((B,), bool)
    # dedupe seeds within a lane (sort-based segmented idiom)
    seeds = jnp.where(segments.mask_row_duplicates(seeds), -1, seeds)
    in_range = (seeds >= 0) & (seeds < g.n_valid)
    seeds = jnp.where(in_range & g.alive[jnp.maximum(seeds, 0)], seeds, -1)
    # Seed distances enter the beam and the visited hash, so they follow the
    # engine precision for bf16/int8 (those ARE the engine's distances) but
    # stay exact under pq — ADC scores never land in the hash by policy, and
    # p seeds are too few for the prerank to pay for itself.
    seed_precision = cfg.precision if cfg.precision in ("bf16", "int8") else "fp32"
    seed_dist = ops.gather_distance(
        q, x, seeds, cfg.metric, sq_norms=g.sq_norms, dispatch=cfg.dispatch,
        enc=enc if seed_precision != "fp32" else None, precision=seed_precision,
    )

    beam_ids = jnp.full((B, e), -1, jnp.int32)
    beam_dist = jnp.full((B, e), jnp.inf, jnp.float32)
    beam_exp = jnp.ones((B, e), bool)
    vis_ids = jnp.full((B, H), -1, jnp.int32)
    vis_dist = jnp.full((B, H), jnp.inf, jnp.float32)

    # install seeds via one merge + hash insert
    _, ins_ok, ins_slot = _hash_probe_state(vis_ids, seeds, cfg.hash_probes)
    do_ins = (seeds >= 0) & ins_ok
    B_idx = jnp.broadcast_to(jnp.arange(B)[:, None], seeds.shape)
    slot = jnp.where(do_ins, ins_slot, H)
    vis_ids = vis_ids.at[B_idx, slot].set(jnp.where(do_ins, seeds, -1), mode="drop")
    vis_dist = vis_dist.at[B_idx, slot].set(
        jnp.where(do_ins, seed_dist, jnp.inf), mode="drop"
    )
    cat_ids = jnp.concatenate([beam_ids, seeds], axis=1)
    cat_dist = jnp.concatenate([beam_dist, seed_dist], axis=1)
    cat_exp = jnp.concatenate([beam_exp, seeds < 0], axis=1)
    neg, sel = jax.lax.top_k(-cat_dist, e)
    beam_ids = jnp.take_along_axis(cat_ids, sel, axis=1)
    beam_dist = -neg
    beam_exp = jnp.take_along_axis(cat_exp, sel, axis=1)

    seed_comps = jnp.sum(seeds >= 0, axis=1).astype(jnp.int32)
    return _LoopState(
        beam_ids=beam_ids,
        beam_dist=beam_dist,
        beam_exp=beam_exp,
        vis_ids=vis_ids,
        vis_dist=vis_dist,
        n_comps=pre_comps + seed_comps,
        n_iters=jnp.zeros((B,), jnp.int32),
        done=jnp.zeros((B,), bool),
        it=jnp.zeros((), jnp.int32),
        hash_full=pre_full | (_hash_fill(vis_ids) < seed_comps),
        seed_cell=seed_cell,
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def search(
    g: KNNGraph,
    x: Array,
    q: Array,
    key: Array,
    cfg: SearchConfig,
    coarse=None,
    enc=None,
) -> SearchResult:
    """Batched EHC search of queries q against graph g over dataset x.

    Args:
      g: the (possibly under-construction) graph.
      x: (n, d) dataset backing the graph rows.
      q: (B, d) queries.
      key: PRNG key for the entry points.
      cfg: static search configuration.
      coarse: ``core.hierarchy.CoarseLevel`` operand, required when
        ``cfg.seed_mode == "coarse"`` (ignored otherwise).
      enc: ``kernels.precision.EncodedData`` companion table matching
        ``cfg.precision`` (ignored for fp32).  Derived from ``x`` at trace
        time when absent — fine for one-off calls, but persistent callers
        (``index.lifecycle.OnlineIndex``) pass a cached table so encoding
        isn't redone per search; int8 reuses the graph-resident
        ``g.row_scale`` cache either way.

    Returns: SearchResult (top-k per lane + the comparison log).
    """
    if cfg.precision != "fp32" and enc is None:
        reuse_scale = (
            cfg.precision == "int8" and g.row_scale.shape[0] == x.shape[0]
        )
        enc = precision_lib.encode_dataset(
            x, cfg.precision,
            row_scale=g.row_scale if reuse_scale else None,
        )
    st = init_state(g, x, q, key, cfg, coarse=coarse, enc=enc)
    step = _make_step(g, x, q, cfg, enc)
    # device scope read by the benchmark as the expansion loop's time
    with jax.named_scope("expand"):
        st = jax.lax.while_loop(
            lambda s: (~jnp.all(s.done)) & (s.it < cfg.max_iters), step, st
        )
    return SearchResult(
        ids=st.beam_ids[:, : cfg.k],
        dists=st.beam_dist[:, : cfg.k],
        vis_ids=st.vis_ids,
        vis_dist=st.vis_dist,
        n_comps=st.n_comps,
        n_iters=st.n_iters,
        converged=st.done,
        hash_full=st.hash_full,
        seed_cell=st.seed_cell,
    )
