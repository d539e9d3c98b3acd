"""Online k-NN graph construction — OLG (Alg. 2) and LGD (Alg. 3), TPU-native.

The paper inserts samples one at a time: search the graph under construction
with the new sample as query, join its top-k result as a new row, and update
the k-NN lists of every vertex the search compared against.  On TPU we insert
*waves* of W samples (DESIGN.md §2, deviation §8.1):

  1. the whole wave searches the frozen graph G_t in parallel (core.search);
  2. an intra-wave distance tile lets near-simultaneous arrivals find each
     other (what sequential insertion gives for free);
  3. one batched commit produces G_{t+1}:
       * new rows  = top-k over (search result ‖ intra-wave candidates),
       * edge updates to existing rows = the (vertex, query, distance) triples
         logged in the search's visited tables, merged with core.merge,
       * reverse lists appended (ring buffers),
       * LGD occlusion factors λ updated under Rules 1-3 using ONLY distances
         the search already computed — the visited table *is* the paper's D
         array (default ∞), the intra-wave tile covers wave-wave pairs.

W=1 degenerates to the paper's sequential algorithm exactly; W=256..4096 is
the production setting.  ``lgd=False`` gives OLG (Alg. 2): same flow, no λ
bookkeeping and no expansion filtering.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import brute, merge
from repro.core import search as search_lib
from repro.core.counters import Counter64
from repro.core.graph import KNNGraph, row_scales, squared_norms
from repro.core.search import SearchConfig
from repro.kernels import compat, ops
from repro.kernels import precision as precision_lib

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class BuildConfig:
    k: int = 20  # graph degree (size of NN lists)
    metric: str = "l2"
    n_seed_init: int = 256  # |I|, fixed to 256 across the paper
    wave: int = 256  # W — queries inserted per batched round
    lgd: bool = True  # Alg. 3 (True) vs Alg. 2 / OLG (False)
    intra_wave: bool = True  # wave members see each other (W x W tile)
    rev_cap: Optional[int] = None  # reverse-list ring capacity (default 2k)
    ins_cap_per_q: Optional[int] = None  # rows one query may update (default 3k)
    # search parameters (Alg. 1/3 inner loop)
    beam: int = 40
    n_seeds: int = 8  # p
    hash_slots: Optional[int] = None  # None = auto-size from beam/max_iters
    max_iters: int = 60
    use_pallas: Optional[bool] = None  # DEPRECATED -> dispatch
    dispatch: Optional[str] = None  # None -> "auto"; see SearchConfig
    # distance-engine precision of the insertion searches; the serving-side
    # SearchConfig inherits it (index.lifecycle builds its search config here)
    precision: str = "fp32"  # "fp32" | "bf16" | "int8" | "pq"
    rerank_factor: int = 4  # pq: exact re-rank width = rerank_factor * k
    data_bf16: bool = False  # store the dataset bf16 (distances accum f32)
    # hierarchical entry-point seeding (core.hierarchy)
    seed_mode: str = "random"  # "random" | "coarse"
    coarse_landmarks: Optional[int] = None  # L; None = ~4·√n (hierarchy)
    coarse_members: int = 8  # M — member-cell ring capacity per landmark
    coarse_top: int = 4  # T winning landmarks seeding each fine search

    def __post_init__(self):
        if self.use_pallas is not None:
            warnings.warn(
                "BuildConfig.use_pallas is deprecated; use dispatch="
                "'auto'|'pallas'|'interpret'|'reference' instead",
                DeprecationWarning,
                stacklevel=2,
            )
            if self.dispatch is None:
                object.__setattr__(
                    self, "dispatch",
                    "pallas" if self.use_pallas else "reference",
                )
            object.__setattr__(self, "use_pallas", None)
        if self.dispatch is None:
            object.__setattr__(self, "dispatch", "auto")
        assert self.dispatch in ops.DISPATCHES, self.dispatch
        precision_lib.validate_precision(self.precision)

    def search_config(self) -> SearchConfig:
        return SearchConfig(
            k=self.k,
            beam=max(self.beam, self.k),
            n_seeds=self.n_seeds,
            hash_slots=self.hash_slots,
            max_iters=self.max_iters,
            metric=self.metric,
            use_lgd_mask=self.lgd,
            dispatch=self.dispatch,
            precision=self.precision,
            rerank_factor=self.rerank_factor,
            seed_mode=self.seed_mode,
            coarse_top=self.coarse_top,
        )


class BuildStats(NamedTuple):
    """Device-side build counters — the carry of the fused wave loop.

    All leaves live on device; the build loop folds each wave's contribution
    in *inside* the jitted step, so reading a field (``float()`` / ``int()``)
    is the only host sync and happens once, after the loop.
    ``n_comps``/``n_inserted_edges`` are exact 64-bit ``Counter64`` pairs
    (two int32 words with explicit carry) — float32 accumulation was only
    exact to 2^24, far below production comparison counts.
    """

    n_comps: Counter64  # total distance computations (Eq. 2 numerator)
    n_waves: Array  # () int32
    n_inserted_edges: Counter64


def zero_stats(n_comps: float = 0.0) -> BuildStats:
    """Fresh stats carry (optionally pre-charged with seed-graph comps)."""
    return BuildStats(
        n_comps=Counter64.of(n_comps),
        n_waves=jnp.zeros((), jnp.int32),
        n_inserted_edges=Counter64.zero(),
    )


def scanning_rate(stats: BuildStats, n: int) -> float:
    """Eq. 2: c = C / (n (n-1) / 2)."""
    return float(stats.n_comps) / (n * (n - 1) / 2.0)


# ---------------------------------------------------------------------------
# Wave commit
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def commit_wave(
    g: KNNGraph,
    x: Array,
    q_start: Array,  # () int32 — wave rows are [q_start, q_start + W)
    n_real: Array,  # () int32 — how many of the W are real (tail padding)
    res: search_lib.SearchResult,
    cfg: BuildConfig,
) -> tuple[KNNGraph, Array]:
    """Apply one wave's results to the graph. Returns (graph, edges_inserted)."""
    W = res.ids.shape[0]
    cap, k = g.nbr_ids.shape
    lanes = jnp.arange(W, dtype=jnp.int32)
    q_ids = q_start + lanes
    q_mask = lanes < n_real
    xq = x[jnp.minimum(q_ids, cap - 1)]
    # wave-row ‖x‖² and int8 scales: computed ONCE here, the norms reused by
    # the intra-wave tile, and both written into their graph-resident caches
    # at commit (step 4) — the caches' incremental maintenance point for
    # insertions (sq_norms and row_scale share owners everywhere)
    xq_sq = squared_norms(xq)
    xq_sc = row_scales(xq)

    # ---- 1. new-row lists: search results ‖ intra-wave candidates ----------
    new_ids, new_dist = res.ids, res.dists
    if cfg.intra_wave and W > 1:
        tile = ops.pairwise_distance(
            xq, xq, cfg.metric, dispatch=cfg.dispatch,
            x_sq_norms=xq_sq if cfg.metric == "l2" else None,
        )
        off = ~(q_mask[None, :] & q_mask[:, None]) | jnp.eye(W, dtype=bool)
        tile = jnp.where(off, jnp.inf, tile)
        wave_ids = jnp.broadcast_to(q_ids[None, :], (W, W))
        cat_d = jnp.concatenate([new_dist, tile], axis=1)
        cat_i = jnp.concatenate([new_ids, wave_ids], axis=1)
        new_dist, new_ids = ops.topk_smallest(cat_d, cat_i, k)
    new_ids = jnp.where(jnp.isfinite(new_dist), new_ids, -1)
    new_dist = jnp.where(new_ids >= 0, new_dist, jnp.inf)

    # ---- 2. candidate edges into existing rows ------------------------------
    ins_cap = cfg.ins_cap_per_q or 3 * k
    v_all = res.vis_ids  # (W, H)
    d_all = res.vis_dist
    kth = g.nbr_dist[jnp.maximum(v_all, 0), k - 1]
    qual = (v_all >= 0) & q_mask[:, None] & (d_all < kth)
    # keep each query's best ins_cap target rows
    keyed = jnp.where(qual, d_all, jnp.inf)
    order = jnp.argsort(keyed, axis=1)[:, :ins_cap]
    v_kept = jnp.take_along_axis(jnp.where(qual, v_all, -1), order, axis=1)
    d_kept = jnp.take_along_axis(keyed, order, axis=1)
    v_flat = v_kept.reshape(-1)
    d_flat = d_kept.reshape(-1)
    q_flat = jnp.broadcast_to(q_ids[:, None], (W, ins_cap)).reshape(-1)
    lane_flat = jnp.broadcast_to(lanes[:, None], (W, ins_cap)).reshape(-1)

    mres = merge.merge_candidates(
        g.nbr_ids, g.nbr_dist, g.nbr_lam, v_flat, q_flat, d_flat
    )
    m_ids, m_dist, m_lam = mres.nbr_ids, mres.nbr_dist, mres.nbr_lam

    # ---- 3. LGD occlusion-factor rules (Alg. 3 / updateG) -------------------
    if cfg.lgd:
        T = v_flat.shape[0]
        probes = 8
        safe_v = jnp.minimum(jnp.maximum(v_flat, 0), cap - 1)
        row_ids = m_ids[safe_v]  # (T, k) merged list of the target row
        at_q = row_ids == q_flat[:, None]
        inserted = jnp.any(at_q, axis=1) & (v_flat >= 0)
        j_star = jnp.argmax(at_q, axis=1)  # slot of q in the merged row
        # D(q, member_j): wave-wave pairs from the intra tile, others from the
        # visited hash (∞ when the search never compared them — Rule 1).
        is_wave = (row_ids >= q_start) & (row_ids < q_start + W)
        with jax.named_scope("d_lookup"):
            # a lane's candidate rows are contiguous in t (lane_flat), so
            # the (T, k) ids are (W, T // W * k), one row per lane's table
            D_hash = ops.visited_lookup(
                res.vis_ids, res.vis_dist, row_ids.reshape(W, -1),
                probes, dispatch=cfg.dispatch,
            ).reshape(T, k)
        if cfg.intra_wave and W > 1:
            w_idx = jnp.clip(row_ids - q_start, 0, W - 1)
            D_wave = tile[lane_flat[:, None], w_idx]
            D = jnp.where(is_wave, D_wave, D_hash)
        else:
            D = jnp.where(is_wave, jnp.inf, D_hash)
        occludes = (D < d_flat[:, None]) & (row_ids >= 0) & inserted[:, None]
        slots_k = jnp.arange(k, dtype=jnp.int32)[None, :]
        before = slots_k < j_star[:, None]
        after = slots_k > j_star[:, None]
        # Rule 2: λ(q) = #{j ranked before q : D(q, x_j) < m(q, v)}
        lam_q = jnp.sum(occludes & before, axis=1).astype(jnp.int32)
        m_lam = m_lam.at[
            jnp.where(inserted, safe_v, cap), jnp.where(inserted, j_star, 0)
        ].add(jnp.where(inserted, lam_q, 0), mode="drop")
        # Rule 3: λ(x_j) += 1 for j ranked after q with D(q, x_j) < m(q, v)
        add3 = (occludes & after).astype(jnp.int32)  # (T, k)
        m_lam = m_lam.at[jnp.where(inserted, safe_v, cap)[:, None], slots_k].add(
            jnp.where(inserted[:, None], add3, 0), mode="drop"
        )
    else:
        inserted = jnp.any(m_ids[jnp.minimum(jnp.maximum(v_flat, 0), cap - 1)] == q_flat[:, None], axis=1) & (
            v_flat >= 0
        )

    # ---- 4. write back: existing-row merges + new rows ----------------------
    # padding lanes scatter to the drop sentinel: clamping them to cap-1
    # would collide with the real last row when capacity == n and the final
    # wave is partial (duplicate-index scatters resolve in undefined order)
    drop_q = jnp.where(q_mask, jnp.minimum(q_ids, cap - 1), cap)
    nbr_ids = m_ids.at[drop_q].set(new_ids, mode="drop")
    nbr_dist = m_dist.at[drop_q].set(new_dist, mode="drop")
    # λ init 0 on join (Alg. 3)
    nbr_lam = m_lam.at[drop_q].set(jnp.zeros_like(new_ids), mode="drop")
    # norm- and scale-cache maintenance (shared owners, side by side)
    sq_norms = g.sq_norms.at[drop_q].set(xq_sq, mode="drop")
    row_scale = g.row_scale.at[drop_q].set(xq_sc, mode="drop")

    # ---- 5. reverse-list appends --------------------------------------------
    # (a) new rows list their members; (b) inserted queries join target rows.
    # rev_lam snapshots the forward twin's λ at append time: 0 for (a) — new
    # rows join with λ = 0 (Alg. 3) — and the Rule-2 λ(q) for (b).
    own_a = jnp.broadcast_to(q_ids[:, None], (W, k)).reshape(-1)
    mem_a = jnp.where(q_mask[:, None], new_ids, -1).reshape(-1)
    own_b = jnp.where(inserted, v_flat, -1)
    mem_b = jnp.where(inserted, q_flat, -1)
    owners = jnp.concatenate([own_a, own_b])
    members = jnp.concatenate([mem_a, mem_b])
    lam_b = jnp.where(inserted, lam_q, 0) if cfg.lgd else jnp.zeros_like(own_b)
    lams = jnp.concatenate([jnp.zeros_like(own_a), lam_b])
    rev_ids, rev_lam, rev_ptr = merge.append_reverse(
        g.rev_ids, g.rev_lam, g.rev_ptr, owners, members, lams
    )

    alive = g.alive.at[drop_q].set(True, mode="drop")
    n_valid = jnp.minimum(g.n_valid + n_real, cap).astype(jnp.int32)
    g2 = KNNGraph(
        nbr_ids=nbr_ids,
        nbr_dist=nbr_dist,
        nbr_lam=nbr_lam,
        rev_ids=rev_ids,
        rev_lam=rev_lam,
        rev_ptr=rev_ptr,
        alive=alive,
        n_valid=n_valid,
        sq_norms=sq_norms,
        row_scale=row_scale,
    )
    return g2, mres.n_inserted


# ---------------------------------------------------------------------------
# Fused wave step + driver
# ---------------------------------------------------------------------------


def wave_core(
    g: KNNGraph,
    x: Array,
    pos: Array,  # () int32 — wave rows are [pos, pos + W)
    key: Array,
    stats: BuildStats,
    cfg: BuildConfig,
    *,
    n_real: Optional[Array] = None,
    coarse=None,
    enc=None,
):
    """Traceable fused search+commit: one wave of W insertions, no host sync.

    This is the single implementation behind the jitted ``wave_step`` (local
    builds) and the shard-local step of ``core.distributed`` — both paths run
    the identical wave semantics.  ``n_real`` defaults to the in-range tail
    ``min(W, n - pos)``; distributed callers pass their shard-local count.

    ``coarse`` (a ``core.hierarchy.CoarseLevel``) makes the wave's insertion
    searches seed coarsely AND assigns each committed row to its winning
    landmark cell for free (``SearchResult.seed_cell``).  With a coarse
    level the return is the 3-tuple ``(graph, stats, coarse)``; without one
    it stays ``(graph, stats)`` — ``cfg.seed_mode="coarse"`` falls back to
    random seeding for this wave (the distributed shard step runs that way).

    ``enc`` is the compressed companion table of ``x`` when
    ``cfg.precision != "fp32"`` — ``build`` encodes the full dataset once
    up front and threads it through every wave (rows not yet inserted are
    never candidates, so the eager whole-dataset encode is exact); passing
    None makes the search re-derive it per wave, which is correct but
    wasteful.
    """
    W = cfg.wave
    n = x.shape[0]
    pos = pos.astype(jnp.int32)
    if n_real is None:
        n_real = jnp.minimum(W, n - pos).astype(jnp.int32)
    # device scopes: the benchmark reads each wave's device time by these
    # names (the ops' HLO op_name), so they stay as they are
    with jax.named_scope("wave_search"):
        q_ids = jnp.minimum(pos + jnp.arange(W, dtype=jnp.int32), n - 1)
        q = x[q_ids]
        scfg = cfg.search_config()
        if coarse is None and scfg.seed_mode == "coarse":
            scfg = dataclasses.replace(scfg, seed_mode="random")
        res = search_lib.search(g, x, q, key, scfg, coarse=coarse, enc=enc)
        res = res._replace(
            n_comps=jnp.where(jnp.arange(W) < n_real, res.n_comps, 0)
        )
    with jax.named_scope("wave_commit"):
        g2, edges = commit_wave(g, x, pos, n_real, res, cfg)
        comps = jnp.sum(res.n_comps)  # int32; bounded by W*C*max_iters << 2^31
        if cfg.intra_wave and W > 1:
            nr = n_real.astype(jnp.int32)
            comps = comps + nr * (nr - 1) // 2
        stats2 = BuildStats(
            n_comps=stats.n_comps.add(comps),
            n_waves=stats.n_waves + 1,
            n_inserted_edges=stats.n_inserted_edges.add(edges),
        )
        if coarse is None:
            return g2, stats2
        from repro.core import hierarchy  # late: hierarchy imports construct

        lanes = jnp.arange(W, dtype=jnp.int32)
        rows = jnp.where(lanes < n_real, pos + lanes, -1)
        coarse2 = hierarchy.note_inserted(coarse, rows, res.seed_cell)
    return g2, stats2, coarse2


# The production wave step: one compiled call per wave with the graph and the
# stats carry donated (TPU/GPU update the ~O(cap*k) graph buffers in place;
# CPU skips donation — see compat.donating_jit).
wave_step = compat.donating_jit(
    wave_core, static_argnames=("cfg",), donate_argnums=(0, 4)
)


def build(
    x: Array,
    cfg: BuildConfig,
    key: Optional[Array] = None,
    *,
    wave_callback: Optional[Callable[[int, KNNGraph], None]] = None,
    callback_stride: int = 1,
    initial: Optional[tuple[KNNGraph, int]] = None,
    coarse=None,
    return_coarse: bool = False,
    tracker=None,
):
    """Build the k-NN graph over x with OLG (cfg.lgd=False) or LGD (True).

    The loop is host-round-trip free: each iteration is one fused jitted
    ``wave_step`` (search + commit + stats fold) and the Python side only
    advances an integer cursor.  The only host syncs are the optional
    ``wave_callback`` (every ``callback_stride`` waves) and whatever the
    caller reads from the returned device-side ``BuildStats``.

    ``tracker`` (an ``obs.Tracker``) makes the stride boundary a telemetry
    point as well: each ``callback_stride``-wave block runs under a
    ``build/stride`` span synced on the committed graph, and the cumulative
    build counters (comps, edges, partial scanning rate) are logged there —
    the ONLY host syncs telemetry introduces, and only at boundaries that
    are already sync points when a callback is in use.  ``tracker=None``
    (the default) keeps the loop bitwise and sync-wise identical to before.

    Args:
      x: (n, d) dataset.
      cfg: build configuration.
      key: PRNG key (entry-point sampling).
      wave_callback: called as f(wave_index, graph) every ``callback_stride``
        committed waves — checkpoint / progress hook (fault tolerance:
        construction resumes from any wave boundary, see train.checkpoint).
        Touching the graph inside the callback synchronizes the device.
        On TPU/GPU the graph's buffers are donated to the NEXT wave step:
        read/serialize it inside the callback, but copy it
        (``jax.device_get`` / ``jnp.copy``) before retaining it.
      callback_stride: waves between callback invocations (>= 1).
      initial: optional (graph, next_row) to resume from a checkpoint.
      coarse: optional ``core.hierarchy.CoarseLevel``.  With
        ``cfg.seed_mode == "coarse"`` and no level given, a fresh one is
        bootstrapped before the wave loop: over the full x (comps charged to
        the scanning rate) for a from-scratch build, or derived from the
        resumed graph (maintenance, uncharged) when ``initial`` is set.
      return_coarse: also return the (maintained) coarse level.

    Returns: (graph, stats) — stats leaves are device scalars — plus the
    coarse level when ``return_coarse``.
    """
    n = x.shape[0]
    if key is None:
        key = jax.random.PRNGKey(0)
    if callback_stride < 1:
        raise ValueError(f"callback_stride must be >= 1, got {callback_stride}")
    # one whole-dataset encode feeds every wave's insertion searches — rows
    # not yet inserted are masked out of candidate sets, so this is exact
    enc = (
        precision_lib.encode_dataset(x, cfg.precision)
        if cfg.precision != "fp32"
        else None
    )

    from repro.core import hierarchy  # late: hierarchy imports construct
    from repro.obs import NOOP  # late: keep core importable without obs init

    trk = tracker if tracker is not None else NOOP
    if initial is not None:
        g, start = initial
        if compat.donation_enabled():
            # wave_step donates its graph argument; copy so the caller's
            # graph (e.g. dynamic.insert's input index) survives the build
            g = jax.tree.map(jnp.copy, g)
        if coarse is None and cfg.seed_mode == "coarse" and int(start) > 0:
            key, ck = jax.random.split(key)
            coarse = hierarchy.derive_coarse(g, x, cfg, ck)
        pre_charge = 0
    else:
        with trk.span("build/seed"):
            n_seed = min(cfg.n_seed_init, n)
            g = brute.exact_seed_graph(
                x, n_seed, cfg.k, cfg.metric, rev_capacity=cfg.rev_cap,
                dispatch=cfg.dispatch,
            )
            start = n_seed
            # seed-graph comparisons count toward the scanning rate
            pre_charge = n_seed * (n_seed - 1) // 2
            if coarse is None and cfg.seed_mode == "coarse":
                key, ck = jax.random.split(key)
                coarse, coarse_comps = hierarchy.build_coarse(
                    x, cfg, ck, assign_rows=jnp.arange(n_seed, dtype=jnp.int32)
                )
                pre_charge += coarse_comps
    stats = zero_stats(pre_charge)
    W = cfg.wave
    pos = int(start)
    n_waves = 0
    while pos < n:
        # one stride block = one span; under NoopTracker span() and sync()
        # are free passthroughs, so the untracked loop shape is unchanged
        with trk.span("build/stride") as sp:
            stride_end = n_waves + callback_stride
            while pos < n and n_waves < stride_end:
                # the wave's dispatch only: no sync, the device ops that
                # follow it carry the device time
                with trk.span("build/wave"):
                    key, sk = jax.random.split(key)
                    if coarse is None:
                        g, stats = wave_step(
                            g, x, jnp.asarray(pos, jnp.int32), sk, stats, cfg,
                            enc=enc,
                        )
                    else:
                        g, stats, coarse = wave_step(
                            g, x, jnp.asarray(pos, jnp.int32), sk, stats, cfg,
                            coarse=coarse, enc=enc,
                        )
                pos += min(W, n - pos)
                n_waves += 1
            sp.sync(g.nbr_ids)
        if wave_callback is not None and n_waves % callback_stride == 0:
            wave_callback(n_waves, g)
        if tracker is not None:
            # int()/float() on Counter64 is the host sync — stride-boundary
            # only, per the sync-boundary-only capture policy
            comps = int(stats.n_comps)
            trk.log_metrics(
                {
                    "build/rows_inserted": pos,
                    "build/n_comps": comps,
                    "build/n_inserted_edges": int(stats.n_inserted_edges),
                    "build/scanning_rate_partial": (
                        comps / (n * (n - 1) / 2.0) if n > 1 else 0.0
                    ),
                },
                step=n_waves,
            )

    if return_coarse:
        return g, stats, coarse
    return g, stats


# ---------------------------------------------------------------------------
# Divide-and-conquer construction: parallel sub-builds + symmetric merge
# ---------------------------------------------------------------------------


def partition_bounds(n: int, shards: int):
    """Contiguous partition boundaries (shards + 1 ints, balanced ±1 row).

    Matches the sharded router's split, so a catalog partitioned here and one
    partitioned by ``ShardedIndex.build`` agree row for row.
    """
    import numpy as np

    if not 1 <= shards <= n:
        raise ValueError(f"need 1 <= shards <= n, got {shards} for n={n}")
    return np.linspace(0, n, shards + 1).astype(int)


def build_parallel(
    x: Array,
    cfg: BuildConfig,
    key: Optional[Array] = None,
    *,
    shards: int = 2,
    refine_rounds: int = 1,
    search_chunk: int = 512,
    mesh=None,
    return_coarse: bool = False,
    sub_cfg: Optional[BuildConfig] = None,
    merge_scfg=None,
):
    """Divide-and-conquer build: S concurrent sub-builds + symmetric merges.

    The sequential online build caps construction throughput at one wave
    pipeline.  This path partitions ``x`` into ``shards`` contiguous blocks,
    builds an independent sub-graph per block through the SAME fused
    ``wave_core`` pipeline (host threads on CPU — each shard's compiled wave
    steps overlap; a ``mesh`` routes the sub-builds through
    ``core.distributed``'s shard_map step on multi-device), then folds the
    sub-graphs together with a balanced ``merge.merge_subgraphs`` tree of
    ``symmetric_merge`` calls and closes the residual recall gap with a
    bounded NN-Descent sweep (``nndescent.refine``).

    The merged graph lives in the same id space as a sequential build over
    ``x`` (global ids = row indices), and the result supports every online
    operation — ``dynamic.insert``/``remove`` ride on it unchanged.

    Args:
      x: (n, d) dataset.
      cfg: build configuration (shared by every sub-build and the merge
        searches).
      key: PRNG key; sub-build s folds in s, merges fold in their step.
      shards: number of partitions (1 degenerates to ``build``).
      refine_rounds: NN-Descent join rounds after the final merge (0 = none).
      search_chunk: cross-search batch size inside ``symmetric_merge``.
      mesh: optional device mesh — sub-builds run via
        ``distributed.build_subgraphs`` (requires n % n_devices == 0 and
        ``shards`` equal to the mesh's device count), and the merge-tree
        levels run mesh-resident under shard_map where pair shapes allow.
      return_coarse: append the merged graph's ``CoarseLevel`` to the
        return — the same contract as ``build``: the merge fold's root
        level when the tree produced one, a fresh ``derive_coarse``
        otherwise (always a level under ``seed_mode="coarse"``, else None).
      sub_cfg: optional distinct build configuration for the per-shard
        sub-builds.  The merge's cross-searches + second-hop proposals
        repair boundary and interior alike, so sub-builds can afford a
        lighter effort (smaller ``beam``/``hash_slots``) than a standalone
        build at the same quality target — the wallclock lever behind the
        ``parallel_gate`` CI record.  Defaults to ``cfg``.
      merge_scfg: optional ``SearchConfig`` for the merge-tree cross
        searches.  Merge hits only seed the candidate commit (the hop
        proposals widen them k_t-fold), so a shallow search — low
        ``max_iters``, ``beam == k`` — loses little recall; coarse-seeded
        entry points (``seed_mode="coarse"``) keep the shallow walks on
        target.  Defaults to ``cfg.search_config()``.

    Returns: (graph, stats) — stats aggregate sub-builds, merge candidate
    distances, and refinement comps (host-side fold, exact) — plus the
    coarse level when ``return_coarse``.
    """
    n = x.shape[0]
    if key is None:
        key = jax.random.PRNGKey(0)
    if shards == 1 and mesh is None:
        return build(x, cfg, key, return_coarse=return_coarse)
    bounds = partition_bounds(n, shards)
    sub = sub_cfg if sub_cfg is not None else cfg

    if mesh is not None:
        from repro.core import distributed  # late: distributed imports construct

        n_dev = int(mesh.devices.size)
        if shards != n_dev:  # validate BEFORE the expensive sub-builds
            raise ValueError(
                f"mesh has {n_dev} devices, build_parallel got "
                f"shards={shards} — on a mesh, one sub-graph per device"
            )
        graphs, coarses, sub_comps, sub_waves, sub_edges = (
            distributed.build_subgraphs(mesh, x, sub, key)
        )
    else:
        import concurrent.futures

        def _one(s: int):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            return build(
                x[lo:hi], sub, jax.random.fold_in(key, s), return_coarse=True
            )

        with concurrent.futures.ThreadPoolExecutor(max_workers=shards) as ex:
            results = list(ex.map(_one, range(shards)))
        graphs = [g for g, _, _ in results]
        # leaf coarse levels (shard-LOCAL ids) seed the level-0 merge
        # cross-searches; None everywhere under random seeding
        coarses = [c for _, _, c in results]
        sub_comps = sum(int(st.n_comps) for _, st, _ in results)
        sub_waves = sum(int(st.n_waves) for _, st, _ in results)
        sub_edges = sum(int(st.n_inserted_edges) for _, st, _ in results)

    from repro.core import nndescent  # late: nndescent is a leaf consumer

    scfg = merge_scfg if merge_scfg is not None else cfg.search_config()
    g, merge_comps, coarse = merge.merge_subgraphs(
        graphs, x, scfg, jax.random.fold_in(key, 1_000_000),
        search_chunk=search_chunk, coarses=coarses, mesh=mesh,
    )

    g, refine_comps = nndescent.refine(
        g, x, cfg.metric, rounds=refine_rounds, dispatch=cfg.dispatch
    )

    stats = BuildStats(
        n_comps=Counter64.of(sub_comps + merge_comps + refine_comps),
        n_waves=jnp.asarray(sub_waves, jnp.int32),
        n_inserted_edges=Counter64.of(sub_edges),
    )
    if not return_coarse:
        return g, stats
    if coarse is None and cfg.seed_mode == "coarse":
        # no folded level survived the tree (e.g. a seed-mode mismatch on
        # one shard) — re-derive on the merged graph, maintenance-style
        from repro.core import hierarchy  # late: hierarchy imports construct

        coarse = hierarchy.derive_coarse(
            g, x, cfg, jax.random.fold_in(key, 2_000_000)
        )
    return g, stats, coarse
