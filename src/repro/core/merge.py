"""Batched ``insertG``: merge a flat stream of candidate edges into k-NN lists.

This is the vectorized form of the paper's ``insertG(a, b, m(a,b), 𝒢)``.  On
a CPU each call surgically splices one node into one sorted linked list; on a
TPU we instead collect *all* candidate edges produced by a wave (OLG/LGD
construction), a local-join round (NN-Descent) or a refinement pass into flat
``(row, id, dist)`` triples and commit them in one shot:

  qualify -> dedupe -> segment-rank -> scatter to per-row buffers -> row merge

The merge is exact with respect to the final top-k content: any candidate
that sequential insertion would have kept is kept (rank-<k filtering per row
is lossless because at most k candidates can enter a k-list).  What differs
from sequential semantics is only *when* displaced entries disappear — the
same batching trade NN-Descent makes (DESIGN.md §8.1).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import segments

Array = jax.Array

# Second-hop expansion width for merge proposals: only the nearest HOP_TOP
# cross-search hits donate their neighbor lists.  Proposal volume (and the
# two full lexsorts inside ``merge_candidates``) scales linearly with this;
# the recall contribution concentrates in the first few hits' neighborhoods,
# so a small cap keeps the k² candidate quality at a fraction of the cost.
HOP_TOP = 20


class MergeResult(NamedTuple):
    nbr_ids: Array  # (cap, k) int32  merged lists
    nbr_dist: Array  # (cap, k) float32
    nbr_lam: Array  # (cap, k) int32 — carried for old entries, 0 for new
    is_new: Array  # (cap, k) bool — slot filled by this merge
    old_slot: Array  # (cap, k) int32 — original slot index if carried, -1 if new
    cand_ids: Array  # (cap, k) int32 — per-row qualified candidates (post rank-filter)
    cand_dist: Array  # (cap, k) float32
    n_inserted: Array  # () int32 — number of slots that changed


def merge_candidates(
    nbr_ids: Array,
    nbr_dist: Array,
    nbr_lam: Array,
    v: Array,
    q: Array,
    d: Array,
) -> MergeResult:
    """Commit candidate edges (v -> q with distance d) into the k-NN lists.

    Args:
      nbr_ids/nbr_dist/nbr_lam: (cap, k) graph rows (sorted ascending).
      v: (T,) int32 target rows; -1 (or any negative) = padding.
      q: (T,) int32 candidate neighbor ids.
      d: (T,) float32 distances m(v, q).

    Returns: MergeResult with merged rows and provenance masks.
    """
    cap, k = nbr_ids.shape
    v = v.astype(jnp.int32)
    q = q.astype(jnp.int32)
    d = d.astype(jnp.float32)

    # --- qualify -----------------------------------------------------------
    valid = (v >= 0) & (v < cap) & (q >= 0) & (q != v) & jnp.isfinite(d)
    vs = jnp.where(valid, v, cap)
    kth = jnp.where(valid, nbr_dist[jnp.minimum(vs, cap - 1), k - 1], -jnp.inf)
    valid &= d < kth
    # drop candidates already present in the row
    row_ids = nbr_ids[jnp.minimum(vs, cap - 1)]  # (T, k)
    present = jnp.any(row_ids == q[:, None], axis=1)
    valid &= ~present

    # --- dedupe exact (v, q) duplicates (NN-Descent emits them) ------------
    v1 = jnp.where(valid, v, cap)
    q1 = jnp.where(valid, q, cap)
    order1 = jnp.lexsort((q1, v1))
    sv1, sq1 = v1[order1], q1[order1]
    dup = jnp.concatenate(
        [jnp.zeros((1,), bool), (sv1[1:] == sv1[:-1]) & (sq1[1:] == sq1[:-1])]
    )
    dup_unsorted = jnp.zeros_like(dup).at[order1].set(dup)
    valid &= ~dup_unsorted

    # --- segment rank by (v, d), keep top-k per row -------------------------
    vv = jnp.where(valid, v, cap)
    order2 = jnp.lexsort((d, vv))
    sv = vv[order2]
    sq = q[order2]
    sd = d[order2]
    (cand_ids, cand_dist), _ = segments.grouped_top_r(
        sv, [sq, sd], [-1, jnp.inf], cap, k
    )

    # --- row-wise merge: top-k of (old ‖ candidates) ------------------------
    all_ids = jnp.concatenate([nbr_ids, cand_ids], axis=1)  # (cap, 2k)
    all_dist = jnp.concatenate([nbr_dist, cand_dist], axis=1)
    all_lam = jnp.concatenate([nbr_lam, jnp.zeros_like(nbr_lam)], axis=1)
    origin = jnp.broadcast_to(jnp.arange(2 * k, dtype=jnp.int32), (cap, 2 * k))
    # stable sort keeps old entries ahead of equal-distance candidates
    order = jnp.argsort(jnp.where(all_ids >= 0, all_dist, jnp.inf), axis=1, stable=True)
    take = lambda a: jnp.take_along_axis(a, order[:, :k], axis=1)
    m_ids = take(all_ids)
    m_dist = take(all_dist)
    m_lam = take(all_lam)
    m_origin = take(origin)
    is_new = (m_origin >= k) & (m_ids >= 0)
    old_slot = jnp.where(m_origin < k, m_origin, -1)
    m_lam = jnp.where(is_new, 0, m_lam)
    n_inserted = jnp.sum(is_new).astype(jnp.int32)
    return MergeResult(
        nbr_ids=m_ids,
        nbr_dist=m_dist,
        nbr_lam=m_lam,
        is_new=is_new,
        old_slot=old_slot,
        cand_ids=cand_ids,
        cand_dist=cand_dist,
        n_inserted=n_inserted,
    )


@jax.jit
def append_reverse(
    rev_ids: Array,
    rev_lam: Array,
    rev_ptr: Array,
    owner: Array,
    member: Array,
    lam: Array | None = None,
) -> tuple[Array, Array, Array]:
    """Batched FIFO ring-buffer append: owner joins rev list of member.

    Args:
      rev_ids: (cap, R) ring buffers.
      rev_lam: (cap, R) forward-twin λ snapshots aligned with rev_ids.
      rev_ptr: (cap,) total-appends counters.
      owner: (T,) int32 rows that now list ``member`` in their k-NN list.
      member: (T,) int32; negative = padding.
      lam: optional (T,) int32 λ of ``member`` inside G[owner] at append
        time (the rev_lam payload); defaults to 0 (fresh edges join with
        λ = 0 per Alg. 3).

    Returns updated (rev_ids, rev_lam, rev_ptr).  Jitted, like
    ``graph.rebuild_reverse``: eager callers (coarse-level assignment) would
    otherwise compile its scans op by op.
    """
    cap, R = rev_ids.shape
    if lam is None:
        lam = jnp.zeros_like(owner)
    valid = (member >= 0) & (member < cap) & (owner >= 0)
    m = jnp.where(valid, member, cap)
    order = jnp.argsort(m)
    sm = m[order]
    so = jnp.where(valid, owner, -1)[order]
    sl = jnp.where(valid, lam.astype(jnp.int32), 0)[order]
    rank = segments.segment_rank(sm)
    # If more than R appends hit one member in a single wave, keep the last R
    # (FIFO overwrite — matches ring semantics of sequential appends).
    counts = segments.segment_counts(sm, cap)
    cnt_e = jnp.where(sm < cap, counts[jnp.minimum(sm, cap - 1)], 0)
    # keep only the last R appends per member so ring slots are unique within
    # one batch (deterministic FIFO overwrite)
    ok = (sm < cap) & (rank >= cnt_e - R)
    base = rev_ptr[jnp.minimum(sm, cap - 1)]
    slot = (base + rank) % R
    row = jnp.where(ok, sm, cap)
    col = jnp.where(ok, slot, 0)
    ext = jnp.concatenate([rev_ids, jnp.full((1, R), -1, jnp.int32)], axis=0)
    ext = ext.at[row, col].set(jnp.where(ok, so, -1))
    ext_l = jnp.concatenate([rev_lam, jnp.zeros((1, R), jnp.int32)], axis=0)
    ext_l = ext_l.at[row, col].set(jnp.where(ok, sl, 0))
    rev_ids = ext[:cap]
    rev_lam = ext_l[:cap]
    rev_ptr = rev_ptr + counts
    return rev_ids, rev_lam, rev_ptr


# ---------------------------------------------------------------------------
# Symmetric sub-graph merge (divide-and-conquer construction)
# ---------------------------------------------------------------------------


def stack_subgraphs(g_a, g_b, n_a: int):
    """Concatenate two fully-allocated sub-graphs into one id space.

    ``g_a`` covers global rows [0, n_a) and ``g_b`` LOCAL rows [0, n_b) that
    become global rows [n_a, n_a + n_b).  Forward lists are remapped by
    offset; the reverse side is left empty (callers rebuild it canonically
    via ``graph.rebuild_reverse`` after cross edges land).  The norm cache is
    *gathered* (concatenated), never recomputed — the cache owners already
    paid for it.
    """
    n_b = g_b.capacity
    if int(g_a.n_valid) != g_a.capacity or int(g_b.n_valid) != n_b:
        raise ValueError(
            "stack_subgraphs needs fully-allocated sub-graphs "
            f"(n_valid == capacity); got {int(g_a.n_valid)}/{g_a.capacity} "
            f"and {int(g_b.n_valid)}/{n_b} — compact first"
        )
    return _stack_core(g_a, g_b)


def _stack_core(g_a, g_b):
    """Traceable body of ``stack_subgraphs`` (shapes carry the capacities,
    so the concatenation works identically under jit/shard_map — the host
    wrapper keeps the fully-allocated precondition check)."""
    from repro.core.graph import KNNGraph  # graph does not import merge

    n_a = g_a.nbr_ids.shape[0]
    n_b = g_b.nbr_ids.shape[0]
    b_ids = jnp.where(g_b.nbr_ids >= 0, g_b.nbr_ids + n_a, -1)
    R = max(g_a.rev_capacity, g_b.rev_capacity)
    cap = n_a + n_b
    return KNNGraph(
        nbr_ids=jnp.concatenate([g_a.nbr_ids, b_ids]),
        nbr_dist=jnp.concatenate([g_a.nbr_dist, g_b.nbr_dist]),
        nbr_lam=jnp.concatenate([g_a.nbr_lam, g_b.nbr_lam]),
        rev_ids=jnp.full((cap, R), -1, jnp.int32),
        rev_lam=jnp.zeros((cap, R), jnp.int32),
        rev_ptr=jnp.zeros((cap,), jnp.int32),
        alive=jnp.concatenate([g_a.alive, g_b.alive]),
        n_valid=jnp.asarray(cap, jnp.int32),
        sq_norms=jnp.concatenate([g_a.sq_norms, g_b.sq_norms]),
        row_scale=jnp.concatenate([g_a.row_scale, g_b.row_scale]),
    )


def _chunked_cross_search(g, xg, queries, key, scfg, chunk: int, coarse=None):
    """Search ``queries`` against sub-graph ``g`` in fixed-size chunks.

    Chunking bounds the (B, hash_slots) visited tables AND pins the jitted
    search (``core.search.search`` is already jit-compiled over static cfg)
    to one batch shape per merge — the last chunk is padded, not
    specialized.  Returns (ids (B, k) LOCAL, dists (B, k), n_comps int).
    Comps accumulate as a host int: per-chunk counts fit int32 comfortably
    (chunk * C * max_iters), but a whole production-scale merge does not —
    the same 2^31 wrap Counter64 exists to prevent in the wave pipeline.
    """
    from repro.core import search as search_lib  # search never imports merge

    import dataclasses

    if coarse is None and scfg.seed_mode == "coarse":
        # no level for this sub-graph's id space — fall back to random seeds
        scfg = dataclasses.replace(scfg, seed_mode="random")
    B = queries.shape[0]
    nchunks = -(-B // chunk)
    qp = jnp.pad(queries, ((0, nchunks * chunk - B), (0, 0)))
    ids, dists, comps = [], [], 0
    for i in range(nchunks):
        res = search_lib.search(
            g, xg, qp[i * chunk : (i + 1) * chunk],
            jax.random.fold_in(key, i), scfg, coarse=coarse,
        )
        ids.append(res.ids)
        dists.append(res.dists)
        comps += int(jnp.sum(res.n_comps))
    return jnp.concatenate(ids)[:B], jnp.concatenate(dists)[:B], comps


def merge_commit_core(
    g_a, g_b, xa, xb, ab_ids, ab_d, ba_ids, ba_d, metric, dispatch,
    hop_top=HOP_TOP,
):
    """Traceable merge commit: stack + proposals + candidate commit + reverse.

    The single implementation behind the host path (jitted as
    ``_merge_commit``) and the mesh fold (inlined into ``distributed
    .merge_pairs_mesh``'s shard_map body).  Cross-search hits come in as
    ``ab_ids``/``ab_d`` ((n_a, k), b-LOCAL ids: a's points vs g_b) and
    ``ba_ids``/``ba_d`` ((n_b, k), a's ids — already the global [0, n_a)
    space).  On top of the hits, each direction proposes the hits' own
    neighbor lists through ``ops.merge_proposals`` (second-hop candidates,
    distances via the one blocked engine), every pair goes in both
    directions, and ``merge_candidates`` re-selects the joint top-k.

    Returns (merged KNNGraph, hop-proposal comps () int32 — the cross-search
    comps are the caller's, hop distances are charged here).
    """
    from repro.core import graph as graph_lib
    from repro.kernels import ops

    n_a, n_b = xa.shape[0], xb.shape[0]
    stacked = _stack_core(g_a, g_b)

    # second-hop proposals: the hits' own neighbor lists, blocked engine
    ab_hop, ab_hop_d, c_ab = ops.merge_proposals(
        xa, xb, ab_ids, g_b.nbr_ids, g_b.alive, metric,
        dispatch=dispatch, sq_norms=g_b.sq_norms, hop_top=hop_top,
    )
    ba_hop, ba_hop_d, c_ba = ops.merge_proposals(
        xb, xa, ba_ids, g_a.nbr_ids, g_a.alive, metric,
        dispatch=dispatch, sq_norms=g_a.sq_norms, hop_top=hop_top,
    )

    # per-query pre-selection: of the h·k_t hop lanes only the best 2k can
    # matter (at most k enter the query's own list; the surplus k keeps the
    # reverse direction rich).  This caps the global candidate sort inside
    # ``merge_candidates`` — its two full lexsorts are the commit's dominant
    # cost — at O(n·k) instead of O(n·h·k_t).
    k = g_a.nbr_ids.shape[1]
    if ab_hop.shape[1] > 2 * k:
        ab_hop_d, ab_hop = ops.topk_smallest(ab_hop_d, ab_hop, 2 * k)
        ba_hop_d, ba_hop = ops.topk_smallest(ba_hop_d, ba_hop, 2 * k)

    # a dead row must not receive or donate edges (search already masks dead
    # *targets*; this masks dead *queries*)
    def rows_for(side_lo, live, like):
        r = jnp.arange(like.shape[0], dtype=jnp.int32) + side_lo
        r = jnp.broadcast_to(r[:, None], like.shape)
        return jnp.where(live[:, None], r, -1)

    to_global_b = lambda ids: jnp.where(ids >= 0, ids + n_a, -1)
    # (query rows, candidate ids GLOBAL, distances) per proposal family
    families = (
        (rows_for(0, g_a.alive, ab_ids), to_global_b(ab_ids), ab_d),
        (rows_for(n_a, g_b.alive, ba_ids), ba_ids, ba_d),
        (rows_for(0, g_a.alive, ab_hop), to_global_b(ab_hop), ab_hop_d),
        (rows_for(n_a, g_b.alive, ba_hop), ba_hop, ba_hop_d),
    )
    # both directions for every pair: (row -> cand, d) and (cand -> row, d)
    v = jnp.concatenate(
        [r.reshape(-1) for r, _, _ in families]
        + [c.reshape(-1) for _, c, _ in families]
    )
    q = jnp.concatenate(
        [c.reshape(-1) for _, c, _ in families]
        + [r.reshape(-1) for r, _, _ in families]
    )
    d = jnp.concatenate([dd.reshape(-1) for _, _, dd in families] * 2)
    # a pair with either end masked is dropped entirely (q < 0 or v < 0)
    v = jnp.where((q >= 0) & (v >= 0), v, -1)

    mres = merge_candidates(
        stacked.nbr_ids, stacked.nbr_dist, stacked.nbr_lam, v, q, d
    )
    merged = stacked._replace(
        nbr_ids=mres.nbr_ids,
        nbr_dist=mres.nbr_dist,
        nbr_lam=mres.nbr_lam,
    )
    return graph_lib.rebuild_reverse(merged), c_ab + c_ba


_merge_commit = jax.jit(
    merge_commit_core, static_argnames=("metric", "dispatch", "hop_top")
)


def symmetric_merge(
    g_a,
    g_b,
    x: Array,
    scfg,
    key: Optional[Array] = None,
    *,
    search_chunk: int = 512,
    coarse_a=None,
    coarse_b=None,
):
    """Merge two independently built sub-graphs into one graph (1908.00814).

    ``g_a`` covers rows [0, n_a) of ``x`` (ids already global for the fold),
    ``g_b`` covers x[n_a:] in LOCAL ids.  The merge is symmetric: each side's
    points search the *other* side's graph (cross-graph candidate generation
    out of each side's lists, distances through the blocked engine the search
    already rides, norm caches gathered from the sub-graphs — never
    recomputed), every cross pair is proposed in both directions, and
    ``merge_candidates`` re-selects the joint top-k per row over
    (own list ‖ cross candidates).  Reverse lists and their ``rev_lam``
    snapshots are rebuilt canonically from the merged forward lists via the
    segmented-scan core (``graph.rebuild_reverse``).

    Dead rows neither search nor receive edges: a removed sample must not
    re-enter anyone's list through a merge.

    Args:
      g_a, g_b: fully-allocated sub-graphs (compact churned shards first).
      x: (n_a + n_b, d) combined data, sub-graph order.
      scfg: ``search.SearchConfig`` for the cross searches (k = graph degree).
      key: PRNG key for search entry points.
      search_chunk: cross-search batch size (bounds memory + compile count).
      coarse_a, coarse_b: optional ``core.hierarchy.CoarseLevel`` per side,
        in that side's LOCAL id space — the cross searches then seed
        coarsely (``scfg.seed_mode == "coarse"``); a side without a level
        falls back to random seeding.

    Returns:
      (merged KNNGraph, n_comps) — comps spent on cross candidate distances
      plus the second-hop proposal distances (``ops.merge_proposals``).
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    n_a = g_a.capacity
    n_b = g_b.capacity
    if x.shape[0] != n_a + n_b:
        raise ValueError(f"x has {x.shape[0]} rows, graphs cover {n_a + n_b}")
    if int(g_a.n_valid) != n_a or int(g_b.n_valid) != n_b:
        # host-cheap; checked BEFORE the expensive cross searches (the same
        # precondition aborts stack_subgraphs, but only after the searches)
        raise ValueError(
            "symmetric_merge needs fully-allocated sub-graphs "
            f"(n_valid == capacity); got {int(g_a.n_valid)}/{n_a} and "
            f"{int(g_b.n_valid)}/{n_b} — compact/trim first"
        )
    xa, xb = x[:n_a], x[n_a:]
    ka, kb = jax.random.split(key)

    # cross-graph candidates: each side's points walk the other side's graph
    ab_ids, ab_d, comps_a = _chunked_cross_search(
        g_b, xb, xa, ka, scfg, search_chunk, coarse=coarse_b
    )
    ba_ids, ba_d, comps_b = _chunked_cross_search(
        g_a, xa, xb, kb, scfg, search_chunk, coarse=coarse_a
    )

    # one jitted commit: stack + second-hop proposals + candidate merge +
    # reverse rebuild stay on-device (no per-pair eager dispatch)
    merged, hop_comps = _merge_commit(
        g_a, g_b, xa, xb, ab_ids, ab_d, ba_ids, ba_d,
        metric=scfg.metric, dispatch=scfg.dispatch,
    )
    return merged, comps_a + comps_b + int(hop_comps)


def _pairs_mesh_ready(pairs, mesh) -> bool:
    """A fold level can go mesh-resident iff every pair has identical leaf
    shapes (shard_map stacks them) and there are enough devices."""
    if mesh is None or len(pairs) > int(mesh.devices.size):
        return False

    def shape_sig(node):
        g = node[0]
        return (g.capacity, g.k, g.rev_capacity)

    a0 = shape_sig(pairs[0][0])
    b0 = shape_sig(pairs[0][1])
    return all(
        shape_sig(a) == a0 and shape_sig(b) == b0 for a, b in pairs
    )


def merge_subgraphs(
    graphs,
    x: Array,
    scfg,
    key: Optional[Array] = None,
    *,
    search_chunk: int = 512,
    coarses=None,
    mesh=None,
):
    """Fold S adjacent sub-graphs into one via a balanced pairwise merge tree.

    ``graphs[s]`` covers (in LOCAL ids) the s-th contiguous block of ``x``,
    block sizes given by each graph's capacity.  Adjacent pairs merge with
    ``symmetric_merge`` level by level — O(log S) cross-searches per point
    instead of the O(S) a left-to-right fold costs (shard 0's points would
    re-search every later shard) — and the merges within a level run on
    host threads, or mesh-resident under ``shard_map`` when ``mesh`` is
    given (``distributed.merge_pairs_mesh``, one pair per device; a level
    whose pair shapes disagree or outnumber the devices falls back to host
    threads).

    ``coarses`` (optional, aligned with ``graphs``, entries may be None)
    supplies each leaf's ``core.hierarchy.CoarseLevel`` for the level-0
    cross searches.  Each merged intermediate then gets a FOLDED level
    (``hierarchy.fold_coarse`` — the two sides' landmark graphs merged by
    this same ``symmetric_merge``, members remapped by the block offset), so
    every deeper fold level seeds coarsely too, and the root level rides
    out to the caller instead of being re-derived from scratch.

    Returns (merged KNNGraph over all of x, total cross-search + fold
    comps, root CoarseLevel or None).
    """
    import concurrent.futures

    from repro.core import hierarchy  # late: hierarchy imports merge

    if not graphs:
        raise ValueError("merge_subgraphs needs at least one sub-graph")
    if coarses is not None and len(coarses) != len(graphs):
        raise ValueError(
            f"coarses has {len(coarses)} entries for {len(graphs)} sub-graphs"
        )
    if sum(g.capacity for g in graphs) != x.shape[0]:
        raise ValueError(
            f"sub-graphs cover {sum(g.capacity for g in graphs)} rows, "
            f"x has {x.shape[0]}"
        )
    if key is None:
        key = jax.random.PRNGKey(0)
    # (graph, lo, hi, coarse): graph covers x[lo:hi] in slice-local ids.
    # Merging adjacent pairs keeps every node contiguous, so the final
    # graph's ids are exactly the row indices of x.
    nodes = []
    off = 0
    for s, g in enumerate(graphs):
        nodes.append((g, off, off + g.capacity, coarses[s] if coarses else None))
        off += g.capacity
    total_comps = 0
    level = 0
    while len(nodes) > 1:
        pairs = [
            (nodes[i], nodes[i + 1]) for i in range(0, len(nodes) - 1, 2)
        ]
        carry = [nodes[-1]] if len(nodes) % 2 else []
        pair_keys = [
            jax.random.fold_in(key, (level << 16) | i)
            for i in range(len(pairs))
        ]

        if _pairs_mesh_ready(pairs, mesh):
            from repro.core import distributed  # late: imports construct

            pair_coarses = [(a[3], b[3]) for a, b in pairs]
            if any(ca is None or cb is None for ca, cb in pair_coarses):
                pair_coarses = None
            merged_graphs, c = distributed.merge_pairs_mesh(
                [(a[0], b[0]) for a, b in pairs],
                [x[a[1] : b[2]] for a, b in pairs],
                scfg,
                pair_keys,
                coarses=pair_coarses,
            )
            merged = [
                (g, None) for g in merged_graphs
            ]
            total_comps += c
        else:

            def _merge_pair(item):
                i, ((ga, lo, mid, ca), (gb, mid2, hi, cb)) = item
                assert mid == mid2
                return symmetric_merge(
                    ga, gb, x[lo:hi], scfg, pair_keys[i],
                    search_chunk=search_chunk,
                    coarse_a=ca, coarse_b=cb,
                )

            with concurrent.futures.ThreadPoolExecutor(
                max_workers=len(pairs)
            ) as ex:
                merged = list(ex.map(_merge_pair, enumerate(pairs)))
            total_comps += sum(c for _, c in merged)

        # fold the coarse levels host-side (landmark graphs are tiny): the
        # merged intermediate seeds the NEXT level's cross searches coarsely
        out = []
        for i, ((ga, lo, mid, ca), (gb, _, hi, cb)) in enumerate(pairs):
            lvl, cc = hierarchy.fold_coarse(
                ca, cb, mid - lo, scfg, jax.random.fold_in(pair_keys[i], 7)
            )
            total_comps += cc
            out.append((merged[i][0], lo, hi, lvl))
        nodes = out + carry
        level += 1
    return nodes[0][0], total_comps, nodes[0][3]
