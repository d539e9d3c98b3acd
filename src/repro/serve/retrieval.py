"""ANN retrieval serving: the paper's LGD graph as a production index.

This is the paper's own deployment story (§IV-C e-shopping scenario) wired
to the MIND recsys arch (DESIGN.md §5): candidate item embeddings are
indexed once with online LGD construction; at serve time each user's
interest vectors (from MIND's capsule encoder) query the graph with
EHC search under the inner-product metric; results from the K interests are
deduped and re-ranked.

Because construction is online, catalog churn (new items listed, stale items
withdrawn) maps to ``core.dynamic.insert``/``remove`` — no index rebuilds,
which is precisely the capability the paper contributes over offline
builders (NN-Descent / DPG / HNSW).

The index object here is ``repro.index.OnlineIndex`` — the lifecycle facade
that owns capacity (auto-growth instead of the old hard assert), recycles
removed rows (free-slot ledger + compaction), coalesces small inserts, and
snapshots to disk.  ``RetrievalIndex`` remains as an alias for existing
callers.  The entry points below keep their functional contract: they
``clone()`` (O(fields); jax buffers are immutable) and mutate the copy.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import brute, construct, segments
from repro.index.lifecycle import OnlineIndex

Array = jax.Array

# legacy name — the serving index IS the lifecycle facade now
RetrievalIndex = OnlineIndex

#: metrics where the underlying "distance" is a negated similarity, so the
#: serving score flips sign to restore "higher = better"
SIMILARITY_METRICS = ("ip", "cosine")


def score_from_dist(dist: Array, metric: str) -> Array:
    """Serving score convention, one place for every metric.

    Similarity metrics (inner product, cosine) surface scores where higher =
    better; true distance metrics (l2, l1, chi2) surface the distance itself
    (lower = better).  The helper is an involution — applying it to a score
    returns the distance — which is what the sharded router relies on to
    merge per-shard results in a convention-free way.
    """
    return -dist if metric in SIMILARITY_METRICS else dist


def build_index(
    items: Array,
    *,
    k: int = 20,
    metric: str = "ip",
    wave: int = 512,
    capacity: Optional[int] = None,
    key: Optional[Array] = None,
    beam: int = 40,
    use_pallas: Optional[bool] = None,
    dispatch: Optional[str] = None,
    precision: str = "fp32",
) -> OnlineIndex:
    """Index a candidate bank with online LGD construction.

    ``dispatch`` follows the four-way enum of ``SearchConfig`` (the default
    ``"auto"`` rides the fused Pallas expansion kernel on TPU and the
    pure-JAX reference elsewhere); ``use_pallas`` is the deprecated
    tri-state spelling.  ``precision`` selects the distance-engine
    representation (``"fp32"|"bf16"|"int8"|"pq"``).  All three are stored in
    ``build_cfg`` so serving (``retrieve``) and catalog churn
    (``add_items``, via ``dynamic.insert``) run the same path as the build.
    """
    cfg = construct.BuildConfig(
        k=k, metric=metric, wave=wave, lgd=True, beam=beam,
        use_pallas=use_pallas, dispatch=dispatch, precision=precision,
    )
    return OnlineIndex.build(items, cfg, capacity=capacity, key=key)


def retrieve(
    index: OnlineIndex,
    interests: Array,  # (K, d) query vectors (MIND interests, or any queries)
    top_k: int,
    *,
    beam: Optional[int] = None,
    key: Optional[Array] = None,
    with_stats: bool = False,
):
    """k-NN retrieval: EHC search per interest + cross-interest dedupe/merge.

    Returns (item_ids (top_k,), scores (top_k,)) — scores follow
    ``score_from_dist``: higher = better for similarity metrics (ip,
    cosine), plain distances (lower = better) otherwise.

    ``with_stats=True`` appends the raw per-interest ``SearchResult`` as a
    third element.  The search computes ``n_comps``/``hash_full``/``n_iters``
    exactly for every query anyway; the default 2-tuple used to be the only
    surface, silently discarding them — serving telemetry (``obs.SearchStats``
    saturation/scanning-rate accounting) folds this object at its own sync
    boundary, so requesting it adds no host sync here.
    """
    # one search dispatch for facade and serving: OnlineIndex.search flushes
    # buffered writes and serves on the build's kernel path / LGD setting
    res = index.search(interests, top_k, beam=beam, key=key)
    ids = res.ids.reshape(-1)
    dist = res.dists.reshape(-1)
    # cross-interest dedupe: keep the best (smallest-distance) copy —
    # sort-based segmented idiom (core.segments), not a pairwise matrix
    order = jnp.argsort(dist)
    ids_s = ids[order]
    dup = segments.mask_row_duplicates(ids_s[None, :])[0]
    dist_s = jnp.where(dup | (ids_s < 0), jnp.inf, dist[order])
    sel = jnp.argsort(dist_s)[:top_k]
    out_ids = ids_s[sel]
    scores = score_from_dist(dist_s[sel], index.metric)
    if with_stats:
        return out_ids, scores, res
    return out_ids, scores


def retrieve_brute(index: OnlineIndex, interests: Array, top_k: int):
    """Exact baseline (the retrieval_cand roofline cell): full GEMM + top-k.

    Honors catalog churn exactly: buffered adds are flushed and removed rows
    are masked out via ``KNNGraph.alive``, so this stays the oracle for the
    graph path on a churned index.
    """
    index.flush()
    ids, dist = brute.brute_force_knn(
        index.items, interests, top_k, index.metric,
        n_valid=index.graph.n_valid, alive=index.graph.alive,
        dispatch="reference",
    )
    flat_i = ids.reshape(-1)
    flat_d = dist.reshape(-1)
    order = jnp.argsort(flat_d)
    ids_s = flat_i[order]
    dup = segments.mask_row_duplicates(ids_s[None, :])[0]
    d_s = jnp.where(dup | (ids_s < 0), jnp.inf, flat_d[order])
    sel = jnp.argsort(d_s)[:top_k]
    return ids_s[sel], score_from_dist(d_s[sel], index.metric)


def add_items(index: OnlineIndex, new_items: Array, key=None) -> OnlineIndex:
    """Catalog insert: append rows + online insertion waves (§IV-C).

    Functional: returns a new index, the argument is untouched.  Capacity is
    managed by the lifecycle layer — an over-capacity insert recycles free
    slots or grows the index (amortized doubling), it never raises.
    """
    return index.clone().add(new_items, key=key, flush=True)


def remove_items(index: OnlineIndex, ids: Array) -> OnlineIndex:
    """Catalog withdraw: the paper's O(k²/2) removal with λ repair.

    Functional, like ``add_items``; the victims enter the returned index's
    free-slot ledger for later recycling.
    """
    return index.clone().remove(ids)
