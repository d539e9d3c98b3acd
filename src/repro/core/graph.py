"""k-NN graph state: the TPU-native replacement of the paper's orthogonal list.

The paper keeps G (k-NN lists) and Ḡ (reverse lists) as one pointer-linked
"orthogonal list" (Fig. 2).  Linked lists do not exist on a TPU; the state
here is a pytree of dense, fixed-capacity arrays that supports the same four
operations the paper needs — expand(G[r]), expand(Ḡ[r]), insertG, removal —
as vectorized gathers/scatters:

* ``nbr_ids/nbr_dist``: (cap, k) k-NN lists, rows sorted ascending by
  distance, padded with (-1, +inf).  This *is* G.
* ``nbr_lam``: (cap, k) the LGD occlusion factor λ attached to each directed
  edge (Alg. 3).
* ``rev_ids/rev_ptr``: (cap, R) reverse lists as FIFO ring buffers. Ḡ[i] in
  the paper is unbounded; a production system cannot allocate unbounded
  per-row storage, so we bound it at R (default 2k) and overwrite oldest
  entries first (deviation §8.2 of DESIGN.md).  Stale entries (edges whose
  forward counterpart was displaced) are *kept*: they act as extra shortcut
  candidates during search, never as correctness hazards.
* ``alive``: removal support (§IV-C) — dead rows are masked out of search
  rather than compacted, matching the paper's O(1)-ish delete.
* ``sq_norms``: (cap,) graph-resident cache of ``‖x_i‖²`` backing the blocked
  MXU distance engine (``‖q‖² + ‖x‖² − 2 q·x``).  Invariant: valid for every
  allocated row, 0 for unallocated/removed rows.  Owners: ``brute
  .exact_seed_graph`` (seed rows), ``construct.commit_wave`` (wave rows),
  ``dynamic.remove`` (zeroes victims); hand-built graphs attach it with
  ``attach_sq_norms``.  No search/construction path recomputes norms per
  iteration.
* ``row_scale``: (cap,) per-row symmetric int8 quantization scales
  (``max|x_i|/127``) backing the compressed distance engine
  (``precision="int8"``).  Same invariant and the same owners as
  ``sq_norms`` — the two tables are maintained side by side everywhere, and
  ``attach_sq_norms`` fills both.  Rows with scale 0 (unallocated, removed,
  or the all-zero vector) dequantize through a scale of 1 in the engine, so
  a stale zero can never produce NaNs.
* ``rev_lam``: (cap, R) snapshot of the forward twin's λ for each reverse
  edge — Ḡ[i] entry j means i ∈ G[j], and ``rev_lam[i, slot]`` is λ of i
  inside G[j] at append/rebuild time.  Search's LGD reverse-edge filter
  (Alg. 3 line 19) reads this flat table instead of gathering the (R, k)
  twin rows per expansion.  Like ``rev_ids`` it may go stale (λ updates on
  the forward side do not propagate); stale values only perturb the
  expansion *filter*, never distances or results ordering.

Everything is int32/float32; the graph for n=10^8, k=40, R=80 is ~50 GB —
sharded over a pod it is ~200 MB/device, which is why this layout scales
where pointer structures cannot.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import segments

Array = jax.Array


class KNNGraph(NamedTuple):
    nbr_ids: Array  # (cap, k) int32
    nbr_dist: Array  # (cap, k) float32, sorted ascending per row
    nbr_lam: Array  # (cap, k) int32  (LGD occlusion factor)
    rev_ids: Array  # (cap, R) int32 ring buffer
    rev_lam: Array  # (cap, R) int32 — forward-twin λ snapshot per rev edge
    rev_ptr: Array  # (cap,) int32 — total appends (mod R = write slot)
    alive: Array  # (cap,) bool
    n_valid: Array  # () int32 — rows [0, n_valid) are allocated
    sq_norms: Array  # (cap,) float32 — ‖x_i‖² cache (0 where unallocated)
    row_scale: Array  # (cap,) float32 — int8 quant scale cache (0 where unallocated)

    @property
    def capacity(self) -> int:
        return self.nbr_ids.shape[0]

    @property
    def k(self) -> int:
        return self.nbr_ids.shape[1]

    @property
    def rev_capacity(self) -> int:
        return self.rev_ids.shape[1]


def empty_graph(capacity: int, k: int, rev_capacity: int | None = None) -> KNNGraph:
    if rev_capacity is None:
        rev_capacity = 2 * k
    return KNNGraph(
        nbr_ids=jnp.full((capacity, k), -1, jnp.int32),
        nbr_dist=jnp.full((capacity, k), jnp.inf, jnp.float32),
        nbr_lam=jnp.zeros((capacity, k), jnp.int32),
        rev_ids=jnp.full((capacity, rev_capacity), -1, jnp.int32),
        rev_lam=jnp.zeros((capacity, rev_capacity), jnp.int32),
        rev_ptr=jnp.zeros((capacity,), jnp.int32),
        alive=jnp.zeros((capacity,), bool),
        n_valid=jnp.zeros((), jnp.int32),
        sq_norms=jnp.zeros((capacity,), jnp.float32),
        row_scale=jnp.zeros((capacity,), jnp.float32),
    )


def squared_norms(x: Array) -> Array:
    """(n, d) data -> (n,) float32 ‖x_i‖² (the norm-cache values).

    The one place the cache contents are defined; every owner of
    ``KNNGraph.sq_norms`` computes its entries through here.
    """
    xf = x.astype(jnp.float32)
    return jnp.sum(xf * xf, axis=-1)


def row_scales(x: Array) -> Array:
    """(n, d) data -> (n,) float32 symmetric int8 scales (``max|x_i|/127``).

    The one place the scale-table contents are defined; every owner of
    ``KNNGraph.row_scale`` computes its entries through here (mirroring
    ``squared_norms`` for the norm cache).

    Written as a multiply by the precomputed f32 reciprocal, NOT ``/ 127.0``:
    XLA's algebraic simplifier rewrites the constant divide into exactly this
    multiply inside jit, so the explicit form yields the same bits from
    eager owners (``attach_sq_norms`` on a hand-built graph) and jitted
    owners (wave commit) — a plain divide diverges by one ulp on ~4% of
    rows depending on compilation context.
    """
    xf = x.astype(jnp.float32)
    return jnp.max(jnp.abs(xf), axis=-1) * jnp.float32(1.0 / 127.0)


def attach_sq_norms(g: KNNGraph, x: Array) -> KNNGraph:
    """Populate the norm and scale caches of a hand-built graph from its
    backing data.

    Rows at or beyond ``n_valid`` — and dead rows — keep 0 per the cache
    invariant.
    """
    cap = g.capacity
    sq = squared_norms(x[:cap])
    sc = row_scales(x[:cap])
    if sq.shape[0] < cap:
        sq = jnp.pad(sq, (0, cap - sq.shape[0]))
        sc = jnp.pad(sc, (0, cap - sc.shape[0]))
    row = jnp.arange(cap, dtype=jnp.int32)
    allocated = (row < g.n_valid) & g.alive
    return g._replace(
        sq_norms=jnp.where(allocated, sq, 0.0),
        row_scale=jnp.where(allocated, sc, 0.0),
    )


def grow_graph(g: KNNGraph, new_capacity: int) -> KNNGraph:
    """Extend capacity with unallocated rows (append-only data region)."""
    cap = g.capacity
    if new_capacity <= cap:
        return g
    extra = new_capacity - cap
    return KNNGraph(
        nbr_ids=jnp.concatenate([g.nbr_ids, jnp.full((extra, g.k), -1, jnp.int32)]),
        nbr_dist=jnp.concatenate([g.nbr_dist, jnp.full((extra, g.k), jnp.inf, jnp.float32)]),
        nbr_lam=jnp.concatenate([g.nbr_lam, jnp.zeros((extra, g.k), jnp.int32)]),
        rev_ids=jnp.concatenate([g.rev_ids, jnp.full((extra, g.rev_capacity), -1, jnp.int32)]),
        rev_lam=jnp.concatenate([g.rev_lam, jnp.zeros((extra, g.rev_capacity), jnp.int32)]),
        rev_ptr=jnp.concatenate([g.rev_ptr, jnp.zeros((extra,), jnp.int32)]),
        alive=jnp.concatenate([g.alive, jnp.zeros((extra,), bool)]),
        n_valid=g.n_valid,
        sq_norms=jnp.concatenate([g.sq_norms, jnp.zeros((extra,), jnp.float32)]),
        row_scale=jnp.concatenate([g.row_scale, jnp.zeros((extra,), jnp.float32)]),
    )


def trim_graph(g: KNNGraph, new_capacity: int) -> KNNGraph:
    """Drop unallocated tail rows (the inverse of ``grow_graph``).

    Only rows at or beyond ``n_valid`` may be trimmed — stored ids are all
    < n_valid, so no list can dangle.  Used by the sub-graph merge path,
    which requires fully-allocated operands (capacity == n_valid).
    """
    cap = g.capacity
    if new_capacity >= cap:
        return g
    if new_capacity < int(g.n_valid):
        raise ValueError(
            f"cannot trim below n_valid: {new_capacity} < {int(g.n_valid)}"
        )
    return KNNGraph(
        nbr_ids=g.nbr_ids[:new_capacity],
        nbr_dist=g.nbr_dist[:new_capacity],
        nbr_lam=g.nbr_lam[:new_capacity],
        rev_ids=g.rev_ids[:new_capacity],
        rev_lam=g.rev_lam[:new_capacity],
        rev_ptr=g.rev_ptr[:new_capacity],
        alive=g.alive[:new_capacity],
        n_valid=g.n_valid,
        sq_norms=g.sq_norms[:new_capacity],
        row_scale=g.row_scale[:new_capacity],
    )


@jax.jit
def rebuild_reverse(g: KNNGraph) -> KNNGraph:
    """Recompute rev lists from forward lists (checkpoint-restore / repair).

    Edges are grouped by member id; each member keeps its most recent R
    owners.  The forward twin's λ rides along as a second payload, so the
    ``rev_lam`` snapshot is exact at rebuild time.  Pure function of the
    forward graph — used to verify the incremental ring-buffer maintenance
    in tests.  Jitted: run op by op, its scans would compile dozens of
    programs on a TPU.
    """
    cap, k = g.nbr_ids.shape
    R = g.rev_capacity
    owners = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32)[:, None], (cap, k))
    members = g.nbr_ids
    valid = members >= 0
    flat_owner = jnp.where(valid, owners, cap).reshape(-1)
    flat_member = jnp.where(valid, members, cap).reshape(-1)
    flat_lam = jnp.where(valid, g.nbr_lam, 0).reshape(-1)
    order = jnp.argsort(flat_member, stable=True)
    sm = flat_member[order]
    so = flat_owner[order]
    sl = flat_lam[order]
    # group owners by member, keep each member's first R (most recent) owners
    (rev_ids, rev_lam), counts = segments.grouped_top_r(
        sm, [so, sl], [-1, 0], cap, R
    )
    return g._replace(
        rev_ids=rev_ids,
        rev_lam=rev_lam,
        rev_ptr=jnp.minimum(counts, R).astype(jnp.int32),
    )


def graph_invariants_ok(g: KNNGraph) -> dict:
    """Structural invariants (used by property tests).

    Returns a dict of boolean arrays — all must be all-True:
      * rows sorted ascending (padding +inf at the tail)
      * no self loops
      * no duplicate ids within a row
      * ids within [0, n_valid) or -1
      * liveness: no alive row references a dead (``~alive``) neighbor —
        forward or reverse.  ``dynamic.remove`` purges victims from every
        list, so any dead reference after a removal is a leak.
    """
    ids, dist = g.nbr_ids, g.nbr_dist
    cap, k = ids.shape
    row = jnp.arange(cap, dtype=jnp.int32)[:, None]
    sorted_ok = jnp.all(dist[:, 1:] >= dist[:, :-1], axis=1)
    no_self = jnp.all(ids != row, axis=1)
    eq = (ids[:, :, None] == ids[:, None, :]) & (ids[:, :, None] >= 0)
    dup = jnp.sum(eq, axis=(1, 2)) > jnp.sum(ids >= 0, axis=1)
    in_range = jnp.all((ids == -1) | ((ids >= 0) & (ids < g.n_valid)), axis=1)
    live_nbrs = jnp.all((ids < 0) | g.alive[jnp.maximum(ids, 0)], axis=1)
    live_rev = jnp.all(
        (g.rev_ids < 0) | g.alive[jnp.maximum(g.rev_ids, 0)], axis=1
    )
    active = jnp.arange(cap) < g.n_valid
    live_row = active & g.alive
    return {
        "sorted": jnp.where(active, sorted_ok, True),
        "no_self_loops": jnp.where(active, no_self, True),
        "no_duplicates": jnp.where(active, ~dup, True),
        "ids_in_range": jnp.where(active, in_range, True),
        "live_neighbors": jnp.where(live_row, live_nbrs, True),
        "live_reverse": jnp.where(live_row, live_rev, True),
    }
