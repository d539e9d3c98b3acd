"""OnlineIndex: the index's life outside a single build call.

``construct.build`` produces a graph; ``dynamic.insert``/``remove`` keep it
current; but the seed repo left everything around those calls to the caller:
capacity was a hard assert, removed rows leaked their slots forever, every
tiny insert paid a full wave dispatch, and nothing survived the process.
``OnlineIndex`` owns that lifecycle, riding the fused wave pipeline
untouched:

  * **amortized-doubling auto-growth** — an insert that would overflow the
    data region grows graph + items to ``growth_factor * capacity`` (one
    O(cap) copy amortized over O(cap) inserts) instead of asserting;
  * **free-slot ledger** — ``remove`` records its victims; before growing,
    an insert first reclaims those slots via ``compact()`` (when
    ``auto_compact``), so steady-state churn (insert ≈ remove) runs in
    bounded memory forever;
  * **compact()** — re-packs alive rows with ``dynamic.compact`` and returns
    the old→new id map so callers holding row ids (the sharded router,
    result caches) can follow the move;
  * **micro-batched ingest** — ``add(..., flush=False)`` buffers small
    inserts host-side and coalesces them into ONE ``construct.build`` wave
    (via ``dynamic.insert``) once ``ingest_batch`` items accumulate; a
    search flushes first, so reads always observe prior writes;
  * **snapshots** — ``save``/``load`` wrap ``repro.index.snapshot`` so a
    serving replica restores graph + data + build config (and therefore the
    same kernel dispatch) bit-for-bit.

The facade is mutable — it *is* the serving-side state machine — but every
underlying buffer is an immutable jax array, so ``clone()`` is O(fields) and
gives the functional entry points in ``serve.retrieval`` copy-on-write
semantics for free.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import construct, dynamic
from repro.core import graph as graph_lib
from repro.core import search as search_lib
from repro.core.graph import KNNGraph
from repro.index import snapshot as snapshot_lib
from repro.obs import NOOP

Array = jax.Array


@dataclasses.dataclass
class OnlineIndex:
    """A long-lived online k-NN index: graph + data + config + churn state.

    Field-compatible with the old ``serve.retrieval.RetrievalIndex``
    (``graph``, ``items``, ``build_cfg``, ``metric``), plus the lifecycle
    state described in the module docstring.
    """

    graph: KNNGraph
    items: Array  # (capacity, d); rows beyond n_valid are free
    build_cfg: construct.BuildConfig
    coarse: object = None  # hierarchy.CoarseLevel under seed_mode="coarse"
    free_ids: tuple = ()  # ledger of removed (dead) rows < n_valid
    pending: tuple = ()  # micro-batch ingest buffer: tuples of (m_i, d) arrays
    ingest_batch: int = 64  # coalesce threshold for buffered adds
    auto_compact: bool = True  # reclaim free slots before growing
    growth_factor: float = 2.0  # amortized-doubling factor
    last_compact_map: Optional[np.ndarray] = None  # old->new rows, last compact
    pending_key: Optional[Array] = None  # PRNG key stashed by buffered adds
    pq_codebook: Optional[Array] = None  # trained PQ code space (precision="pq")
    tracker: object = None  # obs.Tracker for lifecycle spans (None -> no-op)
    _enc: object = None  # cached kernels.precision.EncodedData (serving table)
    _ledger_synced: bool = False  # reconciliation ran (clones inherit True)

    def __post_init__(self):
        # The ledger is a host-side cache of the graph's liveness holes; the
        # alive mask stays the ground truth.  A graph that arrives with dead
        # rows but no ledger (a hand-built graph, or a churned graph saved
        # through ``snapshot.save`` directly rather than ``OnlineIndex.save``)
        # reconciles here, so capacity accounting and auto-compaction never
        # trust stale state.  Runs once per lineage: ``clone()`` carries
        # ``_ledger_synced``, keeping it O(fields) with no device sync.
        if not self._ledger_synced:
            if not self.free_ids:
                n_valid = int(self.graph.n_valid)
                dead = np.flatnonzero(~np.asarray(self.graph.alive[:n_valid]))
                if dead.size:
                    self.free_ids = tuple(int(i) for i in dead)
            self._ledger_synced = True

    # -- views ---------------------------------------------------------------

    @property
    def metric(self) -> str:
        return self.build_cfg.metric

    @property
    def capacity(self) -> int:
        return self.graph.capacity

    @property
    def n_pending(self) -> int:
        return sum(int(p.shape[0]) for p in self.pending)

    @property
    def free_slots(self) -> int:
        return len(self.free_ids)

    @property
    def n_items(self) -> int:
        """Live catalog size: allocated − removed + buffered."""
        return int(self.graph.n_valid) - len(self.free_ids) + self.n_pending

    def clone(self) -> "OnlineIndex":
        """O(fields) copy; jax buffers are immutable and shared."""
        return dataclasses.replace(self)

    # -- construction --------------------------------------------------------

    @classmethod
    def build(
        cls,
        items: Array,
        cfg: Optional[construct.BuildConfig] = None,
        *,
        capacity: Optional[int] = None,
        key: Optional[Array] = None,
        ingest_batch: int = 64,
        auto_compact: bool = True,
        growth_factor: float = 2.0,
        **cfg_kw,
    ) -> "OnlineIndex":
        """Index ``items`` with online LGD/OLG construction.

        ``capacity > n`` pre-allocates headroom; either way later inserts
        auto-grow, so capacity is a hint, not a ceiling.  The build runs
        under an ``index/build`` span of ``obs.NOOP``: a profiler trace
        shows it, no event log does (no tracker is attached yet).
        """
        if cfg is None:
            cfg = construct.BuildConfig(**cfg_kw)
        elif cfg_kw:
            raise ValueError(
                f"pass either cfg or BuildConfig kwargs, not both (got cfg "
                f"and {sorted(cfg_kw)})"
            )
        n = items.shape[0]
        cap = capacity or n
        with NOOP.span("index/build"):
            g, _, coarse = construct.build(items, cfg, key, return_coarse=True)
            if cap > n:
                g = graph_lib.grow_graph(g, cap)
                items = jnp.pad(items, ((0, cap - n), (0, 0)))
        return cls(
            graph=g,
            items=items,
            build_cfg=cfg,
            coarse=coarse,
            ingest_batch=ingest_batch,
            auto_compact=auto_compact,
            growth_factor=growth_factor,
        )

    # -- churn ---------------------------------------------------------------

    def add(
        self,
        new_items: Array,
        *,
        key: Optional[Array] = None,
        flush: Optional[bool] = None,
    ) -> "OnlineIndex":
        """Insert items (catalog listing).

        ``flush=False`` only buffers; ``flush=True`` forces the insertion
        wave now; the default flushes once ``ingest_batch`` items are
        buffered — the micro-batch path that coalesces trickling single-item
        inserts into one wave.  A ``key`` supplied with a buffered add is
        stashed and used by the eventual coalescing flush, so replicas fed
        the same (items, key) sequence build the same graph regardless of
        when the threshold trips.  Returns self (mutates in place).
        """
        new_items = jnp.asarray(new_items)
        if new_items.ndim == 1:
            new_items = new_items[None, :]
        if new_items.shape[0]:
            self.pending = self.pending + (new_items,)
            # the key belongs to THIS batch: an empty add must not stash one
            # (it would outlive this call and redirect a later, unrelated
            # flush — the replica-determinism leak), so the stash rides the
            # same condition as the buffer append and the invariant
            # ``pending == () ⇒ pending_key is None`` holds everywhere
            if key is not None:
                self.pending_key = key
        do_flush = flush if flush is not None else self.n_pending >= self.ingest_batch
        if do_flush:
            self.flush(key=key)
        return self

    def flush(self, *, key: Optional[Array] = None) -> "OnlineIndex":
        """Coalesce buffered adds into one insertion wave.

        Every exit clears ``pending_key``: a stale key surviving an
        empty-buffer flush would silently redirect the next coalescing
        flush's PRNG stream and break replica determinism (replaying the
        same (items, key) sequence with different flush timing must build
        the same graph).
        """
        if not self.pending:
            self.pending_key = None
            return self
        if key is None:
            key = self.pending_key
        trk = self.tracker or NOOP
        with trk.span("index/flush") as sp:
            batch = jnp.concatenate(
                [p.astype(self.items.dtype) for p in self.pending], axis=0
            )
            m = batch.shape[0]
            self._ensure_room(m)
            n0 = int(self.graph.n_valid)
            items = self.items.at[n0 : n0 + m].set(batch)
            out = dynamic.insert(
                self.graph, items, m, self.build_cfg, key, coarse=self.coarse
            )
            if len(out) == 3:
                g, _, self.coarse = out
            else:
                g, _ = out
            self.graph, self.items = g, items
            self._enc = None  # compressed serving table re-derives lazily
            # drained only after the wave landed: a failure above (growth OOM,
            # insert error) leaves the buffer intact for retry, not silently
            # lost
            self.pending = ()
            self.pending_key = None
            sp.sync(self.graph.nbr_ids)
        trk.log_metrics(
            {
                "index/flushed": m,
                "index/n_items": self.n_items,
                "index/ledger_depth": self.free_slots,
                "index/capacity": self.capacity,
            }
        )
        return self

    def remove(self, ids: Array) -> "OnlineIndex":
        """Remove items (catalog withdrawal); victims enter the free-slot
        ledger for later reclamation.  Flushes pending adds first so the
        ledger and the graph agree on liveness; if that flush auto-compacts,
        the caller's (pre-flush) row ids are remapped through the compaction
        id map, so they always name the rows the caller saw.

        Only ids that are in range and currently alive act (-1 result
        padding and stale ids are no-ops); the removal batch is padded to
        power-of-two buckets so the jitted ``dynamic.remove`` compiles
        O(log cap) shapes, not one per batch size.
        """
        pre_map = self.last_compact_map
        self.flush()
        ids_np = np.unique(np.asarray(ids).reshape(-1).astype(np.int64))
        if self.last_compact_map is not pre_map:
            # the flush compacted: translate the caller's pre-flush rows
            id_map = self.last_compact_map
            ok = (ids_np >= 0) & (ids_np < len(id_map))
            ids_np = id_map[ids_np[ok]]
        alive = np.asarray(self.graph.alive)
        ids_np = ids_np[(ids_np >= 0) & (ids_np < alive.shape[0])]
        newly_dead = ids_np[alive[ids_np]]
        if not newly_dead.size:
            return self
        trk = self.tracker or NOOP
        with trk.span("index/remove") as sp:
            bucket = 1 << int(newly_dead.size - 1).bit_length()
            padded = np.full(bucket, -1, np.int64)
            padded[: newly_dead.size] = newly_dead
            self.graph = dynamic.remove(
                self.graph, self.items, jnp.asarray(padded, jnp.int32),
                self.metric,
            )
            if self.coarse is not None:
                # landmark victims are masked like any dead row; their frozen
                # routing vectors keep steering the coarse walk
                from repro.core import hierarchy

                self.coarse = hierarchy.purge_rows(
                    self.coarse, jnp.asarray(newly_dead, jnp.int32)
                )
            self.free_ids = self.free_ids + tuple(int(i) for i in newly_dead)
            self._enc = None  # victims' rows must drop out of the table
            sp.sync(self.graph.alive)
        trk.log_metrics(
            {
                "index/removed": int(newly_dead.size),
                "index/n_items": self.n_items,
                "index/ledger_depth": self.free_slots,
            }
        )
        return self

    def compact(self) -> np.ndarray:
        """Re-pack alive rows to the front, reclaiming the ledger's slots.

        Returns the (capacity,) old→new row map (-1 for removed rows); it is
        also retained as ``last_compact_map`` so batch entry points that
        compact implicitly (``flush`` under ``auto_compact``) leave a trail
        for id-holding callers (the sharded router).
        """
        trk = self.tracker or NOOP
        with trk.span("index/compact") as sp:
            reclaimed = len(self.free_ids)
            g, x, id_map = dynamic.compact(self.graph, self.items)
            self.graph, self.items = g, x
            if self.coarse is not None:
                from repro.core import hierarchy

                self.coarse = hierarchy.remap_rows(self.coarse, id_map)
            self.free_ids = ()
            self.last_compact_map = np.asarray(id_map)  # this IS a host sync
            self._enc = None  # rows moved; compressed serving table re-derives
            sp.synced = True
        trk.log_metrics(
            {
                "index/compact_reclaimed": reclaimed,
                "index/n_items": self.n_items,
                "index/capacity": self.capacity,
            }
        )
        return self.last_compact_map

    def _ensure_room(self, m: int) -> None:
        """Make room for m tail inserts: recycle free slots, then grow."""
        tail_room = self.capacity - int(self.graph.n_valid)
        if m <= tail_room:
            return
        # recycle before growing: compaction frees the ledger's slots
        if self.auto_compact and self.free_ids:
            n_alive = int(self.graph.n_valid) - len(self.free_ids)
            if n_alive + m <= self.capacity:
                self.compact()
                return
        needed = int(self.graph.n_valid) + m
        old_cap = self.capacity
        new_cap = max(needed, int(self.capacity * self.growth_factor), 1)
        self.graph = graph_lib.grow_graph(self.graph, new_cap)
        self.items = jnp.pad(
            self.items, ((0, new_cap - self.items.shape[0]), (0, 0))
        )
        (self.tracker or NOOP).log_metrics(
            {"index/grow_from": old_cap, "index/grow_to": new_cap}
        )

    # -- search --------------------------------------------------------------

    def search_config(
        self, top_k: int, beam: Optional[int] = None
    ) -> search_lib.SearchConfig:
        """The serving SearchConfig: the build-time search parameters
        (``build_cfg.search_config()`` — n_seeds, hash_slots, max_iters,
        seed_mode, …) with only the per-request k/beam overridden.  Serving
        with anything else would silently diverge from the configuration the
        index was built and validated with (the old from-scratch
        ``SearchConfig(...)`` here dropped every non-default build field)."""
        return dataclasses.replace(
            self.build_cfg.search_config(),
            k=top_k,
            beam=max(beam or 2 * top_k, top_k),
        )

    def _ensure_coarse(self):
        """Lazily (re-)derive the coarse level when serving wants coarse
        seeding but none is attached (pre-v2 snapshot, hand-built index, or
        ``seed_mode`` flipped on after the build)."""
        if self.coarse is None and self.build_cfg.seed_mode == "coarse":
            if int(self.graph.n_valid) - len(self.free_ids) > 0:
                from repro.core import hierarchy

                self.coarse = hierarchy.derive_coarse(
                    self.graph, self.items, self.build_cfg,
                    jax.random.PRNGKey(int(self.graph.n_valid)),
                )
        return self.coarse

    def _ensure_enc(self):
        """Lazily (re-)encode the compressed serving table when the build
        precision is not fp32 — the ``_ensure_coarse`` pattern for the
        distance engine's companion data.  Invalidated by every mutation of
        the rows (``flush``/``remove``/``compact``), re-derived once here
        rather than per search; int8 scales come from the graph-resident
        ``row_scale`` cache, and the PQ codebook is trained ONCE and pinned
        (``pq_codebook``) so churn never shifts the code space under a
        serving replica."""
        precision = self.build_cfg.precision
        if precision == "fp32":
            return None
        if self._enc is None:
            from repro.kernels import precision as precision_lib

            self._enc = precision_lib.encode_dataset(
                self.items.astype(jnp.float32),
                precision,
                row_scale=self.graph.row_scale if precision == "int8" else None,
                codebook=self.pq_codebook if precision == "pq" else None,
            )
            if precision == "pq" and self.pq_codebook is None:
                self.pq_codebook = self._enc.codebook
        return self._enc

    def search(
        self,
        queries: Array,
        top_k: int,
        *,
        beam: Optional[int] = None,
        key: Optional[Array] = None,
    ) -> search_lib.SearchResult:
        """Per-query EHC search (flushes buffered adds first).

        This is the raw (B, k) search surface; the serving-side merge/dedupe
        and score convention live in ``serve.retrieval.retrieve``.  Serving
        inherits the builder's precision (``search_config``); the compressed
        companion table is cached on the index and re-derived only after
        catalog churn.  The call runs under an ``index/search`` span, which
        marks the dispatch and does not sync.
        """
        with (self.tracker or NOOP).span("index/search"):
            self.flush()
            if key is None:
                key = jax.random.PRNGKey(0)
            scfg = self.search_config(top_k, beam)
            coarse = None
            if scfg.seed_mode == "coarse":
                coarse = self._ensure_coarse()
                if coarse is None:  # nothing alive to derive from
                    scfg = dataclasses.replace(scfg, seed_mode="random")
            return search_lib.search(
                self.graph, self.items, queries, key, scfg, coarse=coarse,
                enc=self._ensure_enc(),
            )

    # -- persistence ---------------------------------------------------------

    def save(self, path: str) -> str:
        """Snapshot graph + data + config + coarse level + PQ codebook
        (flushes buffered adds first).  The compressed tiles/codes/scales are
        not persisted — they re-derive canonically on load — but a trained PQ
        codebook is, so the replica serves the same code space."""
        self.flush()
        return snapshot_lib.save(
            path,
            self.graph,
            self.items,
            self.build_cfg,
            coarse=self.coarse,
            pq_codebook=self.pq_codebook,
            extra_meta={"free_ids": [int(i) for i in self.free_ids]},
        )

    @classmethod
    def load(cls, path: str, **lifecycle_kw) -> "OnlineIndex":
        """Restore an index a snapshot-for-snapshot replica of the saved one.

        Pre-v2 snapshots carry no coarse payload; under
        ``seed_mode="coarse"`` the level is re-derived here (offline
        maintenance) so the replica serves coarsely from the first query.
        Pre-v3 snapshots carry no PQ codebook; a ``precision="pq"`` config
        then retrains deterministically from the restored items on first
        search."""
        g, items, cfg, manifest, coarse, pq_cb = snapshot_lib.load(
            path, with_coarse=True, with_pq_codebook=True
        )
        free = tuple(manifest.get("extra", {}).get("free_ids", []))
        idx = cls(
            graph=g, items=items, build_cfg=cfg, coarse=coarse, free_ids=free,
            pq_codebook=pq_cb, **lifecycle_kw,
        )
        if coarse is None and cfg.seed_mode == "coarse":
            idx._ensure_coarse()
        return idx
