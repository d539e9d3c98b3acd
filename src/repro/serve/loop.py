"""Instrumented serving loop: continuous query batching over an OnlineIndex.

Serving so far has been batch-function calls (``serve.retrieval.retrieve``)
— the caller owns batching, latency is whatever ``time.time`` around the
call says, and the per-query search signals vanish.  ``ServingLoop`` is the
production-shaped front end the ROADMAP's item 3 asked for:

  * **arrival queue + pow2-bucketed coalescing** — queries arrive one by one
    or in bursts (``submit``); each ``step`` drains up to ``max_batch`` of
    them and pads the wave to the next power of two (the PR-4 ingest-
    coalescing idiom applied to reads), so the jitted search compiles
    O(log max_batch) shapes instead of one per arrival pattern;
  * **churn interleave** — writes (``add``/``remove``) ride the index's
    micro-batch buffer and are flushed *between* query waves by the loop, so
    reads always observe prior writes (the index's own flush-on-read
    guarantee) and the flush cost lands in its own span, not smeared into
    query latency;
  * **latency truth** — per-query latency is measured enqueue→result with
    the result synced (``block_until_ready``) before the clock stops, so
    p50/p99 include queueing delay and device work, not just dispatch;
  * **recall reservoir** — every ``recall_sample_every``-th served query is
    stashed (query vector + the ids actually served) in a fixed-size
    round-robin reservoir; ``audit_recall`` brute-forces those queries
    against the live index (alive-aware) and reports both the recall of a
    *fresh* search (current serving quality — the gated number) and of the
    *served* ids (what users actually got, which churn can have invalidated);
  * **telemetry** — every wave folds its ``SearchResult`` accounting into a
    ``SearchStats`` (scanning rate, hash saturation, comps histogram) at the
    sync boundary the latency clock already created, and ``report()`` logs
    p50/p99/QPS through the attached ``Tracker``.

The loop is deliberately synchronous and deterministic — a host-side state
machine, not a thread pool: benchmarks and tests drive it step by step, and
the paper's online claim (serve while building/churning) is exercised by
interleaving ``submit``/``add``/``remove``/``pump`` calls, which is exactly
what ``benchmarks.bench_serving`` does under the CI gate.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import brute
from repro.index.lifecycle import OnlineIndex
from repro.obs import NOOP, SearchStats, Tracker

Array = jax.Array

__all__ = ["ServeLoopConfig", "ServingLoop"]


@dataclasses.dataclass(frozen=True)
class ServeLoopConfig:
    """Static serving-loop configuration.

    ``max_batch`` must be a power of two — it is the largest coalescing
    bucket, and every wave is padded up to a pow2 ≤ it, bounding jit
    recompiles to log2(max_batch)+1 shapes.  ``recall_sample_every`` is a
    deterministic stride (no RNG in the sampling path: replaying the same
    arrival sequence audits the same queries)."""

    top_k: int = 10
    beam: Optional[int] = None  # None -> the index's default (2*top_k)
    max_batch: int = 64  # pow2 coalescing cap per query wave
    recall_reservoir: int = 64  # audited-query slots (round-robin overwrite)
    recall_sample_every: int = 7  # stride between sampled queries

    def __post_init__(self):
        assert self.max_batch >= 1 and (
            self.max_batch & (self.max_batch - 1) == 0
        ), "max_batch must be a power of two"
        assert self.recall_sample_every >= 1
        assert self.recall_reservoir >= 1


class ServingLoop:
    """Query/churn front end over one ``OnlineIndex`` (see module doc)."""

    def __init__(
        self,
        index: OnlineIndex,
        cfg: ServeLoopConfig = ServeLoopConfig(),
        tracker: Optional[Tracker] = None,
        seed: int = 0,
    ):
        self.index = index
        self.cfg = cfg
        self.tracker = tracker or NOOP
        # the index reports its lifecycle spans (flush/remove/compact/grow)
        # through the same tracker, so the JSONL is one nested trace
        if tracker is not None and index.tracker is None:
            index.tracker = tracker
        self.stats = SearchStats(n_items=index.n_items)
        self._queue: deque = deque()  # (query row np (d,), t_enqueue)
        self._key = jax.random.PRNGKey(seed)
        self._wave_idx = 0
        self._served = 0
        self._lat: List[float] = []  # per-query enqueue->synced-result secs
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        # recall reservoir: parallel lists, round-robin slot assignment
        self._res_q: List[np.ndarray] = []
        self._res_ids: List[np.ndarray] = []
        self._sample_count = 0

    # -- ingress -------------------------------------------------------------

    def submit(self, queries) -> int:
        """Enqueue one query (1-D) or a burst (2-D); returns queue depth."""
        q = np.asarray(queries, np.float32)
        if q.ndim == 1:
            q = q[None, :]
        now = time.perf_counter()
        for row in q:
            self._queue.append((row, now))
        return len(self._queue)

    def add(self, items, *, key: Optional[Array] = None) -> None:
        """Catalog insert, buffered: the write lands at the next wave
        boundary (the loop flushes before searching), never mid-wave."""
        with self.tracker.span("serve/add"):
            self.index.add(items, key=key, flush=False)

    def remove(self, ids) -> None:
        """Catalog withdraw (flushes buffered adds first, like the index)."""
        with self.tracker.span("serve/remove") as sp:
            self.index.remove(ids)
            sp.sync(self.index.graph.alive)

    # -- the wave ------------------------------------------------------------

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def served(self) -> int:
        return self._served

    def _next_key(self) -> Array:
        self._key, k = jax.random.split(self._key)
        return k

    def step(self) -> Optional[dict]:
        """Serve one coalesced wave; returns a per-wave summary (None if the
        queue was empty).  The wave: flush pending writes, drain up to
        ``max_batch`` queries, pad to the pow2 bucket, search, sync, stamp
        latencies, fold stats, feed the reservoir."""
        if not self._queue:
            return None
        cfg = self.cfg
        t_wave0 = time.perf_counter()
        if self._t_first is None:
            self._t_first = t_wave0

        with self.tracker.span("serve/step") as step_sp:
            if self.index.n_pending:
                # writes land between waves; OnlineIndex.flush carries its
                # own span through the shared tracker
                self.index.flush()

            m = min(len(self._queue), cfg.max_batch)
            rows, t_enq = zip(*(self._queue.popleft() for _ in range(m)))
            P = 1 << (m - 1).bit_length()  # pow2 bucket (compile-bounded)
            batch = np.empty((P, rows[0].shape[0]), np.float32)
            batch[:m] = np.stack(rows)
            batch[m:] = rows[-1]  # pad with a real row: no NaN/dtype hazards
            n_live = self.index.n_items

            with self.tracker.span("serve/search") as sp:
                res = self.index.search(
                    jnp.asarray(batch), cfg.top_k, beam=cfg.beam,
                    key=self._next_key(),
                )
                # materialize the answers: serving hands ids to the caller,
                # so this pull is the wave's OWN host sync (tracker or not) —
                # the latency clock must not stop before the device finishes
                ids = np.asarray(res.ids)[:m]
                sp.synced = True
            t_done = time.perf_counter()
            step_sp.synced = True  # the search sync covers the step's device work
            self._lat.extend(t_done - t for t in t_enq)
            self._served += m
            self._t_last = t_done
            self.stats.update(
                _slice_result(res, m), n_items=n_live
            )
            for i in range(m):
                c = self._sample_count
                self._sample_count += 1
                if c % cfg.recall_sample_every:
                    continue
                slot = (c // cfg.recall_sample_every) % cfg.recall_reservoir
                if slot < len(self._res_q):
                    self._res_q[slot] = batch[i]
                    self._res_ids[slot] = ids[i]
                else:
                    self._res_q.append(batch[i])
                    self._res_ids.append(ids[i])

        self._wave_idx += 1
        wave = {
            "wave": self._wave_idx,
            "batch": m,
            "bucket": P,
            "latency_s": t_done - t_wave0,
            "queue_depth": len(self._queue),
        }
        self.tracker.log_metrics(
            {f"serve/{k}": v for k, v in wave.items() if k != "wave"},
            step=self._wave_idx,
        )
        return wave

    def pump(self) -> int:
        """Drain the queue; returns the number of waves served."""
        waves = 0
        while self._queue:
            self.step()
            waves += 1
        return waves

    # -- audits + reporting --------------------------------------------------

    def audit_recall(self, k: int = 10) -> dict:
        """Brute-force the recall reservoir against the live index.

        ``recall_at_k`` — a FRESH search of each sampled query scored
        against exact (alive-aware) ground truth: current serving quality,
        the number the CI gate floors.  ``recall_at_k_served`` — the ids
        actually served at sample time scored against the same truth:
        under churn it can trail the fresh number (rows served earlier may
        since have been removed), which is a fact about the workload worth
        seeing, not a serving bug."""
        if not self._res_q:
            return {"n_audited": 0}
        with self.tracker.span("serve/audit") as sp:
            q = np.stack(self._res_q)
            self.index.flush()
            true_ids, _ = brute.brute_force_knn(
                self.index.items, jnp.asarray(q), k, self.index.metric,
                n_valid=self.index.graph.n_valid, alive=self.index.graph.alive,
                dispatch="reference",
            )
            fresh = self.index.search(
                jnp.asarray(q), self.cfg.top_k, beam=self.cfg.beam,
                key=self._next_key(),
            )
            sp.sync((true_ids, fresh.ids))
            fresh_rec = float(brute.recall_at_k(fresh.ids, true_ids, k))
            served = jnp.asarray(np.stack(self._res_ids))
            served_rec = float(brute.recall_at_k(served, true_ids, k))
        out = {
            "n_audited": len(self._res_q),
            f"recall_at_{k}": fresh_rec,
            f"recall_at_{k}_served": served_rec,
        }
        self.tracker.log_metrics({f"serve/{kk}": v for kk, v in out.items()})
        return out

    def report(self, audit_k: int = 10) -> dict:
        """The sustained-load record: p50/p99 latency, QPS, scanning rate,
        hash saturation, sampled recall — logged through the tracker and
        returned as a flat dict (what ``bench_serving`` emits to CI)."""
        lat = np.asarray(self._lat, np.float64)
        span_s = (
            (self._t_last - self._t_first)
            if self._t_first is not None and self._t_last is not None
            else 0.0
        )
        rec = {
            "n_served": self._served,
            "n_waves": self._wave_idx,
            "qps": self._served / span_s if span_s > 0 else 0.0,
            "p50_latency_ms": float(np.percentile(lat, 50) * 1e3) if lat.size else 0.0,
            "p99_latency_ms": float(np.percentile(lat, 99) * 1e3) if lat.size else 0.0,
            "mean_latency_ms": float(lat.mean() * 1e3) if lat.size else 0.0,
            "comps_per_query": self.stats.comps_per_query,
            "scanning_rate": self.stats.scanning_rate,
            "hash_saturation_ratio": self.stats.hash_saturation_ratio,
            "capped_ratio": self.stats.capped_ratio,
        }
        if self._res_q:
            rec.update(self.audit_recall(k=audit_k))
        self.tracker.log_metrics(
            {f"serve/{k}": v for k, v in rec.items()}
        )
        return rec

    def reset_window(self) -> None:
        """Start a fresh measurement window (latency, stats, reservoir,
        wave clock) without touching the index or the queue — call after
        warm-up so compile time never lands in the sustained-load record."""
        self.stats.reset()
        self._lat = []
        self._served = 0
        self._wave_idx = 0
        self._t_first = None
        self._t_last = None
        self._res_q, self._res_ids = [], []
        self._sample_count = 0


def _slice_result(res, m: int):
    """First m lanes of a padded wave's SearchResult (padding lanes repeat a
    real query; their accounting must not be double-counted)."""
    return res._replace(
        ids=res.ids[:m], dists=res.dists[:m],
        vis_ids=res.vis_ids[:m], vis_dist=res.vis_dist[:m],
        n_comps=res.n_comps[:m], n_iters=res.n_iters[:m],
        converged=res.converged[:m], hash_full=res.hash_full[:m],
        seed_cell=res.seed_cell[:m],
    )
