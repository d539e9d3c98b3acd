"""serve: one open-loop Poisson stream of single queries through ``ServingLoop``.

* Set-up: the catalog and a held-out query pool from the configuration's
  data seed, the index (``OnlineIndex.build``), a
  ``ServingLoop(ServeLoopConfig(top_k, beam, max_batch))``, and one wave of
  every size from 1 to ``max_batch``: the loop pads a wave to its pow2
  bucket, then slices its result eagerly, which compiles once per size.
* Arrivals: ``rate`` × ``--seconds`` queries, their due times uniform over
  the window and sorted (a Poisson stream at ``rate`` given its count), each
  a row of the pool; both drawn from ``--seed``.  Every seed sends as many
  queries, at other times.
* The window, in one host thread: submit every query whose due time has
  passed, then step the loop once; sleep when nothing waits.  After the last
  arrival the loop drains for at most ``DRAIN_S`` (a minute: an answer that
  comes late is late, and its latency says so, not wrong).  A query's latency runs
  from its due time to the return of the step that answered it, so time the
  generator spent inside a step counts against the queries it delayed (the
  loop's own clock starts at ``submit``).  A query still unanswered at the
  end counts in ``failed`` and in the ``unanswered`` check.
* The answers: the loop hands no answers back, so the driver observes the
  index's ``search``: each wave's ids and distances are kept, and lane ``i``
  of a wave of ``m`` answers the ``i``-th oldest waiting query (the loop's
  queue is first in, first out).  A loop that answered other queries, or
  dropped one, reads as wrong answers or as unanswered queries.
* The check, after the window: a sample of the answered queries, drawn from
  the seed, against the float64 exact scan of ``bench/reference.py``.

With ``program=False`` the loop serves over the reference's control (the
exact scan at ``high``) in the index's place.
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from bench import data as data_lib
from bench import drive
from bench import reference as ref

DRAIN_S = 60.0  # the longest the window waits for answers after the last arrival


def arrivals(seed: int, rate: float, seconds: float, pool: int):
    """(due times in seconds from the window's start, pool rows) of one
    seed's stream: ``round(rate * seconds)`` queries."""
    rng = np.random.default_rng([seed, 21])
    n = max(1, int(round(rate * seconds)))
    due = np.sort(rng.uniform(0.0, seconds, n))
    rows = np.concatenate([rng.permutation(pool) for _ in range(-(-n // pool))])[:n]
    return due, rows


class _LoopControl(ref.ControlIndex):
    """``ref.ControlIndex`` with what ``ServingLoop`` reads of an index: no
    pending writes, no tracker, the catalog's size, and a search that takes
    the loop's key and carries the accounting fields the loop folds."""

    n_pending = 0
    tracker = None

    @property
    def n_items(self) -> int:
        return self.items.shape[0]

    def search(self, queries, top_k, *, beam=None, key=None):
        from repro.core.search import SearchResult

        res = super().search(queries, top_k, beam=beam)
        none = jnp.zeros_like(res.n_iters)
        return SearchResult(ids=res.ids, dists=res.dists, vis_ids=res.ids,
                            vis_dist=res.dists, n_comps=none, n_iters=res.n_iters,
                            converged=none == 0, hash_full=none != 0, seed_cell=none - 1)


def server(cell: drive.Cell, catalog):
    """(submit, step) of a ``ServingLoop`` over the built index, or over the
    control where ``cell.program`` is false; ``step`` returns (lanes,
    bucket, ids, dists) of the wave it served, or None."""
    from repro.serve.loop import ServeLoopConfig, ServingLoop

    cfg, mix = cell.config, cell.mix
    if cell.program:
        index = drive.build_index(cell, catalog, data_lib.seed_key(cfg["data"]["seed"], 1))
    else:
        index = _LoopControl(catalog, cfg["metric"])
    search, last = index.search, []

    def observed(*args, **kw):
        res = search(*args, **kw)
        last.append((res.ids, res.dists))
        return res

    index.search = observed
    loop = ServingLoop(index, ServeLoopConfig(top_k=mix["top_k"], beam=mix["beam"],
                                              max_batch=mix["max_batch"]),
                       seed=cell.seed % (1 << 31))

    def step():
        wave = loop.step()
        if wave is None:
            return None
        ids, dists = last.pop()
        return wave["batch"], wave["bucket"], ids, dists

    return loop.submit, step


def warm(submit, step, pool, max_batch: int) -> None:
    """One wave of every size from 1 to ``max_batch``."""
    for m in range(1, max_batch + 1):
        submit(pool[:m])
        jax.block_until_ready(step()[3])


def window(submit, step, queries, due):
    """Send ``queries[i]`` at ``due[i]`` seconds from now, stepping the
    server in between, then drain for at most ``DRAIN_S``.

    Returns (waves, late, due_at, t_end): each wave as (first query,
    lanes, bucket, return time, ids, dists), each query's submit time less
    its due time, the absolute due times, and the time the loop ended."""
    N = len(due)
    waves = []
    late = np.zeros(N)
    sent = done = 0
    due_at = time.perf_counter() + due
    stop = due_at[-1] + DRAIN_S
    with jax.profiler.TraceAnnotation("bench.window"):
        while done < N:
            now = time.perf_counter()
            upto = int(np.searchsorted(due_at, now, side="right"))
            if upto > sent:
                submit(queries[sent:upto])
                late[sent:upto] = now - due_at[sent:upto]
                sent = upto
            wave = None
            if done < sent:
                with jax.profiler.TraceAnnotation("bench.step"):
                    wave = step()
                if wave is not None:
                    m, P, ids, dists = wave
                    waves.append((done, m, P, time.perf_counter(), ids, dists))
                    done += m
            if wave is None:
                if sent == N:
                    break  # nothing waits that the server still holds
                time.sleep(max(0.0, due_at[sent] - time.perf_counter()))
            if time.perf_counter() > stop:
                break
    return waves, late, due_at, time.perf_counter()


def answers(waves, N: int, k: int, t_end: float):
    """(queries answered, each query's answer time, ids, dists); a query
    not answered reads ``t_end``, ids -1 and distances +inf."""
    t_ans = np.full(N, t_end)
    ids = np.full((N, k), -1, np.int64)
    dists = np.full((N, k), np.inf)
    answered = 0
    for first, m, _, t, w_ids, w_dists in waves:
        m = min(m, N - first)
        t_ans[first : first + m] = t
        ids[first : first + m] = np.asarray(w_ids)[:m]
        dists[first : first + m] = np.asarray(w_dists)[:m]
        answered = first + m
    return answered, t_ans, ids, dists


def run(cell: drive.Cell, ready: Callable[[], None]) -> drive.Outcome:
    cfg, mix = cell.config, cell.mix
    n, k = cfg["n"], mix["top_k"]
    rows = drive.make_rows(cell, n + mix["pool"])
    catalog = rows[:n]
    pool = np.asarray(rows[n:])
    submit, step = server(cell, catalog)
    warm(submit, step, pool, mix["max_batch"])
    due, qrows = arrivals(cell.seed, mix["rate"], cell.seconds, mix["pool"])
    ready()

    waves, late, due_at, t_end = window(submit, step, pool[qrows], due)
    N = len(due)
    answered, t_ans, ids, dists = answers(waves, N, k, t_end)
    lat_ms = 1e3 * (t_ans - due_at)
    t0 = due_at[0] - due[0]  # the window's start

    rng = np.random.default_rng([cell.seed, 22])
    pick = np.sort(rng.choice(answered, min(mix["check_queries"], answered), replace=False))
    x = np.asarray(catalog)
    q = pool[qrows[pick]]
    true = ref.exact_topk(cfg["metric"], q, x, k)
    rec = ref.recall(ids[pick], true, k)
    lim = cfg["limits"]
    checks = {
        "recall_at_10": (rec, ">=", lim["recall_at_10"]),
        "dist_err": (ref.max_dist_err(cfg["metric"], q, x, ids[pick], dists[pick]), "<=",
                     lim["dist_err"]),
        "bad_ids": (ref.bad_ids(ids[:answered], n), "<=", lim["bad_ids"]),
        "unanswered": (N - answered, "<=", lim["unanswered"]),
    }
    return drive.Outcome(
        metrics={"latency_p95_ms": float(np.percentile(lat_ms, 95)), "recall_at_10": rec},
        attempted=N, failed=N - answered, checks=checks,
        readings={"n_waves": len(waves),
                  "holes": ref.holes(ids[:answered]),
                  "latency_p50_ms": float(np.percentile(lat_ms, 50)),
                  "latency_p99_ms": float(np.percentile(lat_ms, 99)),
                  "late_ms_max": 1e3 * float(late.max()),
                  "late_ms_mean": 1e3 * float(late.mean()),
                  "in_window": int(np.sum(t_ans[:answered] <= t0 + cell.seconds)),
                  "offered_qps": N / cell.seconds},
    )
