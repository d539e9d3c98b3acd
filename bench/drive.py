"""The kinds of traffic a mix can name, each driven through the program.

A driver gets the run's ``Cell`` (configuration, mix, seed, seconds) and
returns an ``Outcome``: the end-to-end numbers, the numbers compared with the
reference and their limits, and what the per-layer readers read.  Each has
three phases:

* set-up: make the data on the device, build what the window needs, and run
  every program the window will use once;
* the window, inside the ``bench.window`` host span, timed by the host clock
  around results pulled back to the host;
* the check, after the window: a sample of the answers, drawn from the seed,
  against the float64 exact scan of ``bench/reference.py``.

The catalog comes from the configuration's own data seed, the same in every
run; ``--seed`` orders it and picks the queries, the build keys and the
rows checked.  Every seed so does the same amount of work on the same rows.

``program=False`` puts the reference's control in the program's place (see
``bench/reference.py``); the benchmark's own runs never do.
"""

from __future__ import annotations

import dataclasses
import sys
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from bench import data as data_lib
from bench import reference as ref


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool = False
    program: bool = True  # False: the control in the program's place


@dataclasses.dataclass
class Outcome:
    metrics: dict  # end-to-end name -> value
    attempted: int
    failed: int
    checks: dict  # name -> (value, op, limit)
    readings: dict  # what the per-layer readers read


def build_config(config: dict):
    from repro.core.construct import BuildConfig

    return BuildConfig(**config["build_config"])


def make_rows(cell: Cell, n_rows: int):
    """The configuration's ``n_rows`` rows, the same for every ``--seed``."""
    cfg = cell.config
    key = data_lib.seed_key(cfg["data"]["seed"])
    return jax.block_until_ready(data_lib.make(cfg["data"], key, n_rows, cfg["d"]))


def build_index(cell: Cell, rows, key):
    from repro.index import OnlineIndex

    t = time.perf_counter()
    index = OnlineIndex.build(rows, build_config(cell.config), key=key)
    jax.block_until_ready(index.graph)
    print(f"build of {rows.shape[0]} rows: {time.perf_counter() - t:.3f} s",
          file=sys.stderr, flush=True)
    return index


def passes(value, op: str, limit) -> bool:
    """Whether a number compared keeps to its limit."""
    return value >= limit if op == ">=" else value <= limit


# ---------------------------------------------------------------------------
# batch: closed loop, one client, fixed batches of held-out queries
# ---------------------------------------------------------------------------


def run_batch(cell: Cell, ready: Callable[[], None]) -> Outcome:
    cfg, mix = cell.config, cell.mix
    n, B, P, k = cfg["n"], mix["batch"], mix["pool_batches"], mix["top_k"]
    rows = make_rows(cell, n + B * P)
    catalog = rows[:n]
    # the seed deals the fixed pool of held-out queries into batches
    order = np.random.default_rng([cell.seed, 11]).permutation(B * P)
    pool = rows[n:]
    batches = jax.block_until_ready(
        [pool[jnp.asarray(order[i * B : (i + 1) * B])] for i in range(P)])
    if cell.program:
        index = build_index(cell, catalog, data_lib.seed_key(cfg["data"]["seed"], 1))
    else:
        index = ref.ControlIndex(catalog, cfg["metric"])

    def search(q):
        res = index.search(q, k, beam=mix["beam"])
        return np.asarray(res.ids), np.asarray(res.dists), np.asarray(res.n_iters)

    search(batches[0])  # the window's one shape
    ready()
    done, call_s = [], []
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.search"):
                done.append(search(batches[len(done) % P]))
            call_s.append(time.perf_counter() - t)
            if time.perf_counter() - t0 >= cell.seconds:
                break
    t1 = time.perf_counter()
    n_q = len(done) * B

    rng = np.random.default_rng([cell.seed, 12])
    pick = np.sort(rng.choice(n_q, min(mix["check_queries"], n_q), replace=False))
    host = np.asarray(rows)
    x = host[:n]
    qrows = host[n + order[(pick // B) % P * B + pick % B]]
    ids = np.stack([done[i // B][0][i % B] for i in pick])
    dists = np.stack([done[i // B][1][i % B] for i in pick])
    true = ref.exact_topk(cfg["metric"], qrows, x, k)
    lim = cfg["limits"]
    rec = ref.recall(ids, true, k)
    all_ids = np.stack([d[0] for d in done])
    checks = {
        "recall_at_10": (rec, ">=", lim["recall_at_10"]),
        "dist_err": (ref.max_dist_err(cfg["metric"], qrows, x, ids, dists), "<=", lim["dist_err"]),
        "bad_ids": (ref.bad_ids(all_ids.reshape(-1, k), n), "<=", lim["bad_ids"]),
    }
    return Outcome(
        metrics={"search_qps": n_q / (t1 - t0), "recall_at_10": rec},
        attempted=n_q, failed=0, checks=checks,
        readings={"searches": [d[2] for d in done], "holes": ref.holes(all_ids),
                  "calls": len(done), "call_s_min": min(call_s), "call_s_max": max(call_s)},
    )


# ---------------------------------------------------------------------------
# build: whole builds of the catalog, back to back
# ---------------------------------------------------------------------------


class _FirstWave(Exception):
    """Stops a warm-up build once its first wave has committed."""


def _lists(g, n: int, pick):
    """(nbr_ids, nbr_dist, committed rows) of a built graph's ``pick`` rows."""
    committed = jnp.sum(g.alive[:n] & (jnp.arange(n) < g.n_valid))
    return g.nbr_ids[pick], g.nbr_dist[pick], committed


def run_build(cell: Cell, ready: Callable[[], None]) -> Outcome:
    from repro.core import construct

    cfg, mix = cell.config, cell.mix
    n = cfg["n"]
    catalog = make_rows(cell, n)
    rng = np.random.default_rng([cell.seed, 13])
    bcfg = build_config(cfg)

    def catalog_order(j: int):
        """The catalog's rows in the order of this seed's ``j``-th build."""
        perm = jnp.asarray(np.random.default_rng([cell.seed, 14, j]).permutation(n))
        return jax.block_until_ready(catalog[perm])

    def one(j: int):
        rows = catalog_order(j)
        sample = np.sort(rng.choice(n, mix["check_rows"], replace=False))
        pick = jnp.asarray(sample)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.build"):
            if cell.program:
                g = build_index(cell, rows, data_lib.seed_key(cell.seed, 200 + j)).graph
                lists = _lists(g, n, pick)
            else:
                ids, dists = ref.control_graph(rows, bcfg.k, cfg["metric"])
                lists = (ids[pick], dists[pick], jnp.asarray(n))
            jax.block_until_ready(lists)
        return time.perf_counter() - t, rows, sample, lists

    # set-up: the build's programs, traced and loaded by its first wave (a
    # whole build, once they are in the compile cache, reads as any other)
    rows = catalog_order(0)
    if cell.program:
        def first_wave(_, g):
            jax.block_until_ready(_lists(g, n, jnp.arange(mix["check_rows"])))
            raise _FirstWave

        try:
            construct.build(rows, bcfg, data_lib.seed_key(cell.seed, 200),
                            return_coarse=True, wave_callback=first_wave)
        except _FirstWave:
            pass
    else:
        one(0)
    ready()
    built, times = [], []
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            dt, *b = one(len(built) + 1)
            built.append(b)
            times.append(dt)
            if time.perf_counter() - t0 >= cell.seconds:
                break

    k = mix["graph_k"]
    metric = cfg["metric"]
    recs, errs, bad, uncommitted, holes = [], [], 0, 0, 0
    for rows, sample, (ids, dists, committed) in built:
        x = np.asarray(rows)
        ids, dists = np.asarray(ids), np.asarray(dists)
        true = ref.exact_topk(metric, x[sample], x, k, self_ids=sample)
        recs.append(ref.recall(ids, true, k))
        errs.append(ref.max_dist_err(metric, x[sample], x, ids, dists))
        bad += ref.bad_ids(ids, n, self_ids=sample)
        holes += ref.holes(ids)
        uncommitted += n - int(committed)
    lim = cfg["limits"]
    rec = float(np.mean(recs))
    checks = {
        "recall_at_10": (rec, ">=", lim["recall_at_10"]),
        "dist_err": (max(errs), "<=", lim["dist_err"]),
        "bad_ids": (bad, "<=", lim["bad_ids"]),
        "uncommitted_rows": (uncommitted, "<=", lim["uncommitted_rows"]),
    }
    return Outcome(
        metrics={"build_rows_per_s": n * len(built) / sum(times), "recall_at_10": rec},
        attempted=n * len(built), failed=uncommitted, checks=checks,
        readings={"builds": len(built), "build_s": times, "holes": holes},
    )


DRIVERS = {"batch": run_batch, "build": run_build}
