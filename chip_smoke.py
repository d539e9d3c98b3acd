#!/usr/bin/env python3
"""Bring-up smoke test: the online k-NN index end to end on a TPU.

    python chip_smoke.py    # one chip: build, engines, serve, churn

An ``OnlineIndex`` is built through its normal constructor at the shape of
ANN-Benchmarks ``sift-128-euclidean`` (d=128, l2; 131,072 of its 1,000,000
base rows, see ``N_BASE``) from clustered synthetic data
(``repro.data.synthetic``, seeded by ``--seed``), with the ``knn-lgd`` full
config (k=20, wave=4096, beam=40, LGD), coarse seeding and
``dispatch="auto"``.  Then:

  * engines — which engine each op runs (the dispatch predicates, and the
    Pallas kernels found in the traced search program, compiled or
    interpreted), and the compiled kernels' distances against a float64
    NumPy oracle;
  * serve — held-out queries through ``ServingLoop`` in pow2 waves, with
    recall@10 of a sample audited against the exact scan (itself checked
    against a float64 scan) and held to 0.90;
  * churn — 4,096 removals and 4,096 insertions, searches and a compaction:
    removed rows never come back and new rows are found; recall@10 after
    the churn is printed, not held to the floor (compaction costs recall,
    see ROADMAP).

Each phase prints one line with its wall time and, apart, the part of it
spent compiling.  Latencies are a smoke check, not benchmark numbers.  Any
failed check raises; the last line of standard output, printed only when
every phase passed, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script refuses to run anywhere but on a TPU, runs in one process, and
starts none.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
D = 128  # sift-128-euclidean
# Rows of the one-chip index.  The sift-128 catalog has 1,000,000 rows.  On
# one v5e a build wave of the knn-lgd config took 3.64 s on average at
# 131,072 rows (search 2.10 s; the commit, 1.75 s, re-sorts the whole graph,
# so it grows with n), and the run compiled for ~300 s at any n; the whole
# smoke took 458 s.  That projects 2^18 rows to ~740 s of the smoke's 1200 s
# and 2^19 to ~1450 s.  2^18 has not been run on a chip, so the smoke keeps
# 2^17, the largest n measured to pass; pass --n 262144 to try 2^18.
N_BASE = 131_072
N_QUERIES = 1024  # held-out queries served through the loop
WAVE = 256  # pow2 serving wave
AUDIT_EVERY = 4  # the recall audit samples every 4th served query
N_CHURN = 4096  # rows removed and rows added
K = 10  # recall@K
RECALL_FLOOR = 0.90
N_F64 = 32  # queries checked against the float64 exact scan


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, as it reports them."""

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.total += secs


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    """Run one phase; print its line with wall and compile time apart, and
    what it measured — also when a check fails, before the error escapes."""
    info: dict = {}
    t0, c0 = time.perf_counter(), clock.total
    status = "FAILED"
    try:
        yield info
        status = "ok"
    finally:
        wall = time.perf_counter() - t0
        fields = " ".join(f"{k}={v}" for k, v in info.items())
        print(
            f"[{name}] {status} wall_s={wall:.3f} "
            f"compile_s={clock.total - c0:.3f} {fields}",
            flush=True,
        )


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def f64_l2(q: np.ndarray, x: np.ndarray) -> np.ndarray:
    """All-pairs squared l2 distances in float64."""
    q = q.astype(np.float64)
    x = x.astype(np.float64)
    return (np.sum(q * q, axis=1)[:, None] + np.sum(x * x, axis=1)[None, :]
            - 2.0 * q @ x.T)


def f64_topk(q: np.ndarray, x: np.ndarray, k: int, chunk: int = 65536):
    """Exact top-k ids over all rows of x, float64 on the host."""
    q64 = q.astype(np.float64)
    qn = np.sum(q64 * q64, axis=1, keepdims=True)
    best_d = np.full((q.shape[0], 0), np.inf)
    best_i = np.zeros((q.shape[0], 0), np.int64)
    for lo in range(0, x.shape[0], chunk):
        xc = x[lo : lo + chunk].astype(np.float64)
        dc = qn + np.sum(xc * xc, axis=1)[None, :] - 2.0 * q64 @ xc.T
        ic = np.broadcast_to(np.arange(lo, lo + xc.shape[0]), dc.shape)
        cat_d = np.concatenate([best_d, dc], axis=1)
        cat_i = np.concatenate([best_i, ic], axis=1)
        sel = np.argsort(cat_d, axis=1, kind="stable")[:, :k]
        best_d = np.take_along_axis(cat_d, sel, axis=1)
        best_i = np.take_along_axis(cat_i, sel, axis=1)
    return best_i


def recall(pred: np.ndarray, true: np.ndarray, k: int) -> float:
    hits = sum(len(set(p[:k]) & set(t[:k])) for p, t in zip(pred, true))
    return hits / (len(true) * k)


def pallas_kernels(jax, fn, *args, **kw) -> dict:
    """Pallas kernels in the traced program of ``fn``: name -> compiled?"""
    found: dict = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = not eqn.params["interpret"]
                continue
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    if hasattr(sub, "eqns"):
                        walk(sub)
                    elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                        walk(sub.jaxpr)

    walk(jax.make_jaxpr(fn, static_argnums=())(*args, **kw).jaxpr)
    return found


def device_phase(jax, clock, cache_dir: str) -> dict:
    with phase("device", clock) as info:
        devs = jax.devices()
        dev = {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        }
        info.update(
            platform=dev["platform"], kind=repr(dev["kind"]),
            count=dev["count"], jax=jax.__version__, compile_cache=cache_dir,
        )
        if dev["platform"] != "tpu":
            print(f"[device] no TPU: JAX found {dev['platform']}; refusing to "
                  f"run anywhere else", file=sys.stderr)
            sys.exit(3)
    return dev


def make_data(jax, seed: int, n: int, extra: int):
    """Base rows plus ``extra`` held-out rows of the same distribution."""
    from repro.data import synthetic

    return synthetic.make("clustered", jax.random.PRNGKey(seed), n + extra, D)


def build_config():
    from repro.configs import knn_lgd

    return dataclasses.replace(
        knn_lgd.full_config(), seed_mode="coarse", dispatch="auto"
    )


def search_chunks(jax, index, q: np.ndarray, beam: int) -> np.ndarray:
    """Ids of a graph search over ``q`` in serving-wave chunks."""
    out = []
    for lo in range(0, q.shape[0], WAVE):
        res = index.search(jax.numpy.asarray(q[lo : lo + WAVE]), K, beam=beam)
        out.append(np.asarray(res.ids))
    return np.concatenate(out)


def one_chip(args, jax, clock, cache_dir) -> dict:
    import jax.numpy as jnp

    from repro.core import brute
    from repro.core import search as search_lib
    from repro.index import OnlineIndex
    from repro.kernels import ops
    from repro.serve.loop import ServeLoopConfig, ServingLoop

    dev = device_phase(jax, clock, cache_dir)
    n = args.n
    cfg = build_config()

    with phase("build", clock) as info:
        data = make_data(jax, args.seed, n, N_QUERIES + WAVE + N_CHURN)
        base = data[:n]
        held = np.asarray(data[n:])
        queries, warm, fresh = np.split(held, [N_QUERIES, N_QUERIES + WAVE])
        jax.block_until_ready(base)
        t0 = time.perf_counter()
        index = OnlineIndex.build(base, cfg, key=jax.random.PRNGKey(args.seed + 1))
        jax.block_until_ready(index.graph)
        build_s = time.perf_counter() - t0
        check(int(index.graph.n_valid) == n, "every base row committed")
        check(bool(jnp.all(index.graph.alive[:n])), "every base row alive")
        info.update(
            n=n, d=D, k=cfg.k, wave=cfg.wave, beam=cfg.beam,
            seed_mode=cfg.seed_mode, build_s=f"{build_s:.3f}",
            waves=-(-(n - cfg.n_seed_init) // cfg.wave),
        )

    beam = cfg.beam
    with phase("engines", clock) as info:
        eng = ops.engines(cfg.dispatch, index.items.dtype, D)
        scfg = index.search_config(K, beam)
        q0 = jnp.asarray(queries[:WAVE])
        traced = pallas_kernels(
            jax, lambda g, x, q, key, c: search_lib.search(g, x, q, key, scfg, coarse=c),
            index.graph, index.items, q0, jax.random.PRNGKey(0), index.coarse,
        )
        info.update(**{f"engine.{k}": v for k, v in eng.items()})
        info.update(**{f"traced.{k}": "compiled" if v else "interpret"
                       for k, v in traced.items()})
        check(eng["gather_distance"] == "pallas", "auto runs the gather kernel")
        check(traced.get("gather_distance") is True,
              "the search program runs the compiled gather kernel")
        # the compiled kernels against a float64 oracle (test_norm_cache's
        # fp32 tolerances), plus an error bound scaled by the norms that a
        # bf16-pass contraction would break and a full-fp32 one keeps
        rng = np.random.default_rng(args.seed)
        idx = rng.integers(-1, n, size=(WAVE, 60)).astype(np.int32)
        x_host = np.asarray(index.items[:n])
        sq = index.graph.sq_norms
        got_g = np.asarray(ops.gather_distance(
            q0, index.items, jnp.asarray(idx), "l2", sq_norms=sq,
            dispatch=cfg.dispatch,
        ))
        want_g = np.where(
            idx >= 0,
            np.sum((queries[:WAVE, None, :].astype(np.float64)
                    - x_host[np.maximum(idx, 0)].astype(np.float64)) ** 2, -1),
            np.inf,
        )
        tile = x_host[:8192]
        got_p = np.asarray(ops.pairwise_distance(
            q0, jnp.asarray(tile), "l2", x_sq_norms=sq[:8192],
            dispatch=cfg.dispatch,
        ))
        want_p = f64_l2(queries[:WAVE], tile)
        qn = np.sum(queries[:WAVE].astype(np.float64) ** 2, axis=1)
        xn = np.sum(x_host.astype(np.float64) ** 2, axis=1)
        live = idx >= 0
        check(np.all(np.isinf(got_g[~live])), "padding lanes are +inf")
        for name, got, want, scale in (
            ("gather", got_g[live], want_g[live],
             (qn[:, None] + xn[np.maximum(idx, 0)])[live]),
            ("pairwise", got_p.ravel(), want_p.ravel(),
             (qn[:, None] + xn[None, :8192]).ravel()),
        ):
            np.testing.assert_allclose(got, want, rtol=1e-3, atol=2e-3 * D)
            err = float(np.max(np.abs(got - want) / scale))
            info[f"{name}_max_err_over_norms"] = f"{err:.3e}"
            check(err < 1e-4, f"{name} distances contract at full fp32")

    with phase("serve", clock) as info:
        loop = ServingLoop(
            index,
            ServeLoopConfig(
                top_k=K, beam=beam, max_batch=WAVE,
                recall_reservoir=N_QUERIES // AUDIT_EVERY,
                recall_sample_every=AUDIT_EVERY,
            ),
            seed=args.seed,
        )
        loop.submit(warm)
        loop.pump()  # compiles the wave shape
        loop.reset_window()
        for lo in range(0, N_QUERIES, WAVE):
            loop.submit(queries[lo : lo + WAVE])
            loop.pump()
        rec = loop.report(audit_k=K)
        check(rec["n_served"] == N_QUERIES, "every query served")
        info.update(
            queries=rec["n_served"], waves=rec["n_waves"],
            recall_at_10=f"{rec['recall_at_10']:.4f}",
            recall_at_10_served=f"{rec['recall_at_10_served']:.4f}",
            scanning_rate=f"{rec['scanning_rate']:.6f}",
            smoke_p50_ms=f"{rec['p50_latency_ms']:.3f}",
            smoke_p99_ms=f"{rec['p99_latency_ms']:.3f}",
        )
        check(rec["recall_at_10"] >= RECALL_FLOOR, "fresh recall@10 >= 0.90")
        check(rec["recall_at_10_served"] >= RECALL_FLOOR,
              "served recall@10 >= 0.90")
        # the audit's exact scan is itself exact: the chip's brute force
        # (reference engine, HIGHEST precision; the audit's own call) against
        # float64 on the host
        true_ids, _ = brute.brute_force_knn(
            index.items, q0, K, "l2",
            n_valid=index.graph.n_valid, alive=index.graph.alive,
            dispatch="reference",
        )
        agree = recall(np.asarray(true_ids)[:N_F64],
                       f64_topk(queries[:N_F64], x_host, K), K)
        info["exact_scan_vs_f64"] = f"{agree:.4f}"
        check(agree >= 0.99, "the exact scan agrees with float64")

    with phase("churn", clock) as info:
        rng = np.random.default_rng(args.seed + 2)
        victims = np.sort(rng.choice(n, N_CHURN, replace=False))
        victim_vecs = x_host[victims]
        loop.remove(victims)
        check(index.n_items == n - N_CHURN, "removals counted")
        got = search_chunks(jax, index, victim_vecs, beam)
        check(not np.isin(got, victims).any(), "removed ids never returned")
        loop.add(fresh)  # buffered; lands at the next wave boundary
        loop.submit(queries[:WAVE])
        loop.pump()  # flush: reclaims the freed slots, then one insert wave
        check(index.n_items == n, "insertions counted")
        check(int(index.graph.n_valid) == n, "freed slots were recycled")
        new_ids = np.arange(n - N_CHURN, n)
        check(np.array_equal(np.asarray(index.items[n - N_CHURN : n]), fresh),
              "new rows hold the added vectors")
        found = float(np.mean(
            np.any(search_chunks(jax, index, fresh, beam) == new_ids[:, None], 1)
        ))
        info["new_found"] = f"{found:.4f}"
        check(found >= RECALL_FLOOR, "new ids retrievable")

        def removed_rows_absent():
            ids = search_chunks(jax, index, victim_vecs, beam)
            rows = np.asarray(index.items[jnp.asarray(np.maximum(ids, 0))])
            return not np.any(np.all(rows == victim_vecs[:, None, :], axis=-1))

        check(removed_rows_absent(), "removed rows never returned after reuse")
        index.compact()
        check(index.n_items == n and index.free_slots == 0, "compacted")
        check(removed_rows_absent(), "removed rows never returned after compact")
        found = float(np.mean(np.any(
            search_chunks(jax, index, fresh, beam)
            == index.last_compact_map[new_ids][:, None], 1,
        )))
        rec = loop.audit_recall(k=K)
        info.update(
            removed=N_CHURN, added=N_CHURN, new_found_after_compact=f"{found:.4f}",
            recall_at_10_after_churn=f"{rec['recall_at_10']:.4f}",
        )
        check(found >= RECALL_FLOOR, "new ids retrievable after compact")
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=N_BASE,
                    help=f"base rows (default {N_BASE})")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    clock = CompileClock(jax)
    dev = one_chip(args, jax, clock, cache_dir)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
