"""k-NN graph construction launcher (the paper's pipeline, end to end).

    PYTHONPATH=src python -m repro.launch.build_graph \
        --n 20000 --d 32 --k 20 --algo lgd --ckpt /tmp/gck --eval

Builds online (OLG/LGD), checkpointing at wave boundaries; ``--resume``
restarts from the last committed wave (fault-tolerance demo).  ``--eval``
reports graph recall vs exact ground truth and the scanning rate (Eq. 2).
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.core import brute, construct
from repro.core.graph import empty_graph
from repro.data import synthetic
from repro.launch.cache import enable_compile_cache
from repro.train import checkpoint as ckpt_lib


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--metric", default="l2")
    ap.add_argument("--kind", default="clustered", choices=list(synthetic.GENERATORS))
    ap.add_argument("--algo", default="lgd", choices=["lgd", "olg"])
    ap.add_argument("--seed-mode", default="random", choices=["random", "coarse"],
                    help="entry-point seeding for the insertion searches: "
                         "'coarse' routes through a landmark level "
                         "(core.hierarchy) — polylog scanning rate at scale")
    ap.add_argument("--coarse-landmarks", type=int, default=None, metavar="L",
                    help="landmark count for --seed-mode coarse (default ~4·√n)")
    ap.add_argument("--wave", type=int, default=512)
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "bf16", "int8", "pq"],
                    help="distance-engine representation for the insertion "
                         "searches (kernels.precision): compressed tiles "
                         "(bf16/int8) or PQ rank-then-rerank")
    ap.add_argument("--parallel-shards", type=int, default=1, metavar="S",
                    help="divide-and-conquer build: S concurrent sub-graphs "
                         "merged via core.merge.symmetric_merge (S=1: the "
                         "sequential online build)")
    ap.add_argument("--refine-rounds", type=int, default=1,
                    help="NN-Descent sweeps after the merge (parallel builds)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=8, help="waves between checkpoints")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--eval", action="store_true")
    args = ap.parse_args()

    if args.parallel_shards > 1 and args.resume:
        raise SystemExit("--resume is a sequential-build feature "
                         "(parallel builds restart their sub-builds)")

    x = synthetic.make(args.kind, jax.random.PRNGKey(0), args.n, args.d)
    cfg = construct.BuildConfig(
        k=args.k, metric=args.metric, wave=args.wave,
        lgd=(args.algo == "lgd"), beam=max(40, args.k),
        precision=args.precision,
        seed_mode=args.seed_mode, coarse_landmarks=args.coarse_landmarks,
    )

    initial = None
    if args.resume and args.ckpt and os.path.exists(os.path.join(args.ckpt, "manifest.json")):
        like = empty_graph(args.n, args.k, cfg.rev_cap or 2 * args.k)
        g0, _ = ckpt_lib.restore_graph(args.ckpt, like)
        initial = (g0, int(g0.n_valid))
        print(f"resumed with {int(g0.n_valid)} rows already committed")

    def cb(widx, g):
        ckpt_lib.save_graph(args.ckpt, g, int(g.n_valid), cfg.__dict__)
        print(f"  wave {widx}: checkpointed at row {int(g.n_valid)}", flush=True)

    t0 = time.time()
    if args.parallel_shards > 1:
        if args.ckpt:
            print("note: periodic wave checkpoints do not apply to parallel "
                  "builds; only the final graph is saved to --ckpt")
        g, stats = construct.build_parallel(
            x, cfg, jax.random.PRNGKey(1),
            shards=args.parallel_shards,
            refine_rounds=args.refine_rounds,
        )
    else:
        g, stats = construct.build(
            x, cfg, jax.random.PRNGKey(1),
            wave_callback=cb if args.ckpt else None,
            callback_stride=args.ckpt_every,
            initial=initial,
        )
    dt = time.time() - t0
    c = construct.scanning_rate(stats, args.n)
    mode = (f"{args.parallel_shards}-shard parallel"
            if args.parallel_shards > 1 else "sequential")
    print(f"built {args.algo.upper()} graph ({mode}): n={args.n} d={args.d} "
          f"k={args.k} metric={args.metric} in {dt:.1f}s, "
          f"scanning rate c={c:.5f}")
    if args.ckpt:
        ckpt_lib.save_graph(args.ckpt, g, args.n, cfg.__dict__)

    if args.eval:
        tids, _ = brute.brute_force_knn(
            x, x, args.k, args.metric,
            exclude_ids=jnp.arange(args.n, dtype=jnp.int32))
        r1 = float(brute.recall_at_k(g.nbr_ids[:, :1], tids[:, :1], 1))
        rk = float(brute.recall_at_k(g.nbr_ids, tids, args.k))
        print(f"graph recall@1={r1:.4f} recall@{args.k}={rk:.4f}")


if __name__ == "__main__":
    main()
