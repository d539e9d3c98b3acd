"""Serving launcher: batched requests against a built index or model.

    # ANN retrieval over an LGD index (the paper's serving story):
    PYTHONPATH=src python -m repro.launch.serve --mode retrieval \
        --n-items 8000 --d 16 --requests 20 --topk 10

    # same, but sharded over 4 OnlineIndex shards and served through the
    # router, with a snapshot save -> restore before serving:
    PYTHONPATH=src python -m repro.launch.serve --mode retrieval \
        --shards 4 --snapshot /tmp/idx_snap

    # LM decode micro-serving (smoke config, KV-cache decode loop):
    PYTHONPATH=src python -m repro.launch.serve --mode lm --arch gemma3-1b \
        --batch 4 --prompt-len 32 --gen 16

Retrieval serving runs on the index lifecycle subsystem (``repro.index``):
a single ``OnlineIndex`` for ``--shards 1``, the fan-out/merge
``ShardedIndex`` router above it otherwise; ``--snapshot PATH`` exercises
the versioned save/restore path before taking traffic.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.cache import enable_compile_cache


def serve_retrieval(args):
    from repro.index import OnlineIndex, ShardedIndex
    from repro.obs import JsonlTracker
    from repro.serve import retrieval
    from repro.serve.loop import ServeLoopConfig, ServingLoop

    tracker = None
    if args.trace:
        tracker = JsonlTracker(
            args.trace,
            run_meta={"launcher": "serve_retrieval", "mode": "retrieval",
                      "n_items": args.n_items, "shards": args.shards},
        )

    key = jax.random.PRNGKey(0)
    items = jax.random.normal(key, (args.n_items, args.d))
    items = items / jnp.linalg.norm(items, axis=1, keepdims=True)
    t0 = time.time()
    if args.shards > 1:
        index = ShardedIndex.build(
            items, args.shards, k=16, metric="ip", wave=512,
            key=jax.random.PRNGKey(1),
        )
        print(f"indexed {args.n_items} items over {args.shards} shards "
              f"in {time.time()-t0:.1f}s")
    else:
        index = retrieval.build_index(items, k=16, metric="ip", wave=512,
                                      key=jax.random.PRNGKey(1))
        print(f"indexed {args.n_items} items in {time.time()-t0:.1f}s")

    if args.snapshot:  # versioned save -> restore before taking traffic
        t0 = time.time()
        index.save(args.snapshot)
        cls = ShardedIndex if args.shards > 1 else OnlineIndex
        index = cls.load(args.snapshot)
        print(f"snapshot round trip ({args.snapshot}) in {time.time()-t0:.1f}s")

    if args.shards > 1:
        # router fan-out path: per-shard spans land in the trace; latency
        # is measured around the merged answer like before
        if tracker is not None:
            index.tracker = tracker
            for sh in index.shards:
                sh.tracker = tracker
        lat = []
        for r in range(args.requests):
            q = jax.random.normal(jax.random.fold_in(key, 100 + r), (4, args.d))
            t0 = time.time()
            ids, scores = index.retrieve(q, args.topk, beam=48)
            jax.block_until_ready(jnp.asarray(scores))
            lat.append(time.time() - t0)
        lat_ms = np.asarray(lat[2:]) * 1e3  # drop warmup
        print(f"{args.requests} requests: p50={np.percentile(lat_ms,50):.1f}ms "
              f"p99={np.percentile(lat_ms,99):.1f}ms")
    else:
        # single index: traffic runs through the instrumented ServingLoop —
        # pow2-coalesced waves, enqueue->synced-result latency, reservoir
        # recall audit, all reported through the tracker
        loop = ServingLoop(
            index,
            ServeLoopConfig(top_k=args.topk, beam=48, max_batch=16),
            tracker=tracker,
        )
        for r in range(args.requests):
            q = jax.random.normal(jax.random.fold_in(key, 100 + r), (4, args.d))
            loop.submit(np.asarray(q))
            loop.step()
            if r == 1:  # drop compile warmup from the reported window
                loop.reset_window()
        rec = loop.report(audit_k=min(args.topk, 10))
        print(f"{loop.served} queries in {rec['n_waves']} waves: "
              f"p50={rec['p50_latency_ms']:.1f}ms "
              f"p99={rec['p99_latency_ms']:.1f}ms qps={rec['qps']:.1f} "
              f"recall@{min(args.topk, 10)}="
              f"{rec.get(f'recall_at_{min(args.topk, 10)}', float('nan')):.3f} "
              f"scan_rate={rec['scanning_rate']:.4f}")
    if tracker is not None:
        tracker.finish()
        print(f"trace written to {args.trace}")


def serve_lm(args):
    from repro.models import transformer as tfm

    cfg = configs.get(args.arch).smoke_config()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab)
    t0 = time.time()
    logits, cache = tfm.prefill(params, prompt, cfg)
    # grow cache for generation
    cache = {
        "k": jnp.pad(cache["k"], ((0, 0), (0, 0), (0, args.gen), (0, 0), (0, 0))),
        "v": jnp.pad(cache["v"], ((0, 0), (0, 0), (0, args.gen), (0, 0), (0, 0))),
        "len": cache["len"],
    }
    decode = jax.jit(lambda p, c, t: tfm.decode_step(p, c, t, cfg))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    for _ in range(args.gen - 1):
        logits, cache = decode(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    total = args.batch * args.gen
    print(f"prefill {args.prompt_len} + decode {args.gen} tokens x {args.batch} "
          f"in {dt:.2f}s ({total/dt:.0f} tok/s); sample: "
          f"{np.asarray(jnp.stack(out, 1))[0][:8].tolist()}")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["retrieval", "lm"], default="retrieval")
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--n-items", type=int, default=8000)
    ap.add_argument("--d", type=int, default=16)
    ap.add_argument("--shards", type=int, default=1,
                    help="serve through the ShardedIndex router (>1)")
    ap.add_argument("--snapshot", type=str, default=None, metavar="PATH",
                    help="save + restore the index through a snapshot "
                         "before serving")
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--trace", type=str, default=None, metavar="PATH",
                    help="write an obs.JsonlTracker event trace "
                         "(spans + metrics) of the serving run")
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    if args.mode == "retrieval":
        serve_retrieval(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
