"""Benchmark driver: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--n N]

Sections:
  Table IV  bench_brute            exhaustive-search timing
  Fig 5     bench_search_baseline  EHC vs HC, approx vs true graph
  Fig 6/7 + Table II  bench_construction  recall vs dim, scanning rates
  Table III bench_datasets         per-dataset scanning rate + recall
  Fig 9/10  bench_search           recall vs speed-up over brute
  §IV-D     bench_refine           local-join refinement rounds
  §IV-C     bench_lifecycle        sustained churn (insert/remove/query)

The dry-run/roofline numbers (EXPERIMENTS.md §Dry-run/§Roofline) come from
``repro.launch.dryrun`` — they need the 512-device XLA flag and therefore a
fresh interpreter, not this driver.
"""

from __future__ import annotations

import argparse
import os
import time


def main():
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=5_000,
                    help="dataset size (paper scale: 100k+; default fits CI)")
    ap.add_argument("--quick", action="store_true", help="tiny sizes, smoke only")
    ap.add_argument("--ci-out", type=str, default=None, metavar="PATH",
                    help="write the machine-readable benchmark record "
                         "(BENCH_ci.json) for benchmarks.ci_gate")
    ap.add_argument("--hier", action="store_true",
                    help="include the hierarchical-seeding gate "
                         "(bench_search.hier_gate, n=10^5 — minutes-long; "
                         "bench-smoke CI only, never tier-1)")
    ap.add_argument("--hier-n", type=int, default=100_000, metavar="N",
                    help="dataset size for --hier (floors are calibrated at "
                         "the canonical 100000)")
    ap.add_argument("--precision", action="store_true",
                    help="include the compressed-engine gate "
                         "(bench_search.run_precision: int8 gather speedup "
                         "at n=2^17/d=256/C=512 + PQ rank-then-rerank recall "
                         "delta — large-allocation bench, opt-in like --hier)")
    ap.add_argument("--parallel", action="store_true",
                    help="include the parallel-build gate "
                         "(bench_construction.parallel_gate: build_parallel "
                         "vs build at n=4000/d=20 — wallclock_ratio ceiling-"
                         "gated below 1.0 AND merged recall@10 floor-gated; "
                         "opt-in like --hier, bench-smoke runs it)")
    ap.add_argument("--serving", action="store_true",
                    help="include the sustained-load serving gate "
                         "(bench_serving.serving_gate: ServingLoop under "
                         "interleaved query bursts + churn; recall@10 "
                         "floored, p99/p50 ratio ceiling-gated, latency/QPS "
                         "recorded; writes the tracker JSONL trace next to "
                         "--ci-out)")
    args = ap.parse_args()
    n = 2000 if args.quick else args.n

    from benchmarks import (
        bench_brute,
        bench_construction,
        bench_datasets,
        bench_lifecycle,
        bench_refine,
        bench_search,
        bench_search_baseline,
        bench_serving,
        common,
    )

    t0 = time.time()
    # the compressed-engine gate allocates a 256 MB fp32 table plus its
    # bf16/int8 companions, so it is opt-in like --hier; it is also measured
    # FIRST, before the suite churns the allocator and LLC — the gated
    # quantity is a DRAM-bandwidth ratio, and measuring it against a clean
    # memory system is the reproducible ordering (when the record is present
    # ci_gate always applies both its bounds)
    precision = (bench_search.run_precision()
                 if args.precision and args.ci_out else None)
    tables = {}
    tables["brute"] = bench_brute.run(
        n, datasets=bench_brute.DATASETS[: 2 if args.quick else 4])
    tables["search_baseline"] = bench_search_baseline.run(n)
    tables["construction"] = bench_construction.run(
        n, dims=(2, 5) if args.quick else (2, 5, 10, 20))
    tables["datasets"] = bench_datasets.run(
        n, datasets=bench_datasets.DATASETS[: 2 if args.quick else 4])
    tables["search"] = bench_search.run(
        n, datasets=bench_search.DATASETS[: 1 if args.quick else 3])
    tables["refine"] = bench_refine.run(n, rounds=1 if args.quick else 3)
    tables["lifecycle"] = bench_lifecycle.run(
        min(n, 2000), rounds=3 if args.quick else 6)

    if args.ci_out:
        # gate metrics run at their FIXED canonical shapes (n=5k/d=20 for the
        # expansion kernel, n=2k/d=20 for build quality, n=8192 with
        # d∈{16,64,256} x C∈{32,128,512} for the distance engine),
        # independent of --n, so the committed baseline stays comparable
        expansion = bench_search.run_expansion()
        quality = bench_construction.quality_gate()
        gather_engine = bench_search.run_gather_engine()
        lifecycle_churn = bench_lifecycle.churn_gate()
        merge_build = bench_construction.merge_build_gate()
        # the hierarchical-seeding gate runs at paper scale (n=10^5) and is
        # therefore opt-in: the bench-smoke CI job passes --hier; quick local
        # --ci-out runs skip it and ci_gate tolerates the absent record
        hier = bench_search.hier_gate(n=args.hier_n) if args.hier else None
        # the serving gate drives the instrumented ServingLoop and writes its
        # JsonlTracker trace next to the CI artifact (uploaded together by
        # the bench-smoke job); opt-in with the same absent-record rule
        # the parallel-build gate times build_parallel against build at its
        # tuned shape (median-of-5, both pipelines warmed); opt-in with the
        # same absent-record rule
        parallel = (bench_construction.parallel_gate()
                    if args.parallel else None)
        serving = None
        if args.serving:
            trace_path = os.path.splitext(args.ci_out)[0] + "_trace.jsonl"
            serving = bench_serving.serving_gate(trace_path=trace_path)
            print(f"wrote {trace_path}")
        payload = {
            "expansion": expansion[16],  # serving batch — the gated record
            "expansion_wave": expansion[256],  # construction wave — recorded
            "quality": quality,
            "gather_engine": gather_engine,  # blocked-vs-rowwise (gated)
            # sustained-churn record: recall gated, throughput informational
            "lifecycle_churn": lifecycle_churn,
            # divide-and-conquer build: merged+refined recall gated at the
            # sequential floor, wall-clock ratio informational
            "merge_build": merge_build,
            "sections": {
                name: t.records()
                for name, t in tables.items()
                if hasattr(t, "records")
            },
        }
        if hier is not None:
            # coarse-seeding quality at n=10^5: recall AND scanning rate
            # both gated; the random-seed baseline rides along inside
            payload["hier_gate"] = hier
        if parallel is not None:
            # divide-and-conquer build, tuned path: wallclock_ratio gated
            # as a CEILING (< 1.0 = parallel actually wins) AND merged
            # recall@10 gated at the same 0.95 floor as merge_build
            payload["parallel_gate"] = parallel
        if precision is not None:
            # compressed engine: int8 gather speedup floor-gated, PQ
            # rank-then-rerank recall delta ceiling-gated; bf16 informational
            payload["precision_gate"] = precision
        if serving is not None:
            # sustained-load serving: recall@10 floored, p99/p50 ratio
            # ceiling-gated (harness sanity); latency + QPS informational
            payload["serving_load"] = serving
        common.emit_json(args.ci_out, payload)
        print(f"wrote {args.ci_out}")
    print(f"\nall benchmarks done in {time.time() - t0:.0f}s (n={n})")


if __name__ == "__main__":
    main()
