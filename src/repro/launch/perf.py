import os

if __name__ == "__main__":  # a process of its own: never on import
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb driver: compile variants of the three chosen cells on the
single-pod production mesh and record roofline deltas.

    PYTHONPATH=src python -m repro.launch.perf --cell gemma-decode
    PYTHONPATH=src python -m repro.launch.perf --cell mixtral-train
    PYTHONPATH=src python -m repro.launch.perf --cell knn-search

Each cell runs {baseline, variants...} and appends JSON records to
perf_results.json — the §Perf before/after evidence.
"""

import argparse
import dataclasses
import json
import time

import jax

from repro.configs import cells
from repro.launch import mesh as mesh_lib
from repro.launch import roofline


def measure(cell, mesh, tag):
    t0 = time.time()
    with mesh:
        comp = cells.lower(cell).compile()
    rec = roofline.analyze(comp, mesh, model_flops=cell.model_flops,
                           loop_factor=cell.loop_factor)
    rec.update(arch=cell.arch, shape=cell.shape, variant=tag,
               wall_s=round(time.time() - t0, 1), notes=cell.notes)
    print(f"[{tag}] t_comp={rec['t_compute_s']:.4f}s t_mem={rec['t_memory_s']:.4f}s "
          f"t_coll={rec['t_collective_s']:.4f}s dom={rec['dominant']} "
          f"peak={rec['bytes_per_device']/2**30:.2f}GiB "
          f"roofline_frac={rec.get('roofline_fraction', float('nan')):.4f}",
          flush=True)
    return rec


def gemma_decode(mesh):
    out = []
    out.append(measure(cells.plan("gemma3-1b", "decode_32k", mesh), mesh, "baseline-dense-cache"))
    out.append(measure(cells.plan("gemma3-1b", "decode_32k", mesh,
                                  opts={"split_cache": True}), mesh, "ring-local-cache"))
    out.append(measure(cells.plan("gemma3-1b", "long_500k", mesh), mesh, "long500k-baseline"))
    out.append(measure(cells.plan("gemma3-1b", "long_500k", mesh,
                                  opts={"split_cache": True}), mesh, "long500k-ring"))
    return out


def mixtral_train(mesh):
    out = []
    out.append(measure(cells.plan("mixtral-8x7b", "train_4k", mesh), mesh, "baseline"))
    # variant: sequence-parallel residual stream (Megatron-SP): h sharded on
    # S over 'model' between blocks -> memory + smaller boundary collectives
    from repro.models import transformer as tfm
    import repro.configs.mixtral_8x7b as mix

    orig = mix.full_config
    try:
        mix.full_config = lambda: dataclasses.replace(orig(), seq_shard=True)
        out.append(measure(cells.plan("mixtral-8x7b", "train_4k", mesh), mesh,
                           "seq-parallel-h"))
    finally:
        mix.full_config = orig
    # variant: ring cache for decode shapes rides the SWA window
    out.append(measure(cells.plan("mixtral-8x7b", "long_500k", mesh), mesh,
                       "long500k-baseline"))
    out.append(measure(cells.plan("mixtral-8x7b", "long_500k", mesh,
                                  opts={"split_cache": True}), mesh, "long500k-ring"))
    return out


def knn_search(mesh):
    out = []
    out.append(measure(cells.plan("knn-lgd", "search_4k", mesh), mesh, "baseline"))
    import repro.configs.knn_lgd as kl

    orig = kl.full_config
    try:
        # variant: bf16 candidate storage (distance accumulation stays f32)
        kl.full_config = lambda: dataclasses.replace(orig(), data_bf16=True)
        out.append(measure(cells.plan("knn-lgd", "search_4k", mesh), mesh, "bf16-data"))
    finally:
        kl.full_config = orig
    try:
        # variant: leaner beam/hash (quality measured separately on CPU)
        kl.full_config = lambda: dataclasses.replace(
            orig(), beam=24, hash_slots=1024)
        out.append(measure(cells.plan("knn-lgd", "search_4k", mesh), mesh,
                           "beam24-hash1024"))
    finally:
        kl.full_config = orig
    return out


CELLS = {
    "gemma-decode": gemma_decode,
    "mixtral-train": mixtral_train,
    "knn-search": knn_search,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, choices=list(CELLS))
    ap.add_argument("--out", default="perf_results.json")
    args = ap.parse_args()
    mesh = mesh_lib.make_production_mesh(multi_pod=False)
    recs = CELLS[args.cell](mesh)
    existing = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            existing = json.load(f)
    with open(args.out, "w") as f:
        json.dump(existing + recs, f, indent=1, default=str)
    print(f"appended {len(recs)} records to {args.out}")


if __name__ == "__main__":
    main()
