import os

if __name__ == "__main__":  # a process of its own: never on import
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be the very first lines above: jax locks the device count on first
init, and the production meshes need 512 placeholder host devices.

For every cell this prints/records:
  * compiled.memory_analysis()  — proves the program fits per-device HBM;
  * compiled.cost_analysis()    — HLO FLOPs / bytes for the roofline;
  * collective bytes parsed from the optimized HLO (all-gather, all-reduce,
    reduce-scatter, all-to-all, collective-permute) — cost_analysis does not
    report them;
  * the three roofline terms + dominant bottleneck (§Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # all cells, both meshes
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh single    # 16x16 only
  PYTHONPATH=src python -m repro.launch.dryrun --knn            # include the paper's cells
  PYTHONPATH=src python -m repro.launch.dryrun --out results.json
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro import configs
from repro.configs import cells
from repro.launch import mesh as mesh_lib
from repro.launch import roofline


def run_cell(arch: str, shape: str, mesh, mesh_name: str, skip_reason=None,
             lower_only: bool = False) -> dict:
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name}
    if skip_reason:
        rec["status"] = "skipped"
        rec["reason"] = skip_reason
        return rec
    t0 = time.time()
    try:
        cell = cells.plan(arch, shape, mesh)
        with mesh:
            lowered = cells.lower(cell)
            if lower_only:
                rec["status"] = "lowered"
                rec["wall_s"] = round(time.time() - t0, 1)
                return rec
            compiled = lowered.compile()
        rec.update(roofline.analyze(
            compiled, mesh, model_flops=cell.model_flops,
            loop_factor=cell.loop_factor,
        ))
        rec["kind"] = cell.kind
        rec["notes"] = cell.notes
        rec["status"] = "ok"
    except Exception as e:  # a failing cell is a bug in the system — surface it
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["wall_s"] = round(time.time() - t0, 1)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--knn", action="store_true", help="include the paper's k-NN cells")
    ap.add_argument("--out", default=None, help="write JSON records here")
    ap.add_argument("--lower-only", action="store_true",
                    help="fast validation: lower every cell, skip compile")
    args = ap.parse_args()

    assert len(jax.devices()) == 512, "dry-run needs the 512 placeholder devices"

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single-pod-16x16", mesh_lib.make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi-pod-2x16x16", mesh_lib.make_production_mesh(multi_pod=True)))

    cell_list = configs.all_cells(include_knn=args.knn)
    if args.arch:
        cell_list = [c for c in cell_list if c[0] == args.arch]
        if args.arch.startswith("knn-"):
            mod = configs.get(args.arch)
            cell_list = [(args.arch, s, mod.SKIP.get(s)) for s in mod.SHAPES]
    if args.shape:
        cell_list = [c for c in cell_list if c[1] == args.shape]

    records = []
    n_fail = 0
    for mesh_name, mesh in meshes:
        for arch, shape, skip in cell_list:
            rec = run_cell(arch, shape, mesh, mesh_name, skip,
                           lower_only=args.lower_only)
            records.append(rec)
            status = rec["status"]
            if status == "lowered":
                line = f"[{mesh_name}] {arch} x {shape}: LOWER-OK ({rec['wall_s']}s)"
            elif status == "ok":
                line = (
                    f"[{mesh_name}] {arch} x {shape}: OK "
                    f"({rec['wall_s']}s) bytes/dev={rec['bytes_per_device']/2**30:.2f}GiB "
                    f"flops={rec['hlo_gflops']:.1f}G coll={rec['collective_gbytes']:.3f}GB "
                    f"dominant={rec['dominant']}"
                )
            elif status == "skipped":
                line = f"[{mesh_name}] {arch} x {shape}: SKIP ({rec['reason'][:60]}...)"
            else:
                n_fail += 1
                line = f"[{mesh_name}] {arch} x {shape}: FAIL {rec['error']}"
            print(line, flush=True)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} records to {args.out}")
    print(f"done: {sum(r['status'] == 'ok' for r in records)} ok, "
          f"{sum(r['status'] == 'skipped' for r in records)} skipped, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
