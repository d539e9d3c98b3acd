"""Read the control of a cell's comparison, on several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

The control is the reference put in the program's place, computed at the
precision just below the configuration's (``bench/reference.py``): the same
driver, window and comparison as a benchmark run, with ``program=False``.
Each seed prints the numbers compared and whether the run came out correct;
the control is meant to come out not correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    for seed in [int(s) for s in args.seeds.split(",")]:
        run_args = argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds, trace=0,
            rehearse=False, trace_dir=None,
            control=True,
        )
        line = bench_run.run(run_args)
        print(json.dumps({"seed": seed, "correct": line["correct"],
                          "checks": line["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
