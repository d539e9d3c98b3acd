"""Run a cell of ``BENCHMARK.json`` in this process at its rehearsal size."""

import argparse
import json
import os

from bench import run as bench_run

SPEC = json.load(open(os.path.join(bench_run.ROOT, "BENCHMARK.json")))
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def run_cell(workload, tmp_path, *, seed=2147483711, seconds=2.0, trace=0, control=False):
    args = argparse.Namespace(
        workload=workload, seed=seed, seconds=seconds, trace=trace, rehearse=True,
        trace_dir=str(tmp_path), control=control,
    )
    return bench_run.run(args)
