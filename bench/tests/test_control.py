"""The control (the reference at the precision below the configuration's,
in the program's place) comes out not correct, and on the distances."""

import pytest

from bench.tests.helpers import WORKLOADS, run_cell


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_is_not_correct(workload, tmp_path):
    line = run_cell(workload, tmp_path, control=True, seconds=1.0)
    assert line["correct"] is False
    c = line["checks"]["dist_err"]
    assert c["value"] > c["limit"]
