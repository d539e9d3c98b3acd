"""Each cell end to end on the CPU at its rehearsal size: the harness path
and the shape of the result line, not its numbers."""

import json

import pytest

from bench.tests.helpers import SPEC, WORKLOADS, run_cell


def _expected(workload, kind):
    names = set()
    for m in SPEC[kind]:
        if "workloads" not in m or workload in m["workloads"]:
            names.add(m["name"])
    return names


@pytest.mark.parametrize("workload", WORKLOADS)
def test_result_line(workload, tmp_path):
    line = run_cell(workload, tmp_path)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == _expected(workload, "end_to_end")
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "op", "limit"}
    json.dumps(line)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traced_line(workload, tmp_path):
    """Off the chip there is no device trace: only the program's own counts
    and spans are read, and no device number is written."""
    line = run_cell(workload, tmp_path, trace=1)
    assert line["correct"] is True
    assert set(line["metrics"]) <= _expected(workload, "per_layer")
    for name in line["metrics"]:
        source = {m["name"]: m["source"] for m in SPEC["per_layer"]}[name]
        assert source != "device_trace"
    assert "busy_s" not in line["device"]
    assert list(line)[-1] == "checks"
