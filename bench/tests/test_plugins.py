"""Traffic kinds, data generators and per-layer readers are found by file
name: a new one takes new files and entries, and no edit to
``bench/run.py``, ``bench/drive.py`` or ``bench/data.py``."""

import argparse
import json
import textwrap

import jax
import numpy as np
import pytest

from bench import data, drive, plugins
from bench import run as bench_run

ECHO_DRIVER = '''
"""A traffic kind of its own: makes the configuration's rows and counts them."""
import numpy as np
from bench import drive


def run(cell, ready):
    rows = np.asarray(drive.make_rows(cell, cell.config["n"]))
    ready()
    made = float(rows.sum())
    want = float(np.arange(rows.size).sum() * cell.config["data"]["params"]["step"])
    return drive.Outcome(metrics={"rows_made": rows.shape[0]}, attempted=rows.shape[0],
                         failed=0, checks={"rows_err": (abs(made - want), "<=", 0.0)},
                         readings={"rows": rows.shape[0]})
'''

RAMP_GENERATOR = '''
import jax.numpy as jnp


def {name}(key, n, d, *, step=1.0):
    return (jnp.arange(n * d, dtype=jnp.float32) * step).reshape(n, d)
'''

COUNT_READER = '''
def read(ctx):
    return ctx.readings["rows"]
'''


def _write(path, text):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(text))


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """A ``bench/`` of its own for the plug-in directories."""
    bench = tmp_path / "bench"
    bench.mkdir()
    monkeypatch.setattr(plugins, "BENCH", str(bench))
    return bench


def test_a_driver_file_is_found(tree):
    _write(tree / "drivers" / "echo.py", "def run(cell, ready):\n    return ('echo', cell)\n")
    assert bench_run.driver("echo")("cell", None) == ("echo", "cell")


def test_a_generator_file_is_found(tree):
    _write(tree / "generators" / "ramp_a.py", RAMP_GENERATOR.format(name="ramp_a"))
    rows = data.make({"generator": "ramp_a", "params": {"step": 2.0}},
                     jax.random.PRNGKey(0), 4, 3)
    assert np.array_equal(np.asarray(rows), 2.0 * np.arange(12, dtype=np.float32).reshape(4, 3))


def test_unknown_kind_and_generator_still_raise(tree):
    with pytest.raises(FileNotFoundError, match="no_such_kind"):
        bench_run.driver("no_such_kind")
    with pytest.raises(FileNotFoundError, match="no_such_generator"):
        data.generator("no_such_generator")


def test_built_in_kinds_and_generators_stay_in_their_lists(tree):
    assert bench_run.driver("batch") is drive.run_batch
    assert bench_run.driver("build") is drive.run_build
    assert data.generator("clustered") is data.clustered


@pytest.mark.parametrize("trace", [0, 1])
def test_a_new_cell_runs_from_new_files_only(tree, monkeypatch, trace):
    """A cell whose configuration, mix, traffic kind, generator and reader are
    all new files runs through ``bench/run.py`` as it stands."""
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 1,
        "configs": [{"name": "ramp", "source": "a test", "file": "bench/configs/ramp.json",
                     "reduced": [], "why": "a test"}],
        "workloads": [{"name": "ramp.echo", "config": "ramp", "traffic": "echo", "chips": 1,
                       "why": "a test"}],
        "end_to_end": [
            {"name": "rows_made", "unit": "rows", "better": "higher", "bound": 0.01,
             "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"}],
        "per_layer": [{"name": "echo.count", "unit": "rows", "better": "higher",
                       "source": "program_counter", "layer": "echo", "moves": "rows_made"}],
    }
    root = tree.parent
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    _write(tree / "configs" / "ramp.json", json.dumps(
        {"n": 8, "d": 4, "data": {"generator": "ramp_b", "seed": 1, "params": {"step": 0.5}}}))
    _write(tree / "mixes" / "echo.json", json.dumps({"kind": "echo"}))
    _write(tree / "drivers" / "echo.py", ECHO_DRIVER)
    _write(tree / "generators" / "ramp_b.py", RAMP_GENERATOR.format(name="ramp_b"))
    _write(tree / "metrics" / "echo.count.py", COUNT_READER)
    monkeypatch.setattr(bench_run, "ROOT", str(root))
    monkeypatch.setattr(bench_run, "BENCH", str(tree))
    args = argparse.Namespace(workload="ramp.echo", seed=2**31 + 7, seconds=0.1, trace=trace,
                              rehearse=True, trace_dir=str(root / "trace"), control=False)
    line = bench_run.run(args)
    assert line["correct"] is True, line["checks"]
    want = {"echo.count": 8.0} if trace else {"rows_made": 8.0}
    assert {k: v["value"] for k, v in line["metrics"].items() if k != "setup_s"} == want
