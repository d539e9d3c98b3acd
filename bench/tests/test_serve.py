"""The ``serve`` traffic kind: its arrival schedule, its clock, a rehearsal
of ``glove100-cos.serve`` on the CPU, and faults planted under it."""

import time

import jax
import numpy as np
import pytest

from bench import plugins
from bench.tests.helpers import run_cell
from bench.tests.test_faults import _search_half, _search_step_unchanged

CELL = "glove100-cos.serve"
serve = plugins.load("drivers", "serve")


def test_the_arrival_schedule_is_fixed_by_the_seed():
    big = 2**31 + 12345
    due, rows = serve.arrivals(big, 300.0, 2.0, 100)
    again = serve.arrivals(big, 300.0, 2.0, 100)
    other = serve.arrivals(big + 1, 300.0, 2.0, 100)
    assert np.array_equal(due, again[0]) and np.array_equal(rows, again[1])
    assert not np.array_equal(due, other[0])
    assert len(due) == len(other[0]) == 600  # every seed sends as many
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < 2.0
    assert rows.min() >= 0 and rows.max() < 100


def test_latency_runs_from_the_due_time_and_answers_go_in_order():
    """A server busy for 50 ms a wave: the query due while it is busy waits,
    and that wait is in its latency; lanes answer the oldest queries."""
    queue = []

    def step():
        if not queue:
            return None
        time.sleep(0.05)
        wave, queue[:] = list(queue), []
        ids = np.array([[int(q[0])] for q in wave])
        return len(wave), 1 << (len(wave) - 1).bit_length(), ids, ids.astype(float)

    queries = np.arange(3, dtype=np.float32)[:, None]
    due = np.array([0.0, 0.01, 0.2])
    waves, late, due_at, t_end = serve.window(queue.extend, step, queries, due)
    answered, t_ans, ids, _ = serve.answers(waves, 3, 1, t_end)
    lat = t_ans - due_at
    assert answered == 3 and [w[1] for w in waves] == [1, 1, 1]
    assert ids[:, 0].tolist() == [0, 1, 2]
    assert lat[0] >= 0.05 and lat[1] >= 0.09  # query 1 waited for wave 0
    assert late[1] >= 0.035  # and was submitted late, after wave 0


def test_the_rehearsal_is_correct_and_compiles_nothing_in_the_window(tmp_path, capsys):
    line = run_cell(CELL, tmp_path, seed=2**31 + 99)
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["unanswered"]["value"] == 0
    assert "window_compiles=0 " in capsys.readouterr().out


def _drop_queries(monkeypatch):
    """Every fifth step loses the newest query waiting, also where it is the
    only one, so that the fault does not hang on how many queries wait."""
    from repro.serve.loop import ServingLoop

    orig, waves = ServingLoop.step, [0]

    def step(self):
        waves[0] += 1
        if waves[0] % 5 == 0 and self._queue:
            self._queue.pop()
        return orig(self)

    monkeypatch.setattr(ServingLoop, "step", step)


def _wrong_ids(monkeypatch):
    from repro.index import OnlineIndex

    orig = OnlineIndex.search

    def search(self, queries, top_k, **kw):
        res = orig(self, queries, top_k, **kw)
        return res._replace(ids=(res.ids + 1) % int(self.graph.n_valid))

    monkeypatch.setattr(OnlineIndex, "search", search)


FAULTS = [
    (_drop_queries, "unanswered"),
    (_wrong_ids, "recall_at_10"),
    (_search_step_unchanged, None),
    (_search_half, None),
]


@pytest.fixture
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("plant,trips", FAULTS, ids=[p.__name__.strip("_") for p, _ in FAULTS])
def test_fault_is_not_correct(plant, trips, monkeypatch, tmp_path, fresh_jit):
    plant(monkeypatch)
    line = run_cell(CELL, tmp_path, seconds=2.0)
    assert line["correct"] is False, line["checks"]
    if trips:
        c = line["checks"][trips]
        assert (c["value"] < c["limit"]) if c["op"] == ">=" else (c["value"] > c["limit"])


def _serve_trace():
    """Two waves in a 1,000 ns window: ``bench.step`` spans of 300 and 200
    ns, each around one ``jit_search`` run (200 and 100 ns of device ops)."""
    from bench import trace as T

    dev = "/device:TPU:0"
    ops = [T.Event("%fusion.1", 120, 200), T.Event("%fusion.2", 520, 100)]
    modules = [T.Event("jit_search(3)", 120, 200), T.Event("jit_search(3)", 520, 100)]
    host = [T.Event(T.WINDOW_SPAN, 0, 1000), T.Event("bench.step", 100, 300),
            T.Event("bench.step", 500, 200)]
    return T.Trace(ops={dev: ops}, modules={dev: modules}, host=host)


class _Ctx:
    def __init__(self, trace, attempted=6, failed=0):
        self.trace = trace
        self.outcome = type("Outcome", (), {"attempted": attempted, "failed": failed})()


def test_serve_readers_read_host_time_per_wave_and_device_time_per_query():
    from bench import run as bench_run

    tr = _serve_trace()
    host = bench_run.reader("serve.host_ms_per_wave").read(_Ctx(tr))
    assert host == pytest.approx((250 - 150) * 1e-6)  # mean step less mean search, ms
    per_query = bench_run.reader("serve.device_ms_per_query").read(_Ctx(tr, 6, 1))
    assert per_query == pytest.approx(300e-9 / 5 * 1e3)  # busy s over 5 answered, ms
    search = bench_run.reader("serve.search_device_ms").read(_Ctx(tr))
    assert search == pytest.approx(150 * 1e-6)
    for name in ("serve.host_ms_per_wave", "serve.device_ms_per_query", "serve.search_device_ms"):
        assert bench_run.reader(name).read(_Ctx(None)) is None


def test_serve_readers_find_nothing_without_steps_or_searches():
    from bench import run as bench_run
    from bench import trace as T

    tr = _serve_trace()
    no_steps = T.Trace(ops=tr.ops, modules=tr.modules, host=tr.host[:1])
    with pytest.raises(KeyError):
        bench_run.reader("serve.host_ms_per_wave").read(_Ctx(no_steps))
    no_search = T.Trace(ops=tr.ops, modules={}, host=tr.host)
    with pytest.raises(KeyError):
        bench_run.reader("serve.host_ms_per_wave").read(_Ctx(no_search))
    assert bench_run.reader("serve.device_ms_per_query").read(_Ctx(tr, 4, 4)) is None
