"""The build's ``build.lookup_device_ms`` reader on hand-made ``jit_wave_core``
runs: it reads the commit's ``d_lookup`` scope, which
``build.commit_device_ms`` also counts, and raises ``KeyError`` where the
scope is absent, as the other scope readers do."""

import pytest

from bench import trace as T

DEV = "/device:TPU:0"
COMMIT = "jit(wave_core)/wave_commit/jit(commit_wave)"


def _op(name, start, dur, tf_op):
    return T.Event(name, start, dur, {"tf_op": tf_op})


def _wave_trace(with_lookup: bool):
    """Two ``jit_wave_core`` runs: a search op, commit ops, and (when
    ``with_lookup``) the commit's D lookup under ``d_lookup``."""
    lookup = f"{COMMIT}/d_lookup/jit(visited_lookup)/visited_lookup/pallas_call:"
    ops = []
    for base in (0, 1000):
        ops += [_op("%fusion.1 = search", base + 5, 300, "jit(wave_core)/wave_search/jit(search)/gather:"),
                _op("%fusion.2 = merge", base + 310, 100, f"{COMMIT}/sort:")]
        if with_lookup:
            ops.append(_op("%visited_lookup.3", base + 420, 40 + base // 100, lookup))
        ops.append(_op("%fusion.4 = rules", base + 470, 20, f"{COMMIT}/scatter-add:"))
    modules = [T.Event("jit_wave_core(7)", 0, 600), T.Event("jit_wave_core(7)", 1000, 600)]
    tr = T.Trace(ops={DEV: ops}, modules={DEV: modules},
                 host=[T.Event(T.WINDOW_SPAN, 0, 2000)])
    tr.tf_ops_attached = True  # the hand-made ops carry their tf_op already
    return tr


class _Ctx:
    def __init__(self, trace):
        self.trace = trace


def test_lookup_reader_reads_d_lookup_inside_the_commit():
    from bench import run as bench_run

    tr = _wave_trace(with_lookup=True)
    lookup = bench_run.reader("build.lookup_device_ms").read(_Ctx(tr))
    assert lookup == pytest.approx((40 + 50) / 2 * 1e-6)  # ms a run
    commit = bench_run.reader("build.commit_device_ms").read(_Ctx(tr))
    assert commit == pytest.approx((100 + 20) * 1e-6 + lookup)
    assert bench_run.reader("build.lookup_device_ms").read(_Ctx(None)) is None


def test_lookup_reader_finds_nothing_without_the_scope():
    from bench import run as bench_run

    with pytest.raises(KeyError):
        bench_run.reader("build.lookup_device_ms").read(_Ctx(_wave_trace(with_lookup=False)))
