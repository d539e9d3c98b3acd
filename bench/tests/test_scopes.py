"""Device time by named scope and idle time by program span (``bench/scopes.py``),
on hand-made events, a hand-made XPlane, and the recorded v5e traces
(``data/tiny.xplane.pb`` by ``record_trace.py``, ``data/scoped.xplane.pb``
by ``record_scoped_trace.py``)."""

import os

import pytest

from bench import scopes as S
from bench import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = os.path.join(DATA, "tiny.xplane.pb")
SCOPED = os.path.join(DATA, "scoped.xplane.pb")
DEV = "/device:TPU:0"

COARSE = "jit(search)/coarse_pass/jit(search)/expand"


def _op(name, start, dur, tf_op=None):
    return T.Event(name, start, dur, {"tf_op": tf_op} if tf_op else {})


def _search_trace():
    """Two ``jit_search`` runs: a coarse pass whose own loop nests
    ``expand`` under ``coarse_pass``, an unscoped seed op, then the fine
    expansion loop; and one ``jit_other`` run using the same scope name."""
    ops = [
        # run 1: [0, 100]
        _op("%while.1 = coarse", 5, 30, f"{COARSE}/while:"),
        _op("%fusion.2 = coarse body", 10, 20, f"{COARSE}/while/body/gather:"),
        _op("%fusion.3 = seeds", 36, 4, "jit(search)/gather:"),
        _op("%while.4 = fine", 40, 55, "jit(search)/expand/while:"),
        _op("%fusion.5 = fine body", 45, 40, "jit(search)/expand/while/body/gather:"),
        _op("%copy-done.6 = no tf_op", 96, 2),
        # run 2: [200, 260]
        _op("%while.1 = coarse", 201, 10, f"{COARSE}/while:"),
        _op("%while.4 = fine", 215, 40, "jit(search)/expand/while:"),
        # jit_other: [300, 320]
        _op("%fusion.9 = other", 301, 15, "jit(other)/expand/add:"),
        # after the window
        _op("%while.4 = fine", 2000, 40, "jit(search)/expand/while:"),
    ]
    modules = [T.Event("jit_search(1)", 0, 100), T.Event("jit_search(1)", 200, 60),
               T.Event("jit_other(2)", 300, 20), T.Event("jit_search(1)", 2000, 50)]
    host = [T.Event(T.WINDOW_SPAN, 0, 1000),
            T.Event("bench.search", 0, 400),
            T.Event("index/search", 0, 120),
            T.Event("build/wave", 500, 300),
            T.Event("PjitFunction(search)", 510, 10)]
    return T.Trace(ops={DEV: ops}, modules={DEV: modules}, host=host)


def test_in_scope_reads_path_segments():
    assert S.in_scope(f"{COARSE}/while:", "coarse_pass")
    assert S.in_scope(f"{COARSE}/while:", "expand")
    assert not S.in_scope(f"{COARSE}/while:", "expand", ("coarse_pass",))
    assert S.in_scope("jit(search)/expand/while:", "expand", ("coarse_pass",))
    assert S.in_scope("jit(wave_core)/wave_search/jit(search)/gather:", "wave_search")
    assert not S.in_scope("jit(search)/expand_more/x:", "expand")  # whole segments only
    assert not S.in_scope("", "expand")


def test_own_times_net_out_nested_ops_as_self_times_does():
    ops = [T.Event("%while.6 = (s32[8,40]{1,0}, f32[8]) while(...)", 0, 100),
           T.Event("%fusion.1 = f32[8,60]{1,0:T(8,128)} fusion(...)", 10, 30),
           T.Event("%fusion.1 = f32[8,60]{1,0:T(8,128)} fusion(...)", 50, 30),
           T.Event("%copy.2 = f32[8]{0} copy(...)", 120, 5)]
    own = {id(e): t for e, t, _ in S.op_paths(ops)}
    assert [own[id(e)] for e in ops] == [40, 30, 30, 5]
    by_label: dict = {}
    for e, t, _ in S.op_paths(ops):
        by_label[T.op_label(e.name)] = by_label.get(T.op_label(e.name), 0) + t
    assert by_label == T.self_times(ops)


def test_coarse_pass_counts_as_coarse_never_as_expansion():
    tr = _search_trace()
    coarse = S.scope_runs(tr, "jit_search", "coarse_pass")
    assert [secs for _, secs in coarse] == pytest.approx([30e-9, 10e-9])
    fine = S.scope_runs(tr, "jit_search", "expand", ("coarse_pass",))
    assert [secs for _, secs in fine] == pytest.approx([55e-9, 40e-9])
    assert [r.start for r, _ in fine] == [0, 200]
    # without the exclusion the coarse pass's loop counts as expansion too
    assert S.scope_seconds(tr, "jit_search", "expand") == pytest.approx(135e-9)
    # another module's op of the same scope, and the run after the window,
    # never count
    assert S.scope_seconds(tr, "jit_other", "expand") == pytest.approx(15e-9)


def test_module_scopes_split_a_run_into_scopes_unscoped_and_idle():
    split = S.module_scopes(_search_trace(), "jit_search",
                            ["coarse_pass", ("expand", ("coarse_pass",))])
    assert split["runs"] == 2
    assert split["coarse_pass"] == pytest.approx(40e-9)
    assert split["expand"] == pytest.approx(95e-9)
    assert split["unscoped"] == pytest.approx(6e-9)  # the seed op and the copy
    # run 1: busy [5, 35] [36, 40] [40, 95] [96, 98] of [0, 100]; run 2:
    # [201, 211] [215, 255] of [200, 260]
    assert split["idle"] == pytest.approx((100 - 91 + 60 - 50) * 1e-9)


def test_a_scope_that_is_not_there_is_an_error():
    tr = _search_trace()
    with pytest.raises(KeyError):
        S.scope_runs(tr, "jit_search", "wave_search")
    with pytest.raises(KeyError):
        S.scope_seconds(tr, "jit_wave_core", "expand")
    with pytest.raises(KeyError):
        S.idle_by_span(T.Trace(ops={}, modules={}, host=[]))


def test_idle_goes_to_the_innermost_program_span():
    rows = S.idle_by_span(_search_trace())
    by = {name: (secs, n) for name, secs, n in rows}
    # the gaps [0, 5], [35, 36] and [95, 96] lie under index/search [0, 120];
    # [98, 201], [211, 215] and [255, 301] under no program span; the middle
    # of [316, 1000] in build/wave [500, 800]
    assert by["index/search"] == (pytest.approx(7e-9), 3)
    assert by["no program span"] == (pytest.approx(153e-9), 3)
    assert by["build/wave"] == (pytest.approx(684e-9), 1)
    assert "PjitFunction(search)" not in by  # runtime events are no program span
    assert rows[0][0] == "build/wave"
    assert sum(s for _, s, _ in rows) == pytest.approx(1000e-9 - 91e-9 - 50e-9 - 15e-9)


# ---------------------------------------------------------------------------
# the XPlane decoder
# ---------------------------------------------------------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name: str, ops, with_ref: bool) -> bytes:
    """An XPlane with one ``XLA Ops`` line: ``ops`` are (name, tf_op) in
    line order; a tf_op is a str_value, or a ref_value when ``with_ref``."""
    stat_meta = [(7, "tf_op"), (8, "flops")]
    body = _field(2, name)
    events, metas = b"", b""
    for i, (op, tf_op) in enumerate(ops, start=1):
        stats = _field(5, _field(1, 8) + _field(4, 12))  # a flops stat: int64_value
        if tf_op is not None:
            if with_ref:
                stat_meta.append((100 + i, tf_op))
                stats += _field(5, _field(1, 7) + _field(7, 100 + i))
            else:
                stats += _field(5, _field(1, 7) + _field(5, tf_op))
        metas += _field(4, _field(1, i) + _field(2, _field(1, i) + _field(2, op) + stats))
        events += _field(4, _field(1, i) + _field(2, 1000 * i) + _field(3, 500))
    line = _field(2, "XLA Ops") + events
    body += _field(3, _field(2, "XLA Modules")) + _field(3, line) + metas
    for sid, sname in stat_meta:
        body += _field(5, _field(1, sid) + _field(2, _field(1, sid) + _field(2, sname)))
    return _field(1, body)


@pytest.mark.parametrize("with_ref", [False, True])
def test_tf_ops_decodes_a_hand_made_xplane(with_ref):
    ops = [("%fusion.1 = a", "jit(search)/coarse_pass/gather:"),
           ("%copy.2 = b", None), ("%fusion.1 = a", "jit(search)/coarse_pass/gather:"),
           ("%while.3 = c", "jit(search)/expand/while:")]
    data = _plane("/host:CPU", [("x", "y")], with_ref) + _plane(DEV, ops, with_ref)
    assert S.tf_ops(data) == {DEV: ops}


def test_tiny_trace_ops_carry_their_tf_op():
    tr = S.attach(T.load(TINY), TINY)
    evs = tr.ops[DEV]
    kernel = T.kernel_events(tr, "gather_distance")
    assert [e.stats["tf_op"] for e in kernel] == [
        "jit(gather_distance)/gather_distance/pallas_call:"] * 2
    # every op but the compiler's own copies carries one
    assert all(("tf_op" in e.stats) != e.name.startswith("%copy-") for e in evs)
    assert sum("tf_op" in e.stats for e in evs) == 20
    # a kernel with nothing nested in it: its scope's time is its own time
    assert S.scope_seconds(tr, "jit_gather_distance", "gather_distance") == pytest.approx(
        T.kernel_seconds(tr, "gather_distance"))
    assert S.scope_seconds(tr, "jit_tiny_step", "jit(tiny_step)") > 0


def test_attach_refuses_ops_that_do_not_line_up():
    tr = T.load(TINY)
    tr.ops[DEV] = tr.ops[DEV][1:]
    with pytest.raises(KeyError):
        S.attach(tr, TINY)


def test_run_trace_dir_follows_run_py_arguments():
    d = S.run_trace_dir(["--workload", "sift128-l2.batch", "--seed", "002147500021",
                         "--seconds", "10", "--trace", "1"])
    assert d.endswith(os.path.join(".bench_trace", "sift128-l2.batch.2147500021"))
    assert S.run_trace_dir(["--workload", "w", "--seed", "3", "--trace-dir", "/t"]) == "/t/w.3"


def test_a_while_without_tf_op_counts_under_its_loop_scope():
    ops = [_op("%while.1 = coarse loop", 0, 50),  # a TPU while op: no tf_op
           _op("%fusion.2", 5, 20, f"{COARSE}/while/body/gather:"),
           _op("%copy.3", 30, 10, f"{COARSE}/while:"),
           _op("%copy-start.4", 60, 5)]  # a compiler copy: no path
    paths = {T.op_label(e.name): (own, path) for e, own, path in S.op_paths(ops)}
    assert paths["while.1"] == (20, COARSE + "/while")
    assert paths["copy-start.4"] == (5, "")
    tr = T.Trace(ops={DEV: ops}, modules={DEV: [T.Event("jit_search(1)", 0, 70)]},
                 host=[T.Event(T.WINDOW_SPAN, 0, 100)])
    assert S.scope_seconds(tr, "jit_search", "coarse_pass") == pytest.approx(50e-9)
    with pytest.raises(KeyError):
        S.scope_seconds(tr, "jit_search", "expand", ("coarse_pass",))


def test_recorded_scoped_trace_keeps_what_the_tiny_trace_shows():
    """``test_trace.test_recorded_tpu_trace``'s assertions, on the trace
    recorded with named scopes and program spans."""
    tr = S.attach(T.load(SCOPED), SCOPED)
    assert tr.devices == [DEV]
    busy, window = T.busy_share(tr)
    assert 0 < busy < window
    kernel = T.kernel_events(tr, "gather_distance")
    assert len(kernel) == 2
    assert T.kernel_shape(kernel[0]) == (8, 16)
    assert len(T.module_events(tr, "jit_tiny_step")) == 4
    name, secs = T.idle_gaps(tr)[0]
    assert name.startswith("bench.wait") and 0.04 < secs < 0.1
    with pytest.raises(KeyError):
        T.module_events(tr, "jit_wave_core")


def test_recorded_scoped_trace_splits_by_scope():
    tr = S.attach(T.load(SCOPED), SCOPED)
    # tiny_step's one op runs under its ``tiny`` scope
    assert S.scope_seconds(tr, "jit_tiny_step", "tiny") == pytest.approx(
        sum(own for e, own, path in S.op_paths(tr.ops[DEV]) if "/tiny/" in path) / 1e9)
    # scoped_step: the coarse pass's own loop (3 trips) under coarse_pass,
    # the outer loop (4 trips) under expand and not under coarse_pass
    coarse = [secs for _, secs in S.scope_runs(tr, "jit_scoped_step", "coarse_pass")]
    fine = [secs for _, secs in S.scope_runs(tr, "jit_scoped_step", "expand", ("coarse_pass",))]
    assert len(coarse) == len(fine) == 2
    assert all(0 < c < f for c, f in zip(coarse, fine))
    # expand without the exclusion: both loops, but not the coarse pass's
    # own ops outside its loop (a slice)
    outside = S.scope_seconds(tr, "jit_scoped_step", "coarse_pass", ("expand",))
    assert 0 < outside < sum(coarse)
    assert S.scope_seconds(tr, "jit_scoped_step", "expand") == pytest.approx(
        sum(coarse) + sum(fine) - outside)
    split = S.module_scopes(tr, "jit_scoped_step",
                            ["coarse_pass", ("expand", ("coarse_pass",))])
    assert split["coarse_pass"] == pytest.approx(sum(coarse))
    assert split["expand"] == pytest.approx(sum(fine))
    runs = T.module_events(tr, "jit_scoped_step")
    assert sum(split[k] for k in ("coarse_pass", "expand", "unscoped", "idle")) == \
        pytest.approx(sum(e.dur for e in runs) / 1e9)
    # every while op of the loops landed in a scope
    assert all(path for e, _, path in S.op_paths(tr.ops[DEV]) if "while" in T.op_label(e.name))


def test_recorded_scoped_trace_idle_by_program_span():
    rows = S.idle_by_span(T.load(SCOPED))
    by = {name: (secs, n) for name, secs, n in rows}
    # the 30 ms sleep under build/wave is one gap of its own
    assert by["build/wave"][1] == 1 and 0.025 < by["build/wave"][0] < 0.04
