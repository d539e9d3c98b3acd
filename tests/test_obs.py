"""Telemetry subsystem tests (repro.obs).

Three contracts pinned here:

  * **span machinery** — nesting depth/parent stamps, close ordering
    (inner spans emit before their enclosing span), the ``synced`` flag,
    the Noop tracker's no-clock/no-sync/no-event behaviour, and every
    span reaching a profiler trace as a TraceAnnotation;
  * **JSONL crash safety** — append mode, flush-per-event (events are
    readable while the tracker is still open), round-trip through
    ``load_events`` with a torn tail skipped rather than fatal;
  * **transparency** — attaching a tracker changes no search result
    bitwise (fp32): construct, lifecycle and the serving loop produce
    identical arrays with telemetry on and off.  (The property tier
    sweeps the construct/search leg over drawn cases via
    ``prop_util.check_tracker_transparency``; here it is pinned once at a
    serving-shaped size.)
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import construct
from repro.index.lifecycle import OnlineIndex
from repro.obs import (
    NOOP,
    InMemoryTracker,
    JsonlTracker,
    NoopTracker,
    SearchStats,
    load_events,
    span_tree,
)
from repro.serve.loop import ServeLoopConfig, ServingLoop


# ---------------------------------------------------------------------------
# span machinery (InMemoryTracker)
# ---------------------------------------------------------------------------


def test_span_nesting_depth_parent_and_order():
    trk = InMemoryTracker()
    with trk.span("outer"):
        with trk.span("inner") as sp:
            sp.synced = True
        with trk.span("inner2"):
            pass
    spans = trk.span_events
    # close order: inner spans emit before the enclosing span
    assert [e["name"] for e in spans] == ["inner", "inner2", "outer"]
    by_name = {e["name"]: e for e in spans}
    assert by_name["outer"]["depth"] == 0 and "parent" not in by_name["outer"]
    assert by_name["inner"]["depth"] == 1
    assert by_name["inner"]["parent"] == "outer"
    assert by_name["inner2"]["parent"] == "outer"
    assert by_name["inner"]["synced"] is True
    assert by_name["inner2"]["synced"] is False
    # wall-clock sanity: the outer span contains both inner spans
    assert by_name["outer"]["dur_s"] >= by_name["inner"]["dur_s"]
    assert all(e["dur_s"] >= 0.0 for e in spans)


def test_span_sync_returns_tree_and_marks():
    trk = InMemoryTracker()
    x = jnp.arange(4.0)
    with trk.span("s") as sp:
        out = sp.sync({"a": x})
    assert out["a"] is x  # passthrough: call sites write res = sp.sync(res)
    assert trk.spans("s")[0]["synced"] is True


def test_metrics_carry_step_and_enclosing_span():
    trk = InMemoryTracker()
    with trk.span("wave"):
        trk.log_metrics({"a": 1, "b": 2.5}, step=7)
    trk.log_metrics({"c": np.int64(3)})  # numpy scalar -> host scalar
    evs = trk.metrics_events
    assert evs[0]["span"] == "wave" and evs[0]["step"] == 7
    assert evs[0]["metrics"] == {"a": 1, "b": 2.5}
    assert "span" not in evs[1] and evs[1]["metrics"]["c"] == 3
    assert isinstance(evs[1]["metrics"]["c"], int)


def test_span_stack_unwinds_on_exception():
    trk = InMemoryTracker()
    with pytest.raises(RuntimeError):
        with trk.span("boom"):
            raise RuntimeError("x")
    # the span still emitted and the stack fully unwound
    assert [e["name"] for e in trk.span_events] == ["boom"]
    trk.log_metrics({"after": 1})
    assert "span" not in trk.metrics_events[-1]


def test_noop_tracker_is_inert_and_allocation_free(monkeypatch):
    """No clock read, no sync, no event: a Noop span's one cost is its
    profiler TraceAnnotation; the span it yields is one shared object."""
    import time

    from repro.obs import tracker as tracker_mod

    trk = NoopTracker()

    def forbidden(*_a, **_k):
        raise AssertionError("NoopTracker touched the clock or synced")

    monkeypatch.setattr(time, "perf_counter", forbidden)
    monkeypatch.setattr(jax, "block_until_ready", forbidden)
    monkeypatch.setattr(trk, "_emit", forbidden)
    x = jnp.arange(3.0)
    with trk.span("a") as sp1:
        assert sp1.sync(x) is x  # passthrough — no block_until_ready
        sp1.synced = True  # annotation writes are discarded, not errors
        assert sp1.synced is False
        with trk.span("b") as sp2:
            assert sp2 is sp1  # shared singleton span: no per-span state
    assert sp1 is tracker_mod._NOOP_SPAN
    assert trk._stack == []  # the span stack is never touched
    trk.log_metrics({"k": 1}, step=0)
    trk.finish()
    assert isinstance(NOOP, NoopTracker)


# ---------------------------------------------------------------------------
# JsonlTracker: crash-safe append + round trip
# ---------------------------------------------------------------------------


def test_jsonl_round_trip_and_header(tmp_path):
    p = str(tmp_path / "trace.jsonl")
    trk = JsonlTracker(p, run_meta={"bench": "unit", "n": 8})
    with trk.span("outer"):
        trk.log_metrics({"x": 1.5}, step=0)
    trk.finish()
    evs = load_events(p)
    assert [e["event"] for e in evs] == ["run", "metrics", "span"]
    assert evs[0]["meta"] == {"bench": "unit", "n": 8}
    assert "wall_time_utc" in evs[0] and "pid" in evs[0]
    assert evs[1]["metrics"] == {"x": 1.5} and evs[1]["span"] == "outer"
    assert evs[2]["name"] == "outer" and evs[2]["depth"] == 0


def test_jsonl_flush_per_event_readable_before_finish(tmp_path):
    p = str(tmp_path / "live.jsonl")
    trk = JsonlTracker(p)
    trk.log_metrics({"early": 1})
    # crash-safety contract: every event is flushed as written, so a
    # reader (or a post-crash inspection) sees it without finish()
    assert [e["event"] for e in load_events(p)] == ["run", "metrics"]
    trk.finish()


def test_jsonl_append_mode_multiple_runs(tmp_path):
    p = str(tmp_path / "multi.jsonl")
    for i in range(2):
        trk = JsonlTracker(p, run_meta={"run": i})
        trk.log_metrics({"i": i})
        trk.finish()
    evs = load_events(p)
    runs = [e for e in evs if e["event"] == "run"]
    assert [r["meta"]["run"] for r in runs] == [0, 1]
    assert len(evs) == 4  # 2 x (header + metrics), nothing clobbered


def test_jsonl_torn_tail_skipped(tmp_path):
    p = str(tmp_path / "torn.jsonl")
    trk = JsonlTracker(p)
    trk.log_metrics({"ok": 1})
    trk.finish()
    with open(p, "a", encoding="utf-8") as f:
        f.write('{"event": "metrics", "metrics": {"to')  # crash mid-write
    evs = load_events(p)
    assert [e["event"] for e in evs] == ["run", "metrics"]
    assert evs[1]["metrics"] == {"ok": 1}


def test_jsonl_post_finish_emit_dropped_not_fatal(tmp_path):
    p = str(tmp_path / "closed.jsonl")
    trk = JsonlTracker(p)
    trk.finish()
    trk.log_metrics({"late": 1})  # dropped, must not raise
    assert [e["event"] for e in load_events(p)] == ["run"]


def test_jsonl_lines_are_valid_json_objects(tmp_path):
    p = str(tmp_path / "schema.jsonl")
    trk = JsonlTracker(p)
    with trk.span("a"):
        with trk.span("b") as sp:
            sp.synced = True
    trk.finish()
    with open(p, encoding="utf-8") as f:
        for line in f:
            ev = json.loads(line)
            assert isinstance(ev, dict) and "event" in ev


def test_span_tree_renders_nesting(tmp_path):
    trk = InMemoryTracker()
    with trk.span("outer"):
        with trk.span("inner") as sp:
            sp.synced = True
    lines = list(span_tree(trk.events))
    assert lines[0].startswith("  inner:") and "[dispatch-only]" not in lines[0]
    assert lines[1].startswith("outer:") and "[dispatch-only]" in lines[1]


# ---------------------------------------------------------------------------
# SearchStats aggregation math
# ---------------------------------------------------------------------------


class _FakeRes:
    """Duck-typed SearchResult accounting surface."""

    def __init__(self, comps, full, iters, conv):
        self.n_comps = np.asarray(comps, np.int32)
        self.hash_full = np.asarray(full, bool)
        self.n_iters = np.asarray(iters, np.int32)
        self.converged = np.asarray(conv, bool)


def test_search_stats_totals_and_ratios():
    st = SearchStats()
    st.update(
        _FakeRes([4, 9, 16, 0], [True, False, False, False],
                 [2, 3, 4, 1], [True, True, False, True]),
        n_items=100,
    )
    assert st.n_queries == 4
    assert st.total_comps == 29
    assert st.comps_per_query == pytest.approx(29 / 4)
    assert st.hash_saturation_ratio == pytest.approx(1 / 4)
    assert st.capped_ratio == pytest.approx(1 / 4)
    assert st.max_comps == 16
    assert st.scanning_rate == pytest.approx(29 / (4 * 100))
    # pow2 histogram: 4 -> bucket 2, 9 -> 3, 16 -> 4, 0 -> 0
    want = np.zeros(32, np.int64)
    want[[2, 3, 4, 0]] += 1
    np.testing.assert_array_equal(st.hist, want)


def test_search_stats_churn_weighted_scanning_rate():
    # the denominator is the catalog size each query actually saw
    st = SearchStats()
    st.update(_FakeRes([10], [False], [1], [True]), n_items=100)
    st.update(_FakeRes([10], [False], [1], [True]), n_items=300)
    assert st.scanning_rate == pytest.approx(20 / (100 + 300))


def test_search_stats_merge_and_reset():
    a = SearchStats(n_items=50)
    a.update(_FakeRes([8], [True], [2], [False]))
    b = SearchStats()
    b.update(_FakeRes([2, 2], [False, False], [1, 1], [True, True]), n_items=10)
    a.merge(b)
    assert a.n_queries == 3 and a.total_comps == 12
    assert a.hash_full_queries == 1 and a.capped_queries == 1
    assert a._n_items_weighted == 50 + 20
    a.reset()
    assert a.n_queries == 0 and a.total_comps == 0
    assert a.default_n_items == 50  # the pinned default survives reset
    assert not a.hist.any()


def test_search_stats_percentile_brackets_true_value():
    st = SearchStats()
    comps = [3] * 50 + [40] * 50
    st.update(_FakeRes(comps, [False] * 100, [1] * 100, [True] * 100),
              n_items=None)
    # histogram percentile reports the upper bucket edge: <= 2x overestimate
    assert 3 <= st.comps_percentile(25) <= 6
    assert 40 <= st.comps_percentile(99) <= 80
    m = st.as_metrics("s")
    assert m["s/n_queries"] == 100
    assert m["s/comps_per_query"] == pytest.approx(21.5)
    for k in ("s/comps_p50", "s/comps_p99", "s/scanning_rate",
              "s/hash_saturation_ratio", "s/capped_ratio"):
        assert k in m


# ---------------------------------------------------------------------------
# transparency: tracker on == tracker off, bitwise (fp32)
# ---------------------------------------------------------------------------


def _mk_items(n=192, d=8, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.rand(n, d).astype(np.float32))


def test_build_bitwise_identical_with_tracker():
    x = _mk_items()
    # n_seed_init below n so the wave loop (the instrumented path) runs
    cfg = construct.BuildConfig(k=6, wave=64, n_seed_init=64)
    key = jax.random.PRNGKey(3)
    g0, s0 = construct.build(x, cfg, key)
    trk = InMemoryTracker()
    g1, s1 = construct.build(x, cfg, key, tracker=trk)
    np.testing.assert_array_equal(np.asarray(g0.nbr_ids), np.asarray(g1.nbr_ids))
    np.testing.assert_array_equal(np.asarray(g0.nbr_dist), np.asarray(g1.nbr_dist))
    assert int(s0.n_comps) == int(s1.n_comps)
    # and the tracker actually saw the build
    assert trk.spans("build/stride")
    assert any("build/n_comps" in e["metrics"] for e in trk.metrics_events)


def test_program_spans_reach_the_profiler_trace(tmp_path):
    """Every tracker span is a profiler TraceAnnotation, NOOP's included: a
    CPU profiler trace of a tiny build + search carries the program's own
    spans on the host plane, nested and in order."""
    import glob

    from jax.profiler import ProfileData

    x = _mk_items(n=96)
    cfg = construct.BuildConfig(k=4, wave=16, n_seed_init=32, max_iters=8,
                                seed_mode="coarse")
    key = jax.random.PRNGKey(1)
    OnlineIndex.build(x, cfg, key=key).search(x[:4], 3)  # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        idx = OnlineIndex.build(x, cfg, key=key)
        jax.block_until_ready(idx.search(x[:4], 3).ids)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("index/build", "build/seed", "build/stride",
                                  "build/wave", "index/search"):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    assert sorted(spans) == ["build/seed", "build/stride", "build/wave",
                             "index/build", "index/search"]
    (build,), (search,) = spans["index/build"], spans["index/search"]

    def inside(inner, outer):
        return outer[0] <= inner[0] and inner[1] <= outer[1]

    # the seed graph and the coarse level's own build: under index/build
    assert all(inside(s, build) for s in spans["build/seed"])
    # one build/wave per wave, each inside a build/stride, all in the build
    waves = sorted(spans["build/wave"])
    assert len(waves) >= (96 - 32) // 16
    assert all(any(inside(w, s) for s in spans["build/stride"]) for w in waves)
    assert all(inside(w, build) for w in waves)
    # in order: the outer seed graph before the first wave, search after build
    assert min(spans["build/seed"])[0] < waves[0][0]
    assert search[0] >= build[1]


def test_serving_loop_bitwise_identical_with_tracker():
    """The full serving surface — churn flushes, waves, padding — serves
    bit-identical ids with telemetry on and off (same seeds throughout)."""
    x = _mk_items()
    rng = np.random.RandomState(7)
    bursts = [rng.rand(m, 8).astype(np.float32) for m in (5, 3, 8, 1)]
    adds = rng.rand(4, 8).astype(np.float32)

    def run(tracker):
        idx = OnlineIndex.build(
            x, construct.BuildConfig(k=6, wave=64), key=jax.random.PRNGKey(1)
        )
        loop = ServingLoop(
            idx, ServeLoopConfig(top_k=5, max_batch=8,
                                 recall_sample_every=3, recall_reservoir=4),
            tracker=tracker, seed=11,
        )
        served = []
        loop.submit(bursts[0])
        loop.step()
        loop.add(adds, key=jax.random.PRNGKey(2))
        loop.remove(jnp.asarray([0, 17]))
        for b in bursts[1:]:
            loop.submit(b)
        while loop.queue_depth:
            w = loop.step()
            served.append(w["bucket"])
        # capture everything that was served via the audit reservoir
        return loop, served

    loop0, buckets0 = run(None)
    trk = InMemoryTracker()
    loop1, buckets1 = run(trk)
    assert buckets0 == buckets1
    assert loop0.served == loop1.served == sum(b.shape[0] for b in bursts)
    for a, b in zip(loop0._res_ids, loop1._res_ids):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(loop0._res_q, loop1._res_q):
        np.testing.assert_array_equal(a, b)
    # lifecycle state equally untouched by telemetry
    np.testing.assert_array_equal(
        np.asarray(loop0.index.graph.nbr_ids), np.asarray(loop1.index.graph.nbr_ids)
    )
    np.testing.assert_array_equal(
        np.asarray(loop0.index.graph.alive), np.asarray(loop1.index.graph.alive)
    )
    # the tracked run produced the expected span skeleton (+1: the first
    # burst's wave is served before the churn, outside the bucket list)
    assert len(trk.spans("serve/step")) == len(buckets1) + 1
    assert len(trk.spans("serve/search")) == len(buckets1) + 1
    assert trk.spans("serve/remove")[0]["synced"] is True
    assert trk.spans("index/flush")  # churn flush nested under the loop
