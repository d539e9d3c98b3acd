"""A run with its timed path broken underneath comes out not correct.

Each fault is planted in the program for one run (and the jit caches are
cleared around it, so no compiled program outlives it): a step that returns
its state unchanged, half of a batch left out, an answer altered where it is
produced.  No cell spans chips, so none can leave out an exchange between
chips.
"""

import jax
import jax.numpy as jnp
import pytest

from bench.tests.helpers import run_cell


@pytest.fixture(autouse=True)
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


def _search_altered(monkeypatch):
    from repro.index import OnlineIndex

    orig = OnlineIndex.search

    def search(self, queries, top_k, **kw):
        res = orig(self, queries, top_k, **kw)
        n = int(self.graph.n_valid)
        return res._replace(ids=res.ids.at[:, 0].set((res.ids[:, 0] + 1) % n))

    monkeypatch.setattr(OnlineIndex, "search", search)


def _search_half(monkeypatch):
    from repro.index import OnlineIndex

    orig = OnlineIndex.search

    def search(self, queries, top_k, **kw):
        B = queries.shape[0]
        res = orig(self, queries[: max(B // 2, 1)], top_k, **kw)
        fill = lambda a: jnp.concatenate([a] * 2)[:B] if a.ndim else a
        return res._replace(**{f: fill(getattr(res, f)) for f in res._fields})

    monkeypatch.setattr(OnlineIndex, "search", search)


def _search_step_unchanged(monkeypatch):
    from repro.core import search as search_lib

    monkeypatch.setattr(search_lib, "_make_step",
                        lambda *a, **k: (lambda st: st._replace(it=st.it + 1)))


def _wave_step_unchanged(monkeypatch):
    from repro.core import construct

    def wave_step(g, x, pos, key, stats, cfg, *, coarse=None, enc=None, n_real=None):
        return (g, stats) if coarse is None else (g, stats, coarse)

    monkeypatch.setattr(construct, "wave_step", wave_step)


def _graph_altered(monkeypatch):
    from repro.index import OnlineIndex

    orig = OnlineIndex.build.__func__

    def build(cls, items, cfg=None, **kw):
        index = orig(cls, items, cfg, **kw)
        g = index.graph
        index.graph = g._replace(nbr_dist=g.nbr_dist.at[:, 0].add(1.0))
        return index

    monkeypatch.setattr(OnlineIndex, "build", classmethod(build))


FAULTS = [
    ("sift128-l2.batch", _search_step_unchanged),
    ("sift128-l2.batch", _search_half),
    ("sift128-l2.batch", _search_altered),
    ("sift128-l2.build", _wave_step_unchanged),
    ("sift128-l2.build", _graph_altered),
]


@pytest.mark.parametrize("workload,plant", FAULTS,
                         ids=[f"{w}-{p.__name__.strip('_')}" for w, p in FAULTS])
def test_fault_is_not_correct(workload, plant, monkeypatch, tmp_path):
    plant(monkeypatch)
    line = run_cell(workload, tmp_path, seconds=2.0)
    assert line["correct"] is False, line["checks"]
