"""Device scopes: the ``jax.named_scope``s the chip benchmark reads by name.

``construct.wave_core`` puts the wave's insertion search under
``wave_search`` and its commit under ``wave_commit``; ``search.search``
puts the coarse pass under ``coarse_pass`` and the expansion loop under
``expand``.  The names reach each compiled op's ``op_name`` metadata (a TPU
trace's ``tf_op``), and the benchmark's per-layer readers sum device time
by them, so these tests pin them in the compiled HLO (CPU).
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import construct
from repro.core import search as search_lib

OP_NAME = re.compile(r'op_name="([^"]*)"')


def _op_names(compiled_text: str) -> list:
    """The op_name of every instruction of the compiled modules, as paths
    (reduction and sort comparators carry bare names and are left out)."""
    return [n for n in OP_NAME.findall(compiled_text) if n.startswith("jit(")]


def _segments(name: str) -> list:
    return name.split("/")


@pytest.fixture(scope="module")
def built():
    x = jax.random.normal(jax.random.PRNGKey(0), (384, 16))
    cfg = construct.BuildConfig(k=8, wave=64, n_seed_init=64, max_iters=10,
                                seed_mode="coarse", dispatch="reference")
    g, stats, coarse = construct.build(x, cfg, jax.random.PRNGKey(1),
                                       return_coarse=True)
    return x, cfg, g, stats, coarse


@pytest.mark.parametrize("scope", ["wave_search", "wave_commit"])
def test_wave_step_ops_fall_under_search_and_commit(built, scope):
    x, cfg, g, stats, coarse = built
    # ``wave_step`` is ``wave_core`` under a donating jit; the same program
    text = jax.jit(construct.wave_core, static_argnames=("cfg",)).lower(
        g, x, jnp.asarray(64, jnp.int32), jax.random.PRNGKey(2), stats, cfg,
        coarse=coarse,
    ).compile().as_text()
    names = _op_names(text)
    assert any(scope in _segments(n) for n in names)
    # the insertion search's ops, the coarse pass's among them, are all
    # under wave_search; commit_wave's are all under wave_commit
    owner = {"wave_search": "jit(search)", "wave_commit": "jit(commit_wave)"}[scope]
    mine = [n for n in names if owner in _segments(n)]
    assert mine and all(scope in _segments(n) for n in mine)
    other = ({"wave_search", "wave_commit"} - {scope}).pop()
    assert not any(other in _segments(n) for n in mine)


def test_d_lookup_ops_fall_under_wave_commit(built):
    """The commit's D lookup (``ops.visited_lookup``) runs under its own
    ``d_lookup`` scope, inside ``wave_commit``'s ``commit_wave``."""
    x, cfg, g, stats, coarse = built
    text = jax.jit(construct.wave_core, static_argnames=("cfg",)).lower(
        g, x, jnp.asarray(64, jnp.int32), jax.random.PRNGKey(2), stats, cfg,
        coarse=coarse,
    ).compile().as_text()
    mine = [_segments(n) for n in _op_names(text) if "d_lookup" in _segments(n)]
    assert mine
    for seg in mine:
        assert seg.index("wave_commit") < seg.index("jit(commit_wave)") < seg.index("d_lookup")
        assert "wave_search" not in seg


def test_search_ops_fall_under_coarse_pass_and_expand(built):
    x, cfg, g, _, coarse = built
    scfg = cfg.search_config()
    text = search_lib.search.lower(
        g, x, x[:32], jax.random.PRNGKey(3), scfg, coarse=coarse
    ).compile().as_text()
    names = _op_names(text)
    coarse_ops = [n for n in names if "coarse_pass" in _segments(n)]
    fine_expand = [n for n in names if "expand" in _segments(n)
                   and "coarse_pass" not in _segments(n)]
    assert coarse_ops and fine_expand
    # the coarse pass's own expansion loop sits under coarse_pass/.../expand
    assert any("expand" in _segments(n) for n in coarse_ops)
    # no coarse-pass op falls outside coarse_pass: every op of the nested
    # search (a second jit(search) in its path) is under it
    nested = [n for n in names if _segments(n).count("jit(search)") > 1]
    assert nested and all(
        _segments(n).index("coarse_pass") < _segments(n).index("jit(search)", 1)
        for n in nested if "coarse_pass" in _segments(n)
    )
    assert all("coarse_pass" in _segments(n) for n in nested)
    # the fine expansion loop is one while under expand, outside the pass
    whiles = [n for n in fine_expand if _segments(n)[-1] == "while"]
    assert whiles == ["jit(search)/expand/while"] * len(whiles) and whiles
