"""The main path's kernels compile for a TPU v5e, checked without the chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached, so a tiling or memory refusal that interpret
mode cannot see fails here, at no chip time.  Each test lowers one program
at the smoke widths of ``chip_smoke.py`` (1,000,000 x 128 fp32 tables,
4096-query waves) for one chip of a described ``v5e:2x2`` and compiles it.

Code that asks the backend still sees the CPU here, so the tests steer
``kernels.ops`` onto its TPU branch themselves.  The topology is described in
a fixture, never at import, and skips where no topology can be described.
The persistent compilation cache is off around the compiles: an entry
written for a described chip cannot be read back without one.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import knn_lgd
from repro.core import graph as graph_lib
from repro.core import hierarchy
from repro.core import search as search_lib
from repro.kernels import distance, gather_dist, ops

N, D = 1_000_000, 128
B, C, E, H = 4096, 60, 40, 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs outside
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch, one_chip, no_compile_cache):
    """Steer dispatch onto its TPU branch; return a shape builder."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )


def compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def placed(tree, spec):
    """Abstract leaves of ``tree`` placed on the described chip."""
    return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)


@pytest.mark.parametrize("c", [C, 300])
def test_gather_distance_compiles(on_tpu, c):
    s = on_tpu
    text = compile_text(
        lambda q, x, i, sn: ops.gather_distance(
            q, x, i, "l2", sq_norms=sn, dispatch="auto"
        ),
        s((B, D)), s((N, D)), s((B, c), jnp.int32), s((N,)),
    )
    assert "tpu_custom_call" in text


def test_gather_kernel_fits_only_fp32_lane_multiples():
    assert gather_dist.kernel_fits(jnp.float32, 128)
    assert gather_dist.kernel_fits(jnp.float32, 256)
    assert not gather_dist.kernel_fits(jnp.float32, 100)
    assert not gather_dist.kernel_fits(jnp.bfloat16, 128)
    assert not gather_dist.kernel_fits(jnp.int8, 128)


def test_auto_expand_step_compiles(on_tpu):
    """The TPU ``auto`` expansion: XLA probe/merge around the gather kernel."""
    s = on_tpu
    assert ops.engines("auto", jnp.float32, D)["expand_step"] == (
        "xla+pallas-gather"
    )
    text = compile_text(
        lambda q, x, c, bi, bd, be, vi, vd, sn: ops.expand_step(
            q, x, c, bi, bd, be, vi, vd, metric="l2", hash_probes=8,
            sq_norms=sn, dispatch="auto",
        ),
        s((B, D)), s((N, D)), s((B, C), jnp.int32), s((B, E), jnp.int32),
        s((B, E)), s((B, E), jnp.bool_), s((B, H), jnp.int32), s((B, H)),
        s((N,)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cached", [True, False])
def test_pairwise_l2_compiles(on_tpu, cached):
    s = on_tpu
    if cached:
        fn = lambda q, x, sn: distance.pairwise_distance(  # noqa: E731
            q, x, metric="l2", x_sq_norms=sn, interpret=False
        )
        args = (s((1024, D)), s((65536, D)), s((65536,)))
    else:
        fn = lambda q, x: distance.pairwise_distance(  # noqa: E731
            q, x, metric="l2", interpret=False
        )
        args = (s((1024, D)), s((65536, D)))
    assert "tpu_custom_call" in compile_text(fn, *args)


def test_pairwise_chi2_compiles(on_tpu):
    s = on_tpu
    text = compile_text(
        lambda q, x: distance.pairwise_distance(
            q, x, metric="chi2", interpret=False
        ),
        s((256, 500)), s((8192, 500)),
    )
    assert "tpu_custom_call" in text


def test_search_wave_compiles(on_tpu):
    """One coarse-seeded 4096-query search wave over 1,000,000 x 128 with
    the ``knn-lgd`` full config, as ``OnlineIndex.search`` runs it."""
    s = on_tpu
    cfg = knn_lgd.full_config()
    scfg = dataclasses.replace(cfg.search_config(), seed_mode="coarse")
    L, M = hierarchy.default_landmarks(N), cfg.coarse_members
    rev = 2 * cfg.k
    g = placed(jax.eval_shape(lambda: graph_lib.empty_graph(N, cfg.k, rev)), s)
    coarse = placed(
        jax.eval_shape(
            lambda: hierarchy.CoarseLevel(
                landmark_rows=jnp.zeros((L,), jnp.int32),
                points=jnp.zeros((L, D)),
                graph=graph_lib.empty_graph(L, cfg.k, rev),
                members=jnp.zeros((L, M), jnp.int32),
                mem_ptr=jnp.zeros((L,), jnp.int32),
            )
        ),
        s,
    )
    key = placed(jax.eval_shape(lambda: jax.random.PRNGKey(0)), s)
    compiled = (
        search_lib.search.lower(g, s((N, D)), s((B, D)), key, scfg, coarse)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9

