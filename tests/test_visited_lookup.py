"""The dense visited-lookup kernel against the probe gathers it replaces.

``ops.visited_lookup`` answers the LGD commit's D(q, x) from each wave lane's
visited-hash table.  Under ``"interpret"`` it runs the Pallas kernel of
``kernels.visited_lookup`` (a dense compare against the lane's whole table,
masked to each id's probe window); under ``"reference"`` the probe gathers of
``expand.hash_lookup``.  The two must return the same float for every id >= 0,
on tables a real search filled and on hand-planted ones, and ``commit_wave``
must commit the same graph, bit for bit, under either.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import brute, construct
from repro.core import search as search_lib
from repro.kernels import expand, ops
from repro.kernels import visited_lookup as vl

N, D, K, W = 1024, 16, 10, 64


@pytest.fixture(scope="module")
def data():
    return jnp.asarray(np.random.RandomState(0).rand(N, D).astype(np.float32))


@pytest.fixture(scope="module")
def seed_graph(data):
    return brute.exact_seed_graph(data, 256, K, "l2")


def _both(vis_ids, vis_dist, ids, probes):
    got = ops.visited_lookup(vis_ids, vis_dist, ids, probes, dispatch="interpret")
    want = ops.visited_lookup(vis_ids, vis_dist, ids, probes, dispatch="reference")
    return np.asarray(got), np.asarray(want)


def _assert_same_bits(got, want, ids):
    live = np.asarray(ids) >= 0
    np.testing.assert_array_equal(got[live].view(np.int32), want[live].view(np.int32))
    assert np.isposinf(got[~live]).all()


# ---------------------------------------------------------------------------
# tables a real search filled
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hash_slots", [128, 512])
@pytest.mark.parametrize("hash_probes", [4, 8, 16])
def test_equal_on_tables_a_search_filled(data, seed_graph, hash_slots, hash_probes):
    cfg = construct.BuildConfig(k=K, wave=W, beam=16, n_seeds=4,
                                hash_slots=hash_slots, max_iters=24)
    scfg = dataclasses.replace(cfg.search_config(), hash_probes=hash_probes)
    res = search_lib.search(seed_graph, data, data[256 : 256 + W],
                            jax.random.PRNGKey(3), scfg)
    # what the commit looks up: the members of the rows each lane visited,
    # and the visited ids themselves
    rows = seed_graph.nbr_ids[jnp.maximum(res.vis_ids[:, :24], 0)].reshape(W, -1)
    ids = jnp.concatenate([rows, res.vis_ids[:, :200]], axis=1)
    assert int(jnp.sum(res.vis_ids >= 0)) > W * min(hash_slots, 100) // 2
    for probes in sorted({8, hash_probes}):  # the commit's 8, and the search's
        got, want = _both(res.vis_ids, res.vis_dist, ids, probes)
        _assert_same_bits(got, want, ids)
        assert np.isfinite(want).sum() > ids.shape[1]


# ---------------------------------------------------------------------------
# planted tables
# ---------------------------------------------------------------------------

H, P = 256, 8


def _homes():
    """{home slot: an id whose first probe slot it is} for tables of H."""
    cand = np.arange(1, 50_000, dtype=np.int32)
    home = np.asarray(expand.probe_slots(jnp.asarray(cand), H, 1)[:, 0])
    first = {}
    for i, h in zip(cand, home):
        first.setdefault(int(h), int(i))
    assert len(first) == H
    return first


def _planted(case):
    """(vis_ids (2, H), vis_dist (2, H), ids (2, M), expected D where known)."""
    homes = _homes()
    vis_ids = np.full((2, H), -1, np.int32)
    vis_dist = np.full((2, H), np.inf, np.float32)
    expect = {}
    if case == "duplicates":
        a = homes[40]
        vis_ids[0, [40, 43, 46]] = a
        vis_dist[0, [40, 43, 46]] = [3.0, 1.5, 2.0]
        vis_ids[1, 41] = a
        vis_dist[1, 41] = 0.25
        ids = np.array([[a, a, homes[7]], [a, -1, a]], np.int32)
        expect = {(0, 0): 1.5, (0, 1): 1.5, (0, 2): np.inf, (1, 0): 0.25, (1, 2): 0.25}
    elif case == "empty":
        ids = np.array([[homes[0], homes[H - 1], -1], [homes[5], 0, 1]], np.int32)
        expect = {(w, m): np.inf for w in range(2) for m in range(3)}
    elif case == "wrap":
        a, b = homes[H - 3], homes[H - 1]
        vis_ids[0, [H - 3, H - 1, 2]] = [b, a, a]  # a at offsets 2 and 5
        vis_dist[0, [H - 3, H - 1, 2]] = [9.0, 4.0, 0.5]
        vis_ids[1, 6] = b  # b's window is H-1, 0, ..., 6: last slot of it
        vis_dist[1, 6] = 7.0
        ids = np.array([[a, b], [b, a]], np.int32)
        expect = {(0, 0): 0.5, (0, 1): np.inf, (1, 0): 7.0, (1, 1): np.inf}
    elif case == "full":
        for s in range(H):  # every slot holds an id at offset s % P
            vis_ids[:, s] = homes[(s - s % P) % H]
            vis_dist[:, s] = np.float32(s) / 7
        vis_ids[1] = np.roll(vis_ids[1], 1)  # lane 1: offsets shift by one
        ids = np.stack([np.unique(vis_ids[0]), np.unique(vis_ids[1])])
        ids = np.concatenate([ids, np.array([[homes[3]], [-1]], np.int32)], axis=1)
        expect = {(0, ids.shape[1] - 1): np.inf}
    elif case == "outside":
        a = homes[100]
        vis_ids[0, [100 + P, 99]] = a  # one slot past the window, one before
        vis_dist[0, [100 + P, 99]] = [1.0, 2.0]
        vis_ids[1, [100 + P - 1, 100 + P]] = a  # its last slot, and past it
        vis_dist[1, [100 + P - 1, 100 + P]] = [6.0, 0.5]
        ids = np.array([[a], [a]], np.int32)
        expect = {(0, 0): np.inf, (1, 0): 6.0}
    else:
        raise KeyError(case)
    return vis_ids, vis_dist, ids, expect


@pytest.mark.parametrize("case", ["duplicates", "empty", "wrap", "full", "outside"])
def test_equal_on_planted_tables(case):
    vis_ids, vis_dist, ids, expect = _planted(case)
    got, want = _both(jnp.asarray(vis_ids), jnp.asarray(vis_dist), jnp.asarray(ids), P)
    _assert_same_bits(got, want, ids)
    for (w, m), d in expect.items():
        assert got[w, m] == np.float32(d), (case, w, m)
    if case == "full":  # every planted id lies in its window: all found
        assert np.isfinite(got[:, :-1]).all()


@pytest.mark.parametrize("hash_slots, n_ids", [(16384, 2100), (64, 5)])
def test_equal_across_blocks(hash_slots, n_ids):
    """Tables wider than one (8, H_blk) block, more ids than one M block,
    a lane count off the 8-row grid; and a table narrower than a chunk."""
    lanes = 3
    rng = np.random.RandomState(hash_slots)
    cand = rng.randint(0, 1_000_000, (lanes, hash_slots // 2)).astype(np.int32)
    home = np.asarray(expand.probe_slots(jnp.asarray(cand), hash_slots, 1)[..., 0])
    slot = (home + rng.randint(0, 2 * P, cand.shape)) & (hash_slots - 1)
    vis_ids = np.full((lanes, hash_slots), -1, np.int32)
    vis_dist = np.full((lanes, hash_slots), np.inf, np.float32)
    for w in range(lanes):  # last write wins, as a colliding insert would
        vis_ids[w, slot[w]] = cand[w]
        vis_dist[w, slot[w]] = rng.rand(cand.shape[1])
    pick = rng.randint(0, cand.shape[1], (lanes, n_ids))
    ids = np.where(rng.rand(lanes, n_ids) < 0.7, np.take_along_axis(cand, pick, 1),
                   rng.randint(-1, 1_000_000, (lanes, n_ids))).astype(np.int32)
    got, want = _both(jnp.asarray(vis_ids), jnp.asarray(vis_dist), jnp.asarray(ids), P)
    _assert_same_bits(got, want, ids)
    assert np.isfinite(want).sum() > n_ids // 4


def test_auto_runs_the_reference_off_the_chip():
    assert ops.engines("auto", jnp.float32, D, hash_slots=2048)["visited_lookup"] == "xla"
    assert ops.engines("interpret", jnp.float32, D)["visited_lookup"] == "pallas-interpret"
    assert vl.kernel_fits(2048) and vl.kernel_fits(vl.MAX_SLOTS)
    assert not vl.kernel_fits(2 * vl.MAX_SLOTS)


# ---------------------------------------------------------------------------
# the commit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "intra_wave, hash_slots, hash_probes",
    [(True, 512, 8), (False, 512, 8), (True, 128, 16), (False, 128, 4)],
)
def test_commit_wave_bit_identical(data, seed_graph, intra_wave, hash_slots, hash_probes):
    cfg = construct.BuildConfig(k=K, wave=W, lgd=True, intra_wave=intra_wave,
                                beam=16, n_seeds=4, hash_slots=hash_slots,
                                max_iters=24, dispatch="reference")
    scfg = dataclasses.replace(cfg.search_config(), hash_probes=hash_probes)
    pos = jnp.asarray(256, jnp.int32)
    res = search_lib.search(seed_graph, data, data[256 : 256 + W],
                            jax.random.PRNGKey(7), scfg)
    n_real = jnp.asarray(W - 5, jnp.int32)  # a partial wave: padding lanes too
    outs = {}
    for dispatch in ("reference", "interpret"):
        c = dataclasses.replace(cfg, dispatch=dispatch)
        if dispatch == "interpret":  # the kernel is in the committed program
            jaxpr = str(jax.make_jaxpr(
                lambda g, r: construct.commit_wave(g, data, pos, n_real, r, c)
            )(seed_graph, res))
            assert "visited_lookup" in jaxpr and "pallas_call" in jaxpr
        outs[dispatch] = construct.commit_wave(seed_graph, data, pos, n_real, res, c)
    (g_ref, e_ref), (g_ker, e_ker) = outs["reference"], outs["interpret"]
    assert int(e_ref) == int(e_ker) > 0
    assert int(jnp.sum(g_ref.nbr_lam)) > 0  # the λ rules saw finite D
    for name in g_ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(g_ker, name)), np.asarray(getattr(g_ref, name)),
            err_msg=name,
        )
