"""Windowed visited-hash lookup as a dense per-lane compare — the LGD commit's D.

The wave commit's occlusion rules (``core.construct.commit_wave``, step 3)
need D(q, x) for every member x of every row a wave lane updates: the
distance the lane's search computed, read from its (H,) visited-hash table,
or +inf where the search never compared the pair (Rule 1).  The reference,
``expand.hash_lookup``, probes each id's ``probes`` linear-probe slots with
two element gathers of shape (W, M, probes).  A TPU runs scalar element
gathers at a small fraction of HBM bandwidth, while every id a lane looks up
lives in that lane's own table, a few KB that fit VMEM.  So this kernel
compares each id against every slot of its lane's table on the vector unit
and keeps the smallest distance among the slots that match.

It is exact, not a heuristic.  An id is only ever written inside its own
probe window (``(slot - home(id)) mod H < probes``, ``home`` the id's first
probe slot), and a slot that holds id x has ``home(x)`` as its owner's home.
So ``_window_table`` masks, once per table and in XLA, every slot whose id
lies outside its own window (and every empty slot) to +inf; the dense min
over the masked table then runs over the same slots as the probe min, and
returns the same float, for every id >= 0.  Negative ids read +inf.

Layout
------
* grid = (W_pad / 8, M_pad / M_blk, H_pad / H_blk): eight wave lanes (one
  f32 vreg's rows) per step; the H axis is last and ``"arbitrary"``, so the
  (8, M_blk) output block stays resident and carries the running min.
* Each step sweeps its (8, H_blk) table block in 128-slot chunks; within a
  chunk, slot j's (8, 1) column is compared against the (8, M_blk) ids and
  folded into the min.  Only compare, select, min and iota run in the kernel,
  and no (W, M, H) array exists anywhere.
* W pads with empty lanes, M with id -1, H with empty (+inf) slots; the
  wrapper slices the (W, M) result back out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat
from repro.kernels.expand import probe_slots

Array = jax.Array

_ROWS = 8  # wave lanes per grid step: one f32 vreg's sublanes
_CHUNK = 128  # table slots per sweep step: one vreg's lanes
_MAX_BLOCK_M = 2048  # ids per block: ids and running min stay in vregs
_MAX_BLOCK_H = 8192  # table slots per block: 2 x 8 x 8192 x 4 B = 512 KiB

# Largest table the compiled kernel takes under dispatch "auto".  Its cost
# grows with H (every id meets every slot) while the gathers' does not.  On
# one TPU v5e at W 4,096 and M 1,200 it took 7.1 ms at H 2,048 and 201 ms at
# H 65,536, the gathers 1,122 and 1,297 ms; 65,536 is the largest H measured,
# and the largest ``search.auto_hash_slots`` makes.
MAX_SLOTS = 65536


def kernel_fits(hash_slots: int) -> bool:
    """Whether ``"auto"`` runs the compiled kernel for (W, H) tables: H up
    to ``MAX_SLOTS``.  VMEM never binds, since every block is tiled."""
    return hash_slots <= MAX_SLOTS


def _window_table(vis_ids: Array, vis_dist: Array, probes: int) -> Array:
    """``vis_dist`` with +inf at every slot no lookup may read: empty slots
    and slots whose id lies outside its own ``probes``-slot window."""
    W, H = vis_ids.shape
    home = probe_slots(vis_ids, H, 1)[..., 0]
    slot = jax.lax.broadcasted_iota(jnp.int32, (W, H), 1)
    ok = (vis_ids >= 0) & (((slot - home) & (H - 1)) < probes)
    return jnp.where(ok, vis_dist, jnp.inf)


def _blocks(n: int, cap: int, unit: int) -> tuple[int, int]:
    """(block, padded n): the fewest blocks of at most ``cap`` that cover n,
    each a multiple of ``unit``."""
    n_blocks = pl.cdiv(max(n, 1), cap)
    blk = pl.cdiv(pl.cdiv(max(n, 1), n_blocks), unit) * unit
    return blk, blk * n_blocks


def _lookup_kernel(tid_ref, tdist_ref, ids_ref, out_ref, *, n_chunks):
    @pl.when(pl.program_id(2) == 0)
    def _():
        out_ref[...] = jnp.full(out_ref.shape, jnp.inf, jnp.float32)

    ids = ids_ref[...]  # (8, M_blk)

    def sweep(c, acc):
        base = pl.multiple_of(c * _CHUNK, _CHUNK)
        t_id = tid_ref[:, pl.ds(base, _CHUNK)]  # (8, 128)
        t_d = tdist_ref[:, pl.ds(base, _CHUNK)]
        for j in range(_CHUNK):
            hit = ids == t_id[:, j : j + 1]
            acc = jnp.minimum(acc, jnp.where(hit, t_d[:, j : j + 1], jnp.inf))
        return acc

    out_ref[...] = jax.lax.fori_loop(0, n_chunks, sweep, out_ref[...])


@functools.partial(jax.jit, static_argnames=("probes", "interpret"))
def visited_lookup(
    vis_ids: Array,  # (W, H) int32 per-lane visited tables
    vis_dist: Array,  # (W, H) float32
    ids: Array,  # (W, M) int32 ids to find, lane w in table w
    probes: int,
    *,
    interpret: bool = False,
) -> Array:
    """(W, M) float32: the distance lane w's table holds for ``ids[w, m]``
    within its ``probes``-slot window, +inf where it holds none or the id is
    negative — ``expand.hash_lookup``'s distance, from a dense compare."""
    W, H = vis_ids.shape
    M = ids.shape[1]
    t_dist = _window_table(vis_ids, vis_dist, probes)
    hb, hp = _blocks(H, _MAX_BLOCK_H, _CHUNK)
    mb, mp = _blocks(M, _MAX_BLOCK_M, 128)
    wp = pl.cdiv(W, _ROWS) * _ROWS
    t_id = jnp.pad(vis_ids, ((0, wp - W), (0, hp - H)), constant_values=-1)
    t_dist = jnp.pad(t_dist, ((0, wp - W), (0, hp - H)), constant_values=jnp.inf)
    idp = jnp.pad(ids, ((0, wp - W), (0, mp - M)), constant_values=-1)
    table = pl.BlockSpec((_ROWS, hb), lambda i, j, h: (i, h))
    out = pl.pallas_call(
        functools.partial(_lookup_kernel, n_chunks=hb // _CHUNK),
        grid=(wp // _ROWS, mp // mb, hp // hb),
        in_specs=[table, table, pl.BlockSpec((_ROWS, mb), lambda i, j, h: (i, j))],
        out_specs=pl.BlockSpec((_ROWS, mb), lambda i, j, h: (i, j)),
        out_shape=jax.ShapeDtypeStruct((wp, mp), jnp.float32),
        compiler_params=compat.compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="visited_lookup",
    )(t_id, t_dist, idp)
    return out[:W, :M]
