"""Segmented-scan / group-by primitives — the shared core of every batched
commit in this codebase.

Four subsystems used to carry private copies of the same sort-based group-by
idiom: ``graph.rebuild_reverse`` (edges grouped by member), ``merge`` (wave
candidates grouped by target row), ``nndescent._reverse_sample`` (reverse
lists grouped by neighbor) and ``models.moe`` (token routing grouped by
expert).  All of them reduce to: sort a key column, find segment boundaries,
rank elements within their segment, and scatter the first R per segment into
a dense (num_segments, R) buffer.

This module is that idiom, written once against stable JAX primitives
(``lax.cummax`` and ``jax.lax.associative_scan`` — the old copies used
``jnp.maximum.accumulate`` which no longer exists).  Conventions:

* key columns are **sorted ascending**; callers sort first (``jnp.argsort`` /
  ``jnp.lexsort``) because they usually need the permutation anyway;
* invalid/padding entries use a **sentinel key >= num_segments** so they sort
  to the tail and scatter with ``mode="drop"``.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

Array = jax.Array


def segment_starts(sorted_keys: Array) -> Array:
    """(T,) sorted keys -> (T,) bool, True where a new segment begins."""
    return jnp.concatenate(
        [jnp.ones((1,), bool), sorted_keys[1:] != sorted_keys[:-1]]
    )


def running_max(values: Array) -> Array:
    """Inclusive prefix maximum along axis 0.

    ``lax.cummax`` is an associative scan everywhere but on a TPU, where it
    lowers to one windowed reduction; the scan's dozens of strided slices
    over a whole graph's edge list are slow to compile there.
    """
    return jax.lax.cummax(values, axis=0)


def running_min(values: Array) -> Array:
    """Inclusive prefix minimum along axis 0 (see ``running_max``)."""
    return jax.lax.cummin(values, axis=0)


def _segmented_combine(op):
    """Combiner for (start_flag, value) pairs: reset the scan at segment
    starts.  Classic segmented-scan construction (Blelloch)."""

    def combine(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, op(va, vb))

    return combine


def segment_max(values: Array, starts: Array) -> Array:
    """Inclusive running max within each segment (reset at ``starts``)."""
    _, out = jax.lax.associative_scan(
        _segmented_combine(jnp.maximum), (starts, values)
    )
    return out


def segment_min(values: Array, starts: Array) -> Array:
    """Inclusive running min within each segment (reset at ``starts``)."""
    _, out = jax.lax.associative_scan(
        _segmented_combine(jnp.minimum), (starts, values)
    )
    return out


def segment_rank(sorted_keys: Array) -> Array:
    """Rank (0-based) of each element within its run of equal keys.

    ``sorted_keys`` must be sorted ascending; padding sentinels form their own
    tail segment and rank normally (callers mask them out).
    """
    idx = jnp.arange(sorted_keys.shape[0])
    starts = segment_starts(sorted_keys)
    seg_start = running_max(jnp.where(starts, idx, 0))
    return (idx - seg_start).astype(jnp.int32)


def segment_counts(sorted_keys: Array, num_segments: int) -> Array:
    """(num_segments,) occurrence count per key; keys >= num_segments dropped."""
    valid = sorted_keys < num_segments
    return jax.ops.segment_sum(
        valid.astype(jnp.int32),
        jnp.where(valid, sorted_keys, num_segments),
        num_segments=num_segments + 1,
    )[:num_segments].astype(jnp.int32)


def mask_row_duplicates(ids: Array) -> Array:
    """(B, C) int ids -> (B, C) bool, True at every later copy of an id >= 0.

    The batched row-local form of the sort-based dedupe idiom: stable-sort
    each row, mark adjacent equal runs past their first element, and scatter
    the marks back through the permutation.  Replaces the O(C²) pairwise
    ``triu`` masks the search layer used to build — same keep-the-earliest
    semantics (stable sort preserves original order within equal runs),
    O(C log C) work and O(B·C) memory.  Negative ids (padding) are never
    marked.
    """
    B, C = ids.shape
    order = jnp.argsort(ids, axis=1, stable=True)
    s = jnp.take_along_axis(ids, order, axis=1)
    dup_sorted = jnp.concatenate(
        [jnp.zeros((B, 1), bool), (s[:, 1:] == s[:, :-1]) & (s[:, 1:] >= 0)],
        axis=1,
    )
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, C))
    return jnp.zeros((B, C), bool).at[rows, order].set(dup_sorted)


def grouped_top_r(
    sorted_keys: Array,
    payloads: Sequence[Array],
    fills: Sequence,
    num_segments: int,
    r: int,
    *,
    keep: Array | None = None,
) -> tuple[list[Array], Array]:
    """Scatter the first ``r`` elements of each segment into dense buffers.

    Args:
      sorted_keys: (T,) int32 segment ids, sorted ascending; >= num_segments
        is padding.
      payloads: sequence of (T,) arrays to scatter, aligned with the keys.
      fills: fill value per payload (buffer background / padding value).
      num_segments: number of output rows.
      r: row width — elements ranked >= r within their segment are dropped.
      keep: optional (T,) bool of extra per-element drops (applied on top of
        the rank filter).

    Returns:
      (buffers, counts): one (num_segments, r) buffer per payload, and the
      (num_segments,) total occurrence count per segment (NOT capped at r —
      ring-buffer callers need the uncapped count).
    """
    rank = segment_rank(sorted_keys)
    ok = (sorted_keys < num_segments) & (rank < r)
    if keep is not None:
        ok &= keep
    row = jnp.where(ok, sorted_keys, num_segments)
    col = jnp.where(ok, rank, 0)
    buffers = []
    for payload, fill in zip(payloads, fills):
        buf = jnp.full((num_segments + 1, r), fill, payload.dtype)
        buf = buf.at[row, col].set(jnp.where(ok, payload, fill), mode="drop")
        buffers.append(buf[:num_segments])
    counts = segment_counts(sorted_keys, num_segments)
    return buffers, counts
