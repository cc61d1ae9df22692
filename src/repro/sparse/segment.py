"""Segment reductions and EmbeddingBag built from JAX primitives.

``jax.ops.segment_sum`` is the TPU-native scatter-reduce; EmbeddingBag is a
ragged gather over a (vocab, dim) table followed by a segment reduce. These
are the hot primitives of both the iCD solver (column sweeps reduce over the
observed-interaction CSR) and multi-hot feature lookups.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def segment_sum(data: jax.Array, segment_ids: jax.Array, num_segments: int) -> jax.Array:
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


# ---------------------------------------------------------------------------
# Sorted-run broadcast. Where the pair list is sorted by row, each row's pairs
# form one contiguous run, so a per-row value reaches its pairs by streaming
# the list in fixed tiles instead of a gather per pair: within a tile, a
# log2(tile)-level scan copies each run's first value along the run (sorted
# ids: equal ids d apart means one run between them).
# ---------------------------------------------------------------------------

SORTED_TILE = 128  # pairs per tile: log2 of it is the in-tile scan depth


def run_offsets(ids_sorted, n_rows: int):
    """``indptr`` of a row-sorted id list: row r's run is
    ``[indptr[r], indptr[r+1])`` (host numpy; empty rows give empty runs)."""
    return np.searchsorted(np.asarray(ids_sorted), np.arange(n_rows + 1),
                           side="left").astype(np.int32)


def _shift(x: jax.Array, d: int, fill) -> jax.Array:
    """x moved ``d`` places up its last axis: out[..., j] = x[..., j-d]."""
    return jnp.pad(x[..., :-d], [(0, 0)] * (x.ndim - 1) + [(d, 0)],
                   constant_values=fill)


def segment_broadcast_sorted(vals: jax.Array, ids_sorted: jax.Array,
                             indptr: jax.Array) -> jax.Array:
    """``jnp.take(vals, ids_sorted)``, bit for bit, for ``ids_sorted``
    ascending with run offsets ``indptr`` (:func:`run_offsets`): each run's
    value is written at its first pair (one write per row and one per tile)
    and copied along the run, Hillis–Steele: at distance d = 1, 2, 4, …
    a pair takes the value d places back where that pair is in its run."""
    n_rows, nnz, tile = indptr.shape[0] - 1, ids_sorted.shape[0], SORTED_TILE
    if nnz == 0:
        return jnp.zeros((0,), vals.dtype)
    n_tiles = -(-nnz // tile)
    ids = jnp.pad(ids_sorted, (0, n_tiles * tile - nnz),
                  constant_values=n_rows).reshape(n_tiles, tile)
    start = indptr[:-1]
    # empty rows write past the end, where the scatter drops them
    at = jnp.where(indptr[1:] > start, start,
                   n_tiles * tile + jnp.arange(n_rows, dtype=start.dtype))
    out = jnp.zeros((n_tiles * tile,), vals.dtype).at[at].set(
        vals, mode="drop", unique_indices=True).reshape(n_tiles, tile)
    first = jnp.take(vals, ids[:, 0], mode="fill", fill_value=0,
                     indices_are_sorted=True)
    out = jnp.where(jnp.arange(tile) == 0, first[:, None], out)
    d = 1
    while d < tile:
        out = jnp.where(_shift(ids, d, -1) == ids, _shift(out, d, 0), out)
        d *= 2
    return out.reshape(-1)[:nnz]


def segment_mean(data: jax.Array, segment_ids: jax.Array, num_segments: int) -> jax.Array:
    total = jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)
    counts = jax.ops.segment_sum(
        jnp.ones(segment_ids.shape, dtype=data.dtype), segment_ids, num_segments=num_segments
    )
    counts = jnp.maximum(counts, 1)
    if data.ndim > 1:
        counts = counts.reshape(counts.shape + (1,) * (data.ndim - 1))
    return total / counts


def segment_max(data: jax.Array, segment_ids: jax.Array, num_segments: int) -> jax.Array:
    return jax.ops.segment_max(data, segment_ids, num_segments=num_segments)


def embedding_bag(
    table: jax.Array,
    ids: jax.Array,
    rows: jax.Array,
    n_rows: int,
    weights: Optional[jax.Array] = None,
    combiner: str = "sum",
) -> jax.Array:
    """Ragged EmbeddingBag: ``out[r] = combine_{j: rows[j]==r} w_j * table[ids[j]]``.

    Args:
      table:   (vocab, dim) embedding table.
      ids:     (nnz,) int32 feature ids (gather indices into ``table``).
      rows:    (nnz,) int32 output row per lookup, sorted or not.
      n_rows:  static number of output rows (batch).
      weights: optional (nnz,) per-lookup weights.
      combiner: 'sum' | 'mean' | 'max'.

    Returns:
      (n_rows, dim).

    """
    gathered = jnp.take(table, ids, axis=0)
    if weights is not None:
        gathered = gathered * weights[:, None].astype(gathered.dtype)
    if combiner == "sum":
        return segment_sum(gathered, rows, n_rows)
    if combiner == "mean":
        return segment_mean(gathered, rows, n_rows)
    if combiner == "max":
        return segment_max(gathered, rows, n_rows)
    raise ValueError(f"unknown combiner {combiner!r}")


def multi_hot_lookup(
    table: jax.Array,
    ids: jax.Array,
    mask: Optional[jax.Array] = None,
    combiner: str = "sum",
) -> jax.Array:
    """Fixed-shape EmbeddingBag for padded multi-hot batches.

    Args:
      table: (vocab, dim).
      ids:   (batch, bag) int32, padded with arbitrary ids where masked.
      mask:  (batch, bag) bool/float — 1 for valid entries; None = all valid.
      combiner: 'sum' | 'mean'.

    Returns:
      (batch, dim).
    """
    gathered = jnp.take(table, ids, axis=0)  # (batch, bag, dim)
    if mask is not None:
        gathered = gathered * mask[..., None].astype(gathered.dtype)
    summed = jnp.sum(gathered, axis=1)
    if combiner == "sum":
        return summed
    if combiner == "mean":
        denom = (
            jnp.sum(mask.astype(gathered.dtype), axis=1, keepdims=True)
            if mask is not None
            else jnp.full((ids.shape[0], 1), ids.shape[1], dtype=gathered.dtype)
        )
        return summed / jnp.maximum(denom, 1)
    raise ValueError(f"unknown combiner {combiner!r}")
