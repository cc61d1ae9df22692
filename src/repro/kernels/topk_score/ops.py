"""Jit'd public wrappers for the fused score+top-K retrieval kernel family.

Two ops and the exclusion form's builder:

  * :func:`topk_score` — the fused streaming kernel over one ψ table (or
    one row-range shard of it, via ``id_offset``/``n_valid``);
  * :func:`topk_merge_shards` — the cross-shard K-way merge that combines
    per-shard top-K candidate lists (already carrying GLOBAL ids) into the
    final (B, k), reproducing the kernel's exact tie-stable
    ascending-global-id policy. The serving mesh (``serve/mesh.py``) is
    ``S × topk_score  →  topk_merge_shards``;
  * :func:`exclude_ids_from_lists` — ragged per-row excluded-id lists in
    the kernel's (B, L) −1-padded exclusion form.
"""
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import kernel_jit
from repro.kernels.topk_score.kernel import topk_score_pallas


def exclude_ids_from_lists(
    item_lists: Sequence, *, min_width: int = 1
) -> jax.Array:
    """(B, L) int32, −1-padded: ragged per-row GLOBAL excluded-id lists
    (train histories) in the kernel's exclude form. L is the widest row
    (≥ ``min_width``); host cost is O(Σ|list|) — never O(B·n_items)."""
    width = max(min_width, max((len(ids) for ids in item_lists), default=0))
    out = np.full((len(item_lists), width), -1, np.int32)
    for r, ids in enumerate(item_lists):
        ids = np.asarray(ids, np.int64).reshape(-1)
        out[r, : ids.size] = ids
    return jnp.asarray(out)


@kernel_jit(static_argnames=("k", "block_b", "block_items"))
def topk_score(phi, psi, k, *, exclude_ids=None, psi_scale=None,
               id_offset=0, n_valid=None, block_b=128, block_items=None,
               interpret=None):
    """Fused streaming top-K over the ψ table: ``(scores, ids) (B, k)``.

    ``exclude_ids`` (B, L) is a −1-padded per-row list of GLOBAL excluded
    ids (:func:`exclude_ids_from_lists`) — the admissibility tile is built
    in-kernel per ψ block, so no (B, n_items) mask is ever materialized.
    Inadmissible slots come back as (−inf, −1). ``id_offset``/``n_valid``
    (traced scalars allowed) serve a row-range ψ shard with global output
    ids; see ``kernel.py`` for the tie policy.

    ``psi`` may be quantized serving storage: bf16, or int8 with the
    per-row ``psi_scale`` from ``core.quant.int8_quantize_rows`` —
    dequantized in-kernel per tile, fp32 accumulate (``serve/ann.py``)."""
    return topk_score_pallas(
        phi, psi, k, exclude_ids=exclude_ids, psi_scale=psi_scale,
        id_offset=id_offset, n_valid=n_valid,
        block_b=block_b, block_items=block_items, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("k",))
def topk_merge_shards(shard_scores, shard_ids, k):
    """Cross-shard K-way merge: ``(S, B, Ks) → (B, k)`` scores and ids.

    Inputs are the stacked per-shard results of :func:`topk_score` with
    per-shard ``id_offset`` — ids are GLOBAL and the shards' row ranges are
    disjoint, so the merge is a pure rank: sort the S·Ks candidates per row
    by ``(−score, id)`` lexicographically (two-key ``lax.sort``) and take
    the first k. That reproduces the kernel's documented policy exactly:

      * descending score, ties in ASCENDING global id — identical to dense
        ``lax.top_k`` over the id-ordered full-catalogue row (shards emit
        id-sorted ties, but their top-K lists are score-ordered, so a
        positional concat-and-top_k would NOT be tie-stable; the explicit
        id key is what makes the merge shard-count-invariant);
      * (−inf, −1) on slots with no admissible candidate anywhere — the
        per-shard kernels already return −inf slots as id −1, and any slot
        still at −inf after the merge is forced to id −1.

    The (B, S·Ks) candidate scratch is the ``S·K`` term in the cluster's
    VMEM footprint model (:func:`repro.kernels.vmem.cluster_block_items`).
    """
    s, b, ks = shard_scores.shape
    flat_s = jnp.swapaxes(shard_scores, 0, 1).reshape(b, s * ks)
    flat_i = jnp.swapaxes(shard_ids, 0, 1).reshape(b, s * ks)
    if k > s * ks:  # fewer candidates than requested: pad inadmissible
        pad = k - s * ks
        flat_s = jnp.pad(flat_s, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        flat_i = jnp.pad(flat_i, ((0, 0), (0, pad)), constant_values=-1)
    neg_sorted, ids_sorted = jax.lax.sort(
        (-flat_s, flat_i), dimension=1, num_keys=2
    )
    scores = -neg_sorted[:, :k]
    ids = jnp.where(jnp.isneginf(scores), -1, ids_sorted[:, :k])
    return scores, ids.astype(jnp.int32)
