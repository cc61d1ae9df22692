"""Pure-jnp oracles for the fused score+top-K kernel.

Two reference paths with the kernel's exact semantics (tie-stable
ascending-id order, (−inf, −1) on inadmissible slots):

- :func:`topk_score_ref` — deliberately "memory-naive": it materializes
  the full ``(B, n_items)`` score matrix the kernel exists to avoid, so it
  doubles as the dense baseline in ``benchmarks/serve_bench``. For the
  same reason ``exclude_ids`` (the kernel's web-scale per-row id-list
  form) is expanded to the dense (B, n_items) mask here.
- :func:`retrieval_topk` — the chunked running-reduce oracle over an
  arbitrary ``score_fn`` (moved here from ``serve/recsys_serve.py``; the
  serving tier re-exports it): never materializes all scores, so it also
  serves as the huge-catalogue baseline.

Score contract between the kernel and these references: both compute
every ⟨φ, ψ⟩ in fp32 at :data:`SCORE_PRECISION` (``HIGHEST``: on the TPU
MXU a plain fp32 dot would round its inputs to bf16, and the kernel and
XLA need not choose the same pass count). Only the summation order
differs, so kernel scores agree with :func:`topk_score_ref` within
:data:`SCORE_RTOL`/:data:`SCORE_ATOL`, and ids agree exactly wherever the
reference's scores are not tied within that tolerance
(:func:`topk_mismatches`). Two runs of the kernel itself on the same
inputs (the mesh at any shard or replica count) stay bit-identical.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SCORE_PRECISION = jax.lax.Precision.HIGHEST
# fp32 reassociation of a D≤1024 dot product stays well inside 1e-5
# relative to the terms' magnitude (~D·2⁻²⁴ in the worst case).
SCORE_RTOL = 1e-5
SCORE_ATOL = 1e-5


def exclude_ids_to_mask(exclude_ids, n_items: int):
    """Dense (B, n_items) bool mask from −1-padded per-row global id lists
    (oracle/test helper — the kernel never builds this)."""
    ids = jnp.asarray(exclude_ids, jnp.int32)
    rows = jnp.broadcast_to(jnp.arange(ids.shape[0])[:, None], ids.shape)
    cols = jnp.where(ids >= 0, ids, n_items)       # −1 padding → dropped
    return jnp.zeros((ids.shape[0], n_items), bool).at[rows, cols].set(
        True, mode="drop")


def topk_score_ref(phi, psi, k, exclude_mask=None, *, exclude_ids=None):
    """Dense reference with the kernel's exact semantics: tie-stable
    ascending-id order (``lax.top_k`` positional stability over the
    id-ordered row) and (−inf, −1) on slots with no admissible candidate."""
    n_items = psi.shape[0]
    scores = jnp.dot(phi.astype(jnp.float32), psi.astype(jnp.float32).T,
                     precision=SCORE_PRECISION)
    if exclude_ids is not None:
        assert exclude_mask is None, "pass exclude_mask OR exclude_ids"
        exclude_mask = exclude_ids_to_mask(exclude_ids, n_items)
    if exclude_mask is not None:
        scores = jnp.where(exclude_mask != 0, -jnp.inf, scores)
    if k > n_items:  # dense top_k cannot rank more slots than exist
        pad = k - n_items
        scores = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    top_s, top_i = jax.lax.top_k(scores, k)
    top_i = jnp.where(jnp.isneginf(top_s), -1, top_i).astype(jnp.int32)
    return top_s, top_i


def topk_mismatches(scores, ids, ref_scores, ref_ids, *, rtol=SCORE_RTOL,
                    atol=SCORE_ATOL) -> dict:
    """Count departures of a top-K result from the reference under the
    score contract above: ``scores`` outside the tolerance, and ``ids`` that
    differ at a slot whose reference score is NOT tied (within tolerance)
    with a neighbouring slot or with the first score below the list — a
    near-tie may legally rank either way. Returns the two counts."""
    s, i = np.asarray(scores, np.float64), np.asarray(ids)
    rs, ri = np.asarray(ref_scores, np.float64), np.asarray(ref_ids)
    tol = atol + rtol * np.abs(rs)
    with np.errstate(invalid="ignore"):     # −inf − −inf on empty slots
        close = (np.isneginf(s) & np.isneginf(rs)) | (np.abs(s - rs) <= tol)
        gap = rs[:, :-1] - rs[:, 1:]        # slot j vs slot j+1
        edge = rs[:, -1:] - s[:, -1:]       # the list's last slot
        tied = np.zeros(rs.shape, bool)
        tied[:, 1:] |= gap <= 2 * tol[:, 1:]
        tied[:, :-1] |= gap <= 2 * tol[:, :-1]
        tied[:, -1:] |= edge <= 2 * tol[:, -1:]
    return {"score_mismatches": int((~close).sum()),
            "id_mismatches": int(((i != ri) & ~tied).sum())}


def retrieval_topk(
    score_fn: Callable[[jax.Array], jax.Array],  # cand_ids → scores
    n_candidates: int,
    k: int = 100,
    chunk: int = 262144,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k over ``n_candidates`` scored in chunks with a running reduce.

    ``score_fn(ids)`` may return ``(chunk,)`` (single query) or
    ``(B, chunk)`` (batched); the reduce carries matching ``(..., k)``
    state. Slots with no real candidate (``n_candidates < k``) stay at
    id −1 / score −inf — no placeholder item id ever leaks into the
    result. Ties resolve toward the smaller candidate id (``lax.top_k``
    positional stability + ascending chunk order), the same policy as the
    fused kernel and :func:`topk_score_ref`.
    """
    best_scores = best_ids = None
    for lo in range(0, n_candidates, chunk):
        ids = jnp.arange(lo, min(lo + chunk, n_candidates), dtype=jnp.int32)
        scores = score_fn(ids)
        if best_scores is None:  # first chunk fixes the (optional) batch dim
            lead = scores.shape[:-1]
            best_scores = jnp.full(lead + (k,), -jnp.inf, scores.dtype)
            best_ids = jnp.full(lead + (k,), -1, jnp.int32)
        merged_s = jnp.concatenate([best_scores, scores], axis=-1)
        merged_i = jnp.concatenate(
            [best_ids, jnp.broadcast_to(ids, scores.shape).astype(jnp.int32)],
            axis=-1,
        )
        best_scores, idx = jax.lax.top_k(merged_s, k)
        best_ids = jnp.take_along_axis(merged_i, idx, axis=-1)
    if best_scores is None:  # n_candidates == 0
        best_scores = jnp.full((k,), -jnp.inf)
        best_ids = jnp.full((k,), -1, jnp.int32)
    return best_scores, best_ids
