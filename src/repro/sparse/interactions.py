"""Observed-interaction container for implicit-feedback learning.

Holds the rescaled positive set ``S`` of Lemma 1 in COO-sorted-by-row layout
(plus the transposed layout for item-side sweeps). All arrays are fixed-shape
device arrays — the iCD solver jits over them.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.segment import run_offsets


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Interactions:
    """Rescaled observed feedback S (Lemma 1, eq. 8) in dual COO layout.

    Context-major arrays (sorted by ``ctx``):
      ctx, item:  (nnz,) int32
      y:          (nnz,) f32 — rescaled targets ȳ = α/(α−α₀)·y
      alpha:      (nnz,) f32 — rescaled confidences ᾱ = α−α₀

    Item-major view of the same triplets (sorted by item):
      t_ctx, t_item, t_perm — ``t_perm`` maps item-major position → context-
      major position so residual caches can be permuted between sweeps.

    Run offsets of the two sorted layouts (for the sorted-run broadcast in
    ``repro.sparse.segment``): context c's pairs are ``[indptr[c],
    indptr[c+1])`` of the context-major arrays, item i's ``[t_indptr[i],
    t_indptr[i+1])`` of the item-major ones.
      indptr: (n_ctx+1,) int32;  t_indptr: (n_items+1,) int32
    """

    ctx: jax.Array
    item: jax.Array
    y: jax.Array
    alpha: jax.Array
    t_ctx: jax.Array
    t_item: jax.Array
    t_perm: jax.Array
    indptr: jax.Array
    t_indptr: jax.Array
    n_ctx: int = dataclasses.field(metadata=dict(static=True))
    n_items: int = dataclasses.field(metadata=dict(static=True))

    @property
    def nnz(self) -> int:
        return int(self.ctx.shape[0])


def build_interactions(
    ctx: np.ndarray,
    item: np.ndarray,
    y: np.ndarray,
    alpha: np.ndarray,
    n_ctx: int,
    n_items: int,
    alpha0: float = 1.0,
    rescale: bool = True,
) -> Interactions:
    """Build the dual-layout container, applying the Lemma 1 rescaling.

    Args:
      ctx, item: observed (context, item) pairs.
      y, alpha: raw scores and confidences (α must exceed α₀).
      alpha0: the implicit confidence α₀ of the zero set S⁰.
      rescale: apply eq. (8); disable when the caller pre-rescaled.
    """
    ctx = np.asarray(ctx, dtype=np.int64)
    item = np.asarray(item, dtype=np.int64)
    y = np.asarray(y, dtype=np.float64)
    alpha = np.asarray(alpha, dtype=np.float64)
    if rescale:
        if np.any(alpha <= alpha0):
            raise ValueError("Lemma 1 rescaling needs alpha > alpha0 on S+")
        y = alpha / (alpha - alpha0) * y
        alpha = alpha - alpha0

    order = np.lexsort((item, ctx))
    ctx, item, y, alpha = ctx[order], item[order], y[order], alpha[order]

    t_order = np.lexsort((ctx, item))
    t_item = item[t_order]
    return Interactions(
        ctx=jnp.asarray(ctx, dtype=jnp.int32),
        item=jnp.asarray(item, dtype=jnp.int32),
        y=jnp.asarray(y, dtype=jnp.float32),
        alpha=jnp.asarray(alpha, dtype=jnp.float32),
        t_ctx=jnp.asarray(ctx[t_order], dtype=jnp.int32),
        t_item=jnp.asarray(t_item, dtype=jnp.int32),
        t_perm=jnp.asarray(t_order, dtype=jnp.int32),
        indptr=jnp.asarray(run_offsets(ctx, n_ctx)),
        t_indptr=jnp.asarray(run_offsets(t_item, n_items)),
        n_ctx=int(n_ctx),
        n_items=int(n_items),
    )
