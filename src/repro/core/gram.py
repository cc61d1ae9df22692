"""Gram matrices J = MᵀM — the engine of Lemma 2.

For any k-separable model the implicit regularizer collapses to
``R(Θ) = Σ_{f,f'} J_C(f,f') · J_I(f,f')`` (paper eq. 12) with
``J_C = ΦᵀΦ`` and ``J_I = ΨᵀΨ``. Both are tall-skinny matmuls
(|C| or |I| rows, k ≤ a few hundred columns) whose k×k results are tiny —
this is what makes implicit CD communication-trivial when the rows are
sharded: each shard computes a partial Gram and a k² all-reduce (64 KB at
k=128 fp32) combines them.

``gram`` dispatches to the Pallas TPU kernel (``repro.kernels.gram``) when
requested; the pure-XLA path is the default and the oracle.

Precision: every Gram product runs at :data:`PRECISION` (``HIGHEST``). On
the TPU an fp32 dot at default precision rounds its inputs to bf16 (one
MXU pass), which would put ~1e-3 relative error into R'/R'' and hence into
every Newton step; the k×k result costs O(rows·k²) either way, so the
fp32-exact passes are cheap. The Pallas kernel and the R' products of the
MF sweeps use the same setting, so flat and fused epochs agree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISION = jax.lax.Precision.HIGHEST


def gram(m: jax.Array, *, implementation: str = "xla",
         weights: jax.Array | None = None) -> jax.Array:
    """J = mᵀm (or mᵀ·diag(w)·m) with fp32 accumulation. m: (rows, k) → (k, k).

    ``weights=None`` is a trace-time branch: the unweighted program is
    untouched on every backend."""
    if implementation == "pallas":
        from repro.kernels.gram import ops as gram_ops

        return gram_ops.gram(m, weights=weights)
    if weights is not None:
        return weighted_gram(m, weights)
    mf = m.astype(jnp.float32)
    return jnp.dot(mf.T, mf, precision=PRECISION,
                   preferred_element_type=jnp.float32)


def gram_pair(phi: jax.Array, psi: jax.Array, *, implementation: str = "xla"):
    """(J_C, J_I) for the two sides of a k-separable model."""
    return (
        gram(phi, implementation=implementation),
        gram(psi, implementation=implementation),
    )


def sharded_gram(m: jax.Array, axis_name: str) -> jax.Array:
    """Per-shard partial Gram + all-reduce over ``axis_name``.

    To be called inside ``shard_map`` with rows of ``m`` sharded over
    ``axis_name``. The all-reduced payload is k² floats — independent of the
    number of rows. This op realizes the paper's O((|C|+|I|)k²) bound in the
    distributed setting: compute scales with local rows, communication is
    constant.
    """
    local = gram(m)
    return jax.lax.psum(local, axis_name)


def weighted_gram(m: jax.Array, w: jax.Array) -> jax.Array:
    """J = mᵀ diag(w) m — used for confidence-weighted variants. w: (rows,)."""
    mf = m.astype(jnp.float32)
    return jnp.dot(mf.T * w[None, :].astype(jnp.float32), mf,
                   precision=PRECISION, preferred_element_type=jnp.float32)
