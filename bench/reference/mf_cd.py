"""Plain iCD-MF, written from the paper and nothing else (arXiv:1611.04666,
§3 and Algorithm 2), to judge the program's training steps.

Objective over all C×I pairs with implicit weight α₀, after Lemma 1's
rescaling of the observed set S (ȳ = α/(α−α₀)·y, ᾱ = α−α₀):

    L = Σ_S ᾱ (ŷ − ȳ)² + α₀ Σ_{c,i} ŷ(c,i)² + λ (‖W‖² + ‖H‖²),  ŷ = ⟨w_c, h_i⟩

and Σ_{c,i} ŷ² = ⟨WᵀW, HᵀH⟩ (Lemma 2). One coordinate step on column f of
W is the exact Newton step of this quadratic in w_{·,f}:

    w_f ← w_f − (Σ_S ᾱ e h_f + α₀ W J_I[:, f] + λ w_f)
                / (Σ_S ᾱ h_f² + α₀ J_I[f, f] + λ),      J_I = HᵀH,

with e = ŷ − ȳ kept current by e += δ_c·h_{i,f}; H's columns likewise
against J_C = WᵀW. One pair order (the generator's, sorted by user) serves
both sides; the item side sums with unsorted segment ids.

``precision`` names that of every matrix product (``precision.MODES``:
``highest`` is the plain float32 reference, a lower one the control).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.precision import dot


def rescale(y, alpha, alpha0: float):
    return alpha / (alpha - alpha0) * y, alpha - alpha0


def residuals(w, h, ctx, item, ybar, batch: int = 1 << 16):
    """e = ⟨w_c, h_i⟩ − ȳ per observed pair, in pair batches."""
    n = ctx.shape[0]
    pad = -n % batch
    c = jnp.pad(ctx, (0, pad)).reshape(-1, batch)
    i = jnp.pad(item, (0, pad)).reshape(-1, batch)

    def one(ci):
        return jnp.sum(w[ci[0]] * h[ci[1]], axis=1)

    pred = jax.lax.map(one, (c, i)).reshape(-1)[:n]
    return pred - ybar


def objective(w, h, e, abar, alpha0: float, l2: float) -> float:
    """L above, from the residual cache ``e``, summed in float64 on the host:
    two runs' objectives of ~1e8 then differ by more than the rounding of
    a float32 sum (one ulp is ~7e-8 of it)."""
    w, h, e = (np.asarray(x, np.float64) for x in (w, h, e))
    return float(np.dot(np.asarray(abar, np.float64) * e, e)
                 + alpha0 * np.sum((w.T @ w) * (h.T @ h))
                 + l2 * (np.sum(w * w) + np.sum(h * h)))


def _side(theta, j_other, other, rows, cols, abar, e, f0, n_cols,
          alpha0, l2, precision):
    """Columns f0 .. f0+n_cols−1 of ``theta`` (rows indexed by ``rows``),
    against the fixed ``other`` side (indexed by ``cols``)."""
    n = theta.shape[0]
    for j in range(n_cols):
        f = f0 + j
        o_f = jnp.take(jax.lax.dynamic_index_in_dim(other, f, 1, False), cols)
        t_f = jax.lax.dynamic_index_in_dim(theta, f, 1, False)
        lp = jax.ops.segment_sum(abar * e * o_f, rows, n)
        lpp = jax.ops.segment_sum(abar * o_f * o_f, rows, n)
        rp = dot(theta, jax.lax.dynamic_index_in_dim(j_other, f, 1, False),
                 precision)
        rpp = jax.lax.dynamic_slice(j_other, (f, f), (1, 1))[0, 0]
        delta = -(lp + alpha0 * rp + l2 * t_f) / (lpp + alpha0 * rpp + l2)
        theta = jax.lax.dynamic_update_index_in_dim(theta, t_f + delta, f, 1)
        e = e + jnp.take(delta, rows) * o_f
    return theta, e


@partial(jax.jit, static_argnames=("n_cols", "alpha0", "l2", "precision"))
def step(w, h, e, ctx, item, abar, f0, *, n_cols: int, alpha0: float,
         l2: float, precision: str = "highest"):
    """Columns [f0, f0+n_cols) of W, then the same columns of H."""
    j_i = dot(h.T, h, precision)
    w, e = _side(w, j_i, h, ctx, item, abar, e, f0, n_cols, alpha0, l2,
                 precision)
    j_c = dot(w.T, w, precision)
    h, e = _side(h, j_c, w, item, ctx, abar, e, f0, n_cols, alpha0, l2,
                 precision)
    return w, h, e
