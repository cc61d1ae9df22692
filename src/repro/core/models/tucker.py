"""iCD for Tucker Decomposition (paper §5.3.2).

Model (eq. 40): ŷ(c1,c2,i) = Σ_{f1,f2,f3} b_{f1,f2,f3} u_{c1,f1} v_{c2,f2} w_{i,f3}
with core tensor B ∈ R^{k1×k2×k3}. k3-separable (paper):

    φ_f(c1,c2) = Σ_{f1,f2} b_{f1,f2,f} u_{c1,f1} v_{c2,f2},   ψ_f(i) = w_{i,f}

Unlike the other models, ∂φ_f/∂u is non-zero for EVERY f (eq. 41) — the
nested factor loops of Lemma 3 do not collapse. Our sweep keeps them as
dense k3-dimensional contractions per context row:

    U mode, dim f1*:  D(pair,f) = Σ_{f2} b_{f1*,f2,f} v_{c2,f2}
        R'/2  = segment_{c1}( Σ_f D_f · (Φ J_I)_f )
        R''/2 = segment_{c1}( Σ_f D_f · (D J_I)_f )
        L'/2  = segment_{c1}( ᾱ e s ),  s = Σ_f D_f w_{i,f}  per observation

Core coordinates b_{f1,f2,f3} all interact through Φ, so they are swept
strictly sequentially (k1·k2·k3 scalar Newton steps — each a cheap
reduction; the paper gives the same O(k1²k2²k3²·…) regime).

Context universe: the observed pair list (the paper's sparse-context case —
its dense-context einsum shortcut changes constants, not semantics; see
DESIGN.md). Item sweep is MF-like via materialized Φ.

Fused padded path (``epoch_padded``, dispatched by ``hp.block_k`` like
``mf_padded``): the U/V mode sweeps run blocked through
``sweeps.sweep_columns`` on :class:`~repro.core.models.parafac.TensorPadded`
grids with the ``cd_block_sweep_rowpatch`` kernel — the per-row patch
tensor P[r, j, f] = segment_r(Σ_g D^f_g (D^j J_I)_g) is exactly how R'
moves when mode coordinate j takes a Newton step (Φ += Δ·D^j), so the
in-kernel Gauss–Seidel patch reproduces the per-column path; Φ itself is
patched between blocks from the returned deltas. The core sweep stays
strictly sequential (flat path); the item sweep reuses PARAFAC's fused
MF-like sweep.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import sweeps
from repro.core.gram import gram
from repro.core.implicit import explicit_loss
from repro.core.models.parafac import (
    TensorContext,
    TensorPadded,
    _item_sweep,
    _item_sweep_padded,
    pad_tensor_groups,
)
from repro.core.padded import append_sentinel_row
from repro.kernels import vmem
from repro.kernels.cd_sweep.ops import (
    cd_block_sweep_rowpatch,
    cd_block_sweep_rowpatch_gather,
)
from repro.sparse.interactions import Interactions
from repro.sparse.segment import segment_sum

__all__ = ["TuckerParams", "TuckerHyperParams", "pad_tensor_groups",
           "init", "phi", "export_psi", "build_phi", "predict", "epoch",
           "epoch_padded", "residuals", "objective", "fit"]


class TuckerParams(NamedTuple):
    u: jax.Array  # (n_c1, k1)
    v: jax.Array  # (n_c2, k2)
    w: jax.Array  # (n_items, k3)
    b: jax.Array  # (k1, k2, k3) core tensor


@dataclasses.dataclass(frozen=True)
class TuckerHyperParams:
    k1: int
    k2: int
    k3: int
    alpha0: float = 1.0
    l2: float = 0.1
    l2_core: float = 0.1
    eta: float = 1.0
    implementation: str = "xla"
    block_k: int = 0  # columns per fused cd_sweep dispatch (epoch_padded):
    #                   0 = auto (min(mode k, 8)), 1 = per-column baseline
    psi_dispatch: str = "gather"  # fused-path Ψ routing: 'gather' =
    #                   in-kernel gather of the flat pseudo-ψ slab (no
    #                   (n, k_b, D_pad) scatter_blk intermediate; auto-
    #                   fallback on VMEM overflow), 'pregather' = host-side
    #                   scatter/pre-gather (the PR 2 path)

    # _item_sweep compatibility (it reads hp.k and hp.alpha0/l2/eta)
    @property
    def k(self) -> int:
        return self.k3


def init(key, n_c1, n_c2, n_items, k1, k2, k3, sigma=0.1) -> TuckerParams:
    ka, kb, kc, kd = jax.random.split(key, 4)
    return TuckerParams(
        u=sigma * jax.random.normal(ka, (n_c1, k1), jnp.float32),
        v=sigma * jax.random.normal(kb, (n_c2, k2), jnp.float32),
        w=sigma * jax.random.normal(kc, (n_items, k3), jnp.float32),
        b=sigma * jax.random.normal(kd, (k1, k2, k3), jnp.float32),
    )


def phi(params: TuckerParams, tc: TensorContext) -> jax.Array:
    """Φ (n_ctx, k3) over the observed pair list."""
    up = jnp.take(params.u, tc.c1, axis=0)  # (n, k1)
    vp = jnp.take(params.v, tc.c2, axis=0)  # (n, k2)
    return jnp.einsum("na,nb,abf->nf", up, vp, params.b)


def predict(params: TuckerParams, c1, c2, item) -> jax.Array:
    up = jnp.take(params.u, c1, axis=0)
    vp = jnp.take(params.v, c2, axis=0)
    wp = jnp.take(params.w, item, axis=0)
    return jnp.einsum("na,nb,nf,abf->n", up, vp, wp, params.b)


def export_psi(params: TuckerParams) -> jax.Array:
    """ψ table for the retrieval engine: (n_items, k3) — Tucker is
    k3-separable with ψ_f(i) = w_{i,f}."""
    return params.w


def build_phi(params: TuckerParams, c1: jax.Array, c2: jax.Array) -> jax.Array:
    """φ rows for query context pairs: the core-contracted
    φ_f = Σ_{f1,f2} b_{f1,f2,f} u_{c1,f1} v_{c2,f2} (B, k3)."""
    up = jnp.take(params.u, c1, axis=0)
    vp = jnp.take(params.v, c2, axis=0)
    return jnp.einsum("na,nb,abf->nf", up, vp, params.b)


def _mode_sweep(
    side,            # U (n_c1,k1) or V (n_c2,k2)
    b_slice_fn,      # f* -> (k_other, k3) core slice for this mode
    partner_of_pair, # c2 (U mode) or c1 (V mode) per pair
    partner,         # V or U
    group_of_pair,   # c1 or c2 per pair
    n_side, k_side,
    phi_m, j_i, data, w_items, e, hp,
    schedule=None, sweep_index=0,
):
    pair_of_nnz = data.ctx
    grp_nnz = jnp.take(group_of_pair, pair_of_nnz)

    def body(fs, carry):
        side_m, phi_m, e = carry
        bsl = b_slice_fn(fs)                                   # (k_other, k3)
        pp = jnp.take(partner, partner_of_pair, axis=0)        # (n_ctx, k_other)
        d = pp @ bsl                                           # (n_ctx, k3)
        s = jnp.sum(
            jnp.take(d, pair_of_nnz, axis=0) * jnp.take(w_items, data.item, axis=0),
            axis=1,
        )                                                      # (nnz,)
        lp = segment_sum(data.alpha * e * s, grp_nnz, n_side)
        lpp = segment_sum(data.alpha * s * s, grp_nnz, n_side)
        rp = segment_sum(jnp.sum(d * (phi_m @ j_i), axis=1), group_of_pair, n_side)
        rpp = segment_sum(jnp.sum(d * (d @ j_i), axis=1), group_of_pair, n_side)
        s_col = sweeps.take_col(side_m, fs)
        delta = sweeps.newton_delta(
            sweeps.NewtonParts(lp + hp.alpha0 * rp, lpp + hp.alpha0 * rpp),
            s_col, hp.l2, hp.eta,
        )
        phi_m = phi_m + jnp.take(delta, group_of_pair)[:, None] * d
        e = e + jnp.take(delta, grp_nnz) * s
        return sweeps.put_col(side_m, fs, s_col + delta), phi_m, e

    return sweeps.sweep_columns(
        k_side, body, (side, phi_m, e),
        schedule=schedule, sweep_index=sweep_index,
    )


def _mode_sweep_padded(
    side,            # U (n_c1,k1) or V (n_c2,k2)
    b_blk_fn,        # (f0, kb) -> (kb, k_other, k3) static core slab
    partner_of_pair, # c2 (U mode) or c1 (V mode) per pair
    partner,         # V or U
    group_of_pair,   # c1 or c2 per pair
    n_side, k_side,
    phi_m, j_i, data, w_items, pg, e_pad, hp, k_b,
):
    """Fused Tucker mode sweep: k_b columns per ``cd_block_sweep_rowpatch``
    dispatch. Per block the pseudo-ψ s^f = Σ_g D^f_g w_{i,g} is scattered
    onto the padded grid; slab state is R'/2 = segment(Σ_g D^f_g (Φ J)_g)
    and the per-row patch P[r, j, f] = segment(Σ_g D^f_g (D^j J)_g) (diag =
    R''/2). D^f is constant during the sweep (partner/core/items fixed), so
    only Φ — patched from the returned deltas — and the in-kernel e/R'
    state move. The flat pseudo-ψ ``s_nnz`` rides into the gather kernel as
    a slab (+ zero sentinel row) with ``pg.flat_ids`` by default; the
    ``scatter_blk`` tile only exists on the pregather/VMEM fallback."""
    pair_of_nnz = data.ctx
    w_nnz = jnp.take(w_items, data.item, axis=0)                 # (nnz, k3)
    use_gather, _ = vmem.resolve_cd_sweep_dispatch(
        pg.d_pad, k_b, data.nnz + 1, n_rows=n_side,
        prefer_gather=sweeps.resolve_psi_dispatch(hp.psi_dispatch),
    )

    def block_body(f0, kb, carry):
        side_m, phi_m, e_pad = carry
        blk = slice(f0, f0 + kb)
        bsl = b_blk_fn(f0, kb)                                   # (kb, k_other, k3)
        pp = jnp.take(partner, partner_of_pair, axis=0)          # (n_pairs, k_other)
        d_blk = jnp.einsum("no,jof->njf", pp, bsl)               # (n_pairs, kb, k3)
        r1_blk = segment_sum(
            jnp.einsum("njf,nf->nj", d_blk, phi_m @ j_i), group_of_pair, n_side
        )
        dj = jnp.einsum("njf,fg->njg", d_blk, j_i)
        p_blk = segment_sum(
            jnp.einsum("njg,nig->nji", dj, d_blk), group_of_pair, n_side
        )
        s_nnz = jnp.einsum(
            "njf,nf->nj", jnp.take(d_blk, pair_of_nnz, axis=0), w_nnz
        )
        if use_gather:
            w_new, e_pad = cd_block_sweep_rowpatch_gather(
                append_sentinel_row(s_nnz), pg.flat_ids, pg.alpha_pad,
                e_pad, side_m[:, blk], r1_blk, p_blk,
                alpha0=hp.alpha0, l2=hp.l2, eta=hp.eta,
            )
        else:
            psi_blk = pg.scatter_blk(s_nnz)
            w_new, e_pad = cd_block_sweep_rowpatch(
                psi_blk, pg.alpha_pad, e_pad, side_m[:, blk], r1_blk, p_blk,
                alpha0=hp.alpha0, l2=hp.l2, eta=hp.eta,
            )
        delta = w_new - side_m[:, blk]
        phi_m = phi_m + jnp.einsum(
            "nj,njf->nf", jnp.take(delta, group_of_pair, axis=0), d_blk
        )
        return side_m.at[:, blk].set(w_new), phi_m, e_pad

    return sweeps.sweep_columns(
        k_side, None, (side, phi_m, e_pad), block=k_b, block_body=block_body
    )


def core_sweep(params, phi_m, j_i, tc, data, e, hp):
    """Sequential core-tensor sweep: scalar Newton step per b_{f1,f2,f3}."""
    u, v, w, b = params
    k1, k2, k3 = b.shape
    pair_of_nnz = data.ctx
    w_nnz_cols = lambda f3: jnp.take(sweeps.take_col(w, f3), data.item)

    def body(idx, carry):
        b, phi_m, e = carry
        f1 = idx // (k2 * k3)
        f2 = (idx // k3) % k2
        f3 = idx % k3
        g = jnp.take(sweeps.take_col(u, f1), tc.c1) * jnp.take(
            sweeps.take_col(v, f2), tc.c2
        )                                                       # (n_ctx,)
        w_col = w_nnz_cols(f3)                                  # (nnz,)
        g_nnz = jnp.take(g, pair_of_nnz)
        lp = jnp.sum(data.alpha * e * g_nnz * w_col)
        lpp = jnp.sum(data.alpha * (g_nnz * w_col) ** 2)
        rp = jnp.dot(phi_m.T @ g, sweeps.take_col(j_i, f3))
        rpp = j_i[f3, f3] * jnp.sum(g * g)
        b_val = b[f1, f2, f3]
        num = lp + hp.alpha0 * rp + hp.l2_core * b_val
        den = lpp + hp.alpha0 * rpp + hp.l2_core
        delta = -hp.eta * num / jnp.maximum(den, 1e-12)
        b = b.at[f1, f2, f3].add(delta)
        phi_m = sweeps.put_col(phi_m, f3, sweeps.take_col(phi_m, f3) + delta * g)
        e = e + delta * g_nnz * w_col
        return b, phi_m, e

    b, phi_m, e = jax.lax.fori_loop(0, k1 * k2 * k3, body, (b, phi_m, e))
    return b, phi_m, e


@partial(jax.jit, static_argnames=("hp", "schedule", "sweep_index"))
def epoch(
    params: TuckerParams,
    tc: TensorContext,
    data: Interactions,
    e: jax.Array,
    hp: TuckerHyperParams,
    schedule=None,
    sweep_index: int = 0,
    weights=None,
) -> Tuple[TuckerParams, jax.Array]:
    """One iCD epoch: U sweep → V sweep → core sweep → item (W) sweep.

    A ``schedule`` restricts the FACTOR-mode sweeps (per-mode k1/k2/k3
    column plans); the scalar core sweep always runs in full.
    ``weights`` (optional, (nnz,) ctx-major) folds per-interaction
    confidence into α exactly; ``None`` traces the identical program."""
    if weights is not None:
        data = dataclasses.replace(data, alpha=data.alpha * weights)
    u, v, w, b = params
    j_i = gram(w, implementation=hp.implementation)
    phi_m = phi(params, tc)

    u, phi_m, e = _mode_sweep(
        u, lambda f1: jax.lax.dynamic_slice_in_dim(b, f1, 1, axis=0)[0],
        tc.c2, v, tc.c1, u.shape[0], hp.k1, phi_m, j_i, data, w, e, hp,
        schedule, sweep_index,
    )
    v, phi_m, e = _mode_sweep(
        v, lambda f2: jax.lax.dynamic_slice_in_dim(b, f2, 1, axis=1)[:, 0],
        tc.c1, u, tc.c2, v.shape[0], hp.k2, phi_m, j_i, data, w, e, hp,
        schedule, sweep_index,
    )
    b, phi_m, e = core_sweep(TuckerParams(u, v, w, b), phi_m, j_i, tc, data, e, hp)

    j_c = gram(phi_m)
    e_t = sweeps.to_item_major(e, data.t_perm)
    alpha_t = sweeps.to_item_major(data.alpha, data.t_perm)
    phi_cols = lambda f: jnp.take(sweeps.take_col(phi_m, f), data.t_ctx)
    w, e_t = _item_sweep(
        w, j_c, phi_cols, data, e_t, alpha_t, hp, schedule, sweep_index
    )
    e = sweeps.to_ctx_major(e_t, data.t_perm)
    return TuckerParams(u, v, w, b), e


@partial(jax.jit, static_argnames=("hp",), donate_argnums=(4,))
def epoch_padded(
    params: TuckerParams,
    tc: TensorContext,
    data: Interactions,
    padded: TensorPadded,
    e: jax.Array,
    hp: TuckerHyperParams,
    weights=None,
) -> Tuple[TuckerParams, jax.Array]:
    """Fused-kernel iCD epoch on the padded layouts; same sweep order and
    fixed point as :func:`epoch` (parity-tested). U/V mode sweeps and the
    MF-like item sweep run blocked; the core sweep is inherently sequential
    and stays on the flat path. ``weights`` rebuilds all three group α
    grids (and the flat α the core sweep reads)."""
    if weights is not None:
        a_eff = data.alpha * weights
        data = dataclasses.replace(data, alpha=a_eff)
        padded = dataclasses.replace(
            padded, g1=padded.g1.with_alpha(a_eff),
            g2=padded.g2.with_alpha(a_eff), gi=padded.gi.with_alpha(a_eff),
        )
    u, v, w, b = params
    j_i = gram(w, implementation=hp.implementation)
    phi_m = phi(params, tc)

    e_g = padded.g1.scatter(e)
    u, phi_m, e_g = _mode_sweep_padded(
        u, lambda f0, kb: b[f0:f0 + kb],
        tc.c2, v, tc.c1, u.shape[0], hp.k1,
        phi_m, j_i, data, w, padded.g1, e_g, hp,
        sweeps.resolve_block_k(hp.block_k, hp.k1),
    )
    e = padded.g1.gather(e_g)

    e_g = padded.g2.scatter(e)
    v, phi_m, e_g = _mode_sweep_padded(
        v, lambda f0, kb: jnp.moveaxis(b[:, f0:f0 + kb], 1, 0),
        tc.c1, u, tc.c2, v.shape[0], hp.k2,
        phi_m, j_i, data, w, padded.g2, e_g, hp,
        sweeps.resolve_block_k(hp.block_k, hp.k2),
    )
    e = padded.g2.gather(e_g)

    b, phi_m, e = core_sweep(TuckerParams(u, v, w, b), phi_m, j_i, tc, data, e, hp)

    j_c = gram(phi_m)
    e_g = padded.gi.scatter(e)
    w, e_g = _item_sweep_padded(
        w, j_c, phi_m, padded, e_g, hp, sweeps.resolve_block_k(hp.block_k, hp.k3)
    )
    e = padded.gi.gather(e_g)
    return TuckerParams(u, v, w, b), e


def residuals(params: TuckerParams, tc: TensorContext, data: Interactions) -> jax.Array:
    return sweeps.residuals_from_factors(
        phi(params, tc), params.w, data.ctx, data.item, data.y
    )


def objective(params: TuckerParams, tc: TensorContext, data: Interactions,
              hp: TuckerHyperParams) -> jax.Array:
    e = residuals(params, tc, data)
    reg = jnp.sum(gram(phi(params, tc)) * gram(params.w))
    sq = jnp.sum(params.u**2) + jnp.sum(params.v**2) + jnp.sum(params.w**2)
    return (
        explicit_loss(e, data.alpha)
        + hp.alpha0 * reg
        + hp.l2 * sq
        + hp.l2_core * jnp.sum(params.b**2)
    )


def fit(params, tc, data, hp, n_epochs, callback=None, schedule=None,
        weights=None):
    e = residuals(params, tc, data)
    for ep in range(n_epochs):
        params, e = epoch(params, tc, data, e, hp, schedule,
                          ep if schedule is not None else 0, weights)
        if callback is not None:
            callback(ep, params)
    return params
