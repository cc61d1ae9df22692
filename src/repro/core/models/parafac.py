"""iCD for PARAFAC tensor factorization (paper §5.3.1).

Model (eq. 34): ŷ(c1,c2,i) = Σ_f u_{c1,f} v_{c2,f} w_{i,f}, the 3-mode
extension of MF. k-separable with φ_f(c1,c2) = u_{c1,f}·v_{c2,f} and
ψ_f(i) = w_{i,f} (eq. 35). The regularizer derivatives (eqs. 37–38) reduce
to per-c1 reductions over that context's *partner* c2 values:

    R'(u_{c1*,f*})  = 2 Σ_f J_I(f,f*) u_{c1*,f} K_{c1*}(f,f*)
    R''(u_{c1*,f*}) = 2 J_I(f*,f*) K_{c1*}(f*,f*)
    K_{c1}(f,f*)    = Σ_{c2:(c1,c2)∈C} v_{c2,f} v_{c2,f*}

Context modes (paper's distinction):
  * ``sparse``  — C ⊂ C1×C2 is exactly the provided pair list; K is a
    segment-reduce over pairs. O((|C|+|I|)k²) per epoch.
  * ``dense``   — C = C1×C2; K decomposes to J_{C2} (eq. 39), identical for
    every c1, and J_C = J_{C1} ⊙ J_{C2} for the item sweep.
    O((|C1|+|C2|+|I|)k²) per epoch — no pair materialization.

The item sweep is exactly MF's (§5.1): "The item side is equivalent to
matrix factorization."

Fused padded path (``epoch_padded``, dispatched by ``hp.block_k`` exactly
like ``mf_padded``): each side's sweep runs on a :class:`PaddedGroup` grid
(nnz grouped by c1 / c2 / item) through ``sweeps.sweep_columns`` block
bodies. The context modes use the ``cd_block_sweep_rowpatch`` kernel —
their R'/R'' coupling is ROW-dependent (P[r, j, f] = J(j,f)·K_r(j,f),
eqs. 37–38) so the Gauss–Seidel patch slab rides per row; the item sweep is
MF-like and reuses the shared-Gram ``cd_block_sweep``. The residual cache
and α stay VMEM-resident across the ``k_b`` columns of each block.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sweeps
from repro.core.gram import gram
from repro.core.implicit import explicit_loss
from repro.core.padded import PaddedGroup, append_sentinel_row, build_group
from repro.kernels import vmem
from repro.kernels.cd_sweep.ops import (
    cd_block_sweep,
    cd_block_sweep_gather,
    cd_block_sweep_rowpatch,
    cd_block_sweep_rowpatch_gather,
)
from repro.sparse.interactions import Interactions
from repro.sparse.segment import segment_sum


class PARAFACParams(NamedTuple):
    u: jax.Array  # (n_c1, k)
    v: jax.Array  # (n_c2, k)
    w: jax.Array  # (n_items, k)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TensorContext:
    """Observed context pairs C ⊆ C1×C2. ``Interactions.ctx`` indexes rows
    of this pair list."""

    c1: jax.Array  # (n_ctx,) int32
    c2: jax.Array  # (n_ctx,) int32
    n_c1: int = dataclasses.field(metadata=dict(static=True))
    n_c2: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_ctx(self) -> int:
        return int(self.c1.shape[0])


@dataclasses.dataclass(frozen=True)
class PARAFACHyperParams:
    k: int
    alpha0: float = 1.0
    l2: float = 0.1
    eta: float = 1.0
    dense_context: bool = False  # True ⇒ regularizer universe is C1×C2
    implementation: str = "xla"
    block_k: int = 0  # columns per fused cd_sweep dispatch on the padded
    #                   layout (epoch_padded): 0 = auto (min(k, 8)),
    #                   1 = per-column baseline through the block path
    psi_dispatch: str = "gather"  # fused-path Ψ routing: 'gather' =
    #                   in-kernel gather of the flat pseudo-ψ slab (no
    #                   (n, k_b, D_pad) scatter_blk intermediate; auto-
    #                   fallback on VMEM overflow), 'pregather' = host-side
    #                   scatter/pre-gather (the PR 2 path)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TensorPadded:
    """Padded layouts for the fused tensor-model sweeps: the flat nnz list
    grouped by c1, by c2, and by item, plus the item-major pair-id grid the
    MF-like item sweep gathers Φ columns through."""

    g1: PaddedGroup
    g2: PaddedGroup
    gi: PaddedGroup
    pair_ids_item: jax.Array  # (n_items, gi.d_pad) int32; garbage on padding


def pad_tensor_groups(tc: TensorContext, data: Interactions, lane: int = 128) -> TensorPadded:
    """Host-side: build the three padded groupings of the observed set."""
    pair_of_nnz = np.asarray(data.ctx)
    alpha = np.asarray(data.alpha)
    g1 = build_group(np.asarray(tc.c1)[pair_of_nnz], alpha, tc.n_c1, lane)
    g2 = build_group(np.asarray(tc.c2)[pair_of_nnz], alpha, tc.n_c2, lane)
    gi = build_group(np.asarray(data.item), alpha, data.n_items, lane)
    pair_ids_item = np.zeros((data.n_items, gi.d_pad), np.int32)
    pair_ids_item[np.asarray(gi.rows), np.asarray(gi.cols)] = pair_of_nnz
    return TensorPadded(g1=g1, g2=g2, gi=gi,
                        pair_ids_item=jnp.asarray(pair_ids_item))


def init(key, n_c1: int, n_c2: int, n_items: int, k: int, sigma: float = 0.1) -> PARAFACParams:
    k1, k2, k3 = jax.random.split(key, 3)
    return PARAFACParams(
        u=sigma * jax.random.normal(k1, (n_c1, k), jnp.float32),
        v=sigma * jax.random.normal(k2, (n_c2, k), jnp.float32),
        w=sigma * jax.random.normal(k3, (n_items, k), jnp.float32),
    )


def phi(params: PARAFACParams, tc: TensorContext) -> jax.Array:
    """Φ over the observed pair list (sparse-context materialization)."""
    return jnp.take(params.u, tc.c1, axis=0) * jnp.take(params.v, tc.c2, axis=0)


def psi(params: PARAFACParams) -> jax.Array:
    return params.w


def export_psi(params: PARAFACParams) -> jax.Array:
    """ψ table for the retrieval engine: (n_items, k)."""
    return params.w


def build_phi(params: PARAFACParams, c1: jax.Array, c2: jax.Array) -> jax.Array:
    """φ rows for query context pairs: φ_f = u_{c1,f}·v_{c2,f} (eq. 35)."""
    return jnp.take(params.u, c1, axis=0) * jnp.take(params.v, c2, axis=0)


def predict(params: PARAFACParams, c1, c2, item) -> jax.Array:
    return jnp.sum(
        jnp.take(params.u, c1, axis=0)
        * jnp.take(params.v, c2, axis=0)
        * jnp.take(params.w, item, axis=0),
        axis=-1,
    )


def _context_mode_sweep(
    side: jax.Array,          # (n_side, k): U (group by c1) or V (group by c2)
    partner: jax.Array,       # (n_partner, k): V or U
    group_of_pair: jax.Array,     # (n_ctx,) c1 or c2 per pair
    partner_of_pair: jax.Array,   # (n_ctx,) c2 or c1 per pair
    j_i: jax.Array,
    data: Interactions,
    w_items: jax.Array,
    e: jax.Array,
    n_side: int,
    hp: PARAFACHyperParams,
    schedule=None,
    sweep_index: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Sweep one context mode (U or V). Sparse-context K via segment sums;
    dense-context K via the partner Gram (eq. 39)."""
    pair_of_nnz = data.ctx

    def body(f, carry):
        side_m, e = carry
        s_col = sweeps.take_col(side_m, f)
        p_col_pair = jnp.take(sweeps.take_col(partner, f), partner_of_pair)  # (n_ctx,)
        w_col_nnz = jnp.take(sweeps.take_col(w_items, f), data.item)
        other_nnz = jnp.take(p_col_pair, pair_of_nnz) * w_col_nnz  # ∂ŷ per nnz

        grp_nnz = jnp.take(group_of_pair, pair_of_nnz)
        lp = segment_sum(data.alpha * e * other_nnz, grp_nnz, n_side)
        lpp = segment_sum(data.alpha * other_nnz * other_nnz, grp_nnz, n_side)

        if hp.dense_context:
            # K_{c1}(·,f*) = J_partner[:, f*] — identical for every group row.
            j_p_col = partner.T @ sweeps.take_col(partner, f)        # (k,)
            kmat = jnp.broadcast_to(j_p_col[None, :], side_m.shape)  # (n_side, k)
        else:
            pp = jnp.take(partner, partner_of_pair, axis=0)          # (n_ctx, k)
            kmat = segment_sum(pp * p_col_pair[:, None], group_of_pair, n_side)
        rp = jnp.sum(kmat * side_m * sweeps.take_col(j_i, f)[None, :], axis=1)
        rpp = j_i[f, f] * sweeps.take_col(kmat, f)

        delta = sweeps.newton_delta(
            sweeps.NewtonParts(lp + hp.alpha0 * rp, lpp + hp.alpha0 * rpp),
            s_col, hp.l2, hp.eta,
        )
        e = e + jnp.take(delta, grp_nnz) * other_nnz
        return sweeps.put_col(side_m, f, s_col + delta), e

    return sweeps.sweep_columns(
        hp.k, body, (side, e), schedule=schedule, sweep_index=sweep_index
    )


def _item_sweep(params_w, j_c, phi_cols_nnz, data, e_t, alpha_t, hp,
                schedule=None, sweep_index=0):
    """MF item sweep (paper: identical to §5.1)."""

    def body(f, carry):
        w_m, e_t = carry
        o_col = phi_cols_nnz(f)
        w_col = sweeps.take_col(w_m, f)
        lp = segment_sum(alpha_t * e_t * o_col, data.t_item, data.n_items)
        lpp = segment_sum(alpha_t * o_col * o_col, data.t_item, data.n_items)
        rp = w_m @ sweeps.take_col(j_c, f)
        rpp = j_c[f, f]
        delta = sweeps.newton_delta(
            sweeps.NewtonParts(lp + hp.alpha0 * rp, lpp + hp.alpha0 * rpp),
            w_col, hp.l2, hp.eta,
        )
        e_t = e_t + jnp.take(delta, data.t_item) * o_col
        return sweeps.put_col(w_m, f, w_col + delta), e_t

    return sweeps.sweep_columns(
        hp.k, body, (params_w, e_t), schedule=schedule, sweep_index=sweep_index
    )


def _context_mode_sweep_padded(
    side: jax.Array,          # (n_side, k): U or V
    partner: jax.Array,       # (n_partner, k): V or U (fixed this sweep)
    group_of_pair: jax.Array,
    partner_of_pair: jax.Array,
    j_i: jax.Array,
    data: Interactions,
    w_items: jax.Array,
    pg: PaddedGroup,          # nnz grouped by this side's context mode
    e_pad: jax.Array,         # (n_side, d_pad) residual grid
    n_side: int,
    hp: PARAFACHyperParams,
    k_b: int,
) -> Tuple[jax.Array, jax.Array]:
    """Fused context-mode sweep: ``k_b`` columns per ``cd_block_sweep_rowpatch``
    dispatch. Slab state per block — R'/2 ``(n, k_b)`` via Φ·J over pairs and
    the per-row patch tensor P = J ⊙ K (diag = R''/2, eqs. 37–38); the
    kernel's Gauss–Seidel r1 patch keeps later block columns exact.

    Ψ routing: the flat per-nnz pseudo-ψ ``s_nnz (nnz, k_b)`` rides into
    the gather kernel as a slab (+ zero sentinel row) with ``pg.flat_ids``
    by default — ``scatter_blk``'s ``(n, k_b, d_pad)`` intermediate only
    exists on the ``'pregather'``/VMEM-overflow fallback."""
    pair_of_nnz = data.ctx
    w_nnz = jnp.take(w_items, data.item, axis=0)               # (nnz, k)
    use_gather, _ = vmem.resolve_cd_sweep_dispatch(
        pg.d_pad, k_b, data.nnz + 1, n_rows=n_side,
        prefer_gather=sweeps.resolve_psi_dispatch(hp.psi_dispatch),
    )

    j_p = partner.T @ partner if hp.dense_context else None  # eq. 39 K

    def block_body(f0, kb, carry):
        side_m, e_pad = carry
        blk = slice(f0, f0 + kb)
        v_pair = jnp.take(partner[:, blk], partner_of_pair, axis=0)  # (n_pairs, kb)
        if hp.dense_context:
            # K = J_partner for EVERY row (regularizer universe C1×C2, even
            # when the observed pair list is sparse): R'_f = Σ_f' J(f',f)
            # K(f',f) θ_{·,f'} collapses to a dense matmul, matching the
            # flat path's broadcast kmat.
            r1_blk = side_m @ (j_p[:, blk] * j_i[:, blk])            # R'/2 slab
            k_blk = jnp.broadcast_to(j_p[blk, blk][None], (n_side, kb, kb))
        else:
            phi_full = jnp.take(side_m, group_of_pair, axis=0) * jnp.take(
                partner, partner_of_pair, axis=0
            )                                                        # (n_pairs, k)
            r1_blk = segment_sum(
                v_pair * (phi_full @ j_i[:, blk]), group_of_pair, n_side
            )                                                        # R'/2 slab
            k_blk = segment_sum(
                v_pair[:, :, None] * v_pair[:, None, :], group_of_pair, n_side
            )
        p_blk = k_blk * j_i[blk, blk][None, :, :]                    # J ⊙ K
        s_nnz = jnp.take(v_pair, pair_of_nnz, axis=0) * w_nnz[:, blk]
        if use_gather:
            w_new, e_pad = cd_block_sweep_rowpatch_gather(
                append_sentinel_row(s_nnz), pg.flat_ids, pg.alpha_pad,
                e_pad, side_m[:, blk], r1_blk, p_blk,
                alpha0=hp.alpha0, l2=hp.l2, eta=hp.eta,
            )
        else:
            psi_blk = pg.scatter_blk(s_nnz)                          # (n, kb, d_pad)
            w_new, e_pad = cd_block_sweep_rowpatch(
                psi_blk, pg.alpha_pad, e_pad, side_m[:, blk], r1_blk, p_blk,
                alpha0=hp.alpha0, l2=hp.l2, eta=hp.eta,
            )
        return side_m.at[:, blk].set(w_new), e_pad

    return sweeps.sweep_columns(
        hp.k, None, (side, e_pad), block=k_b, block_body=block_body
    )


def _item_sweep_padded(
    w_m: jax.Array,
    j_c: jax.Array,
    phi_pairs: jax.Array,     # (n_pairs, k) materialized Φ over the pair list
    padded: TensorPadded,
    e_pad: jax.Array,         # (n_items, d_pad) item-major residual grid
    hp,
    k_b: int,
) -> Tuple[jax.Array, jax.Array]:
    """MF-like fused item sweep (shared-Gram ``cd_block_sweep``): ψ columns
    gathered from Φ through the item-major pair-id grid — in-kernel by
    default (the Φ slab is the ψ table), pre-gathered on fallback."""
    use_gather, _ = vmem.resolve_cd_sweep_dispatch(
        padded.gi.d_pad, k_b, phi_pairs.shape[0], n_rows=w_m.shape[0],
        prefer_gather=sweeps.resolve_psi_dispatch(hp.psi_dispatch),
    )

    def block_body(f0, kb, carry):
        w_m, e_pad = carry
        blk = slice(f0, f0 + kb)
        r1_blk = w_m @ j_c[:, blk]
        if use_gather:
            w_new, e_pad = cd_block_sweep_gather(
                phi_pairs[:, blk], padded.pair_ids_item, padded.gi.alpha_pad,
                e_pad, w_m[:, blk], r1_blk, j_c[blk, blk],
                alpha0=hp.alpha0, l2=hp.l2, eta=hp.eta,
            )
        else:
            psi_blk = jnp.moveaxis(
                jnp.take(phi_pairs[:, blk], padded.pair_ids_item, axis=0), -1, 1
            )                                                        # (n, kb, d_pad)
            w_new, e_pad = cd_block_sweep(
                psi_blk, padded.gi.alpha_pad, e_pad, w_m[:, blk], r1_blk,
                j_c[blk, blk],
                alpha0=hp.alpha0, l2=hp.l2, eta=hp.eta,
            )
        return w_m.at[:, blk].set(w_new), e_pad

    return sweeps.sweep_columns(
        hp.k, None, (w_m, e_pad), block=k_b, block_body=block_body
    )


@partial(jax.jit, static_argnames=("hp", "schedule", "sweep_index"))
def epoch(
    params: PARAFACParams,
    tc: TensorContext,
    data: Interactions,
    e: jax.Array,
    hp: PARAFACHyperParams,
    schedule=None,
    sweep_index: int = 0,
    weights=None,
) -> Tuple[PARAFACParams, jax.Array]:
    """One iCD epoch: U sweep → V sweep → item (W) sweep (scheduled
    columns; ``schedule=None`` = full pass).

    ``weights`` (optional, (nnz,) ctx-major) folds per-interaction
    confidence into α exactly; ``None`` traces the identical program."""
    if weights is not None:
        data = dataclasses.replace(data, alpha=data.alpha * weights)
    u, v, w = params
    j_i = gram(w, implementation=hp.implementation)

    u, e = _context_mode_sweep(
        u, v, tc.c1, tc.c2, j_i, data, w, e, u.shape[0], hp,
        schedule, sweep_index,
    )
    v, e = _context_mode_sweep(
        v, u, tc.c2, tc.c1, j_i, data, w, e, v.shape[0], hp,
        schedule, sweep_index,
    )

    if hp.dense_context:
        j_c = gram(u) * gram(v)  # eq. (39): J_C = J_{C1} ⊙ J_{C2}
    else:
        j_c = gram(jnp.take(u, tc.c1, axis=0) * jnp.take(v, tc.c2, axis=0))
    e_t = sweeps.to_item_major(e, data.t_perm)
    alpha_t = sweeps.to_item_major(data.alpha, data.t_perm)
    phi_cols = lambda f: jnp.take(
        jnp.take(sweeps.take_col(u, f), tc.c1) * jnp.take(sweeps.take_col(v, f), tc.c2),
        data.t_ctx,
    )
    w, e_t = _item_sweep(
        w, j_c, phi_cols, data, e_t, alpha_t, hp, schedule, sweep_index
    )
    e = sweeps.to_ctx_major(e_t, data.t_perm)
    return PARAFACParams(u, v, w), e


@partial(jax.jit, static_argnames=("hp",), donate_argnums=(4,))
def epoch_padded(
    params: PARAFACParams,
    tc: TensorContext,
    data: Interactions,
    padded: TensorPadded,
    e: jax.Array,
    hp: PARAFACHyperParams,
    weights=None,
) -> Tuple[PARAFACParams, jax.Array]:
    """Fused-kernel iCD epoch on the padded layouts; same sweep order and
    fixed point as :func:`epoch` (parity-tested). The flat residual cache is
    re-grouped per sweep (scatter in, gather out — O(nnz), amortized over
    the ⌈k/k_b⌉ VMEM-resident block dispatches of the sweep).
    ``weights`` rebuilds all three group α grids via
    :meth:`~repro.core.padded.PaddedGroup.with_alpha`."""
    if weights is not None:
        a_eff = data.alpha * weights
        data = dataclasses.replace(data, alpha=a_eff)
        padded = dataclasses.replace(
            padded, g1=padded.g1.with_alpha(a_eff),
            g2=padded.g2.with_alpha(a_eff), gi=padded.gi.with_alpha(a_eff),
        )
    u, v, w = params
    k_b = sweeps.resolve_block_k(hp.block_k, hp.k)
    j_i = gram(w, implementation=hp.implementation)

    e_g = padded.g1.scatter(e)
    u, e_g = _context_mode_sweep_padded(
        u, v, tc.c1, tc.c2, j_i, data, w, padded.g1, e_g, u.shape[0], hp, k_b
    )
    e = padded.g1.gather(e_g)

    e_g = padded.g2.scatter(e)
    v, e_g = _context_mode_sweep_padded(
        v, u, tc.c2, tc.c1, j_i, data, w, padded.g2, e_g, v.shape[0], hp, k_b
    )
    e = padded.g2.gather(e_g)

    phi_pairs = jnp.take(u, tc.c1, axis=0) * jnp.take(v, tc.c2, axis=0)
    if hp.dense_context:
        j_c = gram(u) * gram(v)  # eq. (39): J_C = J_{C1} ⊙ J_{C2}
    else:
        j_c = gram(phi_pairs)
    e_g = padded.gi.scatter(e)
    w, e_g = _item_sweep_padded(w, j_c, phi_pairs, padded, e_g, hp, k_b)
    e = padded.gi.gather(e_g)
    return PARAFACParams(u, v, w), e


def residuals(params: PARAFACParams, tc: TensorContext, data: Interactions) -> jax.Array:
    return sweeps.residuals_from_factors(
        phi(params, tc), params.w, data.ctx, data.item, data.y
    )


def objective(params: PARAFACParams, tc: TensorContext, data: Interactions,
              hp: PARAFACHyperParams) -> jax.Array:
    e = residuals(params, tc, data)
    if hp.dense_context:
        reg = jnp.sum(gram(params.u) * gram(params.v) * gram(params.w))
    else:
        reg = jnp.sum(gram(phi(params, tc)) * gram(params.w))
    sq = sum(jnp.sum(p**2) for p in params)
    return explicit_loss(e, data.alpha) + hp.alpha0 * reg + hp.l2 * sq


def fit(params, tc, data, hp, n_epochs, callback=None, schedule=None,
        weights=None):
    e = residuals(params, tc, data)
    for ep in range(n_epochs):
        params, e = epoch(params, tc, data, e, hp, schedule,
                          ep if schedule is not None else 0, weights)
        if callback is not None:
            callback(ep, params)
    return params
