"""iCD for Matrix Factorization with Side Information (paper §5.2.1, Alg. 3).

Model (eq. 20): ŷ(c,i) = x_c W (z_i H)ᵀ with feature embeddings
W ∈ R^{p×k}, H ∈ R^{p'×k}. k-separable via φ_f(c) = Σ_l x_{c,l} w_{l,f}
(eq. 21); gradients sparse in f (eq. 22), so

    R'(w_{l*,f*})  = 2 Σ_f J_I(f,f*) Σ_c x_{c,l*} φ_f(c)        (eq. 23)
    R''(w_{l*,f*}) = 2 J_I(f*,f*) Σ_c x_{c,l*}²                 (eq. 24)

and Φ is kept in sync with the eq. (25) incremental update. Per-epoch cost
O(k²(N_Z(X)+N_Z(Z))) for the implicit part — the paper's bound.

TPU sweep layout (DESIGN.md §3): coordinates of a one-hot field never share
a row, so a whole field × one dimension updates as a single vectorized
Newton step. The explicit part uses three per-context caches that are
patched incrementally instead of recomputed:

    q_c  = Σ_{i∈S_c} ᾱ e ψ_{f*}(i)     (patched: Δq = Δφ_{f*}·p2)
    p2_c = Σ_{i∈S_c} ᾱ ψ_{f*}(i)²      (constant during the side sweep)
    r_c  = Σ_f J(f,f*) φ_f(c)          (patched: Δr = Δφ_{f*}·J(f*,f*))

One-hot (categorical) fields update EXACTLY — no two features of such a
field share a context row, so the vectorized step equals scalar CD. Features
of a multi-hot (bag) field DO share rows; updating them in parallel is not
scalar CD. Two documented modes (the one deliberate deviation from the
paper, forced by TPU parallelism — DESIGN.md §3):

  - ``jacobi`` (default): one damped (η≈0.5) parallel Newton step per field
    with full row sums — parallel-CD à la Bradley et al.; converges in all
    our experiments and is the production mode.
  - ``slot``: sequential over bag slots; each slot update uses only the rows
    where the feature occupies that slot (fresh residuals between slots) —
    a mini-batched CD flavour that tolerates η=1.

Fused padded path (``epoch_padded`` over ``mf_padded.PaddedInteractions``,
dispatched by ``hp.block_k``): per block of ``k_b`` dimensions ONE
``cd_slab_reduce`` pass streams e/α and yields the q/p2 caches for every
block column plus the cross-dimension coupling slab P (q_f' moves by
Δφ_j·P[·,j,f'] when dimension j's features step — the same linearity as the
eq. 25 within-dimension patch), the field-level Newton steps run in XLA on
those slabs, and ONE ``cd_resid_patch`` applies the rank-k_b residual
patch. e-traffic per sweep drops from 2k streams to 2⌈k/k_b⌉.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import sweeps
from repro.core.design import Design, design_matmul, take_rows
from repro.core.gram import gram
from repro.core.implicit import implicit_objective
from repro.core.models.mf_padded import (
    PaddedInteractions,
    pad_interactions,
    reweight_padded,
    scatter_ctx_major,
    transfer_ctx_to_item,
    transfer_item_to_ctx,
)
from repro.kernels import vmem
from repro.kernels.cd_sweep.ops import (
    cd_resid_patch,
    cd_resid_patch_gather,
    cd_slab_reduce,
    cd_slab_reduce_gather,
)
from repro.sparse.interactions import Interactions
from repro.sparse.segment import segment_sum

__all__ = ["MFSIParams", "MFSIHyperParams", "pad_interactions", "init",
           "phi", "psi", "export_psi", "build_phi", "predict", "epoch",
           "epoch_padded", "residuals", "residuals_padded", "objective",
           "fit"]


class MFSIParams(NamedTuple):
    w: jax.Array  # (p_ctx, k)  stacked context-feature embeddings
    h: jax.Array  # (p_item, k) stacked item-feature embeddings


@dataclasses.dataclass(frozen=True)
class MFSIHyperParams:
    k: int
    alpha0: float = 1.0
    l2: float = 0.1
    eta: float = 1.0
    multi_hot_mode: str = "jacobi"  # 'jacobi' | 'slot'
    jacobi_eta: float = 0.5
    implementation: str = "xla"
    block_k: int = 0  # dims per fused slab-reduce/resid-patch dispatch on
    #                   the padded layout (epoch_padded): 0 = auto
    #                   (min(k, 8)), 1 = per-dimension baseline
    psi_dispatch: str = "gather"  # fused-path Ψ routing: 'gather' =
    #                   in-kernel gather (no (n, k_b, D_pad) intermediate;
    #                   auto-fallback on VMEM overflow), 'pregather' =
    #                   host-side pre-gathered tile


def init(key: jax.Array, p_ctx: int, p_item: int, k: int, sigma: float = 0.1) -> MFSIParams:
    kw, kh = jax.random.split(key)
    return MFSIParams(
        w=sigma * jax.random.normal(kw, (p_ctx, k), dtype=jnp.float32),
        h=sigma * jax.random.normal(kh, (p_item, k), dtype=jnp.float32),
    )


def phi(params: MFSIParams, x: Design) -> jax.Array:
    return design_matmul(x, params.w)


def psi(params: MFSIParams, z: Design) -> jax.Array:
    return design_matmul(z, params.h)


def export_psi(params: MFSIParams, z: Design) -> jax.Array:
    """ψ table for the retrieval engine: Ψ = Z·H (n_items, k), one row per
    catalogue item of the item design ``z``."""
    return psi(params, z)


def build_phi(params: MFSIParams, x: Design, rows: Optional[jax.Array] = None) -> jax.Array:
    """φ rows for query contexts: Φ = X·W over ``rows`` of the context
    design ``x`` (rows are gathered BEFORE the matmul — a query batch is
    O(B·k), not a full-design pass); ⟨φ, ψ_i⟩ = ŷ (eq. 20)."""
    return phi(params, x if rows is None else take_rows(x, rows))


def predict(params: MFSIParams, x: Design, z: Design, ctx, item) -> jax.Array:
    ph, ps = phi(params, x), psi(params, z)
    return jnp.sum(jnp.take(ph, ctx, axis=0) * jnp.take(ps, item, axis=0), axis=-1)


def _field_layer_update(
    table_col, phi_col, q, r_vec, p2, jff,
    ids_g, xw, rows, vocab, offset, hp, eta,
):
    """One vectorized Newton update of a one-hot layer (field or bag slot).

    ids_g:  (n,) global feature ids for this layer (offset applied)
    xw:     (n,) feature values x_{c,l} (0 ⇒ row inactive in this layer)
    rows:   (n,) context row per entry (identity for bag=1 fields)

    Patches the per-context caches (eq. 25 and DESIGN.md §3) but NOT the
    residual cache — the caller owns the e layout (flat per-nnz vs padded
    grid) and applies ``dphi_rows`` there (per layer on the flat path, one
    fused rank-k_b ``cd_resid_patch`` per block on the padded path).
    """
    w_layer = table_col[offset : offset + vocab]
    lp = segment_sum(xw * jnp.take(q, rows), ids_g - offset, vocab)
    lpp = segment_sum(xw * xw * jnp.take(p2, rows), ids_g - offset, vocab)
    rp = segment_sum(xw * jnp.take(r_vec, rows), ids_g - offset, vocab)
    rpp = jff * segment_sum(xw * xw, ids_g - offset, vocab)
    num = lp + hp.alpha0 * rp + hp.l2 * w_layer
    den = lpp + hp.alpha0 * rpp + hp.l2
    delta = -eta * num / jnp.maximum(den, 1e-12)

    # scatter the step back + incremental patches (eq. 25 and DESIGN.md §3)
    table_col = table_col.at[offset : offset + vocab].add(delta)
    dphi_rows = segment_sum(xw * jnp.take(delta, ids_g - offset), rows, q.shape[0])
    phi_col = phi_col + dphi_rows
    q = q + dphi_rows * p2
    r_vec = r_vec + dphi_rows * jff
    return table_col, phi_col, q, r_vec, dphi_rows


def _field_layers(design: Design, hp) -> list:
    """Flatten the field loop into (ids, weights, rows, vocab, offset, eta)
    layers: one-hot fields (and 'slot' mode bags) update per slot — exact
    CD; 'jacobi' bags update whole-bag in one damped parallel step."""
    n_rows = design.n_rows
    row_idx = jnp.arange(n_rows, dtype=jnp.int32)
    layers = []
    for field in design.fields:
        gids = design.global_ids(field)
        if field.one_hot or hp.multi_hot_mode == "slot":
            for j in range(field.bag):
                layers.append((gids[:, j], field.weights[:, j], row_idx,
                               field.vocab, field.offset, hp.eta))
        else:
            layers.append((gids.reshape(-1), field.weights.reshape(-1),
                           jnp.repeat(row_idx, field.bag),
                           field.vocab, field.offset, hp.jacobi_eta))
    return layers


def _side_sweep(
    table: jax.Array,       # (p, k) this side's feature embeddings
    phi_m: jax.Array,       # (n_rows, k) this side's Φ (kept in sync)
    other_psi: jax.Array,   # (n_other, k) opposite side's Ψ (fixed)
    other_j: jax.Array,     # (k, k) Gram of Ψ
    design: Design,
    rows_nnz: jax.Array,    # (nnz,) this-side row per observation
    other_nnz_ids: jax.Array,  # (nnz,) opposite-side row per observation
    alpha: jax.Array,
    e: jax.Array,
    hp: MFSIHyperParams,
    schedule=None,
    sweep_index: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    n_rows = design.n_rows
    layers = _field_layers(design, hp)

    def dim_body(f, carry):
        table, phi_m, e = carry
        psi_col = sweeps.take_col(other_psi, f)
        psi_nnz = jnp.take(psi_col, other_nnz_ids)
        p2 = segment_sum(alpha * psi_nnz * psi_nnz, rows_nnz, n_rows)
        q = segment_sum(alpha * e * psi_nnz, rows_nnz, n_rows)
        r_vec = phi_m @ sweeps.take_col(other_j, f)
        jff = other_j[f, f]
        table_col = sweeps.take_col(table, f)
        phi_col = sweeps.take_col(phi_m, f)

        # one-hot layers are EXACT (features never share a row); multi-hot
        # bags run either sequential 'slot' layers (fresh residuals) or one
        # damped 'jacobi' parallel step — see _field_layers.
        for ids_g, xw, rows, vocab, offset, eta in layers:
            table_col, phi_col, q, r_vec, dphi_rows = _field_layer_update(
                table_col, phi_col, q, r_vec, p2, jff,
                ids_g, xw, rows, vocab, offset, hp, eta,
            )
            e = e + jnp.take(dphi_rows, rows_nnz) * psi_nnz

        table = sweeps.put_col(table, f, table_col)
        phi_m = sweeps.put_col(phi_m, f, phi_col)
        return table, phi_m, e

    table, phi_m, e = sweeps.sweep_columns(
        hp.k, dim_body, (table, phi_m, e),
        schedule=schedule, sweep_index=sweep_index,
    )
    return table, phi_m, e


def _side_sweep_padded(
    table: jax.Array,       # (p, k) this side's feature embeddings
    phi_m: jax.Array,       # (n_rows, k) this side's Φ (kept in sync)
    other_psi: jax.Array,   # (n_other, k) opposite side's Ψ (fixed)
    other_j: jax.Array,     # (k, k) Gram of Ψ
    design: Design,
    ids_pad: jax.Array,     # (n_rows, d_pad) opposite-side row ids
    alpha_pad: jax.Array,   # (n_rows, d_pad), 0 on padding
    e_pad: jax.Array,       # (n_rows, d_pad) residual grid
    hp: MFSIHyperParams,
    k_b: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused side sweep: one ``cd_slab_reduce`` per block feeds the
    field-level Newton steps of all k_b dimensions (q patched across block
    columns through the coupling slab P), one ``cd_resid_patch`` closes the
    block. Same fixed point as :func:`_side_sweep` (parity-tested).

    Ψ routing: in-kernel gather by default (the ψ slab ``other_psi[:, blk]``
    rides into the kernels with the id grid; no ``(n, kb, d_pad)`` HBM
    tile), pre-gathered when ``hp.psi_dispatch='pregather'`` or the slab
    busts the VMEM budget."""
    n_rows = design.n_rows
    layers = _field_layers(design, hp)
    use_gather, _ = vmem.resolve_cd_sweep_dispatch(
        ids_pad.shape[1], k_b, other_psi.shape[0], n_rows=n_rows,
        hold_tile=True, prefer_gather=sweeps.resolve_psi_dispatch(hp.psi_dispatch),
    )

    def block_body(f0, kb, carry):
        table, phi_m, e_pad = carry
        blk = slice(f0, f0 + kb)
        if use_gather:
            psi_tab = other_psi[:, blk]                    # (n_other, kb)
            q_slab, p_slab = cd_slab_reduce_gather(
                psi_tab, ids_pad, alpha_pad, e_pad
            )
        else:
            psi_blk = jnp.moveaxis(
                jnp.take(other_psi[:, blk], ids_pad, axis=0), -1, 1
            )                                              # (n, kb, d_pad)
            q_slab, p_slab = cd_slab_reduce(psi_blk, alpha_pad, e_pad)
        dphi_cols = []
        for j in range(kb):
            f = f0 + j
            q = q_slab[:, j]
            p2 = p_slab[:, j, j]
            r_vec = phi_m @ other_j[:, f]
            jff = other_j[f, f]
            table_col = table[:, f]
            phi_col = phi_m[:, f]
            dphi_tot = jnp.zeros((n_rows,), jnp.float32)
            for ids_g, xw, rows, vocab, offset, eta in layers:
                table_col, phi_col, q, r_vec, dphi_rows = _field_layer_update(
                    table_col, phi_col, q, r_vec, p2, jff,
                    ids_g, xw, rows, vocab, offset, hp, eta,
                )
                dphi_tot = dphi_tot + dphi_rows
            table = table.at[:, f].set(table_col)
            phi_m = phi_m.at[:, f].set(phi_col)
            if j + 1 < kb:  # Δe = Δφ_j·ψ_j moves later columns' q caches
                q_slab = q_slab.at[:, j + 1:kb].add(
                    dphi_tot[:, None] * p_slab[:, j, j + 1:kb]
                )
            dphi_cols.append(dphi_tot)
        dphi_blk = jnp.stack(dphi_cols, axis=1)
        if use_gather:
            e_pad = cd_resid_patch_gather(psi_tab, ids_pad, e_pad, dphi_blk)
        else:
            e_pad = cd_resid_patch(psi_blk, e_pad, dphi_blk)
        return table, phi_m, e_pad

    return sweeps.sweep_columns(
        hp.k, None, (table, phi_m, e_pad), block=k_b, block_body=block_body
    )


@partial(jax.jit, static_argnames=("hp", "schedule", "sweep_index"))
def epoch(
    params: MFSIParams,
    x: Design,
    z: Design,
    data: Interactions,
    e: jax.Array,
    hp: MFSIHyperParams,
    schedule=None,
    sweep_index: int = 0,
    weights: Optional[jax.Array] = None,
) -> Tuple[MFSIParams, jax.Array]:
    """One iCD epoch: context-feature sweep, then item-feature sweep, over
    the scheduled columns (``schedule=None`` = full pass).

    ``weights`` (optional, (nnz,) ctx-major) folds per-interaction
    confidence into α exactly (α is purely multiplicative in the explicit
    parts); ``None`` traces the identical unweighted program."""
    if weights is not None:
        data = dataclasses.replace(data, alpha=data.alpha * weights)
    w, h = params
    phi_m = design_matmul(x, w)
    psi_m = design_matmul(z, h)

    j_i = gram(psi_m, implementation=hp.implementation)
    w, phi_m, e = _side_sweep(
        w, phi_m, psi_m, j_i, x, data.ctx, data.item, data.alpha, e, hp,
        schedule, sweep_index,
    )

    j_c = gram(phi_m, implementation=hp.implementation)
    e_t = sweeps.to_item_major(e, data.t_perm)
    alpha_t = sweeps.to_item_major(data.alpha, data.t_perm)
    h, psi_m, e_t = _side_sweep(
        h, psi_m, phi_m, j_c, z, data.t_item, data.t_ctx, alpha_t, e_t, hp,
        schedule, sweep_index,
    )
    e = sweeps.to_ctx_major(e_t, data.t_perm)
    return MFSIParams(w, h), e


@partial(jax.jit, static_argnames=("hp",), donate_argnums=(4,))
def epoch_padded(
    params: MFSIParams,
    x: Design,
    z: Design,
    pdata: PaddedInteractions,
    e_pad: jax.Array,
    hp: MFSIHyperParams,
    weights: Optional[jax.Array] = None,
) -> Tuple[MFSIParams, jax.Array]:
    """Fused iCD epoch over the dual padded layout (``mf_padded``'s
    ``PaddedInteractions``); carries the ctx-major padded residual grid.
    Same sweep order and fixed point as :func:`epoch` (parity-tested).
    ``weights`` folds into both padded α grids (see
    :func:`repro.core.models.mf_padded.reweight_padded`)."""
    if weights is not None:
        pdata = reweight_padded(pdata, weights)
    w, h = params
    k_b = sweeps.resolve_block_k(hp.block_k, hp.k)
    phi_m = design_matmul(x, w)
    psi_m = design_matmul(z, h)

    j_i = gram(psi_m, implementation=hp.implementation)
    w, phi_m, e_pad = _side_sweep_padded(
        w, phi_m, psi_m, j_i, x, pdata.item_ids, pdata.alpha_c, e_pad, hp, k_b
    )

    e_pad_i = transfer_ctx_to_item(pdata, e_pad)

    j_c = gram(phi_m, implementation=hp.implementation)
    h, psi_m, e_pad_i = _side_sweep_padded(
        h, psi_m, phi_m, j_c, z, pdata.ctx_ids, pdata.alpha_i, e_pad_i, hp, k_b
    )
    e_pad = transfer_item_to_ctx(pdata, e_pad_i)
    return MFSIParams(w, h), e_pad


def residuals_padded(
    params: MFSIParams, x: Design, z: Design, data: Interactions,
    pdata: PaddedInteractions,
) -> jax.Array:
    """ŷ−ȳ on the ctx-major padded grid (0 on padding)."""
    return scatter_ctx_major(pdata, residuals(params, x, z, data))


def residuals(params: MFSIParams, x: Design, z: Design, data: Interactions) -> jax.Array:
    return sweeps.residuals_from_factors(
        phi(params, x), psi(params, z), data.ctx, data.item, data.y
    )


def objective(params: MFSIParams, x: Design, z: Design, data: Interactions,
              hp: MFSIHyperParams) -> jax.Array:
    e = residuals(params, x, z, data)
    sq = jnp.sum(params.w**2) + jnp.sum(params.h**2)
    return implicit_objective(phi(params, x), psi(params, z), e, data, hp.alpha0, hp.l2, sq)


def fit(params, x, z, data, hp, n_epochs, callback=None, schedule=None,
        weights=None):
    e = residuals(params, x, z, data)
    for ep in range(n_epochs):
        params, e = epoch(params, x, z, data, e, hp, schedule,
                          ep if schedule is not None else 0, weights)
        if callback is not None:
            callback(ep, params)
    return params
