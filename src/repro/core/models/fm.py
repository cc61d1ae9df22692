"""iCD for Factorization Machines (paper §5.2.2).

FM (eq. 26) over the concatenated feature vector x = (x_c, z_i):

    ŷ(x) = b + Σ_l x_l w̃_l + Σ_{l<l'} ⟨w_l, w_l'⟩ x_l x_l'

is (k+2)-separable (eqs. 27–31). We lay the extended components out as
aligned columns of Φe ∈ R^{C×(k+2)} and Ψe ∈ R^{I×(k+2)}:

    column f < k : φ_f = Σ_l x_l w_{l,f}          ψ_f = Σ_l z_l h_{l,f}
    column k     : φ_spec (ctx bias+linear+pairs)  ones
    column k+1   : ones                            ψ_spec (item side)

so ŷ = ⟨Φe(c), Ψe(i)⟩ exactly. Gradients are sparse (eqs. 32–33): a context
embedding w_{l*,f*} feeds component f* (value x) and the ctx-special
component (value x·g, g = φ_{f*} − x·w_{l*,f*}); FM stays *linear* in every
single coordinate, so full Newton steps (η=1) are exact.

Sweep order per side: all k embedding dims (field-vectorized like MFSI),
then the linear weights, then (context side only) the global bias. One-hot
fields are exact; multi-hot fields use damped Jacobi (DESIGN.md §3) and the
second-order cross-slot residual drift is bounded by refreshing caches every
epoch. Runtime matches the paper: same flow/complexity as MFSI,
O(k² N_Z(X)) per epoch for the implicit part.

Fused padded path (``epoch_padded`` over ``mf_padded.PaddedInteractions``,
dispatched by ``hp.block_k``): per block of k_b dimensions one
``cd_slab_reduce`` over the k_b ψ columns PLUS the ψ_spec column yields
every per-context cache the layer updates need — q/u from Q, p2/p1/p0 from
the moment slab P — and the cross-dimension coupling that patches q for
later block columns (Δe = Δφ_j·ψ_j + Δφ_s·ψ_spec ⇒ Δq_f =
Δφ_j·P[·,j,f] + Δφ_s·P[·,s,f]); one rank-(k_b+1) ``cd_resid_patch``
closes the block. The linear-weight and bias stages run on the padded grid
with the same formulas.
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
from repro.core.models.mfsi import _field_layers
from repro.kernels import vmem
from repro.kernels.cd_sweep.ops import (
    cd_resid_patch,
    cd_resid_patch_gather,
    cd_slab_reduce,
    cd_slab_reduce_gather,
)
from repro.sparse.interactions import Interactions
from repro.sparse.segment import segment_sum

__all__ = ["FMParams", "FMHyperParams", "pad_interactions", "init",
           "phi_ext", "psi_ext", "export_psi", "build_phi", "predict",
           "epoch", "epoch_padded", "residuals", "residuals_padded",
           "objective", "fit"]


class FMParams(NamedTuple):
    b: jax.Array       # () global bias
    w_lin: jax.Array   # (p,)  context linear weights  (paper w̃)
    w: jax.Array       # (p, k) context embeddings
    h_lin: jax.Array   # (p',) item linear weights     (paper h̃)
    h: jax.Array       # (p', k) item embeddings


@dataclasses.dataclass(frozen=True)
class FMHyperParams:
    k: int
    alpha0: float = 1.0
    l2: float = 0.1
    l2_lin: float = 0.1
    eta: float = 1.0
    use_linear: bool = True
    use_bias: bool = True
    multi_hot_mode: str = "jacobi"  # 'jacobi' | 'slot'
    jacobi_eta: float = 0.5
    implementation: str = "xla"
    block_k: int = 0  # dims per fused slab-reduce/resid-patch dispatch on
    #                   the padded layout (epoch_padded): 0 = auto
    #                   (min(k, 8)), 1 = per-dimension baseline
    psi_dispatch: str = "gather"  # fused-path Ψ routing: 'gather' =
    #                   in-kernel gather (no (n, k_b+1, D_pad) intermediate;
    #                   auto-fallback on VMEM overflow), 'pregather' =
    #                   host-side pre-gathered tile


def init(key: jax.Array, p_ctx: int, p_item: int, k: int, sigma: float = 0.1) -> FMParams:
    kw, kh = jax.random.split(key)
    return FMParams(
        b=jnp.zeros((), jnp.float32),
        w_lin=jnp.zeros((p_ctx,), jnp.float32),
        w=sigma * jax.random.normal(kw, (p_ctx, k), dtype=jnp.float32),
        h_lin=jnp.zeros((p_item,), jnp.float32),
        h=sigma * jax.random.normal(kh, (p_item, k), dtype=jnp.float32),
    )


def _self_pairwise(design: Design, table: jax.Array, phi_m: jax.Array) -> jax.Array:
    """Σ_{l<l'} ⟨w_l,w_l'⟩ x_l x_l' = ½ Σ_f (φ_f² − Σ_l x_l² w_{l,f}²)."""
    sq_sum = jnp.zeros((design.n_rows,), jnp.float32)
    for field in design.fields:
        wsq = jnp.take(table * table, design.global_ids(field), axis=0)  # (n,bag,k)
        sq_sum = sq_sum + jnp.sum(
            jnp.sum(wsq, axis=-1) * field.weights * field.weights, axis=-1
        )
    return 0.5 * (jnp.sum(phi_m * phi_m, axis=-1) - sq_sum)


def phi_ext(params: FMParams, x: Design, hp: FMHyperParams) -> jax.Array:
    """Φe (C, k+2): [Φ | φ_spec | 1]."""
    phi_m = design_matmul(x, params.w)
    spec = _self_pairwise(x, params.w, phi_m)
    if hp.use_linear:
        spec = spec + design_matmul(x, params.w_lin[:, None])[:, 0]
    if hp.use_bias:
        spec = spec + params.b
    ones = jnp.ones((x.n_rows,), jnp.float32)
    return jnp.concatenate([phi_m, spec[:, None], ones[:, None]], axis=1)


def psi_ext(params: FMParams, z: Design, hp: FMHyperParams) -> jax.Array:
    """Ψe (I, k+2): [Ψ | 1 | ψ_spec]."""
    psi_m = design_matmul(z, params.h)
    spec = _self_pairwise(z, params.h, psi_m)
    if hp.use_linear:
        spec = spec + design_matmul(z, params.h_lin[:, None])[:, 0]
    ones = jnp.ones((z.n_rows,), jnp.float32)
    return jnp.concatenate([psi_m, ones[:, None], spec[:, None]], axis=1)


def predict(params: FMParams, x: Design, z: Design, ctx, item, hp: FMHyperParams) -> jax.Array:
    pe, se = phi_ext(params, x, hp), psi_ext(params, z, hp)
    return jnp.sum(jnp.take(pe, ctx, axis=0) * jnp.take(se, item, axis=0), axis=-1)


def export_psi(params: FMParams, z: Design, hp: FMHyperParams) -> jax.Array:
    """ψ table for the retrieval engine: Ψe (n_items, k+2) with the FM
    column convention [Ψ | 1 | ψ_spec] — aligned so ⟨Φe, Ψe⟩ = ŷ (eq. 26)
    with Φe's [Φ | φ_spec | 1] ordering."""
    return psi_ext(params, z, hp)


def build_phi(params: FMParams, x: Design, hp: FMHyperParams,
              rows: Optional[jax.Array] = None) -> jax.Array:
    """φ rows for query contexts: Φe = [Φ | φ_spec | 1] (B, k+2) over
    ``rows`` of the context design ``x`` (rows are gathered BEFORE the
    matmuls — a query batch is O(B·k), not a full-design pass)."""
    return phi_ext(params, x if rows is None else take_rows(x, rows), hp)


def _embed_layer_update(
    table_col, self_ext, q, u, r_a, r_b, p2, p1, p0, j_ff, j_fs, j_ss,
    ids_g, xw, rows, vocab, offset, f, spec_col, hp, eta,
):
    """Vectorized Newton update of one embedding layer (field × dim f*).

    Patches the per-context caches but NOT the residual cache — the caller
    owns the e layout and applies (Δφ_{f*}, Δφ_spec) there (per layer on
    the flat path, one fused rank-(k_b+1) ``cd_resid_patch`` per block on
    the padded path)."""
    local = ids_g - offset
    w_rows = jnp.take(table_col, ids_g)                      # w_{l,f*} per entry
    g = jnp.take(sweeps.take_col(self_ext, f), rows) - xw * w_rows
    lp = segment_sum(xw * (jnp.take(q, rows) + g * jnp.take(u, rows)), local, vocab)
    lpp = segment_sum(
        xw * xw * (jnp.take(p2, rows) + 2 * g * jnp.take(p1, rows) + g * g * jnp.take(p0, rows)),
        local, vocab,
    )
    rp = segment_sum(xw * (jnp.take(r_a, rows) + g * jnp.take(r_b, rows)), local, vocab)
    rpp = segment_sum(xw * xw * (j_ff + 2 * g * j_fs + g * g * j_ss), local, vocab)
    w_layer = table_col[offset : offset + vocab]
    num = lp + hp.alpha0 * rp + hp.l2 * w_layer
    den = lpp + hp.alpha0 * rpp + hp.l2
    delta = -eta * num / jnp.maximum(den, 1e-12)
    table_col = table_col.at[offset : offset + vocab].add(delta)

    d_entry = xw * jnp.take(delta, local)                    # per-entry Δ(xw)
    n_rows = self_ext.shape[0]
    dphi_f = segment_sum(d_entry, rows, n_rows)              # Δφ_{f*}
    dphi_s = segment_sum(d_entry * g, rows, n_rows)          # Δφ_spec (linear patch)
    self_ext = sweeps.put_col(self_ext, f, sweeps.take_col(self_ext, f) + dphi_f)
    self_ext = self_ext.at[:, spec_col].add(dphi_s)
    q = q + dphi_f * p2 + dphi_s * p1
    u = u + dphi_f * p1 + dphi_s * p0
    r_a = r_a + dphi_f * j_ff + dphi_s * j_fs
    r_b = r_b + dphi_f * j_fs + dphi_s * j_ss
    return table_col, self_ext, q, u, r_a, r_b, dphi_f, dphi_s


def _side_sweep(
    table: jax.Array,
    lin: Optional[jax.Array],
    bias: Optional[jax.Array],
    self_ext: jax.Array,     # (n, k+2), kept in sync
    other_ext: jax.Array,    # (m, k+2), fixed
    other_j: jax.Array,      # (k+2, k+2) Gram of other_ext
    design: Design,
    rows_nnz: jax.Array,
    other_nnz_ids: jax.Array,
    alpha: jax.Array,
    e: jax.Array,
    spec_col: int,
    hp: FMHyperParams,
    schedule=None,
    sweep_index: int = 0,
):
    n_rows = design.n_rows
    layers = _field_layers(design, hp)
    o_spec_nnz = jnp.take(other_ext[:, spec_col], other_nnz_ids)  # ones, kept generic
    p0 = segment_sum(alpha * o_spec_nnz * o_spec_nnz, rows_nnz, n_rows)
    j_ss = other_j[spec_col, spec_col]

    # ---- embedding dims -------------------------------------------------
    def dim_body(f, carry):
        table, self_ext, e = carry
        other_f_nnz = jnp.take(sweeps.take_col(other_ext, f), other_nnz_ids)
        p2 = segment_sum(alpha * other_f_nnz * other_f_nnz, rows_nnz, n_rows)
        p1 = segment_sum(alpha * other_f_nnz * o_spec_nnz, rows_nnz, n_rows)
        q = segment_sum(alpha * e * other_f_nnz, rows_nnz, n_rows)
        u = segment_sum(alpha * e * o_spec_nnz, rows_nnz, n_rows)
        r_a = self_ext @ sweeps.take_col(other_j, f)
        r_b = self_ext @ other_j[:, spec_col]
        j_ff = other_j[f, f]
        j_fs = other_j[f, spec_col]
        table_col = sweeps.take_col(table, f)

        for ids_g, xw, rows, vocab, offset, eta in layers:
            table_col, self_ext, q, u, r_a, r_b, dphi_f, dphi_s = (
                _embed_layer_update(
                    table_col, self_ext, q, u, r_a, r_b, p2, p1, p0,
                    j_ff, j_fs, j_ss, ids_g, xw, rows, vocab, offset,
                    f, spec_col, hp, eta,
                )
            )
            e = (
                e
                + jnp.take(dphi_f, rows_nnz) * other_f_nnz
                + jnp.take(dphi_s, rows_nnz) * o_spec_nnz
            )
        return sweeps.put_col(table, f, table_col), self_ext, e

    table, self_ext, e = sweeps.sweep_columns(
        hp.k, dim_body, (table, self_ext, e),
        schedule=schedule, sweep_index=sweep_index,
    )

    # ---- linear weights --------------------------------------------------
    if hp.use_linear and lin is not None:
        u = segment_sum(alpha * e * o_spec_nnz, rows_nnz, n_rows)
        r_b = self_ext @ other_j[:, spec_col]
        for ids_g, xw, rows, vocab, offset, eta in layers:
            lin, self_ext, u, r_b, dspec = _linear_layer_update(
                lin, self_ext, u, r_b, p0, j_ss,
                ids_g, xw, rows, vocab, offset, spec_col, hp, eta,
            )
            e = e + jnp.take(dspec, rows_nnz) * o_spec_nnz

    # ---- global bias (context side only) ----------------------------------
    if hp.use_bias and bias is not None:
        u = segment_sum(alpha * e * o_spec_nnz, rows_nnz, n_rows)
        r_b = self_ext @ other_j[:, spec_col]
        bias, self_ext, delta = _bias_update(
            bias, self_ext, u, r_b, p0, j_ss, n_rows, spec_col, hp
        )
        e = e + delta * o_spec_nnz

    return table, lin, bias, self_ext, e


def _linear_layer_update(
    lin, self_ext, u, r_b, p0, j_ss, ids_g, xw, rows, vocab, offset,
    spec_col, hp, eta,
):
    """Newton step of one linear-weight layer; e patch left to the caller."""
    n_rows = self_ext.shape[0]
    local = ids_g - offset
    lp = segment_sum(xw * jnp.take(u, rows), local, vocab)
    lpp = segment_sum(xw * xw * jnp.take(p0, rows), local, vocab)
    rp = segment_sum(xw * jnp.take(r_b, rows), local, vocab)
    rpp = j_ss * segment_sum(xw * xw, local, vocab)
    lin_layer = lin[offset : offset + vocab]
    num = lp + hp.alpha0 * rp + hp.l2_lin * lin_layer
    den = lpp + hp.alpha0 * rpp + hp.l2_lin
    delta = -eta * num / jnp.maximum(den, 1e-12)
    lin = lin.at[offset : offset + vocab].add(delta)
    dspec = segment_sum(xw * jnp.take(delta, local), rows, n_rows)
    self_ext = self_ext.at[:, spec_col].add(dspec)
    u = u + dspec * p0
    r_b = r_b + dspec * j_ss
    return lin, self_ext, u, r_b, dspec


def _bias_update(bias, self_ext, u, r_b, p0, j_ss, n_rows, spec_col, hp):
    """Global-bias Newton step; e patch left to the caller."""
    lp = jnp.sum(u)
    lpp = jnp.sum(p0)
    rp = jnp.sum(r_b)
    rpp = j_ss * n_rows
    delta = -hp.eta * (lp + hp.alpha0 * rp) / jnp.maximum(lpp + hp.alpha0 * rpp, 1e-12)
    bias = bias + delta
    self_ext = self_ext.at[:, spec_col].add(delta)
    return bias, self_ext, delta


def _side_sweep_padded(
    table: jax.Array,
    lin: Optional[jax.Array],
    bias: Optional[jax.Array],
    self_ext: jax.Array,     # (n, k+2), kept in sync
    other_ext: jax.Array,    # (m, k+2), fixed
    other_j: jax.Array,      # (k+2, k+2) Gram of other_ext
    design: Design,
    ids_pad: jax.Array,      # (n, d_pad) opposite-side row ids
    alpha_pad: jax.Array,    # (n, d_pad), 0 on padding
    e_pad: jax.Array,        # (n, d_pad) residual grid
    spec_col: int,
    hp: FMHyperParams,
    k_b: int,
):
    """Fused FM side sweep on the padded grid: per block one
    ``cd_slab_reduce`` over [ψ_{f0..f0+k_b} | ψ_spec] feeds all per-context
    caches (q, u, p2, p1 and the cross-dim coupling), the field-level
    Newton steps run in XLA, one rank-(k_b+1) ``cd_resid_patch`` closes the
    block. Same fixed point as :func:`_side_sweep` (parity-tested).

    Ψ routing: in-kernel gather by default — the `(n_other, kb+1)` slab
    ``[Ψ[:, blk] | ψ_spec]`` rides into the kernels with the id grid, so
    the `(n, kb+1, d_pad)` tile never exists in HBM; pre-gathered when
    ``hp.psi_dispatch='pregather'`` or the slab busts the VMEM budget."""
    n_rows = design.n_rows
    layers = _field_layers(design, hp)
    psi_spec_pad = jnp.take(other_ext[:, spec_col], ids_pad)   # (n, d_pad)
    p0 = jnp.sum(alpha_pad * psi_spec_pad * psi_spec_pad, axis=1)
    j_ss = other_j[spec_col, spec_col]
    use_gather, _ = vmem.resolve_cd_sweep_dispatch(
        ids_pad.shape[1], k_b + 1, other_ext.shape[0], n_rows=n_rows,
        hold_tile=True, prefer_gather=sweeps.resolve_psi_dispatch(hp.psi_dispatch),
    )

    # ---- embedding dims, blocked ----------------------------------------
    def block_body(f0, kb, carry):
        table, self_ext, e_pad = carry
        blk = slice(f0, f0 + kb)
        if use_gather:
            # ψ slab [Ψ[:, blk] | ψ_spec] (n_other, kb+1) — kernel gathers
            psi_tab = jnp.concatenate(
                [other_ext[:, blk], other_ext[:, spec_col:spec_col + 1]],
                axis=1,
            )
            q_slab, p_slab = cd_slab_reduce_gather(
                psi_tab, ids_pad, alpha_pad, e_pad
            )
        else:
            psi_blk = jnp.concatenate(
                [
                    jnp.moveaxis(jnp.take(other_ext[:, blk], ids_pad, axis=0), -1, 1),
                    psi_spec_pad[:, None, :],
                ],
                axis=1,
            )                                                  # (n, kb+1, d_pad)
            q_slab, p_slab = cd_slab_reduce(psi_blk, alpha_pad, e_pad)
        u = q_slab[:, -1]
        dphi_cols = []
        dphi_s_tot = jnp.zeros((n_rows,), jnp.float32)
        for j in range(kb):
            f = f0 + j
            q = q_slab[:, j]
            p2 = p_slab[:, j, j]
            p1 = p_slab[:, j, -1]
            r_a = self_ext @ other_j[:, f]
            r_b = self_ext @ other_j[:, spec_col]
            j_ff = other_j[f, f]
            j_fs = other_j[f, spec_col]
            table_col = table[:, f]
            dphi_f_tot = jnp.zeros((n_rows,), jnp.float32)
            dphi_s_dim = jnp.zeros((n_rows,), jnp.float32)
            for ids_g, xw, rows, vocab, offset, eta in layers:
                table_col, self_ext, q, u, r_a, r_b, dphi_f, dphi_s = (
                    _embed_layer_update(
                        table_col, self_ext, q, u, r_a, r_b, p2, p1, p0,
                        j_ff, j_fs, j_ss, ids_g, xw, rows, vocab, offset,
                        f, spec_col, hp, eta,
                    )
                )
                dphi_f_tot = dphi_f_tot + dphi_f
                dphi_s_dim = dphi_s_dim + dphi_s
            table = table.at[:, f].set(table_col)
            if j + 1 < kb:  # Δe = Δφ_j·ψ_j + Δφ_s·ψ_spec moves later q's
                q_slab = q_slab.at[:, j + 1:kb].add(
                    dphi_f_tot[:, None] * p_slab[:, j, j + 1:kb]
                    + dphi_s_dim[:, None] * p_slab[:, -1, j + 1:kb]
                )
            dphi_cols.append(dphi_f_tot)
            dphi_s_tot = dphi_s_tot + dphi_s_dim
        dphi_blk = jnp.stack(dphi_cols + [dphi_s_tot], axis=1)  # (n, kb+1)
        if use_gather:
            e_pad = cd_resid_patch_gather(psi_tab, ids_pad, e_pad, dphi_blk)
        else:
            e_pad = cd_resid_patch(psi_blk, e_pad, dphi_blk)
        return table, self_ext, e_pad

    table, self_ext, e_pad = sweeps.sweep_columns(
        hp.k, None, (table, self_ext, e_pad), block=k_b, block_body=block_body
    )

    # ---- linear weights --------------------------------------------------
    if hp.use_linear and lin is not None:
        u = jnp.sum(alpha_pad * e_pad * psi_spec_pad, axis=1)
        r_b = self_ext @ other_j[:, spec_col]
        for ids_g, xw, rows, vocab, offset, eta in layers:
            lin, self_ext, u, r_b, dspec = _linear_layer_update(
                lin, self_ext, u, r_b, p0, j_ss,
                ids_g, xw, rows, vocab, offset, spec_col, hp, eta,
            )
            e_pad = e_pad + dspec[:, None] * psi_spec_pad

    # ---- global bias (context side only) ----------------------------------
    if hp.use_bias and bias is not None:
        u = jnp.sum(alpha_pad * e_pad * psi_spec_pad, axis=1)
        r_b = self_ext @ other_j[:, spec_col]
        bias, self_ext, delta = _bias_update(
            bias, self_ext, u, r_b, p0, j_ss, n_rows, spec_col, hp
        )
        e_pad = e_pad + delta * psi_spec_pad

    return table, lin, bias, self_ext, e_pad


@partial(jax.jit, static_argnames=("hp", "schedule", "sweep_index"))
def epoch(
    params: FMParams,
    x: Design,
    z: Design,
    data: Interactions,
    e: jax.Array,
    hp: FMHyperParams,
    schedule=None,
    sweep_index: int = 0,
    weights: Optional[jax.Array] = None,
) -> Tuple[FMParams, jax.Array]:
    # weights (optional, (nnz,) ctx-major): per-interaction confidence folds
    # into α exactly; None traces the identical unweighted program.
    if weights is not None:
        data = dataclasses.replace(data, alpha=data.alpha * weights)
    b, w_lin, w, h_lin, h = params
    pe = phi_ext(params, x, hp)
    se = psi_ext(params, z, hp)

    j_i = gram(se, implementation=hp.implementation)
    w, w_lin, b, pe, e = _side_sweep(
        w, w_lin if hp.use_linear else None, b if hp.use_bias else None,
        pe, se, j_i, x, data.ctx, data.item, data.alpha, e,
        spec_col=hp.k, hp=hp, schedule=schedule, sweep_index=sweep_index,
    )

    j_c = gram(pe, implementation=hp.implementation)
    e_t = sweeps.to_item_major(e, data.t_perm)
    alpha_t = sweeps.to_item_major(data.alpha, data.t_perm)
    h, h_lin, _, se, e_t = _side_sweep(
        h, h_lin if hp.use_linear else None, None,
        se, pe, j_c, z, data.t_item, data.t_ctx, alpha_t, e_t,
        spec_col=hp.k + 1, hp=hp, schedule=schedule, sweep_index=sweep_index,
    )
    e = sweeps.to_ctx_major(e_t, data.t_perm)
    return FMParams(b, w_lin, w, h_lin, h), e


@partial(jax.jit, static_argnames=("hp",), donate_argnums=(4,))
def epoch_padded(
    params: FMParams,
    x: Design,
    z: Design,
    pdata: PaddedInteractions,
    e_pad: jax.Array,
    hp: FMHyperParams,
    weights: Optional[jax.Array] = None,
) -> Tuple[FMParams, jax.Array]:
    """Fused iCD epoch over the dual padded layout; carries the ctx-major
    padded residual grid. Same sweep order and fixed point as :func:`epoch`
    (parity-tested). ``weights`` folds into both padded α grids."""
    if weights is not None:
        pdata = reweight_padded(pdata, weights)
    b, w_lin, w, h_lin, h = params
    k_b = sweeps.resolve_block_k(hp.block_k, hp.k)
    pe = phi_ext(params, x, hp)
    se = psi_ext(params, z, hp)

    j_i = gram(se, implementation=hp.implementation)
    w, w_lin, b, pe, e_pad = _side_sweep_padded(
        w, w_lin if hp.use_linear else None, b if hp.use_bias else None,
        pe, se, j_i, x, pdata.item_ids, pdata.alpha_c, e_pad,
        spec_col=hp.k, hp=hp, k_b=k_b,
    )

    e_pad_i = transfer_ctx_to_item(pdata, e_pad)

    j_c = gram(pe, implementation=hp.implementation)
    h, h_lin, _, se, e_pad_i = _side_sweep_padded(
        h, h_lin if hp.use_linear else None, None,
        se, pe, j_c, z, pdata.ctx_ids, pdata.alpha_i, e_pad_i,
        spec_col=hp.k + 1, hp=hp, k_b=k_b,
    )
    e_pad = transfer_item_to_ctx(pdata, e_pad_i)
    return FMParams(b, w_lin, w, h_lin, h), e_pad


def residuals_padded(
    params: FMParams, x: Design, z: Design, data: Interactions,
    pdata: PaddedInteractions, hp: FMHyperParams,
) -> jax.Array:
    """ŷ−ȳ on the ctx-major padded grid (0 on padding)."""
    return scatter_ctx_major(pdata, residuals(params, x, z, data, hp))


def residuals(params: FMParams, x: Design, z: Design, data: Interactions,
              hp: FMHyperParams) -> jax.Array:
    return sweeps.residuals_from_factors(
        phi_ext(params, x, hp), psi_ext(params, z, hp), data.ctx, data.item, data.y
    )


def objective(params: FMParams, x: Design, z: Design, data: Interactions,
              hp: FMHyperParams) -> jax.Array:
    e = residuals(params, x, z, data, hp)
    sq = jnp.sum(params.w**2) + jnp.sum(params.h**2)
    sq_lin = jnp.sum(params.w_lin**2) + jnp.sum(params.h_lin**2)
    pe, se = phi_ext(params, x, hp), psi_ext(params, z, hp)
    # NOTE: φ_spec/ψ_spec are model components, not free parameters — only
    # the L2 on true parameters enters; the implicit R covers the rest.
    return implicit_objective(
        pe, se, e, data, hp.alpha0, 0.0, jnp.zeros(())
    ) + hp.l2 * sq + hp.l2_lin * sq_lin


def fit(params, x, z, data, hp, n_epochs, callback=None, refresh_residuals=True,
        schedule=None, weights=None):
    e = residuals(params, x, z, data, hp)
    for ep in range(n_epochs):
        if refresh_residuals and ep > 0:
            e = residuals(params, x, z, data, hp)  # bound multi-hot drift
        params, e = epoch(params, x, z, data, e, hp, schedule,
                          ep if schedule is not None else 0, weights)
        if callback is not None:
            callback(ep, params)
    return params
