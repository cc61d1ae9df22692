"""Pallas fused multi-column iCD block-sweeps (Algorithm 2/3's f*-loop, blocked).

Four entry points share the "residual cache VMEM-resident across a block of
embedding dimensions" idea; together they cover the whole k-separable model
zoo (paper §5). Each ships in TWO forms — pre-gathered (the caller
materializes a `(C, k_b, D_pad)` Ψ tile in HBM) and IN-KERNEL GATHER
(``*_gather_pallas``: the kernel takes the full `(n_src, m)` ψ slab plus an
`(C, D_pad)` id tile and gathers Ψ rows inside the kernel, so the
`(C, k_b, D_pad)` intermediate never exists):

  ``cd_block_sweep_pallas``          — MF-style block sweep: the R' slab is
        patched with a SHARED (k_b, k_b) Gram block (R'' is the scalar
        J(f,f)). Exact for models whose φ-gradient is one-hot (MF).
  ``cd_block_sweep_rowpatch_pallas`` — general block sweep: the R'/R''
        coupling is a PER-ROW (bc, k_b, k_b) patch tensor P with
        P[r, j, f] = ∂(R'_f/2)/∂θ_{r,j} and diagonal P[r, f, f] = R''_f/2.
        Exact for PARAFAC (P = J ⊙ K_row, eqs. 37–38) and Tucker
        (P = Σ_g D^f_g (D^j J)_g per row, eq. 41 regime).
  ``cd_slab_reduce_pallas``          — per-field slab moments for the
        feature-based models (MFSI/FM, Algorithm 3): one e/α stream yields
        Q[r, j] = Σ_d α e ψ_j and P[r, i, j] = Σ_d α ψ_i ψ_j for all block
        columns, the per-context caches (q, p2, p1, p0, cross-dim coupling)
        the field-level Newton steps consume.
  ``cd_resid_patch_pallas``          — rank-k_b residual patch
        e += Σ_j Δφ_j·ψ_j closing a feature-model block: one e stream
        instead of one per dimension.

Lineage: generalizes ``kernels/cd_update`` (one embedding dimension per
dispatch) to a block of ``k_b`` dimensions per grid step. The per-column
kernel re-streams the `(C, D_pad)` residual cache ``e`` and confidence
tensor ``α`` from HBM once per column — k round-trips per sweep — even
though the per-column compute is tiny. Here the `(block_ctx, D_pad)` tiles
of ``e`` and ``α`` are loaded into VMEM ONCE and stay resident while all
``k_b`` Newton steps run, statically unrolled over 8-row slices
(``_sweep_rows``; Mosaic lowers no value-level ``dynamic_slice``):

  inputs  (per block): Ψ tile  (bc, k_b, D_pad) — pre-gathered ψ_f(item)
                                                  for every column in block
                       α tile, e tile (bc, D_pad)
                       W slab  (bc, k_b), R' slab (bc, k_b) ≡ (W·J)[:, blk]
                       J block (k_b, k_b)       — diagonal block of the Gram
  compute, for j = 0..k_b−1 (sequential — exact Gauss–Seidel):
           L'/2  = Σ_d α·e·ψ_j            (VPU row reduce)
           L''/2 = Σ_d α·ψ_j²
           Δ     = −η·(L'/2 + α₀R'_j/2 + λw_j)/(L''/2 + α₀J(j,j) + λ)
           e    += Δ·ψ_j                  (rank-1 residual patch, in VMEM)
           R'   += Δ·J(j,·)               (Gauss–Seidel patch: later columns
                                           see the updated w_j through R')
  outputs: W slab (bc, k_b), e (bc, D_pad)

The R' patch is what preserves exact per-column semantics: recomputing
R'_f' = (W·J)[:, f'] after w_j moved by Δ adds exactly Δ·J(j, f'), so the
fused block reproduces the per-column path that recomputes R' from the
updated W before every column.

HBM traffic per sweep (vs per-column): ψ is still read once per column
(k·C·D_pad total, irreducible), but α/e drop from k reads (+k writes of e)
to ⌈k/k_b⌉ — the sweep's (C, D_pad) traffic shrinks ~4/(1+3/k_b)× (≈2.9×
at k_b=8). VMEM per step: (k_b+2)·bc·D_pad·4 B ≈ 5 MiB at bc=128,
D_pad=1024, k_b=8.

HBM capacity: the pre-gathered Ψ tile is a (C, k_b, D_pad) array — k_b×
the residual grid — that must be materialized per block dispatch, so peak
footprint grows ~k_b× over the per-column path. The ``*_gather`` variants
remove the intermediate: the ψ slab is a fixed `(n_src, m)` VMEM resident
(`n_src·m·4 B`, ≪ the `(C, m, D_pad)` tile whenever n_src ≪ C·D_pad) and
each column is gathered per row through the id tile —
``psi_j[r, d] = tab[ids[r, d], j]`` — in interpret-safe form (a value-level
``jnp.take``; the compiled-TPU lowering via ``pltpu`` per-row DMA is the
ROADMAP follow-up). Padding id convention: table callers point padding
slots at row 0 (α=0 keeps them inert, matching the pre-gathered tiles);
flat-nnz callers (the tensor/field pseudo-ψ paths) append a zero sentinel
row and point padding at it, reproducing ``PaddedGroup.scatter_blk``'s
zeros exactly.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import vmem


def _col(x, j):
    """Column ``j`` of a (rows, n) value as (rows, 1): a one-hot lane select
    and a lane reduction, which Mosaic lowers where a lane slice does not."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.sum(jnp.where(lane == j, x, 0.0), axis=-1, keepdims=True)


_ROWS = 8  # rows per inner step: one sublane tile; every block_ctx is a multiple


def _by_rows(n_rows, fn):
    """Run ``fn(rows)`` over the block in ``_ROWS``-row slices. The rows of
    a block are independent, so walking them in sublane-tile slices keeps
    every value a few vregs wide — the unrolled column walk then compiles
    in seconds at any block_ctx instead of growing with it."""
    def step(r, carry):
        fn(pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS))
        return carry

    jax.lax.fori_loop(0, n_rows // _ROWS, step, 0)


def _sweep_rows(alpha0, l2, eta, k_b, psi_col, coupling, alpha_ref, e_ref,
                w_ref, r1_ref, w_out_ref, e_out_ref):
    """The k_b sequential Newton steps of one block, shared by every block
    sweep kernel. Per ``_ROWS``-row slice ``sl`` the column walk is
    statically unrolled (k_b ≤ 8): ``psi_col(sl, j)`` is column j of Ψ
    (rows, d_pad), and ``coupling(sl, j)`` is the (·, k_b) Gauss–Seidel R'
    patch row of column j (its own entry is the R''/2 diagonal)."""
    def rows(sl):
        alpha = alpha_ref[sl, :].astype(jnp.float32)   # (rows, d_pad)
        e = e_ref[sl, :].astype(jnp.float32)           # (rows, d_pad)
        w = w_ref[sl, :].astype(jnp.float32)           # (rows, k_b)
        r1 = r1_ref[sl, :].astype(jnp.float32)         # (rows, k_b)
        lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        for j in range(k_b):
            psi_j = psi_col(sl, j)                     # (rows, d_pad)
            c_row = coupling(sl, j)                    # (·, k_b)
            lp = jnp.sum(alpha * e * psi_j, axis=1, keepdims=True)       # L'/2
            lpp = jnp.sum(alpha * psi_j * psi_j, axis=1, keepdims=True)  # L''/2
            num = lp + alpha0 * _col(r1, j) + l2 * _col(w, j)
            den = lpp + alpha0 * _col(c_row, j) + l2
            delta = -eta * num / jnp.maximum(den, 1e-12)
            w = jnp.where(lane == j, w + delta, w)
            e = e + delta * psi_j
            r1 = r1 + delta * c_row
        w_out_ref[sl, :] = w
        e_out_ref[sl, :] = e

    _by_rows(alpha_ref.shape[0], rows)


def _sweep_kernel(alpha0, l2, eta, k_b, psi_ref, alpha_ref, e_ref, w_ref,
                  r1_ref, jblk_ref, w_out_ref, e_out_ref):
    jblk = jblk_ref[...].astype(jnp.float32)    # (k_b, k_b)
    _sweep_rows(alpha0, l2, eta, k_b,
                lambda sl, j: psi_ref[sl, j, :].astype(jnp.float32),
                lambda sl, j: jblk[j:j + 1, :],  # shared (1, k_b) Gram row
                alpha_ref, e_ref, w_ref, r1_ref, w_out_ref, e_out_ref)


def cd_block_sweep_pallas(
    psi_blk: jax.Array,  # (C, k_b, D_pad) pre-gathered ψ, one slice per column
    alpha: jax.Array,    # (C, D_pad), 0 on padding
    e: jax.Array,        # (C, D_pad) residual cache
    w_blk: jax.Array,    # (C, k_b) parameter slab W[:, f0:f0+k_b]
    r1_blk: jax.Array,   # (C, k_b) R'/2 slab (W·J)[:, f0:f0+k_b]
    j_blk: jax.Array,    # (k_b, k_b) diagonal Gram block J[f0:f0+k_b, f0:f0+k_b]
    *,
    alpha0: float,
    l2: float,
    eta: float = 1.0,
    block_ctx: int | None = None,
    interpret: bool = True,
):
    c, k_b, d_pad = psi_blk.shape
    if block_ctx is None:  # shared VMEM-budget fit (kernels/vmem.py)
        block_ctx = vmem.cd_sweep_block_ctx(d_pad, k_b, n_rows=c)
    c_pad = -(-c // block_ctx) * block_ctx
    if c_pad != c:
        rows = (0, c_pad - c)
        psi_blk = jnp.pad(psi_blk, (rows, (0, 0), (0, 0)))
        alpha = jnp.pad(alpha, (rows, (0, 0)))
        e = jnp.pad(e, (rows, (0, 0)))
        w_blk = jnp.pad(w_blk, (rows, (0, 0)))
        r1_blk = jnp.pad(r1_blk, (rows, (0, 0)))

    e = e.astype(jnp.float32)  # exact dtype match for the e→e_out alias

    grid = (c_pad // block_ctx,)
    w_new, e_new = pl.pallas_call(
        partial(_sweep_kernel, alpha0, l2, eta, k_b),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_ctx, k_b, d_pad), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, k_b), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, k_b), lambda i: (i, 0)),
            pl.BlockSpec((k_b, k_b), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_ctx, k_b), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c_pad, k_b), jnp.float32),
            jax.ShapeDtypeStruct((c_pad, d_pad), jnp.float32),
        ],
        input_output_aliases={2: 1},  # e updates in place — no fresh HBM copy
        interpret=interpret,
    )(psi_blk, alpha, e, w_blk, r1_blk, j_blk)
    return w_new[:c], e_new[:c]


def _sweep_rowpatch_kernel(alpha0, l2, eta, k_b, psi_ref, alpha_ref, e_ref,
                           w_ref, r1_ref, p_ref, w_out_ref, e_out_ref):
    """Block sweep with a per-row R' patch tensor (PARAFAC/Tucker modes)."""
    _sweep_rows(alpha0, l2, eta, k_b,
                lambda sl, j: psi_ref[sl, j, :].astype(jnp.float32),
                lambda sl, j: p_ref[sl, j, :].astype(jnp.float32),
                alpha_ref, e_ref, w_ref, r1_ref, w_out_ref, e_out_ref)


def cd_block_sweep_rowpatch_pallas(
    psi_blk: jax.Array,  # (C, k_b, D_pad) pseudo-ψ per block column
    alpha: jax.Array,    # (C, D_pad), 0 on padding
    e: jax.Array,        # (C, D_pad) residual cache
    w_blk: jax.Array,    # (C, k_b) parameter slab θ[:, f0:f0+k_b]
    r1_blk: jax.Array,   # (C, k_b) R'/2 slab
    p_blk: jax.Array,    # (C, k_b, k_b) per-row patch tensor; diag = R''/2
    *,
    alpha0: float,
    l2: float,
    eta: float = 1.0,
    block_ctx: int | None = None,
    interpret: bool = True,
):
    """General k-separable block sweep: like :func:`cd_block_sweep_pallas`
    but the regularizer coupling between block columns is ROW-dependent —
    P[r, j, f] is both the Gauss–Seidel R' patch coefficient and (on the
    diagonal) the per-row R''/2 of eqs. (14/19/38)."""
    c, k_b, d_pad = psi_blk.shape
    if block_ctx is None:  # shared VMEM-budget fit (kernels/vmem.py)
        block_ctx = vmem.cd_sweep_block_ctx(d_pad, k_b, n_rows=c)
    c_pad = -(-c // block_ctx) * block_ctx
    if c_pad != c:
        rows = (0, c_pad - c)
        psi_blk = jnp.pad(psi_blk, (rows, (0, 0), (0, 0)))
        alpha = jnp.pad(alpha, (rows, (0, 0)))
        e = jnp.pad(e, (rows, (0, 0)))
        w_blk = jnp.pad(w_blk, (rows, (0, 0)))
        r1_blk = jnp.pad(r1_blk, (rows, (0, 0)))
        p_blk = jnp.pad(p_blk, (rows, (0, 0), (0, 0)))

    e = e.astype(jnp.float32)  # exact dtype match for the e→e_out alias

    grid = (c_pad // block_ctx,)
    w_new, e_new = pl.pallas_call(
        partial(_sweep_rowpatch_kernel, alpha0, l2, eta, k_b),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_ctx, k_b, d_pad), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, k_b), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, k_b), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, k_b, k_b), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_ctx, k_b), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c_pad, k_b), jnp.float32),
            jax.ShapeDtypeStruct((c_pad, d_pad), jnp.float32),
        ],
        input_output_aliases={2: 1},
        interpret=interpret,
    )(psi_blk, alpha, e, w_blk, r1_blk, p_blk)
    return w_new[:c], e_new[:c]


def _slab_reduce_kernel(psi_ref, alpha_ref, e_ref, q_ref, p_ref):
    """Per-row moment slabs over a block of m pseudo-ψ columns."""
    psi = psi_ref[...].astype(jnp.float32)      # (bc, m, d_pad)
    alpha = alpha_ref[...].astype(jnp.float32)  # (bc, d_pad)
    e = e_ref[...].astype(jnp.float32)          # (bc, d_pad)
    q_ref[...] = jnp.einsum("bmd,bd->bm", psi, alpha * e)
    p_ref[...] = jnp.einsum("bmd,bnd->bmn", psi * alpha[:, None, :], psi)


def cd_slab_reduce_pallas(
    psi_blk: jax.Array,  # (C, m, D_pad) pseudo-ψ columns (incl. any special col)
    alpha: jax.Array,    # (C, D_pad), 0 on padding
    e: jax.Array,        # (C, D_pad) residual cache (read-only here)
    *,
    block_ctx: int | None = None,
    interpret: bool = True,
):
    """Field-model slab moments in ONE e/α stream (Algorithm 3 caches):

        Q[r, j]    = Σ_d α·e·ψ_j      (q / u caches per block column)
        P[r, i, j] = Σ_d α·ψ_i·ψ_j    (p2 on the diagonal, p1/p0 with a
                                       special column, cross-dim coupling
                                       for the within-block cache patches)

    The per-column path recomputes q (and u for FM) from HBM once per
    dimension; this fuses all m columns of a block into one pass."""
    c, m, d_pad = psi_blk.shape
    if block_ctx is None:  # shared VMEM-budget fit (kernels/vmem.py)
        block_ctx = vmem.cd_sweep_block_ctx(d_pad, m, n_rows=c)
    c_pad = -(-c // block_ctx) * block_ctx
    if c_pad != c:
        rows = (0, c_pad - c)
        psi_blk = jnp.pad(psi_blk, (rows, (0, 0), (0, 0)))
        alpha = jnp.pad(alpha, (rows, (0, 0)))
        e = jnp.pad(e, (rows, (0, 0)))

    grid = (c_pad // block_ctx,)
    q, p = pl.pallas_call(
        _slab_reduce_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_ctx, m, d_pad), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_ctx, m), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, m, m), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c_pad, m), jnp.float32),
            jax.ShapeDtypeStruct((c_pad, m, m), jnp.float32),
        ],
        interpret=interpret,
    )(psi_blk, alpha, e)
    return q[:c], p[:c]


def _resid_rows(m, psi_col, e_ref, dphi_ref, e_out_ref):
    def rows(sl):
        e = e_ref[sl, :].astype(jnp.float32)        # (rows, d_pad)
        dphi = dphi_ref[sl, :].astype(jnp.float32)  # (rows, m)
        for j in range(m):                          # static unroll
            e = e + _col(dphi, j) * psi_col(sl, j)
        e_out_ref[sl, :] = e

    _by_rows(e_ref.shape[0], rows)


def _resid_patch_kernel(m, psi_ref, e_ref, dphi_ref, e_out_ref):
    _resid_rows(m, lambda sl, j: psi_ref[sl, j, :].astype(jnp.float32),
                e_ref, dphi_ref, e_out_ref)


def cd_resid_patch_pallas(
    psi_blk: jax.Array,  # (C, m, D_pad)
    e: jax.Array,        # (C, D_pad) residual cache
    dphi_blk: jax.Array, # (C, m) per-row Δφ of each block column
    *,
    block_ctx: int | None = None,
    interpret: bool = True,
):
    """Rank-m residual patch e += Σ_j Δφ_j·ψ_j in one e stream (the closing
    half of a feature-model block; the per-column path pays one stream per
    dimension)."""
    c, m, d_pad = psi_blk.shape
    if block_ctx is None:  # shared VMEM-budget fit (kernels/vmem.py)
        block_ctx = vmem.cd_sweep_block_ctx(d_pad, m, n_rows=c)
    c_pad = -(-c // block_ctx) * block_ctx
    if c_pad != c:
        rows = (0, c_pad - c)
        psi_blk = jnp.pad(psi_blk, (rows, (0, 0), (0, 0)))
        e = jnp.pad(e, (rows, (0, 0)))
        dphi_blk = jnp.pad(dphi_blk, (rows, (0, 0)))

    e = e.astype(jnp.float32)  # exact dtype match for the e→e_out alias

    grid = (c_pad // block_ctx,)
    e_new = pl.pallas_call(
        partial(_resid_patch_kernel, m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_ctx, m, d_pad), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, m), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c_pad, d_pad), jnp.float32),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(psi_blk, e, dphi_blk)
    return e_new[:c]


# ======================================================================
# In-kernel Ψ gather variants: the ψ slab (n_src, m) stays VMEM-resident
# per dispatch and rows are gathered through an (C, D_pad) id tile — the
# (C, m, D_pad) pre-gathered intermediate never exists in HBM.
# ======================================================================
def _pad_gather_operands(psi_tab, ids, row_arrays, block_ctx):
    """Pad the ψ slab to a sublane multiple and the row-major operands to
    the kernel row tile. Slab padding rows are zeros appended beyond every
    valid id, so gathers never see them; row padding has α=0 ⇒ inert."""
    n_src = psi_tab.shape[0]
    n_src_pad = max(8, -(-n_src // 8) * 8)
    if n_src_pad != n_src:
        psi_tab = jnp.pad(psi_tab, ((0, n_src_pad - n_src), (0, 0)))
    c = ids.shape[0]
    c_pad = -(-c // block_ctx) * block_ctx
    if c_pad != c:
        rows = (0, c_pad - c)
        ids = jnp.pad(ids, (rows, (0, 0)))
        row_arrays = [jnp.pad(a, (rows,) + ((0, 0),) * (a.ndim - 1))
                      for a in row_arrays]
    return psi_tab, ids, row_arrays, c_pad


def _gather_col(tab_ref, ids_ref):
    """Column j of the gathered Ψ for a row slice: ``tab[ids, j]`` — the
    interpret-only value-level gather (module docstring)."""
    tab = tab_ref[...].astype(jnp.float32)      # (n_src_pad, m) ψ slab
    return lambda sl, j: jnp.take(tab[:, j], ids_ref[sl, :], mode="clip")


def _sweep_gather_kernel(alpha0, l2, eta, k_b, tab_ref, ids_ref, alpha_ref,
                         e_ref, w_ref, r1_ref, jblk_ref, w_out_ref, e_out_ref):
    jblk = jblk_ref[...].astype(jnp.float32)    # (k_b, k_b)
    _sweep_rows(alpha0, l2, eta, k_b, _gather_col(tab_ref, ids_ref),
                lambda sl, j: jblk[j:j + 1, :],
                alpha_ref, e_ref, w_ref, r1_ref, w_out_ref, e_out_ref)


def cd_block_sweep_gather_pallas(
    psi_tab: jax.Array,  # (n_src, k_b) ψ slab — columns [f0, f0+k_b) of ψ
    ids: jax.Array,      # (C, D_pad) int32 row ids into psi_tab; pad → 0/α=0
    alpha: jax.Array,    # (C, D_pad), 0 on padding
    e: jax.Array,        # (C, D_pad) residual cache
    w_blk: jax.Array,    # (C, k_b) parameter slab W[:, f0:f0+k_b]
    r1_blk: jax.Array,   # (C, k_b) R'/2 slab (W·J)[:, f0:f0+k_b]
    j_blk: jax.Array,    # (k_b, k_b) diagonal Gram block
    *,
    alpha0: float,
    l2: float,
    eta: float = 1.0,
    block_ctx: int | None = None,
    interpret: bool = True,
):
    """:func:`cd_block_sweep_pallas` with the Ψ gather folded in-kernel."""
    c, d_pad = ids.shape
    n_src, k_b = psi_tab.shape
    if block_ctx is None:  # shared VMEM-budget fit (kernels/vmem.py)
        block_ctx = vmem.cd_sweep_gather_block_ctx(d_pad, k_b, n_src, n_rows=c)
    psi_tab, ids, (alpha, e, w_blk, r1_blk), c_pad = _pad_gather_operands(
        psi_tab, ids, [alpha, e, w_blk, r1_blk], block_ctx
    )
    n_src_pad = psi_tab.shape[0]

    e = e.astype(jnp.float32)  # exact dtype match for the e→e_out alias

    grid = (c_pad // block_ctx,)
    w_new, e_new = pl.pallas_call(
        partial(_sweep_gather_kernel, alpha0, l2, eta, k_b),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_src_pad, k_b), lambda i: (0, 0)),  # resident slab
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, k_b), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, k_b), lambda i: (i, 0)),
            pl.BlockSpec((k_b, k_b), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_ctx, k_b), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c_pad, k_b), jnp.float32),
            jax.ShapeDtypeStruct((c_pad, d_pad), jnp.float32),
        ],
        input_output_aliases={3: 1},  # e updates in place
        interpret=interpret,
    )(psi_tab, ids, alpha, e, w_blk, r1_blk, j_blk)
    return w_new[:c], e_new[:c]


def _sweep_rowpatch_gather_kernel(alpha0, l2, eta, k_b, tab_ref, ids_ref,
                                  alpha_ref, e_ref, w_ref, r1_ref, p_ref,
                                  w_out_ref, e_out_ref):
    _sweep_rows(alpha0, l2, eta, k_b, _gather_col(tab_ref, ids_ref),
                lambda sl, j: p_ref[sl, j, :].astype(jnp.float32),
                alpha_ref, e_ref, w_ref, r1_ref, w_out_ref, e_out_ref)


def cd_block_sweep_rowpatch_gather_pallas(
    psi_tab: jax.Array,  # (n_src, k_b) pseudo-ψ slab (flat nnz values + a
    #                      zero sentinel row for padding slots)
    ids: jax.Array,      # (C, D_pad) int32 rows into psi_tab
    alpha: jax.Array,    # (C, D_pad), 0 on padding
    e: jax.Array,        # (C, D_pad) residual cache
    w_blk: jax.Array,    # (C, k_b)
    r1_blk: jax.Array,   # (C, k_b) R'/2 slab
    p_blk: jax.Array,    # (C, k_b, k_b) per-row patch tensor; diag = R''/2
    *,
    alpha0: float,
    l2: float,
    eta: float = 1.0,
    block_ctx: int | None = None,
    interpret: bool = True,
):
    """:func:`cd_block_sweep_rowpatch_pallas` with the pseudo-ψ scatter
    (``PaddedGroup.scatter_blk``) folded in-kernel as a flat-nnz gather."""
    c, d_pad = ids.shape
    n_src, k_b = psi_tab.shape
    if block_ctx is None:  # shared VMEM-budget fit (kernels/vmem.py)
        block_ctx = vmem.cd_sweep_gather_block_ctx(d_pad, k_b, n_src, n_rows=c)
    psi_tab, ids, (alpha, e, w_blk, r1_blk, p_blk), c_pad = _pad_gather_operands(
        psi_tab, ids, [alpha, e, w_blk, r1_blk, p_blk], block_ctx
    )
    n_src_pad = psi_tab.shape[0]

    e = e.astype(jnp.float32)  # exact dtype match for the e→e_out alias

    grid = (c_pad // block_ctx,)
    w_new, e_new = pl.pallas_call(
        partial(_sweep_rowpatch_gather_kernel, alpha0, l2, eta, k_b),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_src_pad, k_b), lambda i: (0, 0)),  # resident slab
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, k_b), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, k_b), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, k_b, k_b), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_ctx, k_b), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c_pad, k_b), jnp.float32),
            jax.ShapeDtypeStruct((c_pad, d_pad), jnp.float32),
        ],
        input_output_aliases={3: 1},
        interpret=interpret,
    )(psi_tab, ids, alpha, e, w_blk, r1_blk, p_blk)
    return w_new[:c], e_new[:c]


def _slab_reduce_gather_kernel(tab_ref, ids_ref, alpha_ref, e_ref, q_ref, p_ref):
    tab = tab_ref[...].astype(jnp.float32)      # (n_src_pad, m) ψ slab
    ids = ids_ref[...]                          # (bc, d_pad) int32
    alpha = alpha_ref[...].astype(jnp.float32)  # (bc, d_pad)
    e = e_ref[...].astype(jnp.float32)          # (bc, d_pad)
    psi_t = jnp.take(tab, ids, axis=0, mode="clip")  # tile (bc, d_pad, m)
    q_ref[...] = jnp.einsum("bdm,bd->bm", psi_t, alpha * e)
    p_ref[...] = jnp.einsum("bdm,bdn->bmn", psi_t * alpha[:, :, None], psi_t)


def cd_slab_reduce_gather_pallas(
    psi_tab: jax.Array,  # (n_src, m) pseudo-ψ slab (incl. any special col)
    ids: jax.Array,      # (C, D_pad) int32 rows into psi_tab
    alpha: jax.Array,    # (C, D_pad), 0 on padding
    e: jax.Array,        # (C, D_pad) residual cache (read-only here)
    *,
    block_ctx: int | None = None,
    interpret: bool = True,
):
    """:func:`cd_slab_reduce_pallas` with the Ψ gather folded in-kernel.
    The gathered (bc, d_pad, m) tile is a kernel-internal temporary — it
    never lands in HBM (α=0 padding keeps gathered padding slots inert)."""
    c, d_pad = ids.shape
    n_src, m = psi_tab.shape
    if block_ctx is None:  # shared VMEM-budget fit (kernels/vmem.py)
        block_ctx = vmem.cd_sweep_gather_block_ctx(
            d_pad, m, n_src, n_rows=c, hold_tile=True
        )
    psi_tab, ids, (alpha, e), c_pad = _pad_gather_operands(
        psi_tab, ids, [alpha, e], block_ctx
    )
    n_src_pad = psi_tab.shape[0]

    grid = (c_pad // block_ctx,)
    q, p = pl.pallas_call(
        _slab_reduce_gather_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_src_pad, m), lambda i: (0, 0)),  # resident slab
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_ctx, m), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, m, m), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c_pad, m), jnp.float32),
            jax.ShapeDtypeStruct((c_pad, m, m), jnp.float32),
        ],
        interpret=interpret,
    )(psi_tab, ids, alpha, e)
    return q[:c], p[:c]


def _resid_patch_gather_kernel(m, tab_ref, ids_ref, e_ref, dphi_ref, e_out_ref):
    _resid_rows(m, _gather_col(tab_ref, ids_ref), e_ref, dphi_ref, e_out_ref)


def cd_resid_patch_gather_pallas(
    psi_tab: jax.Array,  # (n_src, m) ψ slab
    ids: jax.Array,      # (C, D_pad) int32 rows into psi_tab
    e: jax.Array,        # (C, D_pad) residual cache
    dphi_blk: jax.Array, # (C, m) per-row Δφ of each block column
    *,
    block_ctx: int | None = None,
    interpret: bool = True,
):
    """:func:`cd_resid_patch_pallas` with the Ψ gather folded in-kernel
    (one column gathered at a time — no (bc, m, d_pad) temporary)."""
    c, d_pad = ids.shape
    n_src, m = psi_tab.shape
    if block_ctx is None:  # shared VMEM-budget fit (kernels/vmem.py)
        block_ctx = vmem.cd_sweep_gather_block_ctx(d_pad, m, n_src, n_rows=c)
    psi_tab, ids, (e, dphi_blk), c_pad = _pad_gather_operands(
        psi_tab, ids, [e, dphi_blk], block_ctx
    )
    n_src_pad = psi_tab.shape[0]

    e = e.astype(jnp.float32)  # exact dtype match for the e→e_out alias

    grid = (c_pad // block_ctx,)
    e_new = pl.pallas_call(
        partial(_resid_patch_gather_kernel, m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_src_pad, m), lambda i: (0, 0)),  # resident slab
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_ctx, m), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_ctx, d_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c_pad, d_pad), jnp.float32),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(psi_tab, ids, e, dphi_blk)
    return e_new[:c]
