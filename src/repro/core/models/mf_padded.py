"""Kernel-fused iCD-MF over the padded observation layout.

Mathematically identical to ``repro.core.models.mf`` (same Newton steps, same
sweep order) but laid out for the Pallas kernels:

  * observations padded per row to the max degree (α=0 on padding) so the
    explicit reductions become dense (bc, D_pad) VPU tiles — no segment ops;
  * J via the ``gram`` MXU kernel;
  * the dimension sweep dispatched through ``sweeps.sweep_columns``: blocks
    of ``hp.block_k`` columns run in the fused ``cd_sweep`` kernel (e/α
    VMEM-resident across the block, ⌈k/k_b⌉ HBM round-trips per sweep
    instead of k); ``hp.block_k=1`` falls back to the per-column
    ``cd_update`` kernel.

CAPACITY: the fused path defaults to the IN-KERNEL GATHER kernels
(``hp.psi_dispatch='gather'``): each block dispatch ships the `(n_items,
k_b)` ψ slab plus the `(C, D_pad)` item-id grid and the kernel gathers Ψ
rows itself, so the `(C, k_b, D_pad)` pre-gathered tile (~k_b× the
residual grid, the PR 1–2 capacity trade) never exists in HBM. The
pre-gathered path remains as ``hp.psi_dispatch='pregather'`` and as the
automatic fallback when the ψ slab alone busts the VMEM budget
(``kernels/vmem.resolve_cd_sweep_dispatch``).

This is the "beyond-paper optimized" §Perf variant; the equivalence test
(tests/test_mf_padded.py) pins it to the reference epoch. Degree-skewed data
should be degree-bucketed before padding (see EXPERIMENTS.md §Perf for the
measured padding overhead; the bucketing hook is ``degree_cap``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sweeps
from repro.core.gram import PRECISION as gram_precision
from repro.core.models.mf import MFHyperParams, MFParams
from repro.kernels import vmem
from repro.kernels.cd_sweep.ops import cd_block_sweep, cd_block_sweep_gather
from repro.kernels.cd_update.ops import cd_column_update
from repro.kernels.gram.ops import gram as gram_kernel
from repro.sparse.interactions import Interactions


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PaddedInteractions:
    """Dual padded layout of the rescaled observed set S̄."""

    # context-major: (n_ctx, d_ctx)
    item_ids: jax.Array
    alpha_c: jax.Array   # 0 on padding
    y_c: jax.Array
    # item-major: (n_items, d_item)
    ctx_ids: jax.Array
    alpha_i: jax.Array
    y_i: jax.Array
    # flat(ctx-major nnz) <-> padded coordinates, for residual transfer
    c_rows: jax.Array    # (nnz,) row in ctx-major padded grid
    c_cols: jax.Array    # (nnz,) slot in ctx-major padded grid
    i_rows: jax.Array    # (nnz,) row in item-major padded grid (ctx-major order)
    i_cols: jax.Array
    n_ctx: int = dataclasses.field(metadata=dict(static=True))
    n_items: int = dataclasses.field(metadata=dict(static=True))


def pad_interactions(data: Interactions, lane: int = 128) -> PaddedInteractions:
    """Host-side: build the dual padded layout (degrees padded to the max,
    slot dim rounded up to the TPU lane width)."""
    ctx = np.asarray(data.ctx)
    item = np.asarray(data.item)
    alpha = np.asarray(data.alpha)
    y = np.asarray(data.y)
    nnz = len(ctx)

    def build(rows, n_rows):
        deg = np.bincount(rows, minlength=n_rows)
        d_pad = max(lane, int(-(-max(1, deg.max()) // lane) * lane))
        slot = np.zeros(nnz, np.int64)
        counter = np.zeros(n_rows, np.int64)
        for j, r in enumerate(rows):  # rows are sorted; cheap slot assignment
            slot[j] = counter[r]
            counter[r] += 1
        return d_pad, slot

    d_c, slot_c = build(ctx, data.n_ctx)
    order_i = np.lexsort((ctx, item))
    d_i, slot_i_sorted = build(item[order_i], data.n_items)
    slot_i = np.empty(nnz, np.int64)
    slot_i[order_i] = slot_i_sorted

    def scatter(shape, rows, cols, vals, dtype, fill=0):
        out = np.full(shape, fill, dtype)
        out[rows, cols] = vals
        return out

    item_ids = scatter((data.n_ctx, d_c), ctx, slot_c, item, np.int32)
    alpha_c = scatter((data.n_ctx, d_c), ctx, slot_c, alpha, np.float32)
    y_c = scatter((data.n_ctx, d_c), ctx, slot_c, y, np.float32)
    ctx_ids = scatter((data.n_items, d_i), item, slot_i, ctx, np.int32)
    alpha_i = scatter((data.n_items, d_i), item, slot_i, alpha, np.float32)
    y_i = scatter((data.n_items, d_i), item, slot_i, y, np.float32)

    return PaddedInteractions(
        item_ids=jnp.asarray(item_ids), alpha_c=jnp.asarray(alpha_c),
        y_c=jnp.asarray(y_c),
        ctx_ids=jnp.asarray(ctx_ids), alpha_i=jnp.asarray(alpha_i),
        y_i=jnp.asarray(y_i),
        c_rows=jnp.asarray(ctx, dtype=jnp.int32),
        c_cols=jnp.asarray(slot_c, dtype=jnp.int32),
        i_rows=jnp.asarray(item, dtype=jnp.int32),
        i_cols=jnp.asarray(slot_i, dtype=jnp.int32),
        n_ctx=data.n_ctx, n_items=data.n_items,
    )


def scatter_ctx_major(pdata: PaddedInteractions, e_flat: jax.Array) -> jax.Array:
    """Flat per-nnz vector (ctx-major order) → ctx-major padded grid."""
    return jnp.zeros_like(pdata.alpha_c).at[pdata.c_rows, pdata.c_cols].set(e_flat)


def transfer_ctx_to_item(pdata: PaddedInteractions, e_pad: jax.Array) -> jax.Array:
    """Residual grid ctx-major → item-major through the flat nnz order."""
    e_flat = e_pad[pdata.c_rows, pdata.c_cols]
    return jnp.zeros_like(pdata.alpha_i).at[pdata.i_rows, pdata.i_cols].set(e_flat)


def transfer_item_to_ctx(pdata: PaddedInteractions, e_pad_i: jax.Array) -> jax.Array:
    """Inverse of :func:`transfer_ctx_to_item`."""
    e_flat = e_pad_i[pdata.i_rows, pdata.i_cols]
    return jnp.zeros_like(pdata.alpha_c).at[pdata.c_rows, pdata.c_cols].set(e_flat)


def _padded_side_sweep(side, other, other_j, ids_pad, alpha_pad, e_pad, hp):
    k = side.shape[1]
    k_b = sweeps.resolve_block_k(hp.block_k, k)
    n = side.shape[0]
    use_block = k_b > 1 and not hp.unroll  # unroll = explicit per-column ask

    # Ψ routing + row tile of the cd_sweep dispatches (shared VMEM budget):
    # in-kernel gather by default, pre-gathered tile when pinned or when the
    # ψ slab alone does not fit VMEM.
    use_gather, block_ctx = vmem.resolve_cd_sweep_dispatch(
        ids_pad.shape[1], k_b, other.shape[0], n_rows=n,
        prefer_gather=sweeps.resolve_psi_dispatch(hp.psi_dispatch),
    )

    if use_block:
        # Pad rows to the kernel tile ONCE per sweep — otherwise every block
        # dispatch would pad/slice the full (C, D_pad) grids itself,
        # re-introducing the per-dispatch HBM copies the fused kernel
        # removes (and breaking the e→e_out alias, which would then point
        # at a padded temp). Padding rows have α=0 ⇒ Δ=0, so they are inert.
        n_pad = -(-n // block_ctx) * block_ctx
        if n_pad != n:
            rows = ((0, n_pad - n), (0, 0))
            ids_pad = jnp.pad(ids_pad, rows)
            alpha_pad = jnp.pad(alpha_pad, rows)
            e_pad = jnp.pad(e_pad, rows)
            side = jnp.pad(side, rows)

    def body(f, carry):
        side_m, e_pad = carry
        psi_pad = jnp.take(sweeps.take_col(other, f), ids_pad)   # (n, d_pad)
        r1 = jnp.dot(side_m, sweeps.take_col(other_j, f),
                     precision=gram_precision)
        w_new, e_pad = cd_column_update(
            psi_pad, alpha_pad, e_pad, sweeps.take_col(side_m, f), r1,
            other_j[f, f], alpha0=hp.alpha0, l2=hp.l2, eta=hp.eta,
        )
        return sweeps.put_col(side_m, f, w_new), e_pad

    def block_body(f0, kb, carry):
        side_m, e_pad = carry
        r1_blk = jnp.dot(side_m, other_j[:, f0:f0 + kb],          # R'/2 slab
                         precision=gram_precision)
        if use_gather:
            # ψ slab (n_items, kb) + id grid — the kernel gathers Ψ rows
            w_new, e_pad = cd_block_sweep_gather(
                other[:, f0:f0 + kb], ids_pad, alpha_pad, e_pad,
                side_m[:, f0:f0 + kb], r1_blk,
                other_j[f0:f0 + kb, f0:f0 + kb],
                alpha0=hp.alpha0, l2=hp.l2, eta=hp.eta,
                block_ctx=block_ctx,
            )
        else:
            # pre-gathered Ψ tile (n, kb, d_pad) — the capacity fallback
            psi_blk = jnp.moveaxis(
                jnp.take(other[:, f0:f0 + kb], ids_pad, axis=0), -1, 1
            )
            w_new, e_pad = cd_block_sweep(
                psi_blk, alpha_pad, e_pad, side_m[:, f0:f0 + kb], r1_blk,
                other_j[f0:f0 + kb, f0:f0 + kb],
                alpha0=hp.alpha0, l2=hp.l2, eta=hp.eta,
                block_ctx=block_ctx,
            )
        return side_m.at[:, f0:f0 + kb].set(w_new), e_pad

    side, e_pad = sweeps.sweep_columns(
        k, body, (side, e_pad), unroll=hp.unroll,
        block=k_b, block_body=block_body if use_block else None,
    )
    return side[:n], e_pad[:n]


def reweight_padded(pdata: PaddedInteractions, weights: jax.Array) -> PaddedInteractions:
    """Fold per-interaction weights (flat nnz, ctx-major order) into both
    padded α grids: α_eff = α·w on real slots, padding stays α=0 (the w grid
    defaults to 1 where no observation lands)."""
    w_c = jnp.ones_like(pdata.alpha_c).at[pdata.c_rows, pdata.c_cols].set(weights)
    w_i = jnp.ones_like(pdata.alpha_i).at[pdata.i_rows, pdata.i_cols].set(weights)
    return dataclasses.replace(
        pdata, alpha_c=pdata.alpha_c * w_c, alpha_i=pdata.alpha_i * w_i
    )


@partial(jax.jit, static_argnames=("hp",), donate_argnums=(2,))
def epoch(
    params: MFParams, pdata: PaddedInteractions, e_pad: jax.Array,
    hp: MFHyperParams, weights: jax.Array | None = None,
) -> Tuple[MFParams, jax.Array]:
    """Kernel-fused iCD epoch; carries the ctx-major padded residual grid.

    ``e_pad`` is donated — it is the largest tensor carried ACROSS epochs
    and is replaced every call, so on donation-capable backends the
    caller's buffer is reused instead of holding a second (C, D_pad) fp32
    grid across the call. (Within an epoch the fused path's Ψ tile is
    bigger — see the module docstring's capacity note.) Callers must
    rebind (``params, e_pad = epoch(...)``), which every sweep/fit loop
    already does.

    ``weights`` (optional, flat nnz ctx-major) folds per-interaction
    confidence into both α grids exactly (α is purely multiplicative in the
    explicit loss parts); ``None`` traces the identical unweighted program."""
    if weights is not None:
        pdata = reweight_padded(pdata, weights)
    w, h = params

    j_i = gram_kernel(h)
    w, e_pad = _padded_side_sweep(w, h, j_i, pdata.item_ids, pdata.alpha_c, e_pad, hp)

    e_pad_i = transfer_ctx_to_item(pdata, e_pad)

    j_c = gram_kernel(w)
    h, e_pad_i = _padded_side_sweep(h, w, j_c, pdata.ctx_ids, pdata.alpha_i, e_pad_i, hp)

    e_pad = transfer_item_to_ctx(pdata, e_pad_i)
    return MFParams(w, h), e_pad


def residuals(params: MFParams, pdata: PaddedInteractions) -> jax.Array:
    """ŷ−ȳ on the ctx-major padded grid (garbage on padding, α=0 kills it)."""
    scores = jnp.sum(
        params.w[:, None, :] * jnp.take(params.h, pdata.item_ids, axis=0), axis=-1
    )
    return scores - pdata.y_c


def fit(params, pdata, hp, n_epochs, weights=None):
    e_pad = residuals(params, pdata)
    for _ in range(n_epochs):
        params, e_pad = epoch(params, pdata, e_pad, hp, weights)
    return params
