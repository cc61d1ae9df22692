"""Pallas gram kernel: J = XᵀX (optionally Xᵀ·diag(w)·X) for tall-skinny X.

Grid: 1-D over row blocks. Each step DMAs a (block_rows, k_pad) tile
HBM→VMEM, runs one (k_pad × block_rows)·(block_rows × k_pad) MXU matmul, and
accumulates into the persistent (k_pad, k_pad) output block (same output
tile revisited every step ⇒ VMEM-resident accumulator).

The weighted variant carries a (block_rows, 1) per-row weight tile and
scales one matmul operand in VMEM before the contraction — the weighted
Gram J_w = Σ_r w_r·x_r x_rᵀ used by confidence-weighted fold-in and the
weighted implicit regularizer.

VMEM budget per step: block_rows·k_pad·4 B (input tile, fp32)
                    + block_rows·128·4 B  (weight tile, weighted path only)
                    + k_pad²·4 B          (accumulator).
Defaults (block_rows=1024, k_pad≤512): ≤ 2 MiB + 1 MiB ≪ 16 MiB VMEM.
MXU alignment: k padded to a lane multiple (128); rows padded to the block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.gram import PRECISION


def _gram_kernel(x_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += jax.lax.dot_general(
        x, x, (((0,), (0,)), ((), ())), precision=PRECISION,
        preferred_element_type=jnp.float32,
    )


def _gram_weighted_kernel(x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    wx = x * w_ref[:, 0:1].astype(jnp.float32)  # (block_rows, 1) broadcast
    o_ref[...] += jax.lax.dot_general(
        x, wx, (((0,), (0,)), ((), ())), precision=PRECISION,
        preferred_element_type=jnp.float32,
    )


def gram_pallas(
    x: jax.Array,
    w: jax.Array | None = None,
    *,
    block_rows: int = 1024,
    interpret: bool = True,
) -> jax.Array:
    """J = xᵀx (or xᵀ·diag(w)·x) with fp32 accumulation; x: (rows, k) any
    float dtype, w: optional (rows,) per-row weights (row padding gets w=0,
    which zeroes padded contributions exactly)."""
    rows, k = x.shape
    k_pad = max(128, -(-k // 128) * 128)
    rows_pad = -(-rows // block_rows) * block_rows
    if (rows_pad, k_pad) != (rows, k):
        x = jnp.pad(x, ((0, rows_pad - rows), (0, k_pad - k)))

    if w is None:
        out = pl.pallas_call(
            _gram_kernel,
            grid=(rows_pad // block_rows,),
            in_specs=[pl.BlockSpec((block_rows, k_pad), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((k_pad, k_pad), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((k_pad, k_pad), jnp.float32),
            interpret=interpret,
        )(x)
        return out[:k, :k]

    # weight column lane-padded to 128 (lane alignment; kernel reads col 0)
    w2 = jnp.pad(w.reshape(rows, 1), ((0, rows_pad - rows), (0, 127)))
    out = pl.pallas_call(
        _gram_weighted_kernel,
        grid=(rows_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, k_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((k_pad, k_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k_pad, k_pad), jnp.float32),
        interpret=interpret,
    )(x, w2)
    return out[:k, :k]
