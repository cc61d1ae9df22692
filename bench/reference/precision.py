"""Matrix products at a named precision, the same on every backend.

``highest``: float32 products (``Precision.HIGHEST``) — the plain reference.
``bf16x3``: each float32 operand split into a bfloat16 high part and a
bfloat16 remainder, and hi·hi + hi·lo + lo·hi summed in float32 — what a
TPU does for ``Precision.HIGH``, the step below ``HIGHEST``.
``bf16``: both operands rounded to bfloat16, float32 accumulation — one
MXU pass, the default for float32 on a TPU.

The two lower ones are written out rather than asked of the backend so that
a control computed on the CPU reads as it does on the chip. The rounding to
bfloat16 is ``lax.reduce_precision``, which the compiler keeps: a pair of
``astype`` casts may be folded away where excess precision is allowed (a
TPU does so), which would turn the split into one rounded pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("highest", "bf16x3", "bf16")


def _bf16(x):
    """x rounded to bfloat16, kept in float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def dot(a, b, mode: str = "highest"):
    """a @ b (2-D or matrix-vector) at ``mode``, float32 result."""
    if mode == "highest":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    # operands that bfloat16 holds exactly: a one-pass product is exact
    f = lambda x, y: jnp.dot(x, y, precision=jax.lax.Precision.DEFAULT,
                             preferred_element_type=jnp.float32)
    if mode == "bf16":
        return f(_bf16(a), _bf16(b))
    if mode == "bf16x3":
        a_hi, b_hi = _bf16(a), _bf16(b)
        a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
        return f(a_hi, b_hi) + (f(a_hi, b_lo) + f(a_lo, b_hi))
    raise ValueError(f"unknown precision mode {mode!r}; one of {MODES}")
