"""Shared machinery for iCD column sweeps.

The TPU adaptation of Algorithm 1/2/3 (see DESIGN.md §3): for a fixed
embedding dimension ``f*`` the Newton updates of all coordinates on one side
are independent, so each inner loop of the paper becomes ONE vectorized
column update:

    gather → segment-reduce (explicit part from the residual cache)
    k-vector contraction with the opposite Gram (implicit part, Lemma 3)
    fused Newton step  θ ← θ − η·(L'/2 + α₀R'/2 + λθ)/(L''/2 + α₀R''/2 + λ)
    rank-1 residual patch

All helpers are jit-friendly; the f* loop goes through
:func:`sweep_columns`, which runs either the per-column path (a
``lax.fori_loop`` / unrolled host loop with the parameter matrix as carry)
or, when the model provides one, a fused multi-column block body backed by
the ``kernels/cd_sweep`` Pallas kernel that keeps the residual cache
VMEM-resident across the columns of a block.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np


class NewtonParts(NamedTuple):
    """Halved derivative pieces; the common factor 2 of eqs. (2,3,13,14)
    cancels in the Newton ratio so we carry L'/2 etc. throughout."""

    grad: jax.Array  # L'/2 + α₀·R'/2   (no L2 term yet)
    hess: jax.Array  # L''/2 + α₀·R''/2 (no L2 term yet)


def newton_delta(
    parts: NewtonParts, theta: jax.Array, l2: float, eta: float
) -> jax.Array:
    """η-damped Newton step on the 1-D quadratic (exact at η=1 for
    multilinear models, paper §3.2). Returns Δθ.

    The denominator is clamped like the Pallas kernels do: with l2=0 an
    empty context has L''=R''=0 and the unguarded ratio NaNs."""
    num = parts.grad + l2 * theta
    den = parts.hess + l2
    return -eta * num / jnp.maximum(den, 1e-12)


@dataclasses.dataclass(frozen=True)
class SweepSchedule:
    """Subspace schedule for :func:`sweep_columns` (iALS++-style).

    A fused ``k_b``-block update is already a subspace step, so a "sweep" no
    longer has to be one full pass over all ``n_dims`` columns: a schedule
    names WHICH blocks run this sweep, in WHAT order, and HOW OFTEN.

    ``kind``
      * ``'full'``      — every block, ascending ``f0`` order. With default
        ``block``/``repeats`` this reproduces the unscheduled sweep exactly
        (bit-for-bit; see ``tests/test_schedule.py``).
      * ``'rotating'``  — every block, order rotated by ``sweep_index`` so
        successive sweeps start from a different subspace.
      * ``'randomized'``— every block, order drawn from a deterministic
        permutation seeded by ``(seed, sweep_index)``.

    ``block``            columns per scheduled block (the subspace size
                         ``k_b``); 0 = inherit the caller's ``block`` arg.
    ``blocks_per_sweep`` truncate the ordered block list to this many blocks
                         per sweep (0 = all): the partial-pass mode that
                         makes updates-to-quality scheduling possible —
                         ``rotating`` + ``blocks_per_sweep=1`` visits one
                         ``k_b`` subspace per sweep, cycling through all.
    ``repeats``          per-block repeat counts: an int applied to every
                         block, or a tuple indexed by the block's ordinal
                         ``f0 // block`` (cycled when shorter).
    ``seed``             base seed for ``'randomized'``.

    Frozen + hashable so it can ride as a jit static argument; all schedule
    resolution happens on the host at trace time (static ``(f0, size)``).
    """

    kind: str = "full"
    block: int = 0
    blocks_per_sweep: int = 0
    repeats: Union[int, Tuple[int, ...]] = 1
    seed: int = 0

    def __post_init__(self):
        if self.kind not in ("full", "rotating", "randomized"):
            raise ValueError(
                "SweepSchedule.kind must be 'full' | 'rotating' | "
                f"'randomized', got {self.kind!r}"
            )
        reps = self.repeats if isinstance(self.repeats, tuple) else (self.repeats,)
        if not reps or any(int(r) < 1 for r in reps):
            raise ValueError(f"repeats must be >= 1, got {self.repeats!r}")

    def _repeat(self, ordinal: int) -> int:
        if isinstance(self.repeats, tuple):
            return int(self.repeats[ordinal % len(self.repeats)])
        return int(self.repeats)

    def blocks(
        self, n_dims: int, sweep_index: int = 0, block: int = 0
    ) -> Tuple[Tuple[int, int], ...]:
        """Resolve to a static ``((f0, size), ...)`` sequence for one sweep."""
        b = self.block if self.block >= 1 else (block if block >= 1 else n_dims)
        b = min(b, n_dims)
        base = [(f0, min(b, n_dims - f0)) for f0 in range(0, n_dims, b)]
        if self.kind == "rotating" and base:
            r = sweep_index % len(base)
            order = base[r:] + base[:r]
        elif self.kind == "randomized":
            rng = np.random.default_rng((self.seed, sweep_index))
            order = [base[i] for i in rng.permutation(len(base))]
        else:
            order = base
        if self.blocks_per_sweep >= 1:
            order = order[: self.blocks_per_sweep]
        out = []
        for f0, size in order:
            out.extend([(f0, size)] * self._repeat(f0 // b))
        return tuple(out)

    def n_column_updates(
        self, n_dims: int, sweep_index: int = 0, block: int = 0
    ) -> int:
        """Column-updates this sweep performs (the updates-to-quality unit)."""
        return sum(size for _, size in self.blocks(n_dims, sweep_index, block))


FULL_SCHEDULE = SweepSchedule()


def sweep_columns(
    n_dims: int,
    body: Callable,
    carry,
    *,
    unroll: bool = False,
    block: int = 1,
    block_body: Optional[Callable] = None,
    schedule: Optional[SweepSchedule] = None,
    sweep_index: int = 0,
):
    """Single entry point for the f*-sweep of Algorithms 2/3.

    ``body(f, carry) -> carry`` is the per-column Newton update (any model).
    ``block_body(f0, size, carry) -> carry`` is an optional fused update
    covering columns ``[f0, f0+size)`` in one dispatch (the
    ``kernels/cd_sweep`` path). Dispatch rule: when a block body is
    supplied (and ``block >= 1``), blocks of ``block`` columns run fused
    with a shorter fused tail for non-divisible ``n_dims`` — ``block=1``
    degenerates to a per-column loop THROUGH the block path (static column
    indices; how the padded models express their per-column baseline).
    Otherwise the per-column ``body`` runs (``lax.fori_loop``, or a host
    loop when ``unroll`` — exact HLO costs / cross-column XLA fusion).
    ``unroll=True`` is an explicit request for the per-column unrolled
    program, so it takes precedence over the fused path.

    Block-body contract (slab state): ``f0``/``size`` are STATIC, so the
    body may slice parameter slabs ``θ[:, f0:f0+size]`` and build
    model-specific R'/R'' slab state for the kernels —

      * MF-style (one-hot φ-gradients): an R'/2 slab ``(n, size)`` plus the
        SHARED Gram block ``J[f0:f0+size, f0:f0+size]`` (``cd_block_sweep``);
      * tensor modes (PARAFAC/Tucker): an R'/2 slab plus a PER-ROW patch
        tensor ``P (n, size, size)`` whose diagonal is R''/2 — row-dependent
        curvature, eqs. 37–41 (``cd_block_sweep_rowpatch``);
      * feature models (MFSI/FM): per-field slab moments Q/P from
        ``cd_slab_reduce``, field-level Newton steps in XLA, then one
        rank-``size`` ``cd_resid_patch``.

    Everything the NEXT block needs (θ, e grid, Φ caches) must ride in
    ``carry``; intra-block coupling is the body's own responsibility (the
    kernels' Gauss–Seidel patches / the Q-slab cross-dim patches).

    ``n_dims`` and ``block`` are static, so the fused loop is a host loop of
    ⌈n_dims/block⌉ dispatches with static slab sizes.

    ``schedule`` (a :class:`SweepSchedule`) generalizes the sweep from "one
    full ascending pass" to an arbitrary static sequence of ``(f0, size)``
    subspace blocks for this ``sweep_index``: the fused ``block_body`` runs
    one dispatch per scheduled block, and the per-column ``body`` runs a
    host loop over the scheduled columns (static indices). ``schedule=None``
    is the unscheduled fast path, bit-identical to the pre-schedule code.
    """
    if schedule is not None:
        plan = schedule.blocks(n_dims, sweep_index, block)
        # a plan that is one plain in-order full pass IS the unscheduled
        # sweep — fall through to the canonical paths below so a full
        # schedule stays bit-identical to schedule=None (same compiled
        # program, not just the same math)
        trivial = [f for f0, size in plan for f in range(f0, f0 + size)]
        if trivial == list(range(n_dims)) and (
            block_body is None or plan == SweepSchedule(block=block).blocks(n_dims)
        ):
            schedule = None
    if schedule is not None:
        if block_body is not None and not unroll:
            for f0, size in plan:
                carry = block_body(f0, size, carry)
            return carry
        for f0, size in plan:
            for f in range(f0, f0 + size):
                carry = body(f, carry)
        return carry
    if block_body is not None and block >= 1 and not unroll:
        f0 = 0
        while f0 < n_dims:
            size = min(block, n_dims - f0)
            carry = block_body(f0, size, carry)
            f0 += size
        return carry
    if unroll:
        for f in range(n_dims):
            carry = body(f, carry)
        return carry
    return jax.lax.fori_loop(0, n_dims, body, carry)


def resolve_block_k(block_k: int, k: int) -> int:
    """Shared ``hp.block_k`` policy for every padded/fused epoch:
    0 = auto (min(k, 8)), otherwise clamp to [1, k]."""
    return min(k, 8) if block_k == 0 else max(1, min(block_k, k))


def resolve_psi_dispatch(psi_dispatch: str) -> bool:
    """Shared ``hp.psi_dispatch`` policy: returns ``prefer_gather`` for
    ``kernels.vmem.resolve_cd_sweep_dispatch``. Anything outside the two
    known routings raises — a typo silently selecting the k_b×-peak-HBM
    pre-gathered path would defeat the dispatch's whole point."""
    if psi_dispatch not in ("gather", "pregather"):
        raise ValueError(
            f"psi_dispatch must be 'gather' or 'pregather', got {psi_dispatch!r}"
        )
    return psi_dispatch == "gather"


def take_col(m: jax.Array, f) -> jax.Array:
    """m[:, f] with a traced index."""
    return jax.lax.dynamic_slice_in_dim(m, f, 1, axis=1)[:, 0]


def put_col(m: jax.Array, f, col: jax.Array) -> jax.Array:
    """m with column f replaced (traced index)."""
    return jax.lax.dynamic_update_slice_in_dim(m, col[:, None], f, axis=1)


_RESID_BATCH = 1 << 16


def residuals_from_factors(
    phi: jax.Array, psi: jax.Array, ctx: jax.Array, item: jax.Array, y: jax.Array
) -> jax.Array:
    """e = ŷ − ȳ on observed pairs: Σ_f φ_f(c)ψ_f(i) − ȳ, per nnz.

    Evaluated in batches of ``_RESID_BATCH`` pairs, so the gathered rows
    never exceed ``2·_RESID_BATCH·k`` floats: at 20M pairs and k=128 the
    whole gather would be 20 GB, more than one chip holds."""
    def score(pair):
        c, i = pair
        return jnp.sum(jnp.take(phi, c, axis=0) * jnp.take(psi, i, axis=0))

    scores = jax.lax.map(score, (ctx, item), batch_size=_RESID_BATCH)
    return scores - y


def to_item_major(e_ctx_major: jax.Array, t_perm: jax.Array) -> jax.Array:
    """Permute a per-nnz vector from context-major to item-major order
    (named scope ``icd.permute``, as is the inverse)."""
    with jax.named_scope("icd.permute"):
        return jnp.take(e_ctx_major, t_perm)


def to_ctx_major(e_item_major: jax.Array, t_perm: jax.Array) -> jax.Array:
    """Inverse permutation of :func:`to_item_major`."""
    with jax.named_scope("icd.permute"):
        return jnp.zeros_like(e_item_major).at[t_perm].set(e_item_major)
