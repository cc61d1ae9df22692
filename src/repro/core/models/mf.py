"""iCD for Matrix Factorization (paper §5.1, Algorithm 2).

Model: ŷ(c,i) = ⟨w_c, h_i⟩,  Θ = {W ∈ R^{C×k}, H ∈ R^{I×k}}.
Trivially k-separable with φ_f(c) = w_{c,f}, ψ_f(i) = h_{i,f} (eq. 16);
gradients are one-hot (eq. 17), so the regularizer derivatives collapse to

    R'(w_{c*,f*}) = 2 Σ_f J_I(f,f*)·w_{c*,f}       (eq. 18)
    R''(w_{c*,f*}) = 2 J_I(f*,f*)                  (eq. 19)

Per-epoch complexity O((|C|+|I|)k² + |S|k) — the paper's headline result.

TPU adaptation (DESIGN.md §3): the c*-loop of Algorithm 2 is vectorized into
one column update; the f*-loop and the W↔H alternation stay sequential
(that ordering is what CD convergence relies on). The fixed point is
identical to the scalar algorithm because coordinates within a column touch
disjoint residuals.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import sweeps
from repro.core.gram import PRECISION as gram_precision
from repro.core.gram import gram
from repro.core.implicit import implicit_objective
from repro.sparse.interactions import Interactions
from repro.sparse.segment import segment_broadcast_sorted, segment_sum


class MFParams(NamedTuple):
    w: jax.Array  # (n_ctx, k)   context embeddings
    h: jax.Array  # (n_items, k) item embeddings


@dataclasses.dataclass(frozen=True)
class MFHyperParams:
    k: int
    alpha0: float = 1.0
    l2: float = 0.1
    eta: float = 1.0  # full Newton step — exact for bilinear models
    implementation: str = "xla"  # 'xla' | 'pallas' gram/cd kernels
    unroll: bool = False  # unroll the k-column loop (exact HLO costs; also
    #                       lets XLA pipeline/fuse across columns on TPU)
    block_k: int = 0  # columns per fused cd_sweep dispatch on the padded
    #                   layout: 0 = auto (min(k, 8)), 1 = per-column kernel
    psi_dispatch: str = "gather"  # fused-path Ψ routing: 'gather' = in-kernel
    #                   gather from the ψ table (no (C, k_b, D_pad) HBM
    #                   intermediate; falls back automatically when the ψ
    #                   slab busts the VMEM budget), 'pregather' = host-side
    #                   pre-gathered Ψ tile (the PR 1–2 path)


def init(key: jax.Array, n_ctx: int, n_items: int, k: int, sigma: float = 0.1) -> MFParams:
    kw, kh = jax.random.split(key)
    return MFParams(
        w=sigma * jax.random.normal(kw, (n_ctx, k), dtype=jnp.float32),
        h=sigma * jax.random.normal(kh, (n_items, k), dtype=jnp.float32),
    )


def phi(params: MFParams) -> jax.Array:
    return params.w


def psi(params: MFParams) -> jax.Array:
    return params.h


def export_psi(params: MFParams) -> jax.Array:
    """ψ table for the serving mesh (serve/mesh.py): (n_items, k)."""
    return params.h


def build_phi(params: MFParams, ctx: jax.Array) -> jax.Array:
    """φ rows for a batch of context ids: (B, k); ⟨φ, ψ_i⟩ = ŷ(c, i)."""
    return jnp.take(params.w, ctx, axis=0)


def predict(params: MFParams, ctx: jax.Array, item: jax.Array) -> jax.Array:
    return jnp.sum(
        jnp.take(params.w, ctx, axis=0) * jnp.take(params.h, item, axis=0), axis=-1
    )


def scores_all(params: MFParams) -> jax.Array:
    """Full |C|×|I| score matrix — only for tests / small-scale eval."""
    return params.w @ params.h.T


def _side_sweep(
    side: jax.Array,        # (n, k) parameters being updated
    other_j: jax.Array,     # (k, k) Gram of the fixed side  (J_I for ctx sweep)
    other_cols_nnz,         # callable f -> (nnz,) ψ_{f}(item of nnz)
    rows_nnz: jax.Array,    # (nnz,) row id (this side) per observation, sorted
    indptr: jax.Array,      # (n_rows+1,) run offsets of rows_nnz
    alpha: jax.Array,       # (nnz,)
    e: jax.Array,           # (nnz,) residual cache, this side's sort order
    hp: MFHyperParams,
    schedule: Optional[sweeps.SweepSchedule] = None,
    sweep_index: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """One dimension sweep over one side; returns (new_side, new_e).

    With a ``schedule`` the sweep covers only the scheduled subspace blocks
    for this ``sweep_index`` (iALS++-style); ``None`` is a full pass.

    Each phase of a column update runs under a named scope — ``icd.gather``
    (the other side's column through the pair layout), ``icd.segsum`` (the
    L'/L'' segment sums), ``icd.implicit`` (the R' product with the Gram),
    ``icd.newton`` (the step and the column write), ``icd.patch`` (the
    residual patch) — which the op metadata, and so a profiler trace of
    the compiled step, carries."""

    n_rows = indptr.shape[0] - 1

    def body(f, carry):
        side_m, e = carry
        with jax.named_scope("icd.gather"):
            o_col = other_cols_nnz(f)                  # (nnz,)
        with jax.named_scope("icd.newton"):
            s_col = sweeps.take_col(side_m, f)         # (n,)
        # explicit parts (L'/2, L''/2) from the residual cache
        with jax.named_scope("icd.segsum"):
            lp = segment_sum(alpha * e * o_col, rows_nnz, n_rows)
            lpp = segment_sum(alpha * o_col * o_col, rows_nnz, n_rows)
        # implicit parts (R'/2, R''/2) via the opposite Gram — Lemma 3
        with jax.named_scope("icd.implicit"):
            rp = jnp.dot(side_m, sweeps.take_col(other_j, f),  # Σ_f' J(f',f)·w_{·,f'}
                         precision=gram_precision)
            rpp = other_j[f, f]
        with jax.named_scope("icd.newton"):
            delta = sweeps.newton_delta(
                sweeps.NewtonParts(lp + hp.alpha0 * rp, lpp + hp.alpha0 * rpp),
                s_col,
                hp.l2,
                hp.eta,
            )
        with jax.named_scope("icd.patch"):
            e = e + segment_broadcast_sorted(delta, rows_nnz, indptr) * o_col
        with jax.named_scope("icd.newton"):
            side_m = sweeps.put_col(side_m, f, s_col + delta)
        return side_m, e

    return sweeps.sweep_columns(
        side.shape[1], body, (side, e), unroll=hp.unroll,
        schedule=schedule, sweep_index=sweep_index,
    )


@partial(jax.jit, static_argnames=("hp", "schedule", "sweep_index"))
def epoch(
    params: MFParams,
    data: Interactions,
    e: jax.Array,
    hp: MFHyperParams,
    schedule: Optional[sweeps.SweepSchedule] = None,
    sweep_index: int = 0,
    weights: Optional[jax.Array] = None,
) -> Tuple[MFParams, jax.Array]:
    """One iCD epoch: W sweep then H sweep over the scheduled columns.

    ``e`` is the context-major residual cache (ŷ−ȳ per observation); callers
    obtain the initial one from :func:`residuals`. ``schedule=None`` is the
    classic full pass over all k columns on both sides; a
    :class:`~repro.core.sweeps.SweepSchedule` restricts/reorders the swept
    subspace blocks (``schedule``/``sweep_index`` are static — rotating or
    randomized schedules trace one program per distinct block plan).

    ``weights`` is an optional (nnz,) per-interaction confidence weight in
    ctx-major order: the observed confidence enters the sweep math purely
    multiplicatively, so a weighted epoch is EXACTLY an epoch over
    ``alpha·w`` (the implicit part stays uniform ``alpha0``). ``None`` is a
    trace-time branch — the unweighted program is byte-identical.
    """
    if weights is not None:
        data = dataclasses.replace(data, alpha=data.alpha * weights)
    w, h = params

    # --- context side: J_I from the fixed item factors -------------------
    with jax.named_scope("icd.gram"):
        j_i = gram(h, implementation=hp.implementation)
    h_cols = lambda f: jnp.take(sweeps.take_col(h, f), data.item)
    w, e = _side_sweep(
        w, j_i, h_cols, data.ctx, data.indptr, data.alpha, e, hp,
        schedule, sweep_index,
    )

    # --- item side: J_C from the (just-updated) context factors ----------
    with jax.named_scope("icd.gram"):
        j_c = gram(w, implementation=hp.implementation)
    e_t = sweeps.to_item_major(e, data.t_perm)
    alpha_t = sweeps.to_item_major(data.alpha, data.t_perm)
    w_cols = lambda f: jnp.take(sweeps.take_col(w, f), data.t_ctx)
    h, e_t = _side_sweep(
        h, j_c, w_cols, data.t_item, data.t_indptr, alpha_t, e_t, hp,
        schedule, sweep_index,
    )
    e = sweeps.to_ctx_major(e_t, data.t_perm)
    return MFParams(w, h), e


def residuals(params: MFParams, data: Interactions) -> jax.Array:
    return sweeps.residuals_from_factors(
        params.w, params.h, data.ctx, data.item, data.y
    )


def objective(params: MFParams, data: Interactions, hp: MFHyperParams) -> jax.Array:
    e = residuals(params, data)
    sq = jnp.sum(params.w**2) + jnp.sum(params.h**2)
    return implicit_objective(params.w, params.h, e, data, hp.alpha0, hp.l2, sq)


def fit(
    params: MFParams,
    data: Interactions,
    hp: MFHyperParams,
    n_epochs: int,
    callback=None,
    schedule: Optional[sweeps.SweepSchedule] = None,
    weights: Optional[jax.Array] = None,
) -> MFParams:
    """Run ``n_epochs`` iCD epochs (host loop; each epoch is one jit call).

    With a ``schedule``, epoch ``ep`` sweeps the schedule's blocks for
    ``sweep_index=ep`` — e.g. ``SweepSchedule('rotating',
    blocks_per_sweep=1)`` turns each "epoch" into one k_b subspace step.
    Without one, every epoch passes ``sweep_index=0``: the index is static,
    so unscheduled epochs share one compiled program."""
    e = residuals(params, data)
    for ep in range(n_epochs):
        params, e = epoch(params, data, e, hp, schedule,
                          ep if schedule is not None else 0, weights)
        if callback is not None:
            callback(ep, params)
    return params
