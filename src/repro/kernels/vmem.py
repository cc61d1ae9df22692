"""Shared VMEM-budget blocking policy for the Pallas kernel wrappers.

Every kernel in this package streams `(rows, lanes)` tiles through VMEM
(~16 MiB/core); the row-tile size is the knob that trades grid steps
against VMEM pressure. Before this module each call site carried its own
constant (``mf_padded._SWEEP_BLOCK_CTX = 128``, ``block_ctx=128`` defaults
in the cd_sweep ops, ...). Now there is ONE declared budget and one
fitting rule; the per-kernel helpers below encode each kernel's bytes/row
so wrappers can resolve ``block_ctx``/``block_items`` from the actual tile
shapes at trace time (shapes are static under jit, so the choice bakes
into the compiled program).

The ``k_b`` (columns per fused cd_sweep dispatch) side of the trade lives
in ``core.sweeps.resolve_block_k``: its auto policy ``min(k, 8)`` is the
bandwidth knee of the analytic model in ``benchmarks/roofline_bench`` —
beyond k_b≈8 the amortized α/e traffic saving flattens while the Ψ tile's
VMEM (and HBM capacity) cost keeps growing linearly, so the budget here
only has to fit the row tile given that k_b.

Two cd_sweep footprint models coexist:

  * pre-gathered (:func:`cd_sweep_block_ctx`) — the caller materializes a
    `(C, k_b, D_pad)` Ψ tile, so the Ψ cost is PER ROW;
  * in-kernel gather (:func:`cd_sweep_gather_block_ctx`) — the kernel holds
    the whole `(n_src, m)` ψ slab resident and gathers rows through an id
    grid, so the ψ cost is FIXED and per-row cost drops to the id/α/e
    streams (plus, for the slab-reduce variant, the gathered tile itself).

A tile request whose ``fixed_bytes`` alone busts the budget raises
:class:`VmemBudgetError` instead of silently returning the ``lo`` floor
(which used to overflow VMEM); callers with a shrinkable fixed dimension
catch it and shrink (``topk_score`` halves ``block_b``; the cd_sweep model
dispatch falls back to the pre-gathered path).
"""
from __future__ import annotations

VMEM_BYTES = 16 * 1024 * 1024
# Working budget: half the core's VMEM, leaving headroom for the pipeline's
# double buffering and the compiler's own temporaries.
VMEM_BUDGET_BYTES = VMEM_BYTES // 2


class VmemBudgetError(ValueError):
    """The requested tile cannot fit the VMEM budget at any row count."""


def fit_block_rows(
    per_row_bytes: int,
    *,
    fixed_bytes: int = 0,
    n_rows: int | None = None,
    budget: int | None = None,
    multiple: int = 8,
    lo: int = 8,
    hi: int = 2048,
    overflow: str = "raise",
) -> int:
    """Largest row-tile (multiple of ``multiple``, in [lo, hi]) whose VMEM
    footprint ``fixed_bytes + rows·per_row_bytes`` fits the budget.

    ``n_rows`` (when known) caps the tile at the padded problem size so a
    small problem is one grid step instead of being padded up to a huge
    tile. ``budget`` defaults to :data:`VMEM_BUDGET_BYTES` (resolved at
    call time so tests can shrink it).

    When even the minimal ``lo``-row tile overflows the budget (e.g.
    ``fixed_bytes`` alone exceeds it), ``overflow='raise'`` (default)
    raises :class:`VmemBudgetError` — callers must shrink their fixed
    dimension or dispatch another kernel variant rather than silently
    overflow VMEM. ``overflow='floor'`` returns the ``lo`` floor instead:
    the escape hatch for a LAST-RESORT fit with no fixed dimension left to
    shrink (the budget is a soft target there — interpret mode runs fine,
    and a compiled caller is expected to lower k_b / re-bucket degrees).
    """
    if budget is None:
        budget = VMEM_BUDGET_BYTES
    if fixed_bytes + lo * per_row_bytes > budget and overflow == "raise":
        raise VmemBudgetError(
            f"minimal {lo}-row tile does not fit VMEM budget: "
            f"fixed_bytes={fixed_bytes} + {lo} rows * {per_row_bytes} B/row "
            f"= {fixed_bytes + lo * per_row_bytes} > budget={budget}"
        )
    rows = max(lo, (budget - fixed_bytes) // max(1, per_row_bytes))
    rows = min(rows, hi)
    if n_rows is not None:
        rows = min(rows, -(-n_rows // multiple) * multiple)
    return max(lo, (rows // multiple) * multiple)


def cd_sweep_block_ctx(d_pad: int, k_b: int, *, n_rows: int | None = None) -> int:
    """Row tile for the PRE-GATHERED ``cd_sweep`` kernel family.

    Per row the block kernels hold the Ψ tile (k_b, d_pad), α and e
    (d_pad each, plus the aliased e output) and the small (k_b,) slabs in
    VMEM — ≈ (k_b + 3)·d_pad·4 B/row (the rowpatch variant adds k_b²·4,
    folded into the same bound).

    This is the dispatch of last resort (the gather variant falls back
    HERE), so it floors at the minimal ``lo``-row tile instead of raising
    when a pathological ``d_pad`` (one enormous context degree) busts the
    soft budget — matching the pre-PR-4 behavior; such data should be
    degree-bucketed before padding."""
    per_row = 4 * ((k_b + 3) * d_pad + k_b * k_b + 4 * k_b)
    return fit_block_rows(per_row, n_rows=n_rows, overflow="floor")


def cd_sweep_gather_block_ctx(
    d_pad: int,
    m: int,
    n_src: int,
    *,
    n_rows: int | None = None,
    hold_tile: bool = False,
) -> int:
    """Row tile for the IN-KERNEL-GATHER ``cd_sweep`` variants.

    The whole `(n_src, m)` ψ slab is VMEM-resident per dispatch — a FIXED
    cost — and the per-row cost is the id grid (int32 d_pad), α, e (plus
    the aliased e output) and a one-column gather temporary:
    ≈ 5·d_pad·4 B/row. ``hold_tile=True`` models the slab-reduce variant,
    which gathers the full `(m, d_pad)` tile per row before its einsums —
    ≈ (m + 4)·d_pad·4 B/row (same per-row bound as pre-gathered, but the
    `(C, m, D_pad)` HBM intermediate is gone).

    Raises :class:`VmemBudgetError` when the ψ slab alone busts the budget
    (huge catalogues) — callers fall back to the pre-gathered dispatch."""
    fixed = 4 * n_src * m
    if hold_tile:
        per_row = 4 * ((m + 4) * d_pad + m * m + 4 * m)
    else:
        per_row = 4 * (5 * d_pad + m * m + 4 * m)
    return fit_block_rows(per_row, fixed_bytes=fixed, n_rows=n_rows)


def resolve_cd_sweep_dispatch(
    d_pad: int,
    m: int,
    n_src: int,
    *,
    n_rows: int | None = None,
    hold_tile: bool = False,
    prefer_gather: bool = True,
    interpret: bool | None = None,
) -> tuple[bool, int]:
    """Pick the cd_sweep dispatch for one fused sweep: ``(use_gather,
    block_ctx)``.

    Gather is preferred (no `(C, m, D_pad)` HBM intermediate); the
    pre-gathered tile is the fallback when the ψ slab alone busts the VMEM
    budget, when the caller pinned ``psi_dispatch='pregather'``, or when
    the kernels COMPILE for real (``interpret=None`` resolves via
    ``repro.kernels.use_interpret()``): the gather kernels' value-level
    ``jnp.take`` is interpret-safe only — the Mosaic/``pltpu``-DMA lowering
    is the ROADMAP follow-up, so a compiled backend must not default onto a
    path that cannot lower."""
    if interpret is None:
        from repro.kernels import use_interpret

        interpret = use_interpret()
    if prefer_gather and interpret:
        try:
            return True, cd_sweep_gather_block_ctx(
                d_pad, m, n_src, n_rows=n_rows, hold_tile=hold_tile
            )
        except VmemBudgetError:
            pass
    return False, cd_sweep_block_ctx(d_pad, m, n_rows=n_rows)


def topk_block_items(
    block_b: int,
    d_pad: int,
    k_pad: int,
    *,
    n_items: int | None = None,
    excl_l_pad: int = 0,
    psi_bytes: int = 4,
    per_row_scale: bool = False,
) -> int:
    """ψ-table row tile for the ``topk_score`` kernel.

    Per ψ row: the STORED ψ tile lane (``d_pad·psi_bytes`` — 4 for fp32,
    2 for bf16, 1 for int8 serving storage) plus this row's column in the
    (block_b, block_items) score tile and the concat/merge temporaries
    (≈3 score-tile copies: scores + concatenated scores/ids). Fixed: the
    resident φ tile and the running top-k_pad score/id blocks.

    ``psi_bytes < 4`` models the quantized-ψ variants: the kernel holds the
    narrow stored tile AND its in-VMEM fp32 dequantization (``+4·d_pad``
    per row, plus the f32 per-row scale column when ``per_row_scale``), so
    the VMEM block for int8 is NOT 4× the fp32 one — the capacity win of
    quantized ψ is the HBM/shard-residency side
    (:func:`psi_row_bytes` / :func:`shard_capacity_rows`), while the VMEM
    fit only has to keep working under the same budget.

    ``excl_l_pad`` models the exclude-ID variant: the resident (block_b,
    L_pad) id tile and its tile-local copy are FIXED, and the membership
    walk over the list accumulates a (block_b,) int32 hit column per
    candidate row (:func:`excl_costs`).

    Raises :class:`VmemBudgetError` at large ``block_b·k_pad`` (the fixed
    φ/top-k state alone busts the budget); ``topk_score_pallas`` catches
    it and halves ``block_b``."""
    stored = psi_bytes * d_pad + (4 * d_pad if psi_bytes < 4 else 0)
    excl_fixed, excl_row = excl_costs(block_b, excl_l_pad)
    per_row = stored + 16 * block_b + excl_row
    if per_row_scale:
        per_row += 4
    fixed = 4 * (block_b * d_pad + 4 * block_b * k_pad) + excl_fixed
    return fit_block_rows(
        per_row, fixed_bytes=fixed, n_rows=n_items, multiple=128, lo=128, hi=4096
    )


def excl_costs(block_b: int, excl_l_pad: int) -> tuple[int, int]:
    """(fixed, per ψ row) VMEM bytes of the ``topk_score`` exclude-ID
    membership test: the (block_b, L_pad) id tile plus its tile-local copy,
    and a (block_b,) int32 hit column per candidate row. Zero without a
    list."""
    if not excl_l_pad:
        return 0, 0
    return 2 * 4 * block_b * excl_l_pad, 4 * block_b


def psi_row_bytes(d: int, *, psi_bytes: int = 4,
                  per_row_scale: bool = False) -> int:
    """HBM bytes one ψ catalogue row occupies in serving storage:
    ``d·psi_bytes`` plus the fp32 per-row scale (int8 form). The analytic
    basis for the quantized-capacity and ANN traffic models
    (``benchmarks/serve_bench`` ``ann`` section)."""
    return d * psi_bytes + (4 if per_row_scale else 0)


def shard_capacity_rows(hbm_bytes: int, d: int, *, psi_bytes: int = 4,
                        per_row_scale: bool = False) -> int:
    """ψ rows one shard device can hold in ``hbm_bytes`` of slab budget.
    int8 (+ per-row scale) at D=128 fits ``512/132 ≈ 3.9×`` the fp32 rows —
    the "≥ 3× rows per shard" capacity gate in the serve bench asserts this
    model while :func:`topk_block_items` proves the same tile still fits
    the unchanged VMEM budget."""
    return hbm_bytes // psi_row_bytes(
        d, psi_bytes=psi_bytes, per_row_scale=per_row_scale
    )


def cluster_block_items(
    block_b: int,
    d_pad: int,
    k_pad: int,
    n_shards: int,
    *,
    shard_items: int | None = None,
    excl_l_pad: int = 0,
) -> int:
    """Per-shard ψ row tile for the sharded cluster (``serve/cluster.py``).

    Same footprint as :func:`topk_block_items` plus the cross-shard merge
    scratch: merging S shards' top-K lists holds the (block_b, S·K_pad)
    candidate score AND id rows (``ops.topk_merge_shards``) — a FIXED cost
    of 2·4·block_b·S·K_pad bytes that grows with the shard count.

    Raises :class:`VmemBudgetError` when even one minimal ψ block (128
    rows) cannot fit next to the merge scratch — the cluster PROPAGATES it
    (re-shard coarser, or lower K) instead of silently shrinking the tile
    below one ψ block and overflowing VMEM."""
    merge_scratch = 2 * 4 * block_b * n_shards * k_pad
    excl_fixed, excl_row = excl_costs(block_b, excl_l_pad)
    per_row = 4 * (d_pad + 4 * block_b) + excl_row
    fixed = (
        4 * (block_b * d_pad + 4 * block_b * k_pad) + excl_fixed
        + merge_scratch
    )
    return fit_block_rows(
        per_row, fixed_bytes=fixed, n_rows=shard_items, multiple=128, lo=128,
        hi=4096,
    )
