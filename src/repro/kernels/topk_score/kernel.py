"""Pallas fused score+top-K retrieval kernel (the serving mirror of cd_sweep).

Every model in the zoo is k-separable (paper §4–5): a catalogue item scores
as ``⟨φ(context), ψ(item)⟩``, so retrieval and full-catalogue ranking
evaluation reduce to ONE dense sweep ``Φ_B · Ψᵀ`` followed by a per-row
top-K. The naive serving path materializes the whole ``(B, n_items)`` score
matrix in HBM and runs ``lax.top_k`` over it — at catalogue scale that is
2·B·n_items·4 B of pure score traffic on top of the irreducible ψ-table
read. This kernel fuses the two:

  grid = (B/block_b, n_items/block_items) — item blocks iterate fastest,
  so per φ tile the ψ table streams through VMEM exactly once:

    resident per (b) row-block:  φ tile (block_b, D), running top-K
                                 score/id blocks (block_b, K_pad)
    streamed per (b, i) step:    ψ tile (block_items, D)
                                 [optional] exclude tile (block_b,
                                 block_items) int8, or the per-row exclude
                                 ID tile (block_b, L_pad) int32
    compute per step:  S = φ·ψᵀ (MXU), mask exclusions/padding to −inf,
                       merge: insert the tile's entrants into the sorted
                       running K_pad state — scores and ids together, in
                       registers/VMEM (``_merge_tile``)

  The ``(B, n_items)`` score matrix NEVER exists: per step only the
  (block_b, block_items) tile is alive, and the merged state written back
  to HBM is the (block_b, K_pad) running top-K.

Shard support (serve/cluster.py): the kernel takes a traced ``(id_offset,
n_valid)`` scalar pair. Candidate ids are emitted as GLOBAL catalogue ids
(``id_offset + local``) and rows at local index ≥ ``n_valid`` are
inadmissible, so a row-range ψ shard padded to uniform size runs the very
same program — under ``shard_map`` the offset is ``axis_index·rows_per``
and the cross-shard K-way merge (``ops.topk_merge_shards``) combines the
per-shard (B, K) candidates without any id rebasing.

Exclusion comes in two forms:

  * ``exclude_mask`` (B, n_items) int8 — the legacy dense form; fine for
    query-batch-sized B at test scale, but one row IS the full catalogue.
  * ``exclude_ids`` (B, L) int32, −1-padded GLOBAL ids — the web-scale
    form: the kernel builds each (block_b, block_items) admissibility tile
    in-VMEM by comparing candidate ids against the per-row id list, so no
    (B, n_items) array exists on host or device.

Semantics (pinned by ``ref.topk_score_ref`` and the parity tests):

  * ``lax.top_k`` parity: ids equal the dense ``lax.top_k(Φ·Ψᵀ, K)``
    whenever at least K admissible candidates exist, and scores agree
    under the fp32 score contract of ``ref.py``.
  * Tie policy (stable): equal scores rank in ascending item id, exactly
    like ``lax.top_k`` over an id-ordered dense row. This holds because
    item blocks arrive in ascending id order, a tile candidate enters only
    strictly above the running k-th score and lands after every slot
    scoring ≥ it, and within a tile the smaller id is inserted first.
    Scores rank in ``lax.top_k``'s total order (NaN highest).
  * Inadmissible slots: when a row has fewer than K admissible candidates
    (exclude mask covers the row, or K > n_items), the tail slots return
    id −1 with score −inf — excluded items never leak their ids, unlike a
    dense ``top_k`` over a −inf-masked matrix (which returns arbitrary
    real ids for the −inf tail). A genuinely −inf-scoring admissible item
    is indistinguishable from an excluded one by construction.

HBM traffic per query batch (fp32): dense path reads Ψ (N·D) + writes and
re-reads the score matrix (2·B·N); fused path reads Ψ (N·D) once and keeps
scores in VMEM — advantage ≈ 1 + 2B/D (≈5× at B=256, D=128; the analytic
model lives in ``benchmarks/serve_bench``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import vmem
from repro.kernels.topk_score.ref import SCORE_PRECISION


def _score_and_merge(block_items, k, meta_ref, psi_ref, phi_ref, s_ref,
                     i_ref, excl_ref=None, exclid_ref=None, scale_ref=None):
    """One grid step: score the ψ tile and merge into the running top-K.

    ``meta_ref`` is the (1, 2) int32 ``[id_offset, n_valid]`` pair: ids are
    emitted as ``id_offset + local`` (global catalogue ids — shards pass
    their row-range start) and local ids ≥ ``n_valid`` are inadmissible
    (catalogue tail / shard padding).

    The ψ tile may arrive QUANTIZED (serving storage, ``serve/ann.py``):
    bf16 rows dequantize by the plain fp32 cast below; int8 rows carry a
    per-row fp32 scale tile (``scale_ref``, (block_items, 1)) and
    dequantize in-VMEM as ``q.astype(f32)·scale`` — either way the MXU
    accumulates in fp32 (``preferred_element_type``), so only the stored
    form narrows, never the score arithmetic."""
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        s_ref[...] = jnp.full(s_ref.shape, -jnp.inf, jnp.float32)
        i_ref[...] = jnp.full(i_ref.shape, -1, jnp.int32)

    phi = phi_ref[...].astype(jnp.float32)   # (block_b, d_pad)
    psi = psi_ref[...].astype(jnp.float32)   # (block_items, d_pad)
    if scale_ref is not None:
        psi = psi * scale_ref[...]           # per-row dequant, broadcast (.,1)
    scores = jax.lax.dot_general(
        phi, psi, (((1,), (1,)), ((), ())), precision=SCORE_PRECISION,
        preferred_element_type=jnp.float32,
    )                                        # (block_b, block_items)
    offset = meta_ref[0, 0]
    n_valid = meta_ref[0, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    local = step * block_items + lane
    admissible = local < n_valid
    ids = offset + local                     # GLOBAL catalogue ids
    if excl_ref is not None:
        admissible &= excl_ref[...] == 0
    if exclid_ref is not None:
        admissible &= ~_excluded(exclid_ref[...], offset + step * block_items,
                                 lane)
    # inadmissible candidates keep −inf, and a −inf candidate never enters
    # the running state (entry needs a score STRICTLY above its k-th slot)
    scores = jnp.where(admissible, scores, -jnp.inf)
    s_ref[...], i_ref[...] = _merge_tile(k, scores, ids, s_ref[...],
                                         i_ref[...])


def _excluded(excl_ids, base, lane):
    """(block_b, block_items) hit mask: candidate ``base + lane`` appears in
    its row's exclude-id list (block_b, L_pad), which the wrapper sorts
    ascending, so the −1 pads come first.

    A row's ids inside the tile ``[base, base + block_items)`` are then one
    run of list columns: it starts after the ``lo`` ids below the tile and
    holds ``cnt`` ids. The walk visits only those columns: as many rounds
    as the block's fullest row has ids in this tile, not ``L_pad``, and
    none where the block has no id in it. Each round picks a column by a
    one-hot lane select and a lane reduction (Mosaic lowers neither a
    dynamic lane slice nor the 3-D (block_b, L_pad, block_items)
    broadcast-compare), then compares it against the tile's local
    positions; a row whose run is shorter marks nothing."""
    pos = jnp.where(excl_ids >= 0, excl_ids - base, -1)   # tile-local slot
    col_lane = jax.lax.broadcasted_iota(jnp.int32, pos.shape, 1)
    lo = jnp.sum((pos < 0).astype(jnp.int32), axis=1, keepdims=True)
    cnt = jnp.sum(((pos >= 0) & (pos < lane.shape[1])).astype(jnp.int32),
                  axis=1, keepdims=True)

    def one(j, hit):
        p = jnp.sum(jnp.where(col_lane == lo + j, pos, 0), axis=1,
                    keepdims=True)
        return jnp.where(lane == jnp.where(j < cnt, p, -1), 1, hit)

    hit = jax.lax.fori_loop(0, jnp.max(cnt), one,
                            jnp.zeros(lane.shape, jnp.int32))
    return hit > 0


def _order_key(x):
    """Total-order int32 key of fp32 scores — the order ``lax.top_k`` ranks
    by (NaN above +inf, −0 below +0). The map is its own inverse."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _from_key(key):
    return jax.lax.bitcast_convert_type(_order_key(key), jnp.float32)


def _merge_tile(k, scores, ids, run_s, run_i):
    """Insert one scored tile into the sorted running top-K.

    The running state (block_b, K_pad) is sorted by (score desc, id asc),
    ranked on :func:`_order_key`. Every tile id exceeds every id already in
    the state (item blocks arrive in ascending id order), so a tile
    candidate enters iff its score ranks STRICTLY above the state's k-th
    score, and it goes in after every slot ranking ≥ it — the ascending-id
    tie policy. Each loop round takes every row's best remaining tile
    candidate (top score, ties to the smaller id), inserts it where it
    enters by a one-lane shift (``pltpu.roll``), and retires it from the
    tile; the loop ends once no row has an entrant, so a tile costs as many
    rounds as its most-improved row gains entries."""
    slot = jax.lax.broadcasted_iota(jnp.int32, run_s.shape, 1)
    lo, hi = jnp.iinfo(jnp.int32).min, jnp.iinfo(jnp.int32).max

    def kth(s):                              # (block_b, 1) k-th best key
        return jnp.min(jnp.where(slot < k, s, hi), axis=1, keepdims=True)

    def best(keys):
        m = jnp.max(keys, axis=1, keepdims=True)
        c = jnp.min(jnp.where(keys == m, ids, hi), axis=1, keepdims=True)
        return m, c

    def body(carry):
        s, i, keys, m, c, _ = carry
        enter = m > kth(s)
        pos = jnp.sum((s >= m).astype(jnp.int32), axis=1, keepdims=True)
        ins_s = jnp.where(slot < pos, s,
                          jnp.where(slot == pos, m, pltpu.roll(s, 1, 1)))
        ins_i = jnp.where(slot < pos, i,
                          jnp.where(slot == pos, c, pltpu.roll(i, 1, 1)))
        s = jnp.where(enter, ins_s, s)
        i = jnp.where(enter, ins_i, i)
        keys = jnp.where(ids == c, lo, keys)
        m, c = best(keys)
        return s, i, keys, m, c, jnp.any(m > kth(s))

    run_k, keys = _order_key(run_s), _order_key(scores)
    m, c = best(keys)
    init = (run_k, run_i, keys, m, c, jnp.any(m > kth(run_k)))
    s, i, *_ = jax.lax.while_loop(lambda carry: carry[-1], body, init)
    return _from_key(s), i


def _topk_kernel(block_items, k, has_scale, excl_kind, *refs):
    """Generic ref unpacker for every (scale?, exclusion-form) variant.

    Ref order mirrors the in_specs the wrapper builds: meta, ψ,
    [per-row scale], φ, [exclude mask | exclude ids], then the two outputs.
    ``excl_kind``: 0 none, 1 dense mask, 2 id list."""
    it = iter(refs)
    meta_ref, psi_ref = next(it), next(it)
    scale_ref = next(it) if has_scale else None
    phi_ref = next(it)
    excl_ref = next(it) if excl_kind == 1 else None
    exclid_ref = next(it) if excl_kind == 2 else None
    s_ref, i_ref = next(it), next(it)
    _score_and_merge(block_items, k, meta_ref, psi_ref, phi_ref, s_ref,
                     i_ref, excl_ref=excl_ref, exclid_ref=exclid_ref,
                     scale_ref=scale_ref)


_QUANT_DTYPES = ("int8", "bfloat16")


def topk_score_pallas(
    phi: jax.Array,       # (B, D) query φ rows
    psi: jax.Array,       # (n_rows, D) ψ table (or one row-range shard)
    k: int,
    exclude_mask: jax.Array | None = None,  # (B, n_rows) nonzero ⇒ never recommend
    *,
    exclude_ids: jax.Array | None = None,   # (B, L) GLOBAL ids, −1 padded
    psi_scale: jax.Array | None = None,     # (n_rows,) per-row dequant scale
    id_offset=0,                            # global id of ψ row 0 (traced ok)
    n_valid=None,                           # admissible local rows (traced ok)
    block_b: int = 128,
    block_items: int | None = None,
    interpret: bool = True,
):
    """Streaming fused top-K: returns ``(scores (B, k) f32, ids (B, k) i32)``.

    ``k`` may exceed the row count; inadmissible tail slots are (−inf, −1).
    ``block_items`` defaults to the shared VMEM-budget fit
    (:func:`repro.kernels.vmem.topk_block_items`). ``id_offset``/``n_valid``
    make a row-range shard emit global ids (see the module docstring); both
    may be traced scalars so one compiled program serves every shard.

    Quantized ψ storage: ``psi`` may be bf16 (cast-dequantized per tile) or
    int8 with a REQUIRED per-row ``psi_scale`` (the ``core.quant``
    per-row-scale form); either streams the narrow stored tile through VMEM
    and dequantizes in-kernel before the fp32-accumulating MXU dot, so
    score semantics (tie policy, admissibility) are unchanged — only the
    stored precision differs."""
    b, d = phi.shape
    n_rows, d2 = psi.shape
    assert d == d2, f"phi D={d} vs psi D={d2}"
    assert exclude_mask is None or exclude_ids is None, (
        "pass exclude_mask OR exclude_ids, not both"
    )
    if psi.dtype == jnp.int8 and psi_scale is None:
        raise ValueError("int8 psi needs psi_scale (per-row dequant scales)")
    if psi_scale is not None and psi_scale.shape[0] != n_rows:
        raise ValueError(
            f"psi_scale has {psi_scale.shape[0]} rows, psi has {n_rows}"
        )
    if n_valid is None:
        n_valid = n_rows

    lane = 128
    d_pad = -(-d // lane) * lane
    k_pad = -(-k // lane) * lane
    l_pad = 0
    if exclude_ids is not None:
        l_pad = -(-max(1, exclude_ids.shape[1]) // lane) * lane
    psi_bytes = psi.dtype.itemsize if str(psi.dtype) in _QUANT_DTYPES else 4
    block_b = min(block_b, -(-b // 8) * 8)
    if block_items is None:
        # The φ tile + running top-k_pad state are FIXED VMEM costs scaling
        # with block_b·(d_pad + k_pad); at large k_pad they alone can bust
        # the budget. block_b is ours to shrink — halve it until the tile
        # fits instead of silently overflowing VMEM.
        while True:
            try:
                block_items = vmem.topk_block_items(
                    block_b, d_pad, k_pad, n_items=n_rows, excl_l_pad=l_pad,
                    psi_bytes=psi_bytes, per_row_scale=psi_scale is not None,
                )
                break
            except vmem.VmemBudgetError:
                if block_b <= 8:
                    raise
                block_b = max(8, block_b // 2)
    b_pad = -(-b // block_b) * block_b
    n_pad = -(-n_rows // block_items) * block_items

    phi = jnp.pad(phi.astype(jnp.float32), ((0, b_pad - b), (0, d_pad - d)))
    if str(psi.dtype) not in _QUANT_DTYPES:
        psi = psi.astype(jnp.float32)       # quantized forms pad as stored
    psi = jnp.pad(psi, ((0, n_pad - n_rows), (0, d_pad - d)))
    meta = jnp.stack([
        jnp.asarray(id_offset, jnp.int32),
        jnp.minimum(jnp.asarray(n_valid, jnp.int32), n_rows),
    ]).reshape(1, 2)

    grid = (b_pad // block_b, n_pad // block_items)
    out_specs = [
        pl.BlockSpec((block_b, k_pad), lambda bb, ii: (bb, 0)),
        pl.BlockSpec((block_b, k_pad), lambda bb, ii: (bb, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b_pad, k_pad), jnp.float32),
        jax.ShapeDtypeStruct((b_pad, k_pad), jnp.int32),
    ]
    in_specs = [
        pl.BlockSpec((1, 2), lambda bb, ii: (0, 0)),                 # meta
        pl.BlockSpec((block_items, d_pad), lambda bb, ii: (ii, 0)),  # ψ
    ]
    args = [meta, psi]
    if psi_scale is not None:
        scale = jnp.pad(
            psi_scale.astype(jnp.float32).reshape(-1, 1),
            ((0, n_pad - n_rows), (0, 0)), constant_values=1.0,
        )
        in_specs.append(
            pl.BlockSpec((block_items, 1), lambda bb, ii: (ii, 0))
        )
        args.append(scale)
    in_specs.append(pl.BlockSpec((block_b, d_pad), lambda bb, ii: (bb, 0)))
    args.append(phi)

    excl_kind = 0
    if exclude_mask is not None:
        excl_kind = 1
        in_specs.append(
            pl.BlockSpec((block_b, block_items), lambda bb, ii: (bb, ii))
        )
        args.append(jnp.pad(
            exclude_mask.astype(jnp.int8),
            ((0, b_pad - b), (0, n_pad - n_rows)),
        ))
    elif exclude_ids is not None:
        excl_kind = 2
        in_specs.append(pl.BlockSpec((block_b, l_pad), lambda bb, ii: (bb, 0)))
        # sorted rows, pads first: each tile's ids are one run (_excluded)
        args.append(jnp.sort(jnp.pad(
            exclude_ids.astype(jnp.int32),
            ((0, b_pad - b), (0, l_pad - exclude_ids.shape[1])),
            constant_values=-1,
        ), axis=1))

    scores, ids = pl.pallas_call(
        partial(_topk_kernel, block_items, k, psi_scale is not None,
                excl_kind),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    return scores[:b, :k], ids[:b, :k]


def excl_walk_rounds(exclude_ids, n_rows: int, block_items: int, *,
                     id_offset: int = 0, block_b: int = 128) -> tuple:
    """``(rounds, rounds_full)`` of the exclude-id walk in one
    :func:`topk_score_pallas` call over ``n_rows`` ψ rows, counted on the
    host from the (B, L) id lists (numpy; a device array is read back).

    ``rounds`` is what :func:`_excluded` runs: for each row block and ψ
    tile, the most ids one of the block's rows has in that tile.
    ``rounds_full`` is what a walk over every list column runs: row blocks
    × tiles × L_pad. ``block_b`` is the call's (before it is cut to the
    batch), ``block_items`` its resolved tile."""
    ids = np.asarray(exclude_ids, np.int64)
    b, l = ids.shape
    block_b = min(block_b, -(-b // 8) * 8)
    n_blocks = -(-b // block_b)
    n_tiles = -(-n_rows // block_items)
    l_pad = -(-max(1, l) // 128) * 128
    full = n_blocks * n_tiles * l_pad
    local = ids - id_offset
    rows, cols = np.nonzero((ids >= 0) & (local >= 0)
                            & (local < n_tiles * block_items))
    tile = local[rows, cols] // block_items
    # ids per (row, tile), then the fullest row of each (row block, tile)
    row_tile, per_row = np.unique(rows * n_tiles + tile, return_counts=True)
    most = np.zeros(n_blocks * n_tiles, np.int64)
    np.maximum.at(most, row_tile // n_tiles // block_b * n_tiles
                  + row_tile % n_tiles, per_row)
    return int(most.sum()), full
