"""Operations and bytes of the benchmarked algorithms, counted from shapes.

These are the yardstick's own counts: they follow the algorithm, not the
program's code, so a change to the program cannot move them.
"""
from __future__ import annotations

import json
from pathlib import Path

F32 = 4
I32 = 4


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind`` from ``peaks.json``.
    A kind that is not in the table is an error, never a default."""
    table = json.loads((Path(__file__).with_name("peaks.json")).read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/peaks.json with its source") from None


def mf_step_flops(n_ctx: int, n_items: int, nnz: int, k: int,
                  k_b: int) -> int:
    """Model FLOPs of one iCD-MF subspace step that updates ``k_b`` columns
    of W and then ``k_b`` columns of H (paper Algorithm 2):

    * both Gram matrices J_I = HᵀH and J_C = WᵀW: 2·k²·(C + I);
    * per column and observed pair, the explicit parts and the residual
      patch: α·e·ψ and its segment sum (3), α·ψ² and its sum (3), e + δ·ψ
      (2) — 8 per pair, on each side: 2·k_b·8·nnz;
    * per column and row, R' = Σ_f' J(f', f)·θ_f': 2·k per row on each
      side: 2·k_b·k·(C + I).
    """
    rows = n_ctx + n_items
    return 2 * k * k * rows + 2 * k_b * 8 * nnz + 2 * k_b * k * rows


def topk_score_work(b: int, n_items: int, d: int, k: int,
                    excl_l: int) -> tuple[int, int]:
    """(FLOPs, bytes) that one top-K scoring call over ``n_items`` ψ rows
    must do for ``b`` real query rows: the score matmul 2·b·N·D, and the ψ
    table read once, φ, the exclusion ids and the (score, id) outputs."""
    flops = 2 * b * n_items * d
    nbytes = (n_items * d * F32 + b * d * F32 + b * excl_l * I32
              + 2 * b * k * F32)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
