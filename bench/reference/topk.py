"""Plain top-K retrieval: score every catalogue row, drop the excluded ids,
keep the K best. Written from the definition, to judge the served answers.

The catalogue is read in row blocks through ``table(c)`` (block ``c`` of
``n_blocks``), so it never has to be whole on the device; queries go in
blocks of ``query_block`` rows.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.precision import dot


@partial(jax.jit, static_argnames=("k", "precision"))
def _block_topk(phi, psi_blk, excl, lo, *, k: int, precision: str):
    s = dot(phi, psi_blk.T, precision)
    rows = jnp.arange(phi.shape[0])[:, None]
    local = excl - lo
    hit = (excl >= 0) & (local >= 0) & (local < psi_blk.shape[0])
    s = s.at[rows, jnp.where(hit, local, psi_blk.shape[0])].set(
        -jnp.inf, mode="drop")
    top_s, top_i = jax.lax.top_k(s, min(k, psi_blk.shape[0]))
    return top_s, top_i + lo


@partial(jax.jit, static_argnames=("k",))
def _merge(s_a, i_a, s_b, i_b, *, k: int):
    s = jnp.concatenate([s_a, s_b], axis=1)
    i = jnp.concatenate([i_a, i_b], axis=1)
    top_s, pos = jax.lax.top_k(s, k)
    return top_s, jnp.take_along_axis(i, pos, axis=1)


def topk(phi: np.ndarray, excl: np.ndarray, table, n_blocks: int, k: int,
         *, precision: str = "highest", query_block: int = 256):
    """(scores, ids), each (B, k), best first, for every query row, with
    the scores at ``precision`` (``precision.MODES``)."""
    out_s, out_i = [], []
    for q in range(0, phi.shape[0], query_block):
        p = jnp.asarray(phi[q:q + query_block])
        x = jnp.asarray(excl[q:q + query_block])
        best = None
        lo = 0
        for c in range(n_blocks):
            blk = table(c)
            cur = _block_topk(p, blk, x, lo, k=k, precision=precision)
            best = cur if best is None else _merge(*best, *cur, k=k)
            lo += blk.shape[0]
        out_s.append(np.asarray(best[0]))
        out_i.append(np.asarray(best[1]))
    return np.concatenate(out_s), np.concatenate(out_i)


@jax.jit
def _scores_of(phi, rows):
    return jnp.einsum("bd,bkd->bk", phi, rows,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def scores_of(phi: np.ndarray, ids: np.ndarray, table, block_rows: int,
              n_items: int) -> np.ndarray:
    """⟨φ_b, ψ_id⟩ for each served id (B, k) at HIGHEST; NaN where the id
    is not a catalogue row. The rows are gathered block by block on the
    device."""
    valid = (ids >= 0) & (ids < n_items)
    safe = np.where(valid, ids, 0)
    rows = np.zeros(ids.shape + (phi.shape[1],), np.float32)
    for c in range(-(-n_items // block_rows)):
        lo = c * block_rows
        sel = valid & (safe >= lo) & (safe < lo + block_rows)
        if sel.any():
            want = safe[sel] - lo
            pad = 1 << int(len(want) - 1).bit_length()   # few shapes
            got = jnp.take(table(c), jnp.asarray(np.resize(want, pad)),
                           axis=0)
            rows[sel] = np.asarray(got)[: len(want)]
    s = np.asarray(_scores_of(jnp.asarray(phi), jnp.asarray(rows)))
    return np.where(valid, s, np.nan)


def compare(served_s, served_i, excl, ref_s, ref_served_s) -> dict:
    """The numbers that decide ``correct`` for served top-K lists, each
    over the worst slot of the worst request, relative to that request's
    reference best score:

    * ``bad_ids``: served ids that are excluded, repeated in one list, or
      not a catalogue row (an exact count);
    * ``score_err``: a served score against the reference's score of the
      id served with it;
    * ``rank_gap``: a served score against the reference's score at the
      same rank — a list that is the reference's up to near-ties, with
      scores right to rounding, reads at rounding."""
    scale = np.maximum(np.abs(ref_s[:, :1]), 1e-30)
    excluded = (served_i[:, :, None] == excl[:, None, :]).any(axis=2)
    srt = np.sort(served_i, axis=1)
    repeated = np.zeros_like(served_i, bool)
    repeated[:, 1:] = srt[:, 1:] == srt[:, :-1]
    bad = excluded | repeated | np.isnan(ref_served_s)
    err = np.where(bad, 0.0, np.abs(served_s - ref_served_s)) / scale
    gap = np.abs(np.where(bad, ref_s, served_s) - ref_s) / scale
    return {"bad_ids": int(bad.sum()),
            "score_err": float(err.max(initial=0.0)),
            "rank_gap": float(gap.max(initial=0.0))}
