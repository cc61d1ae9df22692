"""Explicitly-distributed iCD-MF (shard_map) — the paper's complexity bound
realized on a pod.

The naive pjit epoch (repro/launch/cells.py, baseline in EXPERIMENTS.md
§Roofline) lets GSPMD guess: it all-gathers observation arrays and
all-reduces full context-sized segment outputs, making the epoch
collective-bound. But Lemma 2/3 say the ONLY cross-shard state iCD needs is

  * the k×k Gram of the opposite side           → one k² psum per sweep
  * the opposite side's current column ψ_f / w_f → one column all-gather
  * residuals re-grouped ctx-major ↔ item-major → one nnz all-to-all

Everything else (segment reductions, Newton steps, residual patches) is
LOCAL once contexts, items and their observations are partitioned by owner.

Layout (built host-side by ``shard_interactions``): contexts are
range-partitioned over the D shards and so are items; each shard stores its
ctx-major observation block, its item-major observation block, and the
routing indices that move the residual cache between the two groupings with
one ``lax.all_to_all``. All blocks are padded to uniform size (α=0 padding).

Per-epoch wire traffic per device (C contexts, I items, nnz observations):
  2·k² (Grams) + k·(C+I)·4B (column all-gathers) + 2·(nnz/D)·4B (routing)
— compare GSPMD baseline: see EXPERIMENTS.md §Perf hillclimb #1.

The per-shard f*-loops route through ``core.sweeps.sweep_columns`` with the
same Newton body as ``mf._side_sweep`` (``sweeps.newton_delta`` — incl. the
denominator clamp that keeps l2=0 empty contexts finite); only the
opposite-column delivery (all-gather / all-to-all route) is distributed.
Parity vs ``mf.epoch`` is pinned by tests/test_mf_dist.py.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sweeps
from repro.core.models.mf import MFHyperParams, MFParams
from repro.sparse.interactions import Interactions


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedMF:
    """Per-shard blocks; every array has leading dim D (the shard axis)."""

    # ctx-major observations (D, p_c): local ctx row, global item, targets
    ctx_l: jax.Array
    item_g: jax.Array
    y_c: jax.Array
    alpha_c: jax.Array
    # item-major observations (D, p_i)
    item_l: jax.Array
    ctx_g: jax.Array
    y_i: jax.Array
    alpha_i: jax.Array
    # routing: ctx-major → item-major residual exchange
    send_idx: jax.Array   # (D, D, blk) positions into ctx-major block, -1 pad
    recv_pos: jax.Array   # (D, D, blk) positions into item-major block, -1 pad
    c_per: int = dataclasses.field(metadata=dict(static=True))
    i_per: int = dataclasses.field(metadata=dict(static=True))
    n_shards: int = dataclasses.field(metadata=dict(static=True))


def shard_interactions(data: Interactions, n_shards: int,
                       weights=None) -> ShardedMF:
    """Host-side partitioner: range-partition contexts and items, pad blocks,
    precompute the all-to-all routing.

    ``weights`` (optional, (nnz,) ctx-major) folds per-interaction
    confidence into both blocked α layouts exactly (α is purely
    multiplicative in the explicit loss parts); padding stays α=0."""
    d = n_shards
    c_per = -(-data.n_ctx // d)
    i_per = -(-data.n_items // d)
    ctx = np.asarray(data.ctx)
    item = np.asarray(data.item)
    y = np.asarray(data.y)
    alpha = np.asarray(data.alpha)
    if weights is not None:
        alpha = alpha * np.asarray(weights, alpha.dtype)
    nnz = len(ctx)
    ctx_shard = ctx // c_per
    item_shard = item // i_per

    # --- ctx-major blocks -------------------------------------------------
    order_c = np.lexsort((item, ctx))  # already sorted, but be safe
    by_c = [order_c[ctx_shard[order_c] == s] for s in range(d)]
    p_c = max(1, max(len(b) for b in by_c))
    ctx_l = np.zeros((d, p_c), np.int32)
    item_g = np.zeros((d, p_c), np.int32)
    y_c = np.zeros((d, p_c), np.float32)
    alpha_c = np.zeros((d, p_c), np.float32)
    pos_in_ctx_block = np.empty(nnz, np.int64)
    for s, idx in enumerate(by_c):
        n = len(idx)
        ctx_l[s, :n] = ctx[idx] - s * c_per
        item_g[s, :n] = item[idx]
        y_c[s, :n] = y[idx]
        alpha_c[s, :n] = alpha[idx]
        pos_in_ctx_block[idx] = np.arange(n)

    # --- item-major blocks ------------------------------------------------
    order_i = np.lexsort((ctx, item))
    by_i = [order_i[item_shard[order_i] == s] for s in range(d)]
    p_i = max(1, max(len(b) for b in by_i))
    item_l = np.zeros((d, p_i), np.int32)
    ctx_g = np.zeros((d, p_i), np.int32)
    y_i = np.zeros((d, p_i), np.float32)
    alpha_i = np.zeros((d, p_i), np.float32)
    pos_in_item_block = np.empty(nnz, np.int64)
    for s, idx in enumerate(by_i):
        n = len(idx)
        item_l[s, :n] = item[idx] - s * i_per
        ctx_g[s, :n] = ctx[idx]
        y_i[s, :n] = y[idx]
        alpha_i[s, :n] = alpha[idx]
        pos_in_item_block[idx] = np.arange(n)

    # --- routing ctx-shard → item-shard ------------------------------------
    counts = np.zeros((d, d), np.int64)
    for j in range(nnz):
        counts[ctx_shard[j], item_shard[j]] += 1
    blk = max(1, int(counts.max()))
    send_idx = -np.ones((d, d, blk), np.int64)
    recv_pos = -np.ones((d, d, blk), np.int64)
    fill = np.zeros((d, d), np.int64)
    for j in range(nnz):
        cs, its = ctx_shard[j], item_shard[j]
        slot = fill[cs, its]
        send_idx[cs, its, slot] = pos_in_ctx_block[j]
        # receiver `its` sees this entry in its block from source `cs`
        recv_pos[its, cs, slot] = pos_in_item_block[j]
        fill[cs, its] = slot + 1

    return ShardedMF(
        ctx_l=jnp.asarray(ctx_l), item_g=jnp.asarray(item_g),
        y_c=jnp.asarray(y_c), alpha_c=jnp.asarray(alpha_c),
        item_l=jnp.asarray(item_l), ctx_g=jnp.asarray(ctx_g),
        y_i=jnp.asarray(y_i), alpha_i=jnp.asarray(alpha_i),
        send_idx=jnp.asarray(send_idx, jnp.int32),
        recv_pos=jnp.asarray(recv_pos, jnp.int32),
        c_per=c_per, i_per=i_per, n_shards=d,
    )


def shard_params(params: MFParams, sd: ShardedMF) -> MFParams:
    """Pad + block the factor matrices to (D, rows_per_shard, k)."""
    d, k = sd.n_shards, params.w.shape[1]
    w = jnp.zeros((d * sd.c_per, k), params.w.dtype).at[: params.w.shape[0]].set(params.w)
    h = jnp.zeros((d * sd.i_per, k), params.h.dtype).at[: params.h.shape[0]].set(params.h)
    return MFParams(w=w.reshape(d, sd.c_per, k), h=h.reshape(d, sd.i_per, k))


def unshard_params(params: MFParams, n_ctx: int, n_items: int) -> MFParams:
    k = params.w.shape[-1]
    return MFParams(
        w=params.w.reshape(-1, k)[:n_ctx], h=params.h.reshape(-1, k)[:n_items]
    )


def _route(e_src, src_idx, dst_pos, p_dest, axis_name):
    """Move per-observation values between groupings with one all_to_all.
    src_idx (D, blk): positions in e_src per destination shard; dst_pos
    (D, blk): where each received value lands locally (-1 = padding)."""
    send = jnp.where(src_idx >= 0, jnp.take(e_src, jnp.maximum(src_idx, 0)), 0.0)
    recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)
    flat_pos = dst_pos.reshape(-1)
    flat_val = recv.reshape(-1)
    out = jnp.zeros((p_dest,), e_src.dtype)
    return out.at[jnp.maximum(flat_pos, 0)].add(
        jnp.where(flat_pos >= 0, flat_val, 0.0))


def make_shard_mesh(n_shards: int):
    """One flat shard axis over all chips — the optimized iCD layout (the
    hillclimb's alternative to the baseline (data, model) GSPMD layout)."""
    return jax.make_mesh((n_shards,), ("shards",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def build_epoch(mesh, hp: MFHyperParams, sd_template: ShardedMF,
                variant: str = "gather", wire_dtype=jnp.float32):
    """Returns a jitted shard_map epoch over the flat shard axis.

    variant:
      'gather' — iteration 1: the opposite column is ALL-GATHERED per dim
                 (wire/device per sweep: k·rows_other·4B).
      'route'  — iteration 2: the owner shard evaluates its column at the
                 observations and ROUTES per-nnz values (all_to_all) —
                 k·(nnz/D) values instead of k·rows_other; wins whenever
                 nnz/D ≪ opposite-side rows (epoch_web: 5.1×).
    wire_dtype — iteration 3: bf16 on the wire for routed/gathered values
                 (Newton math stays fp32; quantizing ψ/φ inputs only).
    """
    from jax.sharding import PartitionSpec as P

    axes = mesh.axis_names[0]

    def epoch_shard(w_loc, h_loc, sd: ShardedMF, e_loc):
        # leading shard dim is 1 inside shard_map → squeeze
        w_loc = w_loc[0]
        h_loc = h_loc[0]
        e_loc = e_loc[0]
        blkof = lambda a: a[0]
        ctx_l, item_g = blkof(sd.ctx_l), blkof(sd.item_g)
        alpha_c = blkof(sd.alpha_c)
        item_l, ctx_g = blkof(sd.item_l), blkof(sd.ctx_g)
        alpha_i = blkof(sd.alpha_i)
        send_idx, recv_pos = blkof(sd.send_idx), blkof(sd.recv_pos)

        k = w_loc.shape[1]

        def gram_psum(m):
            mf32 = m.astype(jnp.float32)
            return jax.lax.psum(mf32.T @ mf32, axes)

        def opposite_vals(side_col, local_rows_of_entries, out_idx, in_idx,
                          p_dest):
            """ψ/φ of the opposite column at MY observations.

            'gather': all-gather the column, take at global ids (caller
            passes global ids as local_rows_of_entries with the gathered
            column). 'route': evaluate locally on the owner side at its
            entries and all_to_all per-nnz values into place."""
            vals_owner = jnp.take(side_col, local_rows_of_entries)
            return _route(vals_owner.astype(wire_dtype), out_idx, in_idx,
                          p_dest, axes).astype(jnp.float32)

        def side_sweep(side_m, other_m, j_o, rows_l, alpha_l, e_l, n_per,
                       opp_global, opp_local, out_idx, in_idx):
            """One side's k-column sweep through ``sweeps.sweep_columns``:
            the same per-column Newton body as ``mf._side_sweep`` (incl. the
            ``newton_delta`` denominator clamp), with the opposite column
            delivered over the wire per dimension."""

            def body(f, carry):
                side_m, e = carry
                o_col = sweeps.take_col(other_m, f)
                if variant == "gather":
                    col = jax.lax.all_gather(
                        o_col.astype(wire_dtype), axes, tiled=True
                    ).astype(jnp.float32)
                    o_vals = jnp.take(col, opp_global)
                else:  # owners evaluate at their entries, route per-nnz
                    o_vals = opposite_vals(o_col, opp_local, out_idx, in_idx,
                                           alpha_l.shape[0])
                s_col = sweeps.take_col(side_m, f)
                lp = jax.ops.segment_sum(alpha_l * e * o_vals, rows_l, n_per)
                lpp = jax.ops.segment_sum(alpha_l * o_vals * o_vals, rows_l,
                                          n_per)
                rp = side_m @ sweeps.take_col(j_o, f)
                rpp = jnp.take(sweeps.take_col(j_o, f), f)
                delta = sweeps.newton_delta(
                    sweeps.NewtonParts(lp + hp.alpha0 * rp,
                                       lpp + hp.alpha0 * rpp),
                    s_col, hp.l2, hp.eta,
                )
                e = e + jnp.take(delta, rows_l) * o_vals
                return sweeps.put_col(side_m, f, s_col + delta), e

            return sweeps.sweep_columns(k, body, (side_m, e_l),
                                        unroll=hp.unroll)

        # ---------------- context sweep ----------------
        j_i = gram_psum(h_loc)
        w_loc, e_loc = side_sweep(
            w_loc, h_loc, j_i, ctx_l, alpha_c, e_loc, sd.c_per,
            item_g, item_l, recv_pos, send_idx,
        )

        # ---------------- residuals: ctx-major → item-major ----------------
        e_item = _route(e_loc, send_idx, recv_pos, alpha_i.shape[0], axes)

        # ---------------- item sweep ----------------
        j_c = gram_psum(w_loc)
        h_loc, e_item = side_sweep(
            h_loc, w_loc, j_c, item_l, alpha_i, e_item, sd.i_per,
            ctx_g, ctx_l, send_idx, recv_pos,
        )

        # ---------------- residuals back ----------------
        e_loc = _route(e_item, recv_pos, send_idx, alpha_c.shape[0], axes)

        return w_loc[None], h_loc[None], e_loc[None]

    specs = P(axes)
    sd_specs = ShardedMF(
        ctx_l=specs, item_g=specs, y_c=specs, alpha_c=specs,
        item_l=specs, ctx_g=specs, y_i=specs, alpha_i=specs,
        send_idx=specs, recv_pos=specs,
        c_per=sd_template.c_per, i_per=sd_template.i_per,
        n_shards=sd_template.n_shards,
    )
    fn = jax.shard_map(
        epoch_shard, mesh=mesh,
        in_specs=(specs, specs, sd_specs, specs),
        out_specs=(specs, specs, specs),
        check_vma=False,
    )
    return jax.jit(fn)


def residuals_blocked(params_blocked: MFParams, sd: ShardedMF) -> jax.Array:
    """Initial ctx-major residual blocks (D, p_c): ŷ − ȳ (α=0 padding)."""
    d, _, k = params_blocked.w.shape
    h_flat = params_blocked.h.reshape(-1, k)
    w = params_blocked.w                     # (D, c_per, k)
    scores = jnp.einsum(
        "dpk,dpk->dp",
        jnp.take_along_axis(w, sd.ctx_l[..., None], axis=1),
        jnp.take(h_flat, sd.item_g, axis=0),
    )
    return scores - sd.y_c
