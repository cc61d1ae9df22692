"""IVF-tiered approximate retrieval: centroid pruning + exact fused re-rank.

The exact serving stack (engine → cluster → mesh) streams the ENTIRE ψ
catalogue through the fused ``kernels/topk_score`` kernel per query — the
right oracle, and the serving wall at 10⁸+ items (ROADMAP item 4; Rendle
2021 frames large-catalogue implicit retrieval as exactly this
approximate-then-exact regime). Because every zoo model is k-separable
(score = ⟨φ, ψ_i⟩), indexing the ψ SIDE once speeds up serving for the
whole zoo: this module adds the approximate tier.

:class:`PsiIndex` — an inverted-file (IVF) index over one ψ table (or one
row-range shard of it):

  build     ``kmeans`` (JAX Lloyd's) clusters the rows; the table is
            PERMUTED into cluster-contiguous blocks, each padded to the
            uniform ``block_rows`` so every block dispatch runs ONE
            compiled kernel program. Within a block, rows keep ascending
            global id (stable argsort), which is what preserves the
            kernel's ascending-id tie policy through the permutation.
  storage   fp32, bf16, or int8 with per-row scales
            (``core.quant.int8_quantize_rows`` — per-tensor would crush
            tail-item rows); the kernel dequantizes tiles in-VMEM with
            fp32 accumulate, so int8 multiplies HBM rows-per-shard by
            ``≈ 4D/(D+4)`` (:func:`repro.kernels.vmem.psi_row_bytes`).
  query     φ·centroidᵀ scores pick each row's top ``n_probe`` clusters;
            only the selected blocks run the EXACT fused kernel — reusing
            the traced ``(id_offset, n_valid)`` meta with ``id_offset =
            block start`` so emitted candidate ids address the permuted
            table, then one ``ids_global`` gather maps them back to global
            catalogue ids before the cross-block two-key merge
            (``ops.topk_merge_shards``) restores the exact (−score,
            ascending-global-id) policy.
  oracle    ``n_probe ≥ n_clusters`` is HARD-GATED to probe everything —
            no pruning step at all — and is then bit-identical (ids AND
            scores) to the exact path: per-block fp32 dots equal the
            full-table dots, blocks partition the catalogue, and any
            global top-K element is its own block's top-K element under
            the same total order. The CI bench gate pins this.
  delta     ``apply_delta`` folds published fold-in rows in place: patched
            ids re-quantize in their existing slot, appended ids join
            their nearest centroid's block (id order within the block is
            preserved — appends carry the largest ids). Every folded row
            bumps ``staleness``; past ``AnnConfig.reindex_after`` the
            owner rebuilds the index from the authoritative table
            (``needs_reindex`` — centroids drift as the catalogue moves).

Exclusion: callers pass GLOBAL ``exclude_ids``; the index maps them to
permuted positions through its ``inv_pos`` table so the kernel's in-VMEM
membership compare works unchanged. An excluded id living in a pruned
(unprobed) block simply never surfaces — same observable result.

Sharding: each shard of a ``PsiShardSet`` gets its own index over its
row range (:func:`build_shard_indexes`); per-shard candidates carry global
ids, so the existing cross-shard merge works untouched
(:func:`ivf_cluster_topk`), including the coverage/degradation contract.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import int8_quantize_rows
from repro.kernels.topk_score.ops import topk_merge_shards, topk_score
from repro.serve.cluster import (
    PsiShardSet,
    TopKResult,
    colocate_parts,
    coverage_fraction,
    dead_item_ranges,
    empty_topk,
)

_QUANTS = ("none", "bf16", "int8")


@dataclasses.dataclass(frozen=True)
class AnnConfig:
    """Knobs for the IVF tier (engine/cluster/mesh take one of these).

    ``n_clusters=0`` auto-sizes to ≈√n (the classic IVF balance point:
    centroid scan cost ≈ probed-block cost). ``n_probe=0`` auto-sizes to
    ``max(1, n_clusters // 4)``. ``quant`` picks the ψ storage form;
    ``reindex_after`` is the staleness budget: after that many folded-in
    delta rows the owner rebuilds the index (fresh k-means) instead of
    folding further."""

    n_clusters: int = 0
    n_probe: int = 0
    quant: str = "none"
    kmeans_iters: int = 8
    seed: int = 0
    reindex_after: int = 64

    def __post_init__(self):
        if self.quant not in _QUANTS:
            raise ValueError(f"quant must be one of {_QUANTS}, got {self.quant!r}")

    def resolve_clusters(self, n_rows: int) -> int:
        c = self.n_clusters or max(1, int(round(float(n_rows) ** 0.5)))
        return max(1, min(c, n_rows))

    def resolve_probe(self, n_clusters: int) -> int:
        p = self.n_probe or max(1, n_clusters // 4)
        return max(1, min(p, n_clusters))


def kmeans(
    psi: jax.Array, n_clusters: int, *, n_iters: int = 8, seed: int = 0
) -> Tuple[jax.Array, jax.Array]:
    """Lloyd's k-means in JAX: ``(centroids (C, D) f32, assign (n,) i32)``.

    Deterministic (PRNGKey-seeded init from distinct data rows, argmin
    ties take the lowest cluster). A cluster that loses all members keeps
    its previous centroid — empty clusters are legal downstream: their
    blocks hold zero valid rows and the kernel's ``n_valid`` meta keeps
    them inadmissible."""
    psi = jnp.asarray(psi, jnp.float32)
    n, _ = psi.shape
    if not 1 <= n_clusters <= n:
        raise ValueError(f"need 1 <= n_clusters <= {n}, got {n_clusters}")
    init = jax.random.choice(
        jax.random.PRNGKey(seed), n, (n_clusters,), replace=False
    )
    centroids = psi[init]
    x_sq = jnp.sum(psi * psi, axis=1)                       # (n,)

    def assign_to(c):
        d2 = x_sq[:, None] - 2.0 * psi @ c.T + jnp.sum(c * c, axis=1)[None]
        return jnp.argmin(d2, axis=1).astype(jnp.int32)

    def step(c, _):
        a = assign_to(c)
        sums = jax.ops.segment_sum(psi, a, num_segments=n_clusters)
        cnt = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), a,
                                  num_segments=n_clusters)
        new = jnp.where(cnt[:, None] > 0,
                        sums / jnp.maximum(cnt, 1.0)[:, None], c)
        return new, None

    centroids, _ = jax.lax.scan(step, centroids, None, length=n_iters)
    return centroids, assign_to(centroids)


class PsiIndex:
    """IVF index over one ψ table / shard: cluster-permuted quantized
    storage + centroid pruning + exact fused re-rank. Construct with
    :meth:`build`; treat instances as immutable (``apply_delta`` returns a
    new index)."""

    def __init__(self, *, cfg, centroids, psi_q, scales, ids_global,
                 inv_pos, counts, block_rows, id_offset, n_rows, staleness):
        self.cfg = cfg
        self.centroids = centroids        # (C, D) f32
        self.psi_q = psi_q                # (C·block_rows, D) stored dtype
        self.scales = scales              # (C·block_rows,) f32 | None (int8)
        self.ids_global = ids_global      # (C·block_rows,) i32, −1 on pads
        self.inv_pos = inv_pos            # (n_rows,) i32: local id → position
        self.counts = counts              # np (C,) valid rows per cluster
        self.block_rows = block_rows      # uniform padded block size
        self.id_offset = id_offset        # global id of local row 0
        self.n_rows = n_rows              # valid rows indexed
        self.staleness = staleness        # delta rows folded since build

    # -------------------------------------------------------------- build
    @classmethod
    def build(cls, psi: jax.Array, cfg: AnnConfig = AnnConfig(), *,
              id_offset: int = 0) -> "PsiIndex":
        psi = np.asarray(jnp.asarray(psi, jnp.float32))
        n, d = psi.shape
        if n < 1:
            raise ValueError("cannot index an empty ψ table")
        c = cfg.resolve_clusters(n)
        centroids, assign = kmeans(
            psi, c, n_iters=cfg.kmeans_iters, seed=cfg.seed
        )
        assign = np.asarray(assign)
        counts = np.bincount(assign, minlength=c)
        block_rows = -(-max(int(counts.max()), 1) // 8) * 8
        perm = np.zeros((c * block_rows, d), np.float32)
        ids_global = np.full(c * block_rows, -1, np.int32)
        inv_pos = np.full(n, -1, np.int32)
        # stable argsort: within a cluster, rows stay in ascending global id
        # — the invariant that carries the kernel's tie policy through the
        # permutation
        order = np.argsort(assign, kind="stable")
        cursor = np.zeros(c, np.int64)
        for local in order:
            cl = assign[local]
            pos = cl * block_rows + cursor[cl]
            cursor[cl] += 1
            perm[pos] = psi[local]
            ids_global[pos] = id_offset + local
            inv_pos[local] = pos
        psi_q, scales = cls._quantize(perm, cfg.quant)
        return cls(
            cfg=cfg, centroids=centroids, psi_q=psi_q, scales=scales,
            ids_global=jnp.asarray(ids_global), inv_pos=jnp.asarray(inv_pos),
            counts=counts, block_rows=block_rows, id_offset=int(id_offset),
            n_rows=n, staleness=0,
        )

    @staticmethod
    def _quantize(perm: np.ndarray, quant: str):
        if quant == "int8":
            q, s = int8_quantize_rows(jnp.asarray(perm))
            return q, s
        if quant == "bf16":
            return jnp.asarray(perm).astype(jnp.bfloat16), None
        return jnp.asarray(perm), None

    # --------------------------------------------------------- properties
    @property
    def n_clusters(self) -> int:
        return int(self.centroids.shape[0])

    @property
    def d(self) -> int:
        return int(self.centroids.shape[1])

    @property
    def quant(self) -> str:
        return self.cfg.quant

    def needs_reindex(self) -> bool:
        """Staleness budget exhausted: folded-in deltas have drifted the
        catalogue past what frozen centroids index well — rebuild."""
        return self.staleness > self.cfg.reindex_after

    # -------------------------------------------------------------- query
    def _map_exclude(self, exclude_ids):
        """GLOBAL excluded ids → permuted positions (−1 when out of this
        index's range or padding): the kernel's membership compare then
        runs unchanged in position space."""
        if exclude_ids is None:
            return None
        ex = jnp.asarray(exclude_ids, jnp.int32)
        loc = ex - self.id_offset
        ok = (ex >= 0) & (loc >= 0) & (loc < self.n_rows)
        pos = self.inv_pos[jnp.clip(loc, 0, max(self.n_rows - 1, 0))]
        return jnp.where(ok, pos, -1)

    def topk(
        self,
        phi_rows: jax.Array,
        k: int,
        *,
        n_probe: Optional[int] = None,
        exclude_ids: Optional[jax.Array] = None,
        block_items: Optional[int] = None,
        interpret: Optional[bool] = None,
        registry=None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Approximate top-K: ``(scores (B, k), ids (B, k))``, ids GLOBAL.

        Each φ row probes its own top-``n_probe`` clusters; the dispatch
        loop runs each probed block once for the whole batch and masks the
        rows that did not select it, so per-query pruning semantics hold
        at any batch size. ``n_probe ≥ n_clusters`` skips pruning entirely
        (the bit-exact oracle path).

        ``registry`` (an ``obs.metrics`` registry) opts into query/probe
        counters. Unlike the serving components, ``None`` here means NO
        recording — a hot library function must not reach for process
        globals behind its caller's back (the engine/mesh thread their own
        registries through)."""
        phi_rows = jnp.asarray(phi_rows, jnp.float32)
        b = int(phi_rows.shape[0])
        c = self.n_clusters
        n_probe = self.cfg.resolve_probe(c) if n_probe is None else n_probe
        if registry is not None and registry:   # NULL_REGISTRY is falsy
            registry.counter(
                "ann_queries_total", "PsiIndex.topk dispatches").inc()
        if n_probe >= c:
            probe_mask = np.ones((b, c), bool)       # oracle: prune nothing
        else:
            cscores = phi_rows @ self.centroids.T    # (B, C): C ≪ n_items
            sel = np.asarray(jax.lax.top_k(cscores, n_probe)[1])
            probe_mask = np.zeros((b, c), bool)
            np.put_along_axis(probe_mask, sel, True, axis=1)
        excl_pos = self._map_exclude(exclude_ids)
        probed = 0
        parts_s, parts_i = [], []
        for cl in np.nonzero(probe_mask.any(axis=0))[0]:
            if self.counts[cl] == 0:
                continue                             # empty block: no rows
            lo = int(cl) * self.block_rows
            ss, ii = topk_score(
                phi_rows, self.psi_q[lo : lo + self.block_rows], k,
                exclude_ids=excl_pos,
                psi_scale=None if self.scales is None
                else self.scales[lo : lo + self.block_rows],
                id_offset=lo, n_valid=int(self.counts[cl]),
                block_items=block_items, interpret=interpret,
            )
            probed += 1
            mask = jnp.asarray(probe_mask[:, cl])
            ss = jnp.where(mask[:, None], ss, -jnp.inf)
            ii = jnp.where(mask[:, None], ii, -1)
            # permuted positions → global catalogue ids BEFORE the merge:
            # the two-key sort must tie-break on GLOBAL ascending id
            ii = jnp.where(
                ii >= 0, self.ids_global[jnp.clip(ii, 0, None)], -1
            )
            parts_s.append(ss)
            parts_i.append(ii)
        if registry is not None and registry:
            registry.counter(
                "ann_probed_blocks_total",
                "IVF blocks actually dispatched (post-pruning)").inc(probed)
        if not parts_s:
            return empty_topk(b, k)
        if len(parts_s) == 1:
            return parts_s[0], parts_i[0]
        return topk_merge_shards(
            jnp.stack(parts_s), jnp.stack(parts_i), k
        )

    # -------------------------------------------------------------- delta
    def apply_delta(self, rows, ids) -> "PsiIndex":
        """Fold published delta rows into the index without re-clustering.

        Patched ids (already indexed) re-quantize in their existing slot —
        position, hence tie order, is unchanged. Appended ids (must extend
        the local range contiguously, the ``publish.apply_delta`` hole
        rule) join their NEAREST centroid's block; a full block grows by a
        row-multiple repack (no re-quantization of untouched rows). Every
        folded row bumps ``staleness``; the owner checks
        :meth:`needs_reindex` and rebuilds from the authoritative table
        when the budget is spent."""
        rows = np.asarray(jnp.asarray(rows, jnp.float32))
        ids = np.asarray(ids, np.int64).reshape(-1)
        if rows.shape[0] != ids.shape[0]:
            raise ValueError(f"{rows.shape[0]} rows vs {ids.shape[0]} ids")
        order = np.argsort(ids, kind="stable")
        rows, ids = rows[order], ids[order]

        counts = self.counts.copy()
        block_rows = self.block_rows
        c = self.n_clusters
        psi_q = np.asarray(self.psi_q).copy()
        scales = None if self.scales is None else np.asarray(self.scales).copy()
        ids_global = np.asarray(self.ids_global).copy()
        inv_pos = np.asarray(self.inv_pos).copy()
        centroids = np.asarray(self.centroids)
        n_rows = self.n_rows

        def grow(new_block_rows):
            nonlocal psi_q, scales, ids_global, inv_pos, block_rows
            nq = np.zeros((c * new_block_rows,) + psi_q.shape[1:], psi_q.dtype)
            ng = np.full(c * new_block_rows, -1, np.int32)
            ns = None if scales is None else np.zeros(
                c * new_block_rows, np.float32
            )
            for cl in range(c):
                src, dst = cl * block_rows, cl * new_block_rows
                nq[dst : dst + block_rows] = psi_q[src : src + block_rows]
                ng[dst : dst + block_rows] = ids_global[src : src + block_rows]
                if ns is not None:
                    ns[dst : dst + block_rows] = scales[src : src + block_rows]
            psi_q, ids_global, scales = nq, ng, ns
            valid = inv_pos >= 0
            inv_pos = np.where(
                valid,
                (inv_pos // block_rows) * new_block_rows
                + (inv_pos % block_rows),
                -1,
            ).astype(np.int32)
            block_rows = new_block_rows

        for row, gid in zip(rows, ids):
            local = int(gid) - self.id_offset
            if 0 <= local < n_rows:                       # patch in place
                pos = int(inv_pos[local])
                self._store_row(psi_q, scales, pos, row)
            elif local == n_rows:                         # contiguous append
                d2 = np.sum((centroids - row[None]) ** 2, axis=1)
                cl = int(np.argmin(d2))
                if counts[cl] >= block_rows:
                    grow(block_rows + 8)
                pos = cl * block_rows + int(counts[cl])
                counts[cl] += 1
                self._store_row(psi_q, scales, pos, row)
                ids_global[pos] = int(gid)
                inv_pos = np.append(inv_pos, np.int32(pos))
                n_rows += 1
            else:
                raise ValueError(
                    f"delta id {int(gid)} is outside [{self.id_offset}, "
                    f"{self.id_offset + n_rows}] — appends must be "
                    "contiguous (publish.apply_delta's hole rule)"
                )
        return PsiIndex(
            cfg=self.cfg, centroids=self.centroids,
            psi_q=jnp.asarray(psi_q),
            scales=None if scales is None else jnp.asarray(scales),
            ids_global=jnp.asarray(ids_global), inv_pos=jnp.asarray(inv_pos),
            counts=counts, block_rows=block_rows, id_offset=self.id_offset,
            n_rows=n_rows, staleness=self.staleness + len(ids),
        )

    def _store_row(self, psi_q, scales, pos, row):
        """Quantize ONE row into storage slot ``pos`` (delta fold-in)."""
        if self.cfg.quant == "int8":
            absmax = max(float(np.max(np.abs(row))), 1e-12)
            scale = absmax / 127.0
            psi_q[pos] = np.clip(
                np.round(row / scale), -127, 127
            ).astype(psi_q.dtype)
            scales[pos] = scale
        else:
            psi_q[pos] = row.astype(psi_q.dtype)


# ---------------------------------------------------------------- sharding
def build_shard_indexes(
    table: PsiShardSet, cfg: AnnConfig
) -> Tuple[Optional[PsiIndex], ...]:
    """One :class:`PsiIndex` per shard of ``table``, each over its VALID
    rows with ``id_offset`` = the shard's row-range start — per-shard
    candidates come out with global ids, so the existing cross-shard merge
    applies unchanged. A shard with zero valid rows gets ``None``."""
    out = []
    for s in range(table.n_shards):
        valid = table.valid_rows(s)
        if valid <= 0:
            out.append(None)
            continue
        out.append(PsiIndex.build(
            table.shards[s][:valid], cfg, id_offset=s * table.rows_per
        ))
    return tuple(out)


def fold_delta_indexes(
    indexes: Sequence[Optional[PsiIndex]],
    new_table: PsiShardSet,
    rows,
    ids,
    cfg: AnnConfig,
    *,
    registry=None,
) -> Tuple[Optional[PsiIndex], ...]:
    """Per-shard delta fold-in after a ``publish_delta``: route each
    changed/appended row to its owning shard's index, fold it in, and
    REBUILD any index whose staleness budget is spent (or whose shard just
    materialized) from the authoritative ``new_table`` slab. Callers must
    have checked the shard geometry (``rows_per``/``n_shards``) is
    unchanged — a geometry change means re-sharding, not folding.
    ``registry`` opts into the reindex-trigger counter (same convention as
    :meth:`PsiIndex.topk`: ``None`` records nothing)."""
    rows = np.asarray(jnp.asarray(rows, jnp.float32))
    ids = np.asarray(ids, np.int64).reshape(-1)
    shard_of = ids // new_table.rows_per
    out = []
    rebuilt = 0
    for s in range(new_table.n_shards):
        idx = indexes[s] if s < len(indexes) else None
        hit = shard_of == s
        if hit.any() and idx is not None:
            idx = idx.apply_delta(rows[hit], ids[hit])
        # idx None with hits: the shard just gained its first rows — the
        # rebuild below indexes it from the authoritative table
        if (idx is None or idx.needs_reindex()) and new_table.valid_rows(s) > 0:
            idx = PsiIndex.build(
                new_table.shards[s][: new_table.valid_rows(s)], cfg,
                id_offset=s * new_table.rows_per,
            )
            rebuilt += 1
        out.append(idx)
    if registry is not None and registry and rebuilt:
        registry.counter(
            "ann_reindexes_total",
            "per-shard IVF index rebuilds triggered by the staleness "
            "budget (needs_reindex) or a newly materialized shard",
        ).inc(rebuilt)
    return tuple(out)


def ivf_cluster_topk(
    table: PsiShardSet,
    indexes: Sequence[Optional[PsiIndex]],
    phi_rows: jax.Array,
    k: int,
    *,
    n_probe: Optional[int] = None,
    exclude_ids: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
    dead_shards: Sequence[int] = (),
    registry=None,
) -> TopKResult:
    """Sharded IVF top-K: per-shard :meth:`PsiIndex.topk` candidates (each
    shard prunes to its own ``n_probe`` blocks) + the same cross-shard
    merge and coverage/degradation contract as ``cluster.cluster_topk``."""
    phi_rows = jnp.asarray(phi_rows, jnp.float32)
    b = int(phi_rows.shape[0])
    dead = set(dead_shards)
    parts_s, parts_i = [], []
    for s in range(table.n_shards):
        if s in dead or indexes[s] is None:
            continue
        ss, ii = indexes[s].topk(
            phi_rows, k, n_probe=n_probe, exclude_ids=exclude_ids,
            interpret=interpret, registry=registry,
        )
        parts_s.append(ss)
        parts_i.append(ii)
    coverage = coverage_fraction(table, dead)
    ranges = dead_item_ranges(table, dead)
    if not parts_s:
        es, ei = empty_topk(b, k)
        return TopKResult(es, ei, coverage, ranges)
    if len(parts_s) == 1:
        return TopKResult(parts_s[0], parts_i[0], coverage, ranges)
    ms, mi = topk_merge_shards(
        jnp.stack(colocate_parts(parts_s)),
        jnp.stack(colocate_parts(parts_i)), k,
    )
    return TopKResult(ms, mi, coverage, ranges)
