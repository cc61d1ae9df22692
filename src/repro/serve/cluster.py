"""Sharded online retrieval: multi-device ψ shards + cross-shard top-K merge.

The single-device :class:`repro.serve.engine.RetrievalEngine` serves the
whole k-separable zoo from ONE ψ table — which stops working the moment the
catalogue outgrows one device's HBM. This module is the serving mirror of
the ``mf_dist`` training shard story: the ψ table is ROW-RANGE partitioned
over a device mesh (shard s owns global ids ``[s·rows_per, (s+1)·rows_per)``,
every shard padded to the uniform ``rows_per = ⌈n_items/S⌉`` so one compiled
program serves them all), each shard runs the fused ``kernels/topk_score``
kernel over its local slab — emitting GLOBAL candidate ids via the kernel's
``id_offset``/``n_valid`` meta — and a cross-shard K-way merge
(``kernels.topk_score.topk_merge_shards``) ranks the S·K candidates into
the final (B, k). The merge's two-key sort reproduces the engine's exact
tie-stable ascending-global-id policy, so cluster results are BIT-IDENTICAL
to the single-device engine and the dense ``lax.top_k`` oracle at any shard
count (pinned by tests and the CI bench gate).

Three execution paths over the same shard layout:

  * host loop (default) — one fused-kernel dispatch per shard; with
    ``devices=`` the shards live on distinct devices and jax's async
    dispatch overlaps them (the single-process serving path);
  * :func:`shard_map_topk` — all shards in one ``shard_map`` over a flat
    mesh axis, the per-shard offset derived from ``lax.axis_index`` (the
    pod-scale path; same kernel program, traced offset);
  * per-shard exclude: dense masks are SLICED to the shard's row range, the
    web-scale ``exclude_ids`` form is passed through whole (global ids — a
    shard simply never matches ids outside its range).

ψ-table refresh is versioned and double-buffered (``serve/publish.py``):
``publish`` builds the next shard set off to the side and flips it in with
one atomic reference swap, so an in-flight ``topk`` keeps reading the
snapshot it grabbed and never sees a half-written table.

VMEM footprint: per-shard blocking resolves through
:func:`repro.kernels.vmem.cluster_block_items`, which charges the merge
scratch (S·K candidate score+id rows) on top of the kernel's φ/top-K state
and RAISES :class:`~repro.kernels.vmem.VmemBudgetError` instead of silently
shrinking below one ψ block — re-shard coarser or lower K.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import vmem
from repro.kernels.topk_score.ops import topk_merge_shards, topk_score

_LANE = 128


@dataclasses.dataclass(frozen=True)
class TopKResult:
    """Top-K results plus the degraded-service contract.

    Unpacks like the bare ``(scores, ids)`` tuple every pre-existing call
    site expects (``scores, ids = cluster.topk(...)``), and additionally
    carries:

      * ``coverage`` — fraction of the catalogue's items that were actually
        searched (1.0 on a healthy cluster). A dead, unreplicated shard
        lowers it; results are then exact over the SURVIVING row ranges
        but items in the dead ranges can never appear.
      * ``dead_ranges`` — the global item-id ranges ``(lo, hi)`` that were
        unavailable, coalesced and clipped to ``n_items``. Empty when
        ``coverage == 1.0``.

    The contract: a degraded query COMPLETES (never hangs, never raises at
    the query layer) and says so — it must never return a full-looking
    top-K that silently omits part of the catalogue.
    """

    scores: jax.Array                               # (B, k)
    ids: jax.Array                                  # (B, k)
    coverage: float = 1.0
    dead_ranges: Tuple[Tuple[int, int], ...] = ()

    def __iter__(self):
        # (scores, ids) tuple-compat: `s, i = cluster_topk(...)` still works
        return iter((self.scores, self.ids))

    def __getitem__(self, i):
        # positional tuple-compat: result[0] / result[1]
        return (self.scores, self.ids)[i]

    def __len__(self) -> int:
        return 2

    @property
    def degraded(self) -> bool:
        return self.coverage < 1.0


def dead_item_ranges(
    table: PsiShardSet, dead_shards
) -> Tuple[Tuple[int, int], ...]:
    """Coalesced global item-id ranges owned by ``dead_shards``, clipped to
    the real catalogue (a dead LAST shard's padding rows don't count)."""
    ranges = []
    for s in sorted(set(dead_shards)):
        lo = s * table.rows_per
        hi = min(lo + table.rows_per, table.n_items)
        if hi <= lo:
            continue
        if ranges and ranges[-1][1] == lo:
            ranges[-1] = (ranges[-1][0], hi)
        else:
            ranges.append((lo, hi))
    return tuple(ranges)


def coverage_fraction(table: PsiShardSet, dead_shards) -> float:
    """Fraction of real catalogue rows in surviving shards."""
    if table.n_items == 0:
        return 1.0
    dead = sum(hi - lo for lo, hi in dead_item_ranges(table, dead_shards))
    return 1.0 - dead / table.n_items


def empty_topk(b: int, k: int) -> Tuple[jax.Array, jax.Array]:
    """The no-admissible-candidates result: (−inf, −1) everywhere — what a
    query against zero surviving shards degrades to."""
    return (jnp.full((b, k), -jnp.inf, jnp.float32),
            jnp.full((b, k), -1, jnp.int32))


def colocate_parts(parts: List[jax.Array]) -> List[jax.Array]:
    """Per-shard results are committed to their shard's (or replica's)
    device; ``jnp.stack`` refuses a cross-device concatenate, so the merge
    input must first land on one device: that of the first part, a device
    that has just answered (never a fixed one, which may be lost). No-op
    in the single-device case."""
    devs = {getattr(p, "device", None) for p in parts}
    if len(devs) <= 1:
        return parts
    dev = parts[0].device
    return [jax.device_put(p, dev) for p in parts]


def shard_topk(
    table: PsiShardSet,
    s: int,
    phi_rows: jax.Array,
    k: int,
    *,
    slab: Optional[jax.Array] = None,
    exclude_mask: Optional[jax.Array] = None,
    exclude_ids: Optional[jax.Array] = None,
    block_items: int,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One shard's fused-kernel dispatch: (B, k) candidates with GLOBAL
    ids. ``slab`` overrides the table's own copy of shard ``s`` — the
    replication layer (``serve/mesh.py``) routes the same row range to any
    replica slab through here, so every replica runs the identical program
    the unreplicated cluster does."""
    lo = s * table.rows_per
    shard = table.shards[s] if slab is None else slab
    mask_s = None
    if exclude_mask is not None:
        mask_s = _shard_exclude_mask(exclude_mask, lo, table.rows_per)
    dev = getattr(shard, "device", None)
    phi_s = phi_rows if dev is None else jax.device_put(phi_rows, dev)
    if dev is not None and exclude_ids is not None and getattr(
            exclude_ids, "device", None) != dev:
        exclude_ids = jax.device_put(exclude_ids, dev)
    return topk_score(
        phi_s, shard, k, mask_s, exclude_ids=exclude_ids,
        id_offset=lo, n_valid=table.valid_rows(s),
        block_items=block_items, interpret=interpret,
    )


@dataclasses.dataclass(frozen=True)
class PsiShardSet:
    """One immutable, versioned row-range partition of a ψ table.

    ``shards[s]`` is the (rows_per, D) slab owning global item ids
    ``[s·rows_per, (s+1)·rows_per)``; only the LAST shard carries padding
    rows (global id ≥ n_items), which the kernel's ``n_valid`` meta keeps
    inadmissible. ``version`` is the publish counter the serving cache keys
    on (``serve/batcher.py``).
    """

    shards: Tuple[jax.Array, ...]   # S × (rows_per, D)
    n_items: int
    rows_per: int
    version: int = 0

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def d(self) -> int:
        return int(self.shards[0].shape[1])

    @property
    def offsets(self) -> Tuple[int, ...]:
        return tuple(s * self.rows_per for s in range(self.n_shards))

    def valid_rows(self, s: int) -> int:
        """Admissible rows of shard ``s`` (< rows_per only on the last)."""
        return max(0, min(self.rows_per, self.n_items - s * self.rows_per))

    def stacked(self) -> jax.Array:
        """(S, rows_per, D) — the shard_map layout. Shards committed to
        distinct devices cannot be concatenated in place, so this stages
        through host memory once and memoizes on the snapshot (immutable:
        a publish makes a NEW shard set), so serving traffic through the
        shard_map path pays it per published table, not per query."""
        cached = getattr(self, "_stacked_cache", None)
        if cached is None:
            cached = jnp.asarray(np.stack([np.asarray(s) for s in self.shards]))
            object.__setattr__(self, "_stacked_cache", cached)
        return cached


def shard_psi(
    psi_table: jax.Array,
    n_shards: int,
    *,
    devices: Optional[Sequence] = None,
    version: int = 0,
) -> PsiShardSet:
    """Row-range-partition ``psi_table`` into ``n_shards`` uniform slabs.

    ``devices`` (optional) places shard s on ``devices[s % len(devices)]``
    — the multi-device layout: each cut goes to its device on its own, and
    a host (numpy) table is cut on the host, so the whole table never
    lands on a device. Without it all shards share the default device
    (the parity-test / single-host layout)."""
    xp = jnp
    if devices is None or isinstance(psi_table, jax.Array):
        psi_table = jnp.asarray(psi_table, jnp.float32)
    else:
        psi_table, xp = np.asarray(psi_table, np.float32), np
    n_items, _ = psi_table.shape
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    rows_per = -(-n_items // n_shards)
    shards = []
    for s in range(n_shards):
        lo = s * rows_per
        blk = psi_table[lo : lo + rows_per]
        if blk.shape[0] < rows_per:  # last shard: pad to the uniform size
            blk = xp.pad(blk, ((0, rows_per - blk.shape[0]), (0, 0)))
        if devices is not None:
            blk = jax.device_put(blk, devices[s % len(devices)])
        shards.append(blk)
    return PsiShardSet(
        shards=tuple(shards), n_items=n_items, rows_per=rows_per,
        version=version,
    )


def resolve_cluster_block_items(
    table: PsiShardSet,
    b: int,
    k: int,
    *,
    excl_l: int = 0,
    block_b: int = 128,
) -> int:
    """Per-shard ``block_items`` from the shared VMEM budget, charging the
    S·K merge scratch. Raises :class:`vmem.VmemBudgetError` (never shrinks
    below one ψ block) — see :func:`vmem.cluster_block_items`."""
    d_pad = -(-table.d // _LANE) * _LANE
    k_pad = -(-k // _LANE) * _LANE
    l_pad = -(-max(1, excl_l) // _LANE) * _LANE if excl_l else 0
    block_b = min(block_b, -(-b // 8) * 8)
    return vmem.cluster_block_items(
        block_b, d_pad, k_pad, table.n_shards,
        shard_items=table.rows_per, excl_l_pad=l_pad,
    )


def _shard_exclude_mask(exclude_mask, lo: int, rows_per: int):
    """Slice a dense (B, n_items) mask to one shard's row range, padded to
    the uniform shard size — the ψ-block-aligned sliced form; the slice is
    what crosses to the shard's device, never the full-catalogue row set."""
    blk = exclude_mask[:, lo : lo + rows_per]
    short = rows_per - blk.shape[1]
    if short > 0:
        blk = jnp.pad(jnp.asarray(blk, jnp.int8), ((0, 0), (0, short)))
    return blk


def cluster_topk(
    table: PsiShardSet,
    phi_rows: jax.Array,
    k: int,
    *,
    exclude_mask: Optional[jax.Array] = None,
    exclude_ids: Optional[jax.Array] = None,
    block_items: Optional[int] = None,
    interpret: Optional[bool] = None,
    dead_shards: Sequence[int] = (),
) -> TopKResult:
    """Sharded top-K over one table snapshot: S fused-kernel dispatches +
    the cross-shard merge. Functional core of the cluster — callers that
    need snapshot consistency grab ``table`` ONCE and pass it here.

    ``dead_shards`` is the graceful-degradation hook (the failure detector
    in ``serve/mesh.py`` supplies it): those shards are skipped, the query
    completes over the survivors, and the result reports ``coverage < 1``
    plus the dead global-id ranges instead of hanging or silently serving
    a full-looking top-K."""
    phi_rows = jnp.asarray(phi_rows, jnp.float32)
    b = phi_rows.shape[0]
    if block_items is None:
        excl_l = 0 if exclude_ids is None else int(exclude_ids.shape[1])
        block_items = resolve_cluster_block_items(table, b, k, excl_l=excl_l)
    dead = set(dead_shards)
    parts_s, parts_i = [], []
    for s in range(table.n_shards):
        if s in dead:
            continue
        ss, ii = shard_topk(
            table, s, phi_rows, k, exclude_mask=exclude_mask,
            exclude_ids=exclude_ids, block_items=block_items,
            interpret=interpret,
        )
        parts_s.append(ss)
        parts_i.append(ii)
    coverage = coverage_fraction(table, dead)
    ranges = dead_item_ranges(table, dead)
    if not parts_s:  # every shard dead: complete, loudly empty
        es, ei = empty_topk(b, k)
        return TopKResult(es, ei, coverage, ranges)
    if len(parts_s) == 1:  # nothing to merge; skip the sort
        return TopKResult(parts_s[0], parts_i[0], coverage, ranges)
    ms, mi = topk_merge_shards(
        jnp.stack(colocate_parts(parts_s)),
        jnp.stack(colocate_parts(parts_i)), k,
    )
    return TopKResult(ms, mi, coverage, ranges)


def shard_map_topk(
    mesh,
    table: PsiShardSet,
    phi_rows: jax.Array,
    k: int,
    *,
    exclude_ids: Optional[jax.Array] = None,
    block_items: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> TopKResult:
    """All per-shard kernels in ONE ``shard_map`` over ``mesh``'s flat axis
    (one ψ shard per device; φ and the exclude-id lists replicate), then the
    cross-shard merge on the gathered (S, B, K) candidates.

    The per-shard global-id offset is ``lax.axis_index·rows_per`` — a traced
    scalar through the kernel's meta input, so every shard runs the SAME
    compiled program. Exclusion here is the web-scale ``exclude_ids`` form
    only (a dense mask would have to be resharded; the id list is global and
    shard-agnostic)."""
    if mesh.devices.size != table.n_shards:
        raise ValueError(
            f"mesh has {mesh.devices.size} devices but table has "
            f"{table.n_shards} shards"
        )
    phi_rows = jnp.asarray(phi_rows, jnp.float32)
    if block_items is None:
        excl_l = 0 if exclude_ids is None else int(exclude_ids.shape[1])
        block_items = resolve_cluster_block_items(
            table, phi_rows.shape[0], k, excl_l=excl_l
        )
    fn = _shard_map_program(
        mesh, table.rows_per, table.n_items, k,
        block_items, exclude_ids is not None, interpret,
    )
    args = (table.stacked(), phi_rows)
    if exclude_ids is not None:
        args += (jnp.asarray(exclude_ids, jnp.int32),)
    ss, ii = fn(*args)
    ms, mi = topk_merge_shards(ss, ii, k)
    return TopKResult(ms, mi)


@functools.lru_cache(maxsize=64)
def _shard_map_program(mesh, rows_per, n_items, k, block_items, has_eids,
                       interpret):
    """Build + memoize the jitted shard_map program for one (mesh, table
    geometry, k) — ``jax.jit``'s cache keys on function identity, so a
    per-call closure would retrace and recompile on EVERY query; this
    cache makes repeat queries hit the compiled program."""
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]

    def local(psi_blk, phi_rep, *eids):
        off = jax.lax.axis_index(axis).astype(jnp.int32) * rows_per
        nv = jnp.clip(n_items - off, 0, rows_per)
        ss, ii = topk_score(
            phi_rep, psi_blk[0], k,
            exclude_ids=eids[0] if eids else None,
            id_offset=off, n_valid=nv,
            block_items=block_items, interpret=interpret,
        )
        return ss[None], ii[None]

    n_in = 2 + bool(has_eids)
    in_specs = (P(axis),) + (P(),) * (n_in - 1)
    out_specs = (P(axis), P(axis))
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


class ShardedRetrievalCluster:
    """Multi-device retrieval service: versioned ψ shards + merge + refresh.

    The sharded counterpart of :class:`repro.serve.engine.RetrievalEngine`::

        cluster = ShardedRetrievalCluster(
            lambda ctx: mf.build_phi(params, ctx), n_shards=4, k=100)
        cluster.publish(mf.export_psi(params))      # version 1 live
        scores, ids = cluster.topk(user_ids)        # == engine, bit-exact
        ...
        cluster.publish(mf.export_psi(new_params))  # version 2; in-flight
                                                    # queries finish on v1

    ``publish`` is double-buffered and versioned (``serve/publish.py``):
    each ``topk`` grabs the active :class:`PsiShardSet` once and serves the
    whole request from that snapshot. ``devices=`` spreads shards across
    devices; ``mesh=`` on the query methods switches to the one-program
    ``shard_map`` path.
    """

    def __init__(
        self,
        phi_fn: Optional[Callable[..., jax.Array]] = None,
        *,
        n_shards: int = 2,
        k: int = 100,
        block_items: Optional[int] = None,
        devices: Optional[Sequence] = None,
        psi_table: Optional[jax.Array] = None,
        retrieval: str = "exact",
        ann=None,                                  # serve.ann.AnnConfig
        registry=None,
    ):
        from repro.obs.metrics import next_instance_id, resolve_registry
        from repro.serve.publish import VersionedTable

        self.phi_fn = phi_fn
        self.n_shards = int(n_shards)
        self.k = int(k)
        self.block_items = block_items
        self.devices = devices
        if retrieval not in ("exact", "ivf"):
            raise ValueError(f"retrieval must be 'exact' or 'ivf', got {retrieval!r}")
        self.retrieval = retrieval
        self.ann = ann
        self._ivf: dict = {}      # table version → per-shard PsiIndex tuple
        self._table = VersionedTable()
        self.registry = resolve_registry(registry)
        self._m_queries = self.registry.counter(
            "serve_cluster_queries_total", "cluster topk_phi requests",
            labels=("instance",)).labels(instance=next_instance_id())
        if psi_table is not None:
            self.publish(psi_table)

    # ------------------------------------------------------------- publish
    def publish(self, psi_table: jax.Array) -> int:
        """Shard + version a fresh ψ snapshot and flip it live; returns the
        new version. Never disturbs in-flight readers (double buffer)."""
        return self._table.publish(
            lambda version: shard_psi(
                psi_table, self.n_shards, devices=self.devices,
                version=version,
            )
        )

    def publish_delta(self, rows, ids) -> int:
        """Incremental publish: patch/append ψ ``rows`` at global item
        ``ids`` (fold-in output) onto the active table and flip the result
        live under a normal version bump — no model re-export, in-flight
        readers keep their snapshot, and the version key invalidates the
        request cache exactly like a full publish. Appends (ids ≥ n_items)
        grow the catalogue. Returns the new version.

        With ``retrieval='ivf'`` the delta also FOLDS into the live
        per-shard indexes (each changed row re-quantizes in place; each
        appended row joins its nearest cluster) instead of re-running
        k-means per delta; every fold bumps the index staleness counter and
        a shard past ``ann.reindex_after`` rebuilds from the new table
        (``serve.ann.fold_delta_indexes``). A delta that changes the shard
        GEOMETRY (rows_per growth) falls back to lazy full reindex."""
        from repro.serve.publish import apply_delta, dense_table

        old_table = self.table
        old_indexes = self._ivf.get(old_table.version)
        base = dense_table(old_table)
        version = self.publish(jnp.asarray(apply_delta(base, rows, ids)))
        if self.retrieval == "ivf" and old_indexes is not None:
            from repro.serve.ann import fold_delta_indexes

            new_table = self.table
            if (new_table.rows_per == old_table.rows_per
                    and new_table.n_shards == old_table.n_shards):
                self._ivf = {version: fold_delta_indexes(
                    old_indexes, new_table, rows, ids, self._ann_cfg(),
                    registry=self.registry,
                )}
        return version

    def _ann_cfg(self):
        from repro.serve.ann import AnnConfig

        return self.ann or AnnConfig()

    def _ivf_indexes(self, table: PsiShardSet):
        """Per-shard IVF indexes for one table snapshot, built lazily and
        memoized on the publish version (an index is a pure function of
        its snapshot; a publish invalidates implicitly, like the request
        cache). Only the latest version's indexes are retained."""
        cached = self._ivf.get(table.version)
        if cached is None:
            from repro.serve.ann import build_shard_indexes

            cached = build_shard_indexes(table, self._ann_cfg())
            self._ivf = {table.version: cached}
        return cached

    @property
    def table(self) -> PsiShardSet:
        """The active (latest published) shard set."""
        return self._table.active

    @property
    def version(self) -> int:
        return self._table.version

    @property
    def n_items(self) -> int:
        return self.table.n_items

    # -------------------------------------------------------------- query
    def phi(self, *query) -> jax.Array:
        return jnp.asarray(self.phi_fn(*query), jnp.float32)

    def topk(
        self,
        *query,
        k: Optional[int] = None,
        exclude_mask: Optional[jax.Array] = None,
        exclude_ids: Optional[jax.Array] = None,
        mesh=None,
    ) -> TopKResult:
        """(scores, ids) :class:`TopKResult`, both (B, k), for a query
        batch (coverage always 1.0 here — the unreplicated cluster has no
        failure detector; see ``serve/mesh.py`` for the degraded path)."""
        return self.topk_phi(
            self.phi(*query), k=k, exclude_mask=exclude_mask,
            exclude_ids=exclude_ids, mesh=mesh,
        )

    def topk_phi(
        self,
        phi_rows: jax.Array,
        *,
        k: Optional[int] = None,
        exclude_mask: Optional[jax.Array] = None,
        exclude_ids: Optional[jax.Array] = None,
        mesh=None,
    ) -> TopKResult:
        """Like :meth:`topk` from pre-built φ rows (batcher / eval path).

        ``retrieval='ivf'`` routes through the per-shard IVF indexes
        (``serve/ann.py``): each shard prunes to its configured ``n_probe``
        cluster blocks and re-ranks them with the exact fused kernel; the
        cross-shard merge is unchanged. The shard_map path stays exact-only
        (an index is host-driven block dispatch, not a flat-mesh program)."""
        table = self.table  # ONE snapshot: version-consistent whole request
        k = k or self.k
        self._m_queries.inc()
        if mesh is not None:
            if exclude_mask is not None:
                raise ValueError(
                    "the shard_map path takes exclude_ids (global id lists),"
                    " not a dense exclude_mask"
                )
            if self.retrieval == "ivf":
                raise ValueError(
                    "retrieval='ivf' serves through the host-loop path; "
                    "the shard_map path is exact-only"
                )
            return shard_map_topk(
                mesh, table, phi_rows, k, exclude_ids=exclude_ids,
                block_items=self.block_items,
            )
        if self.retrieval == "ivf":
            if exclude_mask is not None:
                raise ValueError(
                    "retrieval='ivf' takes exclude_ids (global id lists), "
                    "not a dense exclude_mask"
                )
            from repro.serve.ann import ivf_cluster_topk

            return ivf_cluster_topk(
                table, self._ivf_indexes(table), phi_rows, k,
                exclude_ids=exclude_ids, registry=self.registry,
            )
        return cluster_topk(
            table, phi_rows, k, exclude_mask=exclude_mask,
            exclude_ids=exclude_ids, block_items=self.block_items,
        )
