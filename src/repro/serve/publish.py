"""Live ψ refresh: double-buffered, versioned publish from training to serving.

Training mutates factor tables every epoch; serving must keep answering
queries meanwhile. The protocol here is the classic double-buffer flip:

  1. ``publish`` builds the NEXT shard set (``cluster.shard_psi`` — slicing,
     padding, device placement) entirely off to the side, in the back
     buffer. Readers still see the old table; nothing they can reach is
     being written.
  2. The flip is ONE reference assignment of the (table, version) pair —
     atomic under the interpreter, so a reader grabbing the active table
     either gets the complete old snapshot or the complete new one, never a
     half-written mix. jax arrays are immutable, so a snapshot stays valid
     for as long as any in-flight request holds it.
  3. The version counter rides on the snapshot
     (:class:`~repro.serve.cluster.PsiShardSet.version`); the request cache
     (``serve/batcher.py``) keys on it, so a publish implicitly invalidates
     every cached result without any flush traffic.

:class:`PsiPublisher` adapts this to the models' ``fit(callback=...)`` hook:
at each epoch boundary it snapshots ``export_psi(params)`` into the mesh,
so online serving tracks training with epoch granularity ("live ψ refresh").

**Delta publish** (continual learning): a fold-in produces ONE new/updated ψ
row (``Model.fold_in_item``), and republishing the whole catalogue through a
:class:`StagedRollout` for one row would be absurd. ``publish_delta(rows,
ids)`` — on :class:`~repro.serve.mesh.FaultTolerantRetrievalMesh` and
:class:`PsiPublisher` — patches existing rows and/or appends new ids onto
the authoritative table copy and flips the result live under a NORMAL
version bump: the double-buffer/atomicity story is unchanged, the batcher
cache invalidates through the version key exactly as for a full publish,
and the mesh's stale-replica refusal keeps protecting reads (every replica
is rebuilt at the new version; an old-version replica is refused before
dispatch). :func:`apply_delta` is the pure patch/append helper.

:class:`StagedRollout` is the OPERATED form of publish for the
fault-tolerant mesh (``serve/mesh.py``): instead of flipping a new ψ table
straight to every replica, it stages the table on one canary replica per
shard, health-checks it under mirrored traffic (live vs canary answers on
the same φ rows), and only then promotes — a bad table (NaNs, truncated
export, wrong geometry) rolls back with zero downtime and zero user-served
queries. See ``serve/README.md`` for the runbook.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import numpy as np

from repro.obs.metrics import next_instance_id, resolve_registry


def dense_table(shard_set) -> np.ndarray:
    """Reassemble the dense (n_items, D) ψ table from a
    :class:`~repro.serve.cluster.PsiShardSet` (drops the last shard's
    padding rows) — the authoritative base a delta patches against."""
    stacked = np.asarray(shard_set.stacked())          # (S, rows_per, D)
    return stacked.reshape(-1, stacked.shape[-1])[: shard_set.n_items]


def apply_delta(psi: np.ndarray, rows, ids) -> np.ndarray:
    """Pure delta: patch/append ψ ``rows`` at global item ``ids``.

    ``ids < n_items`` overwrite existing rows; ``ids >= n_items`` grow the
    catalogue and must cover the appended range ``[n_items, max(ids)]``
    without holes — a hole would silently serve an all-zero embedding for a
    real item id, so it raises instead. Returns a NEW dense table (the
    caller publishes it under a version bump; buffers stay immutable).
    """
    psi = np.asarray(psi)
    rows = np.asarray(rows, psi.dtype)
    ids = np.atleast_1d(np.asarray(ids, np.int64))
    if rows.ndim == 1:
        rows = rows[None, :]
    n, d = psi.shape
    if rows.shape != (ids.size, d):
        raise ValueError(
            f"delta rows must be ({ids.size}, {d}), got {rows.shape}"
        )
    if ids.size == 0:
        return psi.copy()
    if ids.min() < 0:
        raise ValueError(f"negative item id in delta: {ids.min()}")
    if np.unique(ids).size != ids.size:
        raise ValueError("duplicate item ids in one delta")
    n_new = max(int(ids.max()) + 1 - n, 0)
    if n_new:
        appended = set(int(i) for i in ids[ids >= n])
        missing = [i for i in range(n, n + n_new) if i not in appended]
        if missing:
            raise ValueError(
                f"append hole: ids {missing} in [{n}, {n + n_new}) carry no "
                "row — a hole would serve a zero embedding for a real item"
            )
    out = np.concatenate([psi, np.zeros((n_new, d), psi.dtype)], axis=0)
    out[ids] = rows
    return out


class VersionedTable:
    """Double-buffered holder of the active :class:`PsiShardSet`.

    ``publish(build)`` calls ``build(next_version)`` to construct the new
    snapshot into the back buffer, then flips it live with one atomic
    reference swap. ``active`` raises until the first publish — a serving
    path must never silently answer from an empty catalogue.
    """

    def __init__(self):
        self._buffers = [None, None]  # [back, live] payloads
        self._state = (None, 0)       # (live snapshot, version) — ONE ref

    @property
    def version(self) -> int:
        return self._state[1]

    @property
    def active(self):
        snapshot, version = self._state  # single read: consistent pair
        if snapshot is None:
            raise RuntimeError(
                "no table published yet — call publish() before serving"
            )
        return snapshot

    def publish(self, build: Callable[[int], object]) -> int:
        """Build the next snapshot with ``build(version)``, then flip."""
        _, version = self._state
        nxt = build(version + 1)
        # back buffer keeps the previous snapshot alive for stragglers that
        # grabbed it pre-flip; the flip itself is one atomic assignment
        self._buffers = [self._state[0], nxt]
        self._state = (nxt, version + 1)
        return version + 1


class PsiPublisher:
    """``fit(callback=...)`` adapter: publish ψ snapshots at epoch boundaries.

    ::

        mesh = FaultTolerantRetrievalMesh(phi_fn, n_shards=4, k=100)
        pub = PsiPublisher(mesh, mf.export_psi, every=1)
        mf.fit(params, data, hp, n_epochs, callback=pub)
        pub.versions   # [(epoch, version), ...] — the refresh trajectory

    ``export`` maps the training params to the (n_items, D) ψ table (each
    model's ``export_psi``; close over design matrices / hyper-params where
    the model needs them). ``every`` throttles the refresh cadence.

    Registry metrics (``obs/metrics.py``; labels ``instance``):
    ``serve_psi_version`` (gauge: last published version),
    ``serve_psi_last_publish_time`` (gauge: registry-clock timestamp of the
    last publish — staleness age = ``registry.clock() - value``),
    ``serve_psi_publishes_total`` / ``serve_psi_delta_publishes_total`` /
    ``serve_psi_delta_rows_total``.
    """

    def __init__(
        self,
        cluster,
        export: Callable,
        *,
        every: int = 1,
        log: Optional[Callable[[str], None]] = None,
        registry=None,
    ):
        self.cluster = cluster
        self.export = export
        self.every = int(every)
        self.log = log
        self.versions: list = []  # [(epoch, version), ...]
        self.deltas: list = []    # [(version, n_rows), ...] delta publishes
        reg = resolve_registry(registry)
        self.registry = reg
        inst = {"instance": next_instance_id()}
        lab = ("instance",)
        self._g_version = reg.gauge(
            "serve_psi_version", "last published psi table version",
            labels=lab).labels(**inst)
        self._g_pub_time = reg.gauge(
            "serve_psi_last_publish_time",
            "registry-clock timestamp of the last publish (staleness age "
            "= clock() - value)", labels=lab).labels(**inst)
        self._c_publishes = reg.counter(
            "serve_psi_publishes_total", "full-table publishes",
            labels=lab).labels(**inst)
        self._c_deltas = reg.counter(
            "serve_psi_delta_publishes_total", "delta publishes",
            labels=lab).labels(**inst)
        self._c_delta_rows = reg.counter(
            "serve_psi_delta_rows_total",
            "psi rows patched/appended by delta publishes",
            labels=lab).labels(**inst)

    def _mark(self, version: int) -> None:
        self._g_version.set(version)
        self._g_pub_time.set(self.registry.clock())

    def __call__(self, epoch: int, params) -> None:
        if epoch % self.every:
            return
        version = self.cluster.publish(self.export(params))
        self.versions.append((epoch, version))
        self._c_publishes.inc()
        self._mark(version)
        if self.log is not None:
            self.log(f"epoch {epoch}: published psi table version {version}")

    def publish_delta(self, rows, ids) -> int:
        """Incremental publish between epochs: patch/append the fold-in
        ``rows`` at item ``ids`` (see :func:`apply_delta`) without a fresh
        ``export(params)`` full-table pass. Returns the new version and
        records it in ``deltas``."""
        version = self.cluster.publish_delta(rows, ids)
        n_rows = int(np.atleast_1d(ids).size)
        self.deltas.append((version, n_rows))
        self._c_deltas.inc()
        self._c_delta_rows.inc(n_rows)
        self._mark(version)
        if self.log is not None:
            self.log(
                f"delta: {self.deltas[-1][1]} psi row(s) -> version {version}"
            )
        return version


class StagedRollout:
    """Canary-gated ψ publish for the fault-tolerant mesh: stage → mirror →
    promote (or roll back), never a straight flip.

    ::

        rollout = StagedRollout(mesh, mirror_phi=phi_probe_rows)
        promoted, report = rollout.publish(new_psi_table)
        if not promoted:
            alert(report)          # bad table never reached a user

    Protocol (the drain-and-restart shape from the ops exemplars, applied
    to in-memory tables):

      1. ``mesh.begin_canary(table)`` — the staged table lands on ONE extra
         replica per shard, off the routing path; live traffic untouched.
      2. ``mesh.mirror_check(mirror_phi)`` — the probe φ rows run against
         BOTH the live table and the canary; built-in structural checks
         (shapes, finite scores, ids in range) plus the optional
         ``validate(live_result, canary_result)`` policy hook (e.g. demand
         rank overlap, or a quality floor from a held-out eval).
      3. healthy → ``mesh.promote_canary()``: one atomic ReplicaSet flip,
         canary slab becomes replica 0, the rest re-replicate; in-flight
         queries finish on the old snapshot (no drain needed — snapshots
         are immutable). Unhealthy → ``mesh.rollback_canary()``: the
         staged table is dropped, version unchanged, nothing served it.

    ``history`` records every attempt as ``(staged_version, promoted,
    report)`` — the rollout/rollback audit trail.
    """

    def __init__(
        self,
        mesh,
        *,
        mirror_phi: Optional[Sequence] = None,
        validate: Optional[Callable] = None,
        k: Optional[int] = None,
        log: Optional[Callable[[str], None]] = None,
        registry=None,
    ):
        self.mesh = mesh
        self.mirror_phi = mirror_phi
        self.validate = validate
        self.k = k
        self.log = log
        self.history: list = []  # [(staged_version, promoted, report), ...]
        reg = resolve_registry(registry)
        inst = {"instance": next_instance_id()}
        fam = reg.counter(
            "serve_rollout_attempts_total",
            "staged rollout attempts by outcome",
            labels=("instance", "outcome"))
        self._c_outcome = {
            out: fam.labels(**inst, outcome=out)
            for out in ("promoted", "rolled_back")
        }

    def publish(self, psi_table, *, mirror_phi=None) -> tuple:
        """Stage ``psi_table``, mirror-check it, and promote iff healthy.
        Returns ``(promoted: bool, report: dict)``."""
        phi = mirror_phi if mirror_phi is not None else self.mirror_phi
        if phi is None:
            raise ValueError(
                "StagedRollout needs mirror traffic: pass mirror_phi "
                "(probe φ rows) at construction or per publish"
            )
        staged = self.mesh.begin_canary(psi_table)
        report = self.mesh.mirror_check(phi, k=self.k, validate=self.validate)
        promoted = bool(report["healthy"])
        self._c_outcome["promoted" if promoted else "rolled_back"].inc()
        if promoted:
            version = self.mesh.promote_canary()
            report = {**report, "promoted_version": version}
            if self.log is not None:
                self.log(f"staged v{staged} healthy: promoted as v{version}")
        else:
            self.mesh.rollback_canary()
            if self.log is not None:
                self.log(f"staged v{staged} UNHEALTHY: rolled back "
                         f"({report['checks']})")
        self.history.append((staged, promoted, report))
        return promoted, report
