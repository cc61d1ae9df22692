"""The serving object: top-K retrieval over a sharded, replicated ψ table,
with health-checked failover and graceful degradation.

:class:`FaultTolerantRetrievalMesh` is the one front end for top-K
serving, at every scale: one device is ``n_shards=1, n_replicas=1`` (the
slab is the table itself — no copy, no merge), a catalogue past one
device's HBM is ``n_shards=S`` over ``devices=`` (the row-range layout of
``serve/cluster.py``), and replicas make it OPERABLE under the failures a
"millions of users" serving regime implies (Rendle 2021 frames
large-catalogue retrieval as exactly this availability/tail-latency
problem):

  replication — :class:`ReplicaSet` places each row range on R replica
    slabs (numbered shard by shard and dealt round the devices: every
    device holds at most ⌈S·R/D⌉ slabs, and copies of the same shard land
    on DIFFERENT devices), with per-replica health state and two routing
    policies: ``round_robin`` (throughput fan-out; within one query each
    shard takes the live replica whose device has served the fewest of
    that query's dispatches) and ``least_outstanding`` (tail-latency under
    skew). Every replica runs
    the identical fused-kernel program (``cluster.shard_topk``) with the
    same ``id_offset``/``n_valid`` meta, so WHICH replica answered is
    unobservable in the results — failover is bit-invisible.

  failure detection — three signals feed the per-replica health state:
    (1) hard failures (a dispatch raises — or the injectable
    :class:`FaultInjector` makes it raise, so every failure path is
    testable without killing real processes); (2) latency: per-replica
    query wall-times stream into a :class:`ShardHealthMonitor`
    (``runtime.health.StragglerWatchdog`` keyed by ``(shard, replica)``) —
    a replica whose median latency exceeds the fleet's by ``threshold``×
    for ``patience`` checks is flagged and routed around; (3) staleness: a
    replica still serving an old table version (stuck canary, failed
    flip) is refused before dispatch.

  failover + re-placement — a failed dispatch fails over to the next live
    replica of the same range (no backoff for failover: another copy is
    already warm). A replica struck out ``fail_threshold`` times is marked
    dead; :meth:`FaultTolerantRetrievalMesh.heal` then re-places the
    orphaned row range by copying a live replica of it onto a device that
    saw no death and holds no live copy of that range — the
    ``ElasticMeshManager`` recovery shape (rebuild placement over the
    surviving device set), applied per shard. The copy runs in the
    background: the new replica enters routing only once its slab is
    resident (``jax.Array.is_ready``), and until then the range is served
    by its surviving copies.

  bounded, deadline-aware retries — :class:`RetryPolicy` gives each
    request a budget: at most ``max_attempts`` dispatches per shard,
    exponential backoff between SAME-SET retries, and every sleep capped
    by the request's remaining ``deadline`` budget — a retry can never
    blow the micro-batcher's ``max_delay`` contract (wire
    ``retry.deadline = batcher.max_delay``). Injected fault latencies
    count against the budget exactly like real ones.

  graceful degradation — a row range with NO live replica does not hang or
    raise: the query completes over the surviving shards and the
    :class:`~repro.serve.cluster.TopKResult` reports ``coverage < 1.0``
    plus the dead global-id ranges. The same contract flows through the
    batcher's ticket results and ``eval/ranking.py``'s sharded path.

  staged rollout — ``publish.StagedRollout`` drives the canary protocol
    (:meth:`begin_canary` → :meth:`mirror_check` → :meth:`promote_canary`
    / :meth:`rollback_canary`): the next ψ table is installed on ONE
    canary replica per shard, health-checked under mirrored traffic
    against the live table, and only then flipped everywhere — a bad
    table rolls back without downtime and without ever serving a user.

Everything is single-process and clock-injectable (like ``MicroBatcher``):
tests drive simulated clocks and the :class:`FaultInjector` instead of
killing processes, so the chaos suite is deterministic.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import StatsView, next_instance_id, resolve_registry
from repro.runtime.health import StragglerWatchdog
from repro.serve.cluster import (
    PsiShardSet,
    TopKResult,
    colocate_parts,
    coverage_fraction,
    dead_item_ranges,
    empty_topk,
    resolve_cluster_block_items,
    shard_psi,
    shard_topk,
)
from repro.kernels.topk_score import excl_walk_rounds, topk_merge_shards


# ------------------------------------------------------------------ failures
class ReplicaFailure(RuntimeError):
    """A single replica failed one dispatch (crash, injected error)."""

    def __init__(self, msg: str = "replica failure", latency: float = 0.0):
        super().__init__(msg)
        self.latency = float(latency)


class ReplicaTimeout(ReplicaFailure):
    """A dispatch exceeded its time allowance; ``latency`` is what it
    burned from the request's deadline budget before being abandoned."""


class StaleReplicaError(ReplicaFailure):
    """The replica's installed table version lags the live version — it
    must not answer (a stale ψ would silently serve old scores)."""


class FaultInjector:
    """Injectable failure source — the chaos-testing hook.

    ``fail(shard, replica, mode)`` arms a fault on one replica:

      * ``"error"``   — its next dispatches raise :class:`ReplicaFailure`;
      * ``"timeout"`` — raise :class:`ReplicaTimeout` carrying ``latency``
        seconds of burned deadline budget;
      * ``"stale"``   — raise :class:`StaleReplicaError` (simulates a
        replica stuck on an old table version).

    Faults are sticky until :meth:`heal`; ``count=n`` makes a fault
    transient (auto-disarms after n dispatches — the retry-path test)."""

    def __init__(self):
        self._faults: Dict[Tuple[int, int], dict] = {}
        self.triggered = 0

    def fail(self, shard: int, replica: int, mode: str = "error", *,
             latency: float = 0.0, count: Optional[int] = None) -> None:
        if mode not in ("error", "timeout", "stale"):
            raise ValueError(f"unknown fault mode {mode!r}")
        self._faults[(shard, replica)] = {
            "mode": mode, "latency": float(latency), "count": count,
        }

    def heal(self, shard: Optional[int] = None,
             replica: Optional[int] = None) -> None:
        """Disarm faults: all of them, one shard's, or one replica's."""
        if shard is None:
            self._faults.clear()
            return
        for key in list(self._faults):
            if key[0] == shard and (replica is None or key[1] == replica):
                del self._faults[key]

    def before_dispatch(self, shard: int, replica: int) -> None:
        f = self._faults.get((shard, replica))
        if f is None:
            return
        if f["count"] is not None:
            f["count"] -= 1
            if f["count"] < 0:
                del self._faults[(shard, replica)]
                return
        self.triggered += 1
        if f["mode"] == "timeout":
            raise ReplicaTimeout(
                f"injected timeout on replica ({shard}, {replica})",
                latency=f["latency"],
            )
        if f["mode"] == "stale":
            raise StaleReplicaError(
                f"injected stale table on replica ({shard}, {replica})"
            )
        raise ReplicaFailure(
            f"injected error on replica ({shard}, {replica})",
            latency=f["latency"],
        )


# ------------------------------------------------------------------- policy
@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded retries with deadline-aware exponential backoff.

    ``max_attempts`` caps dispatches per shard per request. ``backoff_base``
    seconds doubles per retry (attempt i sleeps ``base · 2^(i-1)``), but a
    sleep is only taken when it FITS the remaining ``deadline`` budget —
    otherwise the shard gives up immediately (degrade beats blowing the
    caller's latency contract). ``deadline=None`` means unbudgeted (retries
    still bounded by ``max_attempts``). Set ``deadline`` to the
    micro-batcher's ``max_delay`` so queue wait + retries share one bound.
    """

    max_attempts: int = 3
    backoff_base: float = 1e-4
    deadline: Optional[float] = None

    def backoff(self, attempt: int) -> float:
        """Sleep before retry number ``attempt`` (1-based)."""
        return self.backoff_base * (2.0 ** max(0, attempt - 1))


# ------------------------------------------------------------------ replicas
def slab_device_index(s: int, r: int, n_replicas: int, n_devices: int) -> int:
    """Device index of replica ``r`` of shard ``s``: slabs are numbered
    shard by shard (``s·R + r``) and dealt round the D devices in turn, so
    every device holds at most ⌈S·R/D⌉ of them, and the R copies of one
    shard (R consecutive numbers) sit on R distinct devices whenever
    R ≤ D. Numbering replica by replica (``r·S + s``) would put both
    copies of a shard on one device at S = D."""
    return (s * n_replicas + r) % n_devices


def _slab_ready(slab) -> bool:
    return slab.is_ready()


@dataclasses.dataclass
class Replica:
    """One placed copy of one ψ row-range, with live health state."""

    shard: int
    idx: int                      # replica slot within the shard
    slab: jax.Array               # (rows_per, D)
    device: Optional[object]
    version: int
    alive: bool = True
    canary: bool = False          # staged next-version copy; not routed
    ready: bool = True            # False while a heal copies its slab in
    outstanding: int = 0          # in-flight dispatches (least_outstanding)
    served: int = 0
    failures: int = 0             # consecutive failures (reset on success)
    dead_reason: Optional[str] = None
    device_id: int = -1           # id of the device holding the slab
    admitted_at: Optional[float] = None   # heal: routed from (mesh clock)

    @property
    def key(self) -> Tuple[int, int]:
        return (self.shard, self.idx)


class ReplicaSet:
    """R health-tracked replicas of every shard of one table snapshot.

    Placement: replica r of shard s goes on
    ``devices[slab_device_index(s, r, R, D)]`` — at most ⌈S·R/D⌉ slabs per
    device, and (whenever R ≤ D) copies of the SAME row range on
    DIFFERENT devices, so one device loss never kills a range. Replica 0
    of each shard IS the table's shard (``publish`` cuts it onto that
    device), so no further copy of the catalogue is pinned anywhere.

    Routing (:meth:`pick`): ``round_robin`` cycles the live replicas of a
    shard (throughput); given the query's per-device dispatch counts, it
    takes the replica on the device that query has used least;
    ``least_outstanding`` picks the live replica with the fewest in-flight
    dispatches (tail latency). Dead replicas are never picked, nor are
    re-placed ones whose slab is still being copied while another copy can
    answer; a shard with zero live replicas has no route and the query
    layer degrades.
    """

    def __init__(
        self,
        table: PsiShardSet,
        n_replicas: int = 2,
        *,
        devices: Optional[Sequence] = None,
        policy: str = "round_robin",
    ):
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        if policy not in ("round_robin", "least_outstanding"):
            raise ValueError(f"unknown routing policy {policy!r}")
        self.table = table              # the published cut (replica 0s)
        self.n_replicas = int(n_replicas)
        self.devices = list(devices) if devices is not None else None
        self.policy = policy
        self.is_ready = _slab_ready     # is a copied-in slab resident yet
        self.pending: List[Replica] = []   # re-placed, not yet routed
        self._rr = [0] * table.n_shards
        self._device_use: Dict[int, int] = {}   # device id -> picks
        self.replicas: List[List[Replica]] = [
            [self._place(s, r) for r in range(self.n_replicas)]
            for s in range(table.n_shards)
        ]

    # ----------------------------------------------------------- placement
    def _device_for(self, s: int, r: int):
        if not self.devices:
            return None
        return self.devices[slab_device_index(s, r, self.n_replicas,
                                              len(self.devices))]

    def _place(self, s: int, r: int, device=None, source=None) -> Replica:
        dev = device if device is not None else self._device_for(s, r)
        slab = self.table.shards[s] if source is None else source
        if dev is not None:
            slab = jax.device_put(slab, dev)
        return Replica(shard=s, idx=r, slab=slab, device=dev,
                       version=self.table.version,
                       device_id=next(iter(slab.devices())).id)

    # ------------------------------------------------------------- health
    @property
    def n_shards(self) -> int:
        return self.table.n_shards

    @property
    def version(self) -> int:
        return self.table.version

    def live(self, s: int) -> List[Replica]:
        """Live replicas of shard ``s``, those still being copied in too."""
        return [r for r in self.replicas[s] if r.alive and not r.canary]

    def routable(self, s: int) -> List[Replica]:
        """Live replicas that may answer: the resident ones, or, where a
        shard has none, those still being copied in (better late than a
        hole in the catalogue)."""
        live = self.live(s)
        return [r for r in live if r.ready] or live

    def dead_shards(self) -> List[int]:
        return [s for s in range(self.n_shards) if not self.live(s)]

    def mark_dead(self, s: int, idx: int, reason: str = "failed") -> None:
        for rep in self.replicas[s]:
            if rep.idx == idx and rep.alive:
                rep.alive = False
                rep.dead_reason = reason

    def mark_live(self, s: int, idx: int) -> None:
        for rep in self.replicas[s]:
            if rep.idx == idx:
                rep.alive = True
                rep.failures = 0
                rep.dead_reason = None

    def admit_ready(self) -> Tuple[List[Replica], List[Replica]]:
        """Admit re-placed replicas whose slab is resident to routing;
        drop those that died while being copied in. Returns (admitted,
        dropped). Never blocks: it asks ``is_ready``."""
        admitted, dropped, waiting = [], [], []
        for rep in self.pending:
            if not rep.alive:
                dropped.append(rep)
            elif self.is_ready(rep.slab):
                rep.ready = True
                admitted.append(rep)
            else:
                waiting.append(rep)
        self.pending = waiting
        return admitted, dropped

    # ------------------------------------------------------------- routing
    def pick(self, s: int, load: Optional[Dict[int, int]] = None) -> Replica:
        """The replica shard ``s``'s next dispatch goes to. ``load`` (device
        id → dispatches so far in this query) steers ``round_robin`` to the
        device least used by this query; ties go to the device that has
        taken fewer of the set's dispatches, then in turn (turns alone can
        fall into step with another shard's and load one device)."""
        live = self.routable(s)
        if not live:
            raise ReplicaFailure(f"shard {s} has no live replica")
        if self.policy == "least_outstanding":
            return min(live, key=lambda r: (r.outstanding, r.idx))
        start = self._rr[s] % len(live)
        self._rr[s] += 1
        turn = live[start:] + live[:start]
        if load is None:
            return turn[0]
        use = self._device_use
        rep = min(turn, key=lambda r: (load.get(r.device_id, 0),
                                       use.get(r.device_id, 0)))
        use[rep.device_id] = use.get(rep.device_id, 0) + 1
        return rep

    # ----------------------------------------------------- re-placement
    def heal_source(self, s: int) -> Optional[Replica]:
        """The replica a heal of shard ``s`` copies from: a live, resident
        one; None where there is none (the table's cut is copied then)."""
        return next((r for r in self.live(s) if r.ready), None)

    def replace(self, s: int, *, device=None,
                source: Optional[Replica] = None) -> Replica:
        """Re-place shard ``s``'s orphaned row range as a fresh replica
        copied from ``source`` (a live replica; by default
        :meth:`heal_source`) — the per-shard mirror of
        ``ElasticMeshManager.on_failure`` (rebuild placement over the
        device set minus the casualties). The target is the least-loaded
        device that holds no dead replica and no live copy of ``s``;
        failing that, one with no dead replica; failing that, any. The
        copy is asynchronous: the replica counts as live at once but is
        routed only after :meth:`admit_ready` finds its slab resident. The
        new replica takes the lowest free slot index."""
        if source is None:
            source = self.heal_source(s)
        if device is None and self.devices:
            tainted = {id(r.device) for row in self.replicas for r in row
                       if not r.alive and r.device is not None}
            holding = {id(r.device) for r in self.live(s)}
            candidates = (
                [d for d in self.devices
                 if id(d) not in tainted and id(d) not in holding]
                or [d for d in self.devices if id(d) not in tainted]
                or list(self.devices))
            loads: Dict[int, int] = {}
            for row in self.replicas:
                for rep in row:
                    if rep.alive and rep.device is not None:
                        loads[id(rep.device)] = loads.get(id(rep.device), 0) + 1
            device = min(candidates, key=lambda d: loads.get(id(d), 0))
        used = {r.idx for r in self.replicas[s]}
        idx = next(i for i in itertools.count() if i not in used)
        rep = self._place(s, idx, device=device,
                          source=None if source is None else source.slab)
        rep.ready = False
        self.pending.append(rep)
        self.replicas[s].append(rep)
        return rep


# ------------------------------------------------------------------- health
class ShardHealthMonitor:
    """Per-replica query-latency watchdog for the serving mesh.

    Wraps :class:`repro.runtime.health.StragglerWatchdog` with
    ``(shard, replica)`` keys and query wall-times as the reported step
    times: a replica whose median latency exceeds the fleet median by
    ``threshold``× for ``patience`` consecutive checks comes back from
    :meth:`flagged` — the mesh then routes around it exactly like a hard
    failure (health-checked failover). Quiet (dead) replicas drop out of
    the baseline automatically (the watchdog's staleness horizon)."""

    def __init__(self, threshold: float = 3.0, patience: int = 3,
                 window: int = 16):
        self._wd = StragglerWatchdog(
            threshold=threshold, patience=patience, window=window
        )

    def observe(self, key: Tuple[int, int], latency: float) -> None:
        self._wd.report(key, latency)

    def flagged(self) -> List[Tuple[int, int]]:
        return list(self._wd.check())


# --------------------------------------------------------------------- mesh
class FaultTolerantRetrievalMesh:
    """Replicated, health-checked, degradation-aware retrieval service.

    Built from a model's φ builder and exported ψ table (the φ/ψ export
    contract, ``serve/README.md``)::

        mesh = FaultTolerantRetrievalMesh(
            lambda ctx: mf.build_phi(params, ctx),
            n_shards=4, n_replicas=2, k=100,
            retry=RetryPolicy(max_attempts=3, deadline=batcher.max_delay))
        mesh.publish(mf.export_psi(params))
        res = mesh.topk(user_ids)          # TopKResult
        res.coverage, res.dead_ranges      # the degradation contract

    Query semantics follow the kernel (``kernels/topk_score``): exact
    dense-``lax.top_k`` parity, ascending-id tie policy, (−inf, −1) on
    slots with no admissible candidate, and results bit-identical to one
    kernel call over the whole table at any shard count whenever every
    row range has ≥ 1 live replica — replicas are exact copies running
    the same program, so a mid-stream replica kill under R ≥ 2 is
    invisible in the results. When a range has NO live replica the query
    completes over the survivors with ``coverage < 1.0`` and the dead
    ranges reported.

    ``publish`` snapshots are versioned double-buffered ReplicaSets
    (``serve/publish.py``): each query grabs the active set once and
    serves the whole request from it; the canary methods implement the
    staged rollout (see module docstring and ``publish.StagedRollout``).
    """

    def __init__(
        self,
        phi_fn: Optional[Callable[..., jax.Array]] = None,
        *,
        n_shards: int = 2,
        n_replicas: int = 2,
        k: int = 100,
        block_items: Optional[int] = None,
        devices: Optional[Sequence] = None,
        policy: str = "round_robin",
        retry: Optional[RetryPolicy] = None,
        injector: Optional[FaultInjector] = None,
        monitor: Optional[ShardHealthMonitor] = None,
        fail_threshold: int = 1,
        auto_heal: bool = False,
        clock: Callable[[], float] = time.monotonic,
        sleep: Optional[Callable[[float], None]] = None,
        psi_table: Optional[jax.Array] = None,
        retrieval: str = "exact",
        ann=None,                                  # serve.ann.AnnConfig
        registry=None,
        tracer=None,
    ):
        from repro.serve.publish import VersionedTable

        if retrieval not in ("exact", "ivf"):
            raise ValueError(f"retrieval must be 'exact' or 'ivf', got {retrieval!r}")
        self.retrieval = retrieval
        self.ann = ann
        self._ivf: Dict[int, tuple] = {}   # table version → per-shard indexes
        self.phi_fn = phi_fn
        self.n_shards = int(n_shards)
        self.n_replicas = int(n_replicas)
        self.k = int(k)
        self.block_items = block_items
        self.devices = devices
        self.policy = policy
        self.retry = retry or RetryPolicy()
        self.injector = injector
        self.monitor = monitor or ShardHealthMonitor()
        self.fail_threshold = int(fail_threshold)
        self.auto_heal = bool(auto_heal)
        self.clock = clock
        self.sleep = sleep if sleep is not None else (lambda dt: None)
        self._set = VersionedTable()
        self._canary: Optional[PsiShardSet] = None
        # counters live on the metrics registry (obs/metrics.py) with a
        # per-instance label; ``self.stats`` is the live back-compat view.
        # ``tracer`` opts into dispatch/retry/failover spans that nest
        # under the batcher's flush span (one trace per request).
        self.registry = resolve_registry(registry)
        self.tracer = tracer
        reg, inst = self.registry, next_instance_id()
        self._inst = inst
        lab = ("instance",)

        def _c(name, help_text):
            return reg.counter(name, help_text, labels=lab).labels(
                instance=inst)

        counter_specs = {
            "queries": ("serve_mesh_queries_total", "topk_phi requests"),
            "dispatches": ("serve_mesh_dispatches_total",
                           "per-replica dispatch attempts"),
            "failovers": ("serve_mesh_failovers_total",
                          "failovers to another live replica"),
            "retries": ("serve_mesh_retries_total",
                        "same-set retries (after backoff)"),
            "faults": ("serve_mesh_faults_total",
                       "dispatches that raised (real or injected)"),
            "replicas_died": ("serve_mesh_replicas_died_total",
                              "replicas marked dead"),
            "replicas_replaced": ("serve_mesh_replicas_replaced_total",
                                  "replicas re-placed by heal()"),
            "degraded_queries": ("serve_mesh_degraded_queries_total",
                                 "queries answered with coverage < 1"),
            "backoff_slept_s": ("serve_mesh_backoff_slept_seconds_total",
                                "total backoff sleep"),
            "deadline_gaveups": ("serve_mesh_deadline_gaveups_total",
                                 "shards given up on over the deadline "
                                 "budget"),
            "fault_burned_s": ("serve_mesh_fault_burned_seconds_total",
                               "deadline budget burned by failed "
                               "dispatches (real wall time + injected "
                               "fault latency)"),
            "heals": ("serve_mesh_heals_total", "heal() invocations"),
            "canary_staged": ("serve_mesh_canary_staged_total",
                              "canary tables staged"),
            "canary_promoted": ("serve_mesh_canary_promoted_total",
                                "canaries promoted live"),
            "canary_rolled_back": ("serve_mesh_canary_rolled_back_total",
                                   "canaries rolled back"),
        }
        self._m = {key: _c(name, help_text)
                   for key, (name, help_text) in counter_specs.items()}
        _float_keys = ("backoff_slept_s", "fault_burned_s")
        self.stats = StatsView({
            key: (lambda ch=ch: ch.value) if key in _float_keys
            else (lambda ch=ch: int(ch.value))
            for key, ch in self._m.items()
        })
        self._m_version = reg.gauge(
            "serve_mesh_version", "live table version", labels=lab,
        ).labels(instance=inst)
        self._m_coverage = reg.gauge(
            "serve_mesh_coverage", "coverage of the last query", labels=lab,
        ).labels(instance=inst)
        self._lat_fam = reg.histogram(
            "serve_mesh_replica_latency_seconds",
            "per-(shard,replica) dispatch wall time (the health monitor's "
            "own observations)", labels=("instance", "shard", "replica"))
        self._lat_children: Dict[Tuple[int, int], object] = {}
        self._dev_fam = reg.counter(
            "serve_mesh_device_dispatches_total",
            "per-replica dispatch attempts by the device holding the slab",
            labels=("instance", "device"))
        self._dev_children: Dict[int, object] = {}
        self._healing: Dict[Tuple[int, int], object] = {}   # open heal spans
        if psi_table is not None:
            self.publish(psi_table)

    # ------------------------------------------------------------- publish
    def publish(self, psi_table: jax.Array) -> int:
        """Shard, replicate, version, and atomically flip a ψ snapshot
        live (the unstaged path — see :meth:`begin_canary` for the staged
        rollout). Returns the new version.

        With ``devices``, each shard is cut straight onto the device of
        its replica 0 and the other replicas copy it from there: no device
        holds a cut beside the whole table, and a host (numpy) table never
        lands whole on any device."""
        first = None
        if self.devices:
            first = [self.devices[slab_device_index(
                s, 0, self.n_replicas, len(self.devices))]
                for s in range(self.n_shards)]
        version = self._set.publish(
            lambda version: ReplicaSet(
                shard_psi(psi_table, self.n_shards, devices=first,
                          version=version),
                self.n_replicas, devices=self.devices, policy=self.policy,
            )
        )
        self._m_version.set(version)
        return version

    def publish_delta(self, rows, ids) -> int:
        """Incremental publish for fold-in rows: patch/append ψ ``rows`` at
        global item ``ids`` onto the authoritative table copy and flip the
        rebuilt ReplicaSet live under a normal version bump. Every replica
        is rebuilt at the new version, so the stale-refusal guard
        (:class:`StaleReplicaError` before dispatch) keeps holding; a
        staged canary (if any) must be resolved first — its row geometry
        may no longer match after an append. Returns the new version."""
        from repro.serve.publish import apply_delta, dense_table

        if self._canary is not None:
            raise RuntimeError(
                "cannot delta-publish with a canary staged — promote or "
                "roll it back first"
            )
        old_table = self.table
        old_indexes = self._ivf.get(old_table.version)
        base = dense_table(old_table)
        version = self.publish(jnp.asarray(apply_delta(base, rows, ids)))
        if self.retrieval == "ivf" and old_indexes is not None:
            # fold the delta into the live indexes (nearest-cluster append,
            # staleness-counted; see serve/ann.py) instead of re-running
            # k-means per delta — unless the shard geometry changed
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

    def _ivf_indexes(self, table: PsiShardSet) -> tuple:
        """Per-shard IVF indexes for one snapshot, lazily built and keyed
        on the publish version. Shared by every replica of a shard — the
        index is a function of the shard's CONTENT, which replicas mirror
        bit-exactly, so failover never changes the index either."""
        cached = self._ivf.get(table.version)
        if cached is None:
            from repro.serve.ann import build_shard_indexes

            cached = build_shard_indexes(table, self._ann_cfg())
            self._ivf = {table.version: cached}
        return cached

    @property
    def replica_set(self) -> ReplicaSet:
        return self._set.active

    @property
    def table(self) -> PsiShardSet:
        return self.replica_set.table

    @property
    def version(self) -> int:
        return self._set.version

    @property
    def n_items(self) -> int:
        return self.table.n_items

    # -------------------------------------------------------------- health
    def apply_health_check(self) -> List[Tuple[int, int]]:
        """Route around latency stragglers: every replica the monitor
        flags is marked dead (reason ``"slow"``). Returns the casualties.
        Call from the serving loop's cadence (or rely on per-query hard
        failures — both paths end in the same routing state)."""
        reaped = []
        rs = self._set.active
        for (s, idx) in self.monitor.flagged():
            live = {r.idx for r in rs.live(s)}
            if idx in live:
                rs.mark_dead(s, idx, reason="slow")
                self._m["replicas_died"].inc()
                reaped.append((s, idx))
        if reaped and self.auto_heal:
            self.heal()
        return reaped

    def heal(self) -> List[Tuple[int, int]]:
        """Re-place orphaned capacity: every shard below its replication
        target gets fresh replicas, each copied from a live replica of the
        shard onto a device with no dead replica and no live copy of it
        (:meth:`ReplicaSet.replace`). The copies run in the background;
        each replica is routed once its slab is resident. With a tracer,
        a ``heal`` span runs from the copy's start to that admission.
        Returns the new (shard, idx) pairs."""
        rs = self._set.active
        self._m["heals"].inc()
        placed = []
        for s in range(rs.n_shards):
            while len(rs.live(s)) < self.n_replicas:
                src = rs.heal_source(s)
                src_slab = rs.table.shards[s] if src is None else src.slab
                rep = rs.replace(s, source=src)
                self._m["replicas_replaced"].inc()
                placed.append(rep.key)
                if self.tracer is not None:
                    self._healing[rep.key] = self.tracer.begin(
                        "heal", parent=None, shard=s, replica=rep.idx,
                        src_device=next(iter(src_slab.devices())).id,
                        dst_device=rep.device_id,
                        bytes=int(rep.slab.nbytes))
        return placed

    def _admit(self, rs: ReplicaSet) -> None:
        """Route re-placed replicas whose slab has become resident."""
        admitted, dropped = rs.admit_ready()
        for rep in admitted:
            rep.admitted_at = self.clock()
        for rep in admitted + dropped:
            sp = self._healing.pop(rep.key, None)
            if sp is not None:
                self.tracer.end(sp, admitted=rep.alive)

    def _replica_latency(self, s: int, idx: int):
        ch = self._lat_children.get((s, idx))
        if ch is None:
            ch = self._lat_fam.labels(
                instance=self._inst, shard=str(s), replica=str(idx))
            self._lat_children[(s, idx)] = ch
        return ch

    # --------------------------------------------------------------- query
    def phi(self, *query) -> jax.Array:
        return jnp.asarray(self.phi_fn(*query), jnp.float32)

    def topk(self, *query, k: Optional[int] = None,
             exclude_ids: Optional[jax.Array] = None,
             budget: Optional[float] = None) -> TopKResult:
        return self.topk_phi(
            self.phi(*query), k=k, exclude_ids=exclude_ids, budget=budget,
        )

    def topk_phi(
        self,
        phi_rows: jax.Array,
        *,
        k: Optional[int] = None,
        exclude_ids: Optional[jax.Array] = None,
        budget: Optional[float] = None,
    ) -> TopKResult:
        """(B, k) :class:`TopKResult` with the degradation contract.

        ``exclude_ids`` is the kernel's (B, L) −1-padded global id form
        (``kernels.topk_score.ops.exclude_ids_from_lists``). ``budget``
        (seconds) overrides ``retry.deadline`` as this request's
        retry allowance — the batcher path sets it so queue wait plus
        retries stay inside ``max_delay``. The whole request is served
        from ONE ReplicaSet snapshot (version-consistent)."""
        rs = self._set.active  # one snapshot end-to-end
        if rs.pending:
            self._admit(rs)
        table = rs.table
        k = k or self.k
        if self.devices and isinstance(phi_rows, np.ndarray):
            # host rows go straight to each dispatched replica's device
            phi_rows = phi_rows.astype(np.float32, copy=False)
        else:
            phi_rows = jnp.asarray(phi_rows, jnp.float32)
        b = int(phi_rows.shape[0])
        indexes = None
        block_items = self.block_items
        if self.retrieval == "ivf":
            # IVF dispatch resolves its own per-block tiling; the replica
            # failover/retry/health machinery below is retrieval-agnostic
            indexes = self._ivf_indexes(table)
        elif block_items is None:
            excl_l = 0 if exclude_ids is None else int(exclude_ids.shape[1])
            block_items = resolve_cluster_block_items(
                table, b, k, excl_l=excl_l
            )
        self._m["queries"].inc()
        budget = self.retry.deadline if budget is None else budget
        parts_s, parts_i, dead = [], [], []
        # device id -> this query's dispatches; a device still receiving a
        # heal's copy counts as used, so the query goes elsewhere if it can
        load: Dict[int, int] = {r.device_id: 1 for r in rs.pending}
        for s in range(table.n_shards):
            out = self._query_shard(
                rs, s, phi_rows, k, exclude_ids, block_items, budget, load,
                indexes=indexes,
            )
            if out is None:
                dead.append(s)
            else:
                parts_s.append(out[0])
                parts_i.append(out[1])
        if dead:
            self._m["degraded_queries"].inc()
        coverage = coverage_fraction(table, dead)
        ranges = dead_item_ranges(table, dead)
        self._m_coverage.set(coverage)
        if not parts_s:
            es, ei = empty_topk(b, k)
            return TopKResult(es, ei, coverage, ranges)
        if len(parts_s) == 1:
            return TopKResult(parts_s[0], parts_i[0], coverage, ranges)
        merge_span = None
        if self.tracer is not None:
            merge_span = self.tracer.begin(
                "merge", shards=len(parts_s), k=k)
        ms, mi = topk_merge_shards(
            jnp.stack(colocate_parts(parts_s)),
            jnp.stack(colocate_parts(parts_i)), k,
        )
        if merge_span is not None:
            self.tracer.end(merge_span)
        return TopKResult(ms, mi, coverage, ranges)

    # ----------------------------------------------------------- internals
    def _device_dispatches(self, device_id: int):
        ch = self._dev_children.get(device_id)
        if ch is None:
            ch = self._dev_fam.labels(instance=self._inst,
                                      device=str(device_id))
            self._dev_children[device_id] = ch
        return ch

    def _query_shard(self, rs, s, phi_rows, k, exclude_ids, block_items,
                     budget, load, indexes=None):
        """One shard's dispatch with failover + bounded deadline-aware
        retries. Returns (scores, ids) or None (shard unavailable for this
        request — the degradation path). ``indexes`` (IVF mode) swaps the
        exact slab sweep for the shard's index dispatch; every replica of
        a shard shares the index (replicas are bit-exact content copies),
        so the fault/stale/latency machinery wraps both paths identically."""
        spent = 0.0       # latency burned: real + injected + backoff
        attempt = 0
        tr = self.tracer
        while attempt < self.retry.max_attempts:
            live = rs.live(s)
            if not live:
                return None
            attempt += 1
            rep = rs.pick(s, load)
            rep.outstanding += 1
            load[rep.device_id] = load.get(rep.device_id, 0) + 1
            self._device_dispatches(rep.device_id).inc()
            sp = None
            if tr is not None:
                walk = {}
                if exclude_ids is not None and indexes is None:
                    rows = rs.table.rows_per
                    walk["excl_rounds"], walk["excl_rounds_full"] = (
                        excl_walk_rounds(exclude_ids, rows, block_items,
                                         id_offset=s * rows))
                sp = tr.begin("dispatch", shard=s, replica=rep.idx,
                              attempt=attempt, device=rep.device_id, **walk)
            t0 = self.clock()
            try:
                if self.injector is not None:
                    self.injector.before_dispatch(s, rep.idx)
                if rep.version != rs.version:
                    raise StaleReplicaError(
                        f"replica ({s}, {rep.idx}) serves table v"
                        f"{rep.version}, live is v{rs.version}"
                    )
                if indexes is not None:
                    if indexes[s] is None:   # shard owns no valid rows
                        ss, ii = empty_topk(int(phi_rows.shape[0]), k)
                    else:
                        ss, ii = indexes[s].topk(
                            phi_rows, k, exclude_ids=exclude_ids,
                            registry=self.registry,
                        )
                else:
                    ss, ii = shard_topk(
                        rs.table, s, phi_rows, k, slab=rep.slab,
                        exclude_ids=exclude_ids, block_items=block_items,
                    )
                lat = self.clock() - t0
                self.monitor.observe(rep.key, lat)
                self._replica_latency(s, rep.idx).observe(lat)
                rep.served += 1
                rep.failures = 0
                self._m["dispatches"].inc()
                if sp is not None:
                    tr.end(sp, outcome="ok")
                return ss, ii
            except ReplicaFailure as e:
                lat = max(self.clock() - t0, e.latency)
                spent += lat
                self._m["dispatches"].inc()
                self._m["faults"].inc()
                # the satellite: burned deadline budget — real wall time
                # OR the injected fault's declared latency, whichever the
                # retry loop actually charged against the budget
                self._m["fault_burned_s"].inc(lat)
                if sp is not None:
                    tr.end(sp, outcome=type(e).__name__, burned_s=lat)
                rep.failures += 1
                if isinstance(e, ReplicaTimeout):
                    self.monitor.observe(rep.key, lat)
                    self._replica_latency(s, rep.idx).observe(lat)
                if rep.failures >= self.fail_threshold:
                    rs.mark_dead(s, rep.idx, reason=type(e).__name__)
                    self._m["replicas_died"].inc()
                    if self.auto_heal:
                        self.heal()
                        for r in rs.pending:    # the copies' targets
                            load.setdefault(r.device_id, 1)
            finally:
                rep.outstanding -= 1
            # burned latency (real + injected) already exhausted the
            # budget: even a free failover dispatch would answer late
            if budget is not None and spent >= budget:
                self._m["deadline_gaveups"].inc()
                return None
            # failover beats backoff: another live replica is already warm
            if any(r.idx != rep.idx for r in rs.live(s)):
                self._m["failovers"].inc()
                if tr is not None:
                    tr.end(tr.begin("failover", shard=s,
                                    from_replica=rep.idx))
                continue
            # same (possibly healed) set again: exponential backoff, but
            # only if the sleep FITS the remaining deadline budget
            if attempt >= self.retry.max_attempts:
                break
            back = self.retry.backoff(attempt)
            if budget is not None:
                remaining = budget - spent
                if remaining <= 0.0 or back >= remaining:
                    self._m["deadline_gaveups"].inc()
                    return None
            self._m["retries"].inc()
            self._m["backoff_slept_s"].inc(back)
            if tr is not None:
                tr.end(tr.begin("retry", shard=s, backoff_s=back))
            spent += back
            self.sleep(back)
        return None

    # ----------------------------------------------------- staged rollout
    def begin_canary(self, psi_table: jax.Array) -> int:
        """Stage the next ψ table on ONE canary replica per shard (slot
        R, off the routing path). Readers keep hitting the live version;
        nothing observable changes until :meth:`promote_canary`. Returns
        the staged version number."""
        if self._canary is not None:
            raise RuntimeError(
                "a canary is already staged — promote or roll it back first"
            )
        rs = self._set.active
        staged = shard_psi(
            psi_table, self.n_shards, version=self.version + 1
        )
        self._canary = staged
        for s in range(staged.n_shards):
            slab = staged.shards[s]
            dev = rs._device_for(s, self.n_replicas)
            if dev is not None:
                slab = jax.device_put(slab, dev)
            rep = Replica(
                shard=s, idx=max(r.idx for r in rs.replicas[s]) + 1,
                slab=slab, device=dev, version=staged.version, canary=True,
            )
            rs.replicas[s].append(rep)
        self._m["canary_staged"].inc()
        return staged.version

    def canary_topk_phi(self, phi_rows, *, k=None,
                        exclude_ids=None) -> TopKResult:
        """Query the CANARY replicas only (mirrored traffic). Not routed
        to users; exists so the rollout can health-check the staged table
        under real query shapes before anyone sees it."""
        if self._canary is None:
            raise RuntimeError("no canary staged")
        staged = self._canary
        k = k or self.k
        phi_rows = jnp.asarray(phi_rows, jnp.float32)
        block_items = self.block_items
        if block_items is None:
            excl_l = 0 if exclude_ids is None else int(exclude_ids.shape[1])
            block_items = resolve_cluster_block_items(
                staged, int(phi_rows.shape[0]), k, excl_l=excl_l
            )
        parts_s, parts_i = [], []
        rs = self._set.active
        for s in range(staged.n_shards):
            canaries = [r for r in rs.replicas[s] if r.canary]
            slab = canaries[0].slab if canaries else staged.shards[s]
            ss, ii = shard_topk(
                staged, s, phi_rows, k, slab=slab, exclude_ids=exclude_ids,
                block_items=block_items,
            )
            parts_s.append(ss)
            parts_i.append(ii)
        if len(parts_s) == 1:
            return TopKResult(parts_s[0], parts_i[0])
        ms, mi = topk_merge_shards(
            jnp.stack(colocate_parts(parts_s)),
            jnp.stack(colocate_parts(parts_i)), k,
        )
        return TopKResult(ms, mi)

    def mirror_check(
        self,
        phi_rows: jax.Array,
        *,
        k: Optional[int] = None,
        validate: Optional[Callable[[TopKResult, TopKResult], bool]] = None,
    ) -> dict:
        """Health-check the canary under mirrored traffic: run ``phi_rows``
        against BOTH the live table and the canary replicas and judge the
        canary's answers. Built-in checks: well-formed shapes, no NaN, no
        +/-inf scores on admissible slots, ids in catalogue range.
        ``validate(live_result, canary_result)`` adds a caller policy
        (e.g. rank-overlap or quality thresholds). Returns a report dict;
        ``report["healthy"]`` is the promote/rollback verdict."""
        if self._canary is None:
            raise RuntimeError("no canary staged")
        k = k or self.k
        live_res = self.topk_phi(phi_rows, k=k)
        t0 = self.clock()
        canary_res = self.canary_topk_phi(phi_rows, k=k)
        latency = self.clock() - t0
        ids = np.asarray(canary_res.ids)
        scores = np.asarray(canary_res.scores)
        n_items = self._canary.n_items
        admissible = ids >= 0
        checks = {
            "shape_ok": ids.shape == np.asarray(live_res.ids).shape,
            "ids_in_range": bool(((ids >= -1) & (ids < n_items)).all()),
            "scores_finite": bool(
                np.isfinite(scores[admissible]).all()
                if admissible.any() else True
            ),
            "not_all_empty": bool(admissible.any()),
        }
        if validate is not None:
            checks["validate_ok"] = bool(validate(live_res, canary_res))
        report = {
            "healthy": all(checks.values()),
            "checks": checks,
            "staged_version": self._canary.version,
            "live_version": self.version,
            "mirror_rows": int(np.asarray(phi_rows).shape[0]),
            "canary_latency_s": latency,
        }
        return report

    def promote_canary(self) -> int:
        """Flip the staged table live everywhere: the canary slabs seed
        replica 0 and the remaining R−1 replicas are placed fresh — one
        atomic ReplicaSet swap, in-flight queries finish on the old
        snapshot (the drainless rollout)."""
        if self._canary is None:
            raise RuntimeError("no canary staged")
        staged = self._canary

        def build(version: int) -> ReplicaSet:
            table = PsiShardSet(
                shards=staged.shards, n_items=staged.n_items,
                rows_per=staged.rows_per, version=version,
            )
            return ReplicaSet(
                table, self.n_replicas, devices=self.devices,
                policy=self.policy,
            )

        version = self._set.publish(build)
        self._canary = None
        self._m["canary_promoted"].inc()
        self._m_version.set(version)
        return version

    def rollback_canary(self) -> None:
        """Drop the staged table: remove the canary replicas, keep serving
        the live version untouched — the no-downtime bad-table path."""
        if self._canary is None:
            raise RuntimeError("no canary staged")
        rs = self._set.active
        for s in range(rs.n_shards):
            rs.replicas[s] = [r for r in rs.replicas[s] if not r.canary]
        self._canary = None
        self._m["canary_rolled_back"].inc()
