"""Chaos suite for the fault-tolerant serving mesh (serve/mesh.py):
replica kills mid-traffic, failover parity, graceful degradation with the
coverage/dead-range contract, deadline-bounded retries, health-checked
latency failover, re-placement, and the canary staged-publish protocol.

Every failure is driven through the injectable FaultInjector and simulated
clocks — deterministic chaos, no real processes harmed."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _zoo import _rand

from repro.core.models import mf
from repro.kernels.topk_score import topk_score_ref
from repro.kernels.topk_score.ops import exclude_ids_from_lists
from repro.kernels.topk_score.ref import (
    SCORE_ATOL,
    SCORE_RTOL,
    exclude_ids_to_mask,
)
from repro.serve.batcher import MicroBatcher
from repro.serve.cluster import dead_item_ranges, shard_psi
from repro.serve.mesh import (
    FaultInjector,
    FaultTolerantRetrievalMesh,
    ReplicaSet,
    RetryPolicy,
    ShardHealthMonitor,
)
from repro.serve.publish import StagedRollout


def _mesh(phi, psi, *, n_shards=4, n_replicas=2, k=13, injector=None,
          retry=None, **kw):
    mesh = FaultTolerantRetrievalMesh(
        lambda p=phi: p, n_shards=n_shards, n_replicas=n_replicas, k=k,
        block_items=32, injector=injector or FaultInjector(),
        retry=retry or RetryPolicy(max_attempts=3, backoff_base=1e-4),
        **kw,
    )
    mesh.publish(psi)
    return mesh


def test_kill_each_replica_in_turn_bit_identical():
    """THE acceptance criterion: with R=2, killing each replica in turn
    mid-traffic leaves every answer bit-identical (ids AND scores) to the
    healthy cluster / dense oracle — failover is invisible in results."""
    phi, psi = _rand((9, 16), 0), _rand((101, 16), 1)
    rs_ref, ri_ref = topk_score_ref(phi, psi, 13)
    inj = FaultInjector()
    mesh = _mesh(phi, psi, injector=inj)
    healthy_s, healthy_i = mesh.topk()
    np.testing.assert_array_equal(np.asarray(healthy_i), np.asarray(ri_ref))
    for s in range(4):
        for r in range(2):
            before = inj.triggered
            inj.fail(s, r, "error")
            # two queries: round-robin guarantees the killed replica is
            # routed to exactly once mid-traffic, whatever the rr phase
            for _ in range(2):
                res = mesh.topk()
                assert res.coverage == 1.0 and res.dead_ranges == ()
                np.testing.assert_array_equal(
                    np.asarray(res.ids), np.asarray(healthy_i)
                )
                assert bool(
                    (np.asarray(res.scores) == np.asarray(healthy_s)).all()
                ), f"scores not bit-identical after killing replica ({s},{r})"
            assert inj.triggered == before + 1  # the kill really was hit
            inj.heal(s, r)
            mesh.replica_set.mark_live(s, r)  # replica restarts before next
    assert mesh.stats["faults"] == 8 and mesh.stats["failovers"] == 8


def test_unreplicated_shard_kill_degrades_with_coverage_and_ranges():
    """R=1 and a shard killed: the query COMPLETES over the survivors and
    reports coverage < 1 plus the exact dead row range; surviving ids are
    bit-identical to the oracle restricted to surviving ranges."""
    phi, psi = _rand((7, 16), 2), _rand((101, 16), 3)
    inj = FaultInjector()
    mesh = _mesh(phi, psi, n_replicas=1, k=30, injector=inj)
    inj.fail(2, 0, "error")
    res = mesh.topk()
    table = mesh.table
    lo, hi = 2 * table.rows_per, min(3 * table.rows_per, 101)
    assert res.degraded and res.dead_ranges == ((lo, hi),)
    assert res.coverage == pytest.approx(1.0 - (hi - lo) / 101)
    # survivors: oracle over the catalogue with the dead range masked out
    mask = np.zeros((7, 101), bool)
    mask[:, lo:hi] = True
    rs_ref, ri_ref = topk_score_ref(phi, psi, 30, jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri_ref))
    # kernel vs reference: the stated fp32 score contract (ref.py)
    np.testing.assert_allclose(np.asarray(res.scores), np.asarray(rs_ref),
                               rtol=SCORE_RTOL, atol=SCORE_ATOL)
    assert not np.isin(np.asarray(res.ids), np.arange(lo, hi)).any()
    # every shard dead: still completes, loudly all-empty
    for s in range(4):
        inj.fail(s, 0, "error")
    res2 = mesh.topk()
    assert res2.coverage == 0.0
    assert bool((np.asarray(res2.ids) == -1).all())
    assert bool(np.isneginf(np.asarray(res2.scores)).all())
    assert res2.dead_ranges == ((0, 101),)  # coalesced across shards


def test_retry_backoff_respects_deadline_budget():
    """Retries must never blow the caller's latency contract: total
    backoff + burned fault latency stays inside the budget, and a retry
    that would not fit is abandoned (degrade, don't be late)."""
    phi, psi = _rand((4, 8), 4), _rand((40, 8), 5)
    inj = FaultInjector()
    budget = 5e-3
    mesh = _mesh(
        phi, psi, n_shards=2, n_replicas=1, injector=inj, k=9,
        retry=RetryPolicy(max_attempts=10, backoff_base=1e-3,
                          deadline=budget),
        fail_threshold=100,  # keep the replica alive: force the retry path
    )
    # transient: two failures then healthy — retries recover within budget
    inj.fail(0, 0, "error", count=2)
    res = mesh.topk()
    assert res.coverage == 1.0
    assert mesh.stats["backoff_slept_s"] <= budget
    rs_ref, ri_ref = topk_score_ref(phi, psi, 9)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri_ref))
    # sticky timeout burning budget: gives up inside the budget, degrades
    inj.heal()
    before = mesh.stats["backoff_slept_s"]
    inj.fail(1, 0, "timeout", latency=4e-3)
    res2 = mesh.topk()
    assert res2.degraded
    assert mesh.stats["deadline_gaveups"] >= 1
    # one 4ms burned fault leaves ~1ms: the backoff must NOT be slept
    assert mesh.stats["backoff_slept_s"] - before < 1e-3


def test_mesh_budget_never_exceeds_batcher_max_delay():
    """The batcher wiring: retry deadline = max_delay ⇒ worst-case added
    service delay (faults + backoffs) stays within the flush contract."""
    phi, psi = _rand((6, 8), 6), _rand((40, 8), 7)
    inj = FaultInjector()
    max_delay = 2e-3
    mesh = _mesh(
        phi, psi, n_shards=2, n_replicas=2, injector=inj, k=9,
        retry=RetryPolicy(max_attempts=5, backoff_base=1e-3,
                          deadline=max_delay),
    )
    batcher = MicroBatcher(
        lambda phi_rows, eids: mesh.topk_phi(phi_rows, exclude_ids=eids),
        max_batch=4, max_delay=max_delay,
        clock=lambda: 0.0, version_fn=lambda: mesh.version,
    )
    inj.fail(0, 0, "timeout", latency=1.5e-3)
    inj.fail(0, 1, "timeout", latency=1.5e-3)
    tickets = [batcher.submit(np.asarray(phi)[r]) for r in range(4)]
    leftovers = batcher.drain()
    spent = mesh.stats["backoff_slept_s"]
    assert spent <= max_delay, (
        f"retry backoff {spent} blew the batcher max_delay {max_delay}"
    )
    # both replicas of shard 0 burned the budget: per-request degradation
    # is reported on the tickets rather than a blown deadline
    for t in tickets:
        got = leftovers.get(t) or batcher.result(t)
        assert got is not None
    assert mesh.stats["deadline_gaveups"] >= 1


def test_latency_straggler_flagged_and_routed_around():
    """Health-checked failover: a replica that answers but SLOWLY gets
    flagged by the latency watchdog and marked dead — subsequent traffic
    routes around it with parity intact."""
    phi, psi = _rand((5, 8), 8), _rand((60, 8), 9)
    clock = {"t": 0.0, "step": 1e-4}
    slow = {(1, 0): 5e-2}  # the scripted straggler: 500x the fleet

    def fake_clock():
        clock["t"] += clock["step"]
        return clock["t"]

    monitor = ShardHealthMonitor(threshold=3.0, patience=2, window=8)
    mesh = _mesh(phi, psi, n_shards=2, n_replicas=2, k=9, clock=fake_clock,
                 monitor=monitor)
    rs_ref, ri_ref = topk_score_ref(phi, psi, 9)
    reaped = []
    for _round in range(8):
        res = mesh.topk()
        np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri_ref))
        # layer the scripted straggler profile on the real observations
        for (s, r), lat in slow.items():
            monitor.observe((s, r), lat)
        reaped = mesh.apply_health_check()
        if reaped:
            break
    assert (1, 0) in [tuple(k) for k in reaped]
    live_idx = {r.idx for r in mesh.replica_set.live(1)}
    assert 0 not in live_idx  # routed around
    res = mesh.topk()
    assert res.coverage == 1.0
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri_ref))


def test_heal_replaces_orphaned_range_on_surviving_devices():
    """Re-placement: after a shard loses replicas, heal() rebuilds them
    from the authoritative copy (ElasticMeshManager recovery shape) and
    full-coverage serving resumes."""
    phi, psi = _rand((5, 8), 10), _rand((60, 8), 11)
    inj = FaultInjector()
    devices = list(jax.devices()) * 2  # degenerate single-host placement
    mesh = _mesh(phi, psi, n_shards=3, n_replicas=2, k=9, injector=inj,
                 devices=devices)
    inj.fail(1, 0, "error")
    inj.fail(1, 1, "error")
    res = mesh.topk()
    assert res.degraded
    inj.heal()
    placed = mesh.heal()
    assert len(placed) == 2 and all(s == 1 for s, _ in placed)
    assert len(mesh.replica_set.live(1)) == 2
    res2 = mesh.topk()
    assert res2.coverage == 1.0 and res2.dead_ranges == ()
    rs_ref, ri_ref = topk_score_ref(phi, psi, 9)
    np.testing.assert_array_equal(np.asarray(res2.ids), np.asarray(ri_ref))
    assert mesh.stats["replicas_replaced"] == 2


def test_auto_heal_restores_replication_after_kill():
    phi, psi = _rand((4, 8), 12), _rand((40, 8), 13)
    inj = FaultInjector()
    mesh = _mesh(phi, psi, n_shards=2, n_replicas=2, k=9, injector=inj,
                 auto_heal=True)
    inj.fail(0, 0, "error", count=1)  # transient: one dispatch fails
    res = mesh.topk()
    assert res.coverage == 1.0
    assert len(mesh.replica_set.live(0)) == 2  # healed back to target R


def test_stale_replica_refused_and_routed_around():
    """A replica stuck on an old table version must not answer: its
    dispatch is refused pre-kernel and traffic fails over."""
    phi, psi = _rand((5, 8), 14), _rand((40, 8), 15)
    inj = FaultInjector()
    mesh = _mesh(phi, psi, n_shards=2, n_replicas=2, k=9, injector=inj)
    inj.fail(1, 0, "stale")
    res = mesh.topk()
    assert res.coverage == 1.0
    rs_ref, ri_ref = topk_score_ref(phi, psi, 9)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri_ref))
    dead = [r for r in mesh.replica_set.replicas[1] if not r.alive]
    assert any(r.dead_reason == "StaleReplicaError" for r in dead)


def test_routing_policies_spread_and_prefer_idle():
    phi, psi = _rand((4, 8), 16), _rand((40, 8), 17)
    mesh = _mesh(phi, psi, n_shards=2, n_replicas=2, k=9)
    for _ in range(4):
        mesh.topk()
    served = [rep.served for rep in mesh.replica_set.replicas[0]]
    assert served == [2, 2]  # round-robin splits evenly
    # least_outstanding: a busy replica is avoided
    rs = ReplicaSet(shard_psi(psi, 2), 2, policy="least_outstanding")
    rs.replicas[0][0].outstanding = 5
    assert rs.pick(0).idx == 1
    rs.replicas[0][0].outstanding = 0
    assert rs.pick(0).idx == 0  # idx tiebreak


@pytest.mark.parametrize("n_shards,n_replicas,n_devices",
                         [(2, 2, 4), (4, 1, 4), (4, 2, 4), (3, 2, 4),
                          (2, 2, 2)])
def test_replica_set_places_copies_on_distinct_devices(n_shards, n_replicas,
                                                       n_devices):
    """Even placement: every device holds at most ⌈S·R/D⌉ slabs, and
    copies of one shard land on different devices whenever R <= D."""
    psi = _rand((40, 8), 18)

    class FakeDev:  # placement bookkeeping only — never dispatched to
        def __init__(self, i):
            self.i = i

        def __repr__(self):
            return f"dev{self.i}"

    devices = [FakeDev(i) for i in range(n_devices)]
    table = shard_psi(psi, n_shards)
    # avoid jax.device_put on fakes: check the placement map only
    rs = ReplicaSet.__new__(ReplicaSet)
    rs.table, rs.n_replicas, rs.devices = table, n_replicas, devices
    per_device = [0] * n_devices
    for s in range(n_shards):
        devs = [rs._device_for(s, r).i for r in range(n_replicas)]
        assert len(set(devs)) == len(devs), (s, devs)
        for d in devs:
            per_device[d] += 1
    assert max(per_device) <= -(-n_shards * n_replicas // n_devices), \
        per_device


def test_staged_rollout_promotes_good_and_rolls_back_bad():
    """The drain-and-restart rollout: a good table promotes after the
    mirrored health check; a bad table (NaN ψ) rolls back with the live
    version untouched and never serves a query."""
    phi, psi = _rand((6, 8), 19), _rand((40, 8), 20)
    mesh = _mesh(phi, psi, n_shards=2, n_replicas=2, k=9)
    assert mesh.version == 1
    rollout = StagedRollout(mesh, mirror_phi=phi)
    ok, report = rollout.publish(psi * 0.5)  # same ranking, scaled scores
    assert ok and mesh.version == 2 and report["promoted_version"] == 2
    res = mesh.topk()
    rs_ref, ri_ref = topk_score_ref(phi, psi * 0.5, 9)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri_ref))
    # bad table: NaN scores fail the mirror check, version stays 2
    bad = jnp.asarray(np.full((40, 8), np.nan), jnp.float32)
    ok2, report2 = rollout.publish(bad)
    assert not ok2 and not report2["checks"]["scores_finite"]
    assert mesh.version == 2
    res2 = mesh.topk()  # still serving the promoted good table
    np.testing.assert_array_equal(np.asarray(res2.ids), np.asarray(ri_ref))
    assert not any(r.canary for row in mesh.replica_set.replicas for r in row)
    assert [h[1] for h in rollout.history] == [True, False]
    # a caller validate policy can also veto (e.g. rank-overlap floor)
    shuffled = np.asarray(psi)[::-1].copy()  # permuted ids: ranking changes
    ok3, _ = StagedRollout(
        mesh, mirror_phi=phi,
        validate=lambda live, canary: bool(
            (np.asarray(live.ids) == np.asarray(canary.ids)).all()
        ),
    ).publish(jnp.asarray(shuffled))
    assert not ok3 and mesh.version == 2


def test_canary_double_stage_and_misuse_raise():
    phi, psi = _rand((4, 8), 21), _rand((40, 8), 22)
    mesh = _mesh(phi, psi, n_shards=2, n_replicas=2, k=9)
    with pytest.raises(RuntimeError, match="no canary"):
        mesh.promote_canary()
    mesh.begin_canary(psi)
    with pytest.raises(RuntimeError, match="already staged"):
        mesh.begin_canary(psi)
    mesh.rollback_canary()
    with pytest.raises(RuntimeError, match="no canary"):
        mesh.rollback_canary()


def test_degraded_tickets_carry_coverage_through_batcher():
    """The batcher surfaces the degradation contract per ticket, and
    degraded answers are never cached (a heal must be visible)."""
    n_ctx, n_items = 30, 77
    params = mf.init(jax.random.PRNGKey(1), n_ctx, n_items, 8)
    inj = FaultInjector()
    mesh = FaultTolerantRetrievalMesh(
        lambda ctx: mf.build_phi(params, ctx), n_shards=2, n_replicas=1,
        k=10, block_items=32, injector=inj,
        retry=RetryPolicy(max_attempts=2, backoff_base=1e-4, deadline=1e-2),
    )
    mesh.publish(mf.export_psi(params))
    clock = {"t": 0.0}
    batcher = MicroBatcher(
        lambda phi, eids: mesh.topk_phi(phi, exclude_ids=eids),
        max_batch=4, max_delay=1.0, clock=lambda: clock["t"],
        version_fn=lambda: mesh.version,
    )
    phi_all = np.asarray(mf.build_phi(params, jnp.arange(n_ctx)))
    inj.fail(1, 0, "error")
    t1 = batcher.submit(phi_all[5], key=("user", 5))
    batcher.flush()
    res = batcher.result(t1)
    scores, ids = res  # tuple-compat intact
    table = mesh.table
    lo, hi = table.rows_per, min(2 * table.rows_per, n_items)
    assert res.degraded and res.dead_ranges == ((lo, hi),)
    assert batcher.stats["degraded_results"] == 1
    assert len(batcher._cache) == 0  # degraded: NOT cached
    # heal; the same key must now be recomputed at full coverage
    inj.heal()
    mesh.replica_set.mark_live(1, 0)
    t2 = batcher.submit(phi_all[5], key=("user", 5))
    assert batcher.stats["cache_hits"] == 0
    batcher.flush()
    res2 = batcher.result(t2)
    assert res2.coverage == 1.0
    rs_ref, ri_ref = topk_score_ref(
        phi_all[5:6], np.asarray(mf.export_psi(params)), 10
    )
    np.testing.assert_array_equal(res2.ids, np.asarray(ri_ref)[0])


def test_degraded_coverage_reported_through_sharded_eval():
    """eval/ranking.py's sharded path labels metrics computed against a
    partially-dead catalogue instead of reporting them as full."""
    from repro.eval.ranking import ranking_eval

    rng = np.random.default_rng(23)
    n_eval, n_items = 24, 60
    params = mf.init(jax.random.PRNGKey(2), n_eval, n_items, 8)
    inj = FaultInjector()
    mesh = FaultTolerantRetrievalMesh(
        lambda ctx: mf.build_phi(params, ctx), n_shards=3, n_replicas=1,
        k=10, block_items=32, injector=inj,
        retry=RetryPolicy(max_attempts=2, backoff_base=1e-4),
    )
    mesh.publish(mf.export_psi(params))
    phi = mf.build_phi(params, jnp.arange(n_eval))
    truth = rng.integers(0, n_items, size=n_eval)
    res_full = ranking_eval(phi, None, truth, k=10, batch_rows=8,
                            cluster=mesh)
    assert res_full["coverage"] == 1.0 and res_full["dead_ranges"] == ()
    inj.fail(0, 0, "error")
    res_deg = ranking_eval(phi, None, truth, k=10, batch_rows=8,
                           cluster=mesh)
    table = mesh.table
    assert res_deg["coverage"] < 1.0
    assert res_deg["dead_ranges"] == ((0, table.rows_per),)


def test_exclusion_rides_through_failover():
    """Per-row exclude-id lists keep filtering correctly when a replica
    dies mid-traffic (global ids are replica-agnostic)."""
    rng = np.random.default_rng(24)
    phi, psi = _rand((6, 16), 25), _rand((101, 16), 26)
    inj = FaultInjector()
    mesh = _mesh(phi, psi, injector=inj, k=20)
    lists = [rng.choice(101, size=7, replace=False) for _ in range(6)]
    eids = exclude_ids_from_lists(lists)
    rs_ref, ri_ref = topk_score_ref(
        phi, psi, 20, exclude_ids_to_mask(eids, 101)
    )
    inj.fail(2, 0, "error")
    res = mesh.topk(exclude_ids=eids)
    assert res.coverage == 1.0
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri_ref))


CHAOS_SUBPROCESS_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["REPRO_PALLAS_INTERPRET"] = "1"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np

    from repro.kernels.topk_score import topk_score_ref
    from repro.serve.mesh import (FaultInjector, FaultTolerantRetrievalMesh,
                                  RetryPolicy)

    rng = np.random.default_rng(0)
    phi = jnp.asarray(rng.normal(size=(9, 16)), jnp.float32)
    psi = jnp.asarray(rng.normal(size=(101, 16)), jnp.float32)
    inj = FaultInjector()
    devices = jax.devices()
    assert len(devices) == 4
    mesh = FaultTolerantRetrievalMesh(
        lambda p=phi: p, n_shards=4, n_replicas=2, k=13, block_items=32,
        devices=devices, injector=inj,
        retry=RetryPolicy(max_attempts=3, backoff_base=1e-4),
    )
    mesh.publish(psi)
    # copies of each range really live on distinct devices
    for s in range(4):
        devs = {str(r.device) for r in mesh.replica_set.replicas[s]}
        assert len(devs) == 2, devs
    rs_ref, ri_ref = topk_score_ref(phi, psi, 13)
    res = mesh.topk()
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ri_ref))
    # kill every replica on device 0 (a whole host dying): arm faults for
    # any stray dispatch AND mark them dead (the detector's verdict)
    dev0 = devices[0]
    for s in range(4):
        for r in mesh.replica_set.replicas[s]:
            if r.device == dev0:
                inj.fail(s, r.idx, "error")
                mesh.replica_set.mark_dead(s, r.idx, reason="host-loss")
    res2 = mesh.topk()
    assert res2.coverage == 1.0
    np.testing.assert_array_equal(np.asarray(res2.ids), np.asarray(ri_ref))
    assert (np.asarray(res2.scores) == np.asarray(res.scores)).all()
    # heal re-places the dead capacity on the surviving devices only
    inj.heal()
    placed = mesh.heal()
    assert placed, "nothing re-placed"
    for s in range(4):
        for r in mesh.replica_set.live(s):
            assert str(r.device) != str(dev0)
    res3 = mesh.topk()
    np.testing.assert_array_equal(np.asarray(res3.ids), np.asarray(ri_ref))
    print("CHAOS-MESH-OK")
    """
)


@pytest.mark.slow
def test_multi_device_chaos_subprocess():
    """4 forced host devices (the PR-5 shard_map harness shape): R=2 over
    4 devices, kill one whole device's replicas, assert bit-identical
    survivors and heal-onto-survivors."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", CHAOS_SUBPROCESS_SCRIPT],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(__file__)),
        env={**env, "PYTHONPATH": "src"}, timeout=600,
    )
    assert "CHAOS-MESH-OK" in proc.stdout, (
        proc.stdout[-2000:] + proc.stderr[-3000:]
    )


def test_dead_item_ranges_coalesce_and_clip():
    table = shard_psi(_rand((10, 4), 27), 4)  # rows_per=3, last shard short
    assert dead_item_ranges(table, [1, 2]) == ((3, 9),)
    assert dead_item_ranges(table, [3]) == ((9, 10),)  # clipped to n_items
    assert dead_item_ranges(table, [0, 2]) == ((0, 3), (6, 9))
    assert dead_item_ranges(table, []) == ()
