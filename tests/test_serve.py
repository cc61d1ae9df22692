"""Serving paths: chunked retrieval top-k and the request micro-batcher
(deadline/size flush, out-of-order routing, LRU cache keyed on the
published table version) under a simulated clock."""
import jax.numpy as jnp
import numpy as np

from repro.core.models import mf
from repro.kernels.topk_score import topk_score_ref
from repro.kernels.topk_score.ops import exclude_ids_from_lists
from repro.serve import retrieval_topk
from repro.serve.batcher import MicroBatcher
from repro.serve.mesh import FaultTolerantRetrievalMesh

import jax


def _score_fn(user_vec, item_table):
    """The separable retrieval product per id chunk: (k)·(k, c) for one
    user, or (B, k)·(k, c) for a batch."""

    def score(ids):
        s = jnp.take(item_table, ids, axis=0) @ user_vec.T  # (c,) | (c, B)
        return s.T if s.ndim == 2 else s

    return score


def test_retrieval_topk_exact():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(5000, 16)), jnp.float32)
    user = jnp.asarray(rng.normal(size=16), jnp.float32)
    scores, ids = retrieval_topk(_score_fn(user, table), 5000,
                                 k=50, chunk=777)
    full = np.asarray(table @ user)
    expect = set(np.argsort(-full)[:50].tolist())
    assert set(np.asarray(ids).tolist()) == expect
    np.testing.assert_allclose(np.sort(np.asarray(scores))[::-1],
                               np.sort(full[np.asarray(ids)])[::-1], rtol=1e-5)


def test_retrieval_topk_batched_matches_per_row():
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.normal(size=(3000, 8)), jnp.float32)
    users = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    scores, ids = retrieval_topk(_score_fn(users, table), 3000,
                                 k=20, chunk=512)
    assert scores.shape == (5, 20) and ids.shape == (5, 20)
    full = np.asarray(users @ table.T)
    for r in range(5):
        s1, i1 = retrieval_topk(_score_fn(users[r], table), 3000,
                                k=20, chunk=512)
        np.testing.assert_array_equal(np.asarray(ids)[r], np.asarray(i1))
        np.testing.assert_array_equal(
            np.asarray(ids)[r], np.argsort(-full[r], kind="stable")[:20])


def test_retrieval_topk_short_catalogue_no_placeholder_leak():
    table = jnp.asarray(np.random.default_rng(3).normal(size=(7, 4)), jnp.float32)
    user = jnp.asarray([1.0, 0.0, 0.0, 0.0])
    scores, ids = retrieval_topk(_score_fn(user, table), 7, k=12)
    # first 7 slots are the real catalogue, exactly ranked
    np.testing.assert_array_equal(
        np.asarray(ids)[:7], np.argsort(-np.asarray(table @ user), kind="stable")[:7])
    # tail is (−inf, −1): id 0 never leaks as a fake recommendation
    assert bool((np.asarray(ids)[7:] == -1).all())
    assert bool(np.isneginf(np.asarray(scores)[7:]).all())


# --------------------------------------------------------------- batcher ---
def _serving_stack(n_shards=2, k=10, n_ctx=40, n_items=77, seed=0):
    params = mf.init(jax.random.PRNGKey(seed), n_ctx, n_items, 8)
    cluster = FaultTolerantRetrievalMesh(
        lambda ctx: mf.build_phi(params, ctx), n_shards=n_shards,
        n_replicas=1, k=k, block_items=32, psi_table=mf.export_psi(params),
    )
    clock = {"t": 0.0}
    batcher = MicroBatcher(
        lambda phi, eids: cluster.topk_phi(phi, exclude_ids=eids),
        max_batch=4, max_delay=1.0, pad_to=8,
        clock=lambda: clock["t"], version_fn=lambda: cluster.version,
    )
    phi_all = np.asarray(mf.build_phi(params, jnp.arange(n_ctx)))
    psi = np.asarray(mf.export_psi(params))
    return params, cluster, clock, batcher, phi_all, psi


def test_batcher_routes_out_of_order_requests_under_simulated_clock():
    """The acceptance criterion: single-row requests submitted out of
    order, flushed in mixed batches, must each get THEIR OWN top-K back —
    pinned against the per-row dense oracle."""
    rng = np.random.default_rng(1)
    _, cluster, clock, batcher, phi_all, psi = _serving_stack()
    users = [31, 4, 17, 2, 25, 9, 11]  # deliberately unsorted
    excls = {u: rng.choice(77, size=int(rng.integers(1, 6)), replace=False)
             for u in users}
    tickets = {}
    for j, u in enumerate(users[:3]):  # under max_batch: queued, no result
        clock["t"] = 0.01 * j
        tickets[u] = batcher.submit(phi_all[u], exclude=excls[u])
    assert batcher.n_queued == 3
    assert all(batcher.result(t, pop=False) is None for t in tickets.values())

    clock["t"] = 5.0  # deadline passes → flush the 3
    assert batcher.step()
    assert batcher.stats["flush_by_deadline"] == 1

    for u in users[3:]:  # 4 more → size flush at max_batch=4
        tickets[u] = batcher.submit(phi_all[u], exclude=excls[u])
    assert batcher.stats["flush_by_size"] == 1 and batcher.n_queued == 0

    for u in users:  # every ticket got ITS row's result
        scores, ids = batcher.result(tickets[u])
        eids = exclude_ids_from_lists([excls[u]])
        rs, ri = topk_score_ref(phi_all[u : u + 1], psi, 10, exclude_ids=eids)
        np.testing.assert_array_equal(ids, np.asarray(ri)[0])
        np.testing.assert_allclose(scores, np.asarray(rs)[0], rtol=1e-5)
        assert not np.isin(ids[ids >= 0], excls[u]).any()


def test_batcher_deadline_bounds_queue_wait():
    """No queued request waits past max_delay: a lone sub-batch request is
    flushed as soon as the clock passes its deadline, not starved until
    max_batch fills."""
    _, _, clock, batcher, phi_all, psi = _serving_stack(seed=2)
    clock["t"] = 10.0
    t = batcher.submit(phi_all[0])
    assert batcher.result(t, pop=False) is None
    clock["t"] = 10.5  # < max_delay=1.0: still queued
    assert not batcher.step()
    clock["t"] = 11.0  # deadline hit
    assert batcher.step()
    scores, ids = batcher.result(t)
    rs, ri = topk_score_ref(phi_all[:1], psi, 10)
    np.testing.assert_array_equal(ids, np.asarray(ri)[0])
    assert batcher.completed_at(t) is None  # popped with the result


def test_batcher_cache_hits_and_version_invalidation():
    """The LRU result cache serves repeats without a kernel dispatch and a
    ψ publish (new table version) invalidates it implicitly."""
    _, cluster, clock, batcher, phi_all, _ = _serving_stack(seed=3)
    key = ("user", 7)
    t1 = batcher.submit(phi_all[7], key=key)
    batcher.flush()
    s1, i1 = batcher.result(t1)
    t2 = batcher.submit(phi_all[7], key=key)  # same key, same version
    assert batcher.stats["cache_hits"] == 1 and batcher.n_queued == 0
    s2, i2 = batcher.result(t2)
    np.testing.assert_array_equal(i1, i2)

    cluster.publish(jnp.zeros((77, 8)))  # version bump: all-zero ψ
    t3 = batcher.submit(phi_all[7], key=key)
    assert batcher.result(t3, pop=False) is None  # miss → queued again
    batcher.flush()
    s3, i3 = batcher.result(t3)
    # zero table: every score 0, ranking degenerates to ascending id
    np.testing.assert_array_equal(i3, np.arange(10))
    assert batcher.stats["cache_misses"] >= 2


def test_batcher_cache_folds_exclude_list_into_key():
    """Same caller key, different exclude list ⇒ MISS: the batcher folds
    the exclusion set into the cache key itself, so a cached result can
    never leak items another request excluded (and a cache-hit admission
    still retires queue deadlines)."""
    _, _, clock, batcher, phi_all, psi = _serving_stack(seed=5)
    t1 = batcher.submit(phi_all[3], exclude=[0, 1], key=("user", 3))
    batcher.flush()
    _, i1 = batcher.result(t1)
    t2 = batcher.submit(phi_all[3], exclude=[int(i1[0])], key=("user", 3))
    assert batcher.result(t2, pop=False) is None  # miss, not the stale hit
    batcher.flush()
    _, i2 = batcher.result(t2)
    assert int(i1[0]) not in i2.tolist()
    # identical key AND exclude list ⇒ hit, and the hit path still flushes
    # an overdue queued request (deadline honored under pure cache traffic)
    clock["t"] = 100.0
    t3 = batcher.submit(phi_all[9])  # queued, uncached
    clock["t"] = 200.0  # way past max_delay: next admission must flush it
    t4 = batcher.submit(phi_all[3], exclude=[0, 1], key=("user", 3))
    assert batcher.stats["cache_hits"] == 1
    assert batcher.result(t4) is not None
    got3 = batcher.result(t3)  # t3 flushed by the hit admission
    assert got3 is not None
    rs, ri = topk_score_ref(phi_all[9:10], psi, 10)
    np.testing.assert_array_equal(got3[1], np.asarray(ri)[0])


def test_batcher_drain_flushes_queue_and_closes():
    """Shutdown must flush (not strand) queued requests: drain() completes
    everything, hands back unclaimed results, and closes admission."""
    import pytest

    _, _, clock, batcher, phi_all, psi = _serving_stack(seed=6)
    t1 = batcher.submit(phi_all[2])
    t2 = batcher.submit(phi_all[8])
    claimed = batcher.result(t1)
    assert claimed is None  # still queued (under max_batch, under deadline)
    leftovers = batcher.drain()
    assert set(leftovers) == {t1, t2}  # nothing stranded
    rs, ri = topk_score_ref(phi_all[8:9], psi, 10)
    np.testing.assert_array_equal(leftovers[t2].ids, np.asarray(ri)[0])
    assert batcher.closed and batcher.n_queued == 0
    with pytest.raises(RuntimeError, match="closed"):
        batcher.submit(phi_all[0])
    assert batcher.drain() == {}  # idempotent


def test_batcher_evicts_superseded_version_cache_entries():
    """A publish must EVICT entries keyed on the old table version (they
    can never hit again), not leave them squatting in the LRU."""
    _, cluster, clock, batcher, phi_all, _ = _serving_stack(seed=7)
    for u in (1, 2, 3):
        batcher.submit(phi_all[u], key=("user", u))
    batcher.flush()
    assert len(batcher._cache) == 3
    cluster.publish(jnp.zeros((77, 8)))  # version bump supersedes all 3
    batcher.submit(phi_all[4], key=("user", 4))  # first post-publish admission
    assert batcher.stats["cache_evicted_stale"] == 3
    assert all(k[1] == cluster.version for k in batcher._cache)
    batcher.flush()
    assert len(batcher._cache) == 1  # only the new-version entry


def test_batcher_pads_batch_and_discards_pad_rows():
    """3 requests pad to pad_to=8 kernel rows; pad rows never produce
    tickets or pollute results."""
    _, _, clock, batcher, phi_all, psi = _serving_stack(seed=4)
    ts = [batcher.submit(phi_all[u]) for u in (5, 6, 7)]
    batcher.flush()
    assert batcher.stats["flushed_rows"] == 3 and batcher.stats["flushes"] == 1
    for u, t in zip((5, 6, 7), ts):
        _, ids = batcher.result(t)
        rs, ri = topk_score_ref(phi_all[u : u + 1], psi, 10)
        np.testing.assert_array_equal(ids, np.asarray(ri)[0])
    assert batcher.result(999) is None  # unknown ticket: no leak
