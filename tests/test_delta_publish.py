"""Delta ψ publish (``serve/publish.py`` + the mesh): pure
``apply_delta`` semantics (patch, append, hole/dup/negative validation),
version-bump invalidation scope (batcher cache keyed on version), stale
refusal across a delta bump, and the canary-staged refusal on the mesh."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.batcher import MicroBatcher
from repro.serve.mesh import FaultTolerantRetrievalMesh
from repro.serve.publish import PsiPublisher, apply_delta, dense_table


def _psi(n=17, d=6, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


# ------------------------------------------------------------ apply_delta
def test_apply_delta_patch_and_append():
    psi = _psi()
    rows = np.arange(12, dtype=np.float32).reshape(2, 6)
    out = apply_delta(psi, rows, [3, 17])          # one patch, one append
    assert out.shape == (18, 6)
    np.testing.assert_array_equal(out[3], rows[0])
    np.testing.assert_array_equal(out[17], rows[1])
    # untouched rows unchanged; input not mutated
    np.testing.assert_array_equal(out[:3], psi[:3])
    assert psi.shape == (17, 6)
    # a single (D,) row auto-reshapes
    out2 = apply_delta(psi, np.ones(6, np.float32), 0)
    np.testing.assert_array_equal(out2[0], np.ones(6))


def test_apply_delta_validation():
    psi = _psi()
    row = np.ones(6, np.float32)
    with pytest.raises(ValueError, match="hole"):
        apply_delta(psi, row, 19)                  # skips id 17, 18
    with pytest.raises(ValueError, match="duplicate"):
        apply_delta(psi, np.stack([row, row]), [3, 3])
    with pytest.raises(ValueError, match="negative"):
        apply_delta(psi, row, -1)
    with pytest.raises(ValueError, match="rows must be"):
        apply_delta(psi, np.ones((2, 6), np.float32), [0])
    # contiguous multi-append is fine, any order
    out = apply_delta(psi, np.stack([row, 2 * row]), [18, 17])
    assert out.shape == (19, 6)
    np.testing.assert_array_equal(out[18], row)


# ---------------------------------- sharded (unreplicated) version + rows
def test_cluster_delta_patch_append_retrievable():
    psi = _psi()
    cl = FaultTolerantRetrievalMesh(
        lambda ctx: jnp.ones((len(ctx), 6)), n_shards=3, n_replicas=1, k=5,
        psi_table=psi,
    )
    v0 = cl.version
    # large magnitude ⇒ the self inner product dominates every cross score
    new_row = 10 * np.random.default_rng(1).normal(size=6).astype(np.float32)
    v1 = cl.publish_delta(new_row, 17)             # append
    assert v1 == v0 + 1 and cl.n_items == 18
    np.testing.assert_allclose(dense_table(cl.table)[17], new_row)
    # the appended item must be retrievable: probe with its own row
    res = cl.topk_phi(jnp.asarray(new_row)[None, :])
    assert int(res[1][0, 0]) == 17
    v2 = cl.publish_delta(2 * new_row, 3)          # patch
    assert v2 == v1 + 1 and cl.n_items == 18
    np.testing.assert_allclose(dense_table(cl.table)[3], 2 * new_row)


def test_publisher_delta_records_versions():
    psi = _psi()
    cl = FaultTolerantRetrievalMesh(
        lambda ctx: jnp.ones((len(ctx), 6)), n_shards=2, n_replicas=1, k=5,
        psi_table=psi,
    )
    pub = PsiPublisher(cl, lambda p: p)
    row = np.ones(6, np.float32)
    v = pub.publish_delta(row, 17)
    assert pub.deltas == [(v, 1)] and cl.version == v


# ------------------------------------------- batcher invalidation scope
def test_delta_bump_invalidates_batcher_cache():
    psi = _psi()
    cl = FaultTolerantRetrievalMesh(
        lambda ctx: jnp.ones((len(ctx), 6)), n_shards=2, n_replicas=1, k=5,
        psi_table=psi,
    )
    batcher = MicroBatcher(
        lambda phi, eids: cl.topk_phi(phi, exclude_ids=eids),
        max_batch=4, version_fn=lambda: cl.version,
    )
    phi = psi[5]
    t1 = batcher.submit(phi, key=("user", 5))
    batcher.flush()
    t2 = batcher.submit(phi, key=("user", 5))      # same key, same version
    batcher.flush()
    assert batcher.stats["cache_hits"] == 1
    ids_before = np.asarray(batcher.result(t2)[1])
    # delta publish bumps the version: the SAME key must recompute
    cl.publish_delta(10 * psi[5], 17)    # aligned with the probe φ ⇒ top-1
    t3 = batcher.submit(phi, key=("user", 5))
    batcher.flush()
    assert batcher.stats["cache_hits"] == 1        # no new hit
    ids_after = np.asarray(batcher.result(t3)[1])
    assert 17 in ids_after and 17 not in ids_before
    assert batcher.result(t1) is not None


# --------------------------------------------------- mesh: stale + canary
def test_mesh_delta_publish_and_canary_refusal():
    psi = _psi()
    mesh = FaultTolerantRetrievalMesh(
        lambda ctx: jnp.ones((len(ctx), 6)), n_shards=2, n_replicas=2, k=5,
        psi_table=jnp.asarray(psi),
    )
    row = np.random.default_rng(2).normal(size=6).astype(np.float32)
    v = mesh.publish_delta(row, 17)
    assert v == 2 and mesh.n_items == 18
    res = mesh.topk_phi(jnp.asarray(row)[None, :])
    assert int(res.ids[0, 0]) == 17 and res.coverage == 1.0
    # every replica was rebuilt at the new version (stale-refusal invariant)
    rs = mesh.replica_set
    for shard_replicas in rs.replicas:
        for rep in shard_replicas:
            assert rep.version == mesh.version
    # a staged canary blocks delta publishes until resolved
    mesh.begin_canary(jnp.asarray(dense_table(mesh.table)))
    with pytest.raises(RuntimeError, match="canary"):
        mesh.publish_delta(row, 3)
    mesh.rollback_canary()
    v2 = mesh.publish_delta(2 * row, 3)
    assert v2 == v + 1
    np.testing.assert_allclose(dense_table(mesh.table)[3], 2 * row)
