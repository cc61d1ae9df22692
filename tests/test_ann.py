"""IVF approximate retrieval tier (serve/ann.py): oracle bit-identity at
n_probe >= n_clusters, quantized-storage parity, empty-cluster and
fully-pruned-exclusion edges, delta fold-in consistency, and the
retrieval='ivf' threading through the mesh."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import int8_dequantize_rows, int8_quantize_rows
from repro.eval.ranking import ann_recall_curve, overlap_recall
from repro.kernels.topk_score import topk_score
from repro.serve.ann import (
    AnnConfig,
    PsiIndex,
    build_shard_indexes,
    fold_delta_indexes,
    kmeans,
)
from repro.serve.cluster import shard_psi
from repro.serve.mesh import FaultInjector, FaultTolerantRetrievalMesh


def _clustered(n, d, n_centers, seed=0, spread=4.0):
    """ψ with real cluster structure so pruning is meaningful."""
    rng = np.random.default_rng(seed)
    cents = rng.normal(size=(n_centers, d)) * spread
    per = -(-n // n_centers)
    rows = np.concatenate(
        [cents[i] + rng.normal(size=(per, d)) for i in range(n_centers)]
    )[:n]
    rng.shuffle(rows)
    return jnp.asarray(rows, jnp.float32)


def _queries(b, d, seed=100):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(b, d)), jnp.float32)


# ---------------------------------------------------------------- kmeans

def test_kmeans_shapes_and_empty_cluster_centroids():
    # more clusters than distinct directions -> some clusters go empty;
    # Lloyd must keep the old centroid, never emit NaN
    psi = jnp.asarray(np.repeat(np.eye(4, 8, dtype=np.float32), 10, axis=0))
    cents, assign = kmeans(psi, 16, n_iters=6, seed=3)
    assert cents.shape == (16, 8) and assign.shape == (40,)
    assert np.isfinite(np.asarray(cents)).all()
    assert np.asarray(assign).min() >= 0 and np.asarray(assign).max() < 16


# --------------------------------------------------- oracle bit-identity

@pytest.mark.parametrize("quant", ["none"])
def test_oracle_bit_identity_ids_and_scores(quant):
    psi = _clustered(400, 16, 8, seed=1)
    phi = _queries(9, 16)
    idx = PsiIndex.build(psi, AnnConfig(n_clusters=8, quant=quant, seed=2))
    es, ei = topk_score(phi, psi, 25)
    # n_probe == n_clusters AND n_probe > n_clusters both hit the oracle gate
    for p in (8, 11):
        s, i = idx.topk(phi, 25, n_probe=p)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ei))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(es))


def test_oracle_bit_identity_with_exclusions():
    psi = _clustered(300, 8, 6, seed=4)
    phi = _queries(5, 8)
    ex = jnp.asarray(
        np.stack([np.arange(r, r + 40, dtype=np.int32) for r in range(5)])
    )
    idx = PsiIndex.build(psi, AnnConfig(n_clusters=6, seed=5))
    es, ei = topk_score(phi, psi, 10, exclude_ids=ex)
    s, i = idx.topk(phi, 10, n_probe=6, exclude_ids=ex)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ei))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(es))


def test_pruned_recall_reasonable_and_monotone_probe_sweep():
    psi = _clustered(600, 16, 8, seed=6)
    phi = _queries(12, 16)
    idx = PsiIndex.build(psi, AnnConfig(n_clusters=8, seed=7))
    curve = ann_recall_curve(idx, phi, psi, k=20, n_probes=(1, 2, 4, 8))
    recalls = [pt["recall@20"] for pt in curve]
    assert recalls[-1] == 1.0          # oracle point closes the curve
    assert recalls[1] >= recalls[0] - 1e-9 or recalls[-1] >= recalls[0]
    assert recalls[1] > 0.3            # clustered data: 2/8 probes finds most


# ------------------------------------------------------------ exclusions

def test_exclude_ids_hitting_fully_pruned_blocks_is_harmless():
    # excluded ids live in clusters the query never probes: the exclusion
    # must neither crash nor change the candidates from probed blocks
    psi = _clustered(200, 8, 4, seed=8)
    phi = _queries(3, 8)
    idx = PsiIndex.build(psi, AnnConfig(n_clusters=4, seed=9))
    s0, i0 = idx.topk(phi, 5, n_probe=1)
    probed = set(np.asarray(i0).reshape(-1).tolist()) - {-1}
    unprobed = [g for g in range(200) if g not in probed][:8]
    ex = jnp.asarray(np.tile(np.asarray(unprobed, np.int32), (3, 1)))
    s1, i1 = idx.topk(phi, 5, n_probe=1, exclude_ids=ex)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))


def test_exclude_everything_probed_yields_sentinels():
    psi = _clustered(64, 8, 2, seed=10)
    phi = _queries(2, 8)
    idx = PsiIndex.build(psi, AnnConfig(n_clusters=2, seed=11))
    ex = jnp.asarray(np.tile(np.arange(64, dtype=np.int32), (2, 1)))
    s, i = idx.topk(phi, 4, n_probe=2, exclude_ids=ex)
    assert (np.asarray(i) == -1).all()
    assert np.isneginf(np.asarray(s)).all()


def test_out_of_range_exclude_ids_ignored():
    psi = _clustered(100, 8, 4, seed=12)
    phi = _queries(2, 8)
    idx = PsiIndex.build(psi, AnnConfig(n_clusters=4, seed=13))
    s0, i0 = idx.topk(phi, 6, n_probe=4)
    ex = jnp.asarray(np.full((2, 3), 10_000, np.int32))
    s1, i1 = idx.topk(phi, 6, n_probe=4, exclude_ids=ex)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))


# ---------------------------------------------------------- quantization

@pytest.mark.parametrize("quant", ["bf16", "int8"])
def test_quantized_index_matches_dequantized_oracle(quant):
    psi = _clustered(300, 16, 6, seed=14)
    phi = _queries(6, 16)
    idx = PsiIndex.build(psi, AnnConfig(n_clusters=6, quant=quant, seed=15))
    # oracle: exact dense top-K over the SAME lossy table the index stores
    if quant == "int8":
        deq = np.zeros((300, 16), np.float32)
        stored = int8_dequantize_rows(idx.psi_q, idx.scales)
    else:
        deq = np.zeros((300, 16), np.float32)
        stored = np.asarray(idx.psi_q, np.float32)
    live = np.asarray(idx.ids_global) >= 0
    deq[np.asarray(idx.ids_global)[live]] = np.asarray(stored)[live]
    es, ei = topk_score(phi, jnp.asarray(deq), 15)
    s, i = idx.topk(phi, 15, n_probe=6)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ei))
    np.testing.assert_allclose(np.asarray(s), np.asarray(es), rtol=1e-5, atol=1e-5)


def test_int8_scores_close_to_f32_relative():
    psi = _clustered(400, 32, 8, seed=16)
    phi = _queries(8, 32)
    exact_s, exact_i = topk_score(phi, psi, 10)
    idx = PsiIndex.build(psi, AnnConfig(n_clusters=8, quant="int8", seed=17))
    s, i = idx.topk(phi, 10, n_probe=8)
    assert overlap_recall(np.asarray(i), np.asarray(exact_i)) >= 0.9
    denom = np.maximum(np.abs(np.asarray(exact_s)), 1e-3)
    hit = np.asarray(i) == np.asarray(exact_i)
    rel = np.abs(np.asarray(s) - np.asarray(exact_s))[hit] / denom[hit]
    assert rel.max() < 0.05


def test_quantized_tie_stability_ascending_ids():
    # identical rows quantize to identical codes -> equal scores; the
    # two-key merge must still emit them in ascending GLOBAL id order
    row = np.random.default_rng(18).normal(size=16).astype(np.float32)
    psi = jnp.asarray(np.tile(row, (24, 1)))
    phi = jnp.asarray(row[None, :] * 0.5)
    for quant in ("none", "bf16", "int8"):
        idx = PsiIndex.build(psi, AnnConfig(n_clusters=3, quant=quant, seed=19))
        s, i = idx.topk(phi, 8, n_probe=3)
        ids = np.asarray(i)[0]
        assert (ids == np.arange(8)).all(), (quant, ids)
        assert np.allclose(np.asarray(s)[0], np.asarray(s)[0][0])


# ---------------------------------------------------------- delta fold-in

def test_apply_delta_patch_and_append_searchable():
    psi = _clustered(120, 8, 4, seed=20)
    idx = PsiIndex.build(psi, AnnConfig(n_clusters=4, seed=21, reindex_after=1000))
    rng = np.random.default_rng(22)
    patch_rows = jnp.asarray(rng.normal(size=(2, 8)) * 9, jnp.float32)
    idx2 = idx.apply_delta(patch_rows, np.asarray([5, 60], np.int64))
    assert idx2.staleness == idx.staleness + 2
    assert idx2.n_rows == 120
    # patched rows dominate in norm -> must be retrievable at their ids
    for r in range(2):
        q = patch_rows[r][None, :]
        _, i = idx2.topk(q, 1, n_probe=4)
        assert int(np.asarray(i)[0, 0]) == [5, 60][r]
    # appends: contiguous ids only
    app_rows = jnp.asarray(rng.normal(size=(3, 8)) * 9, jnp.float32)
    idx3 = idx2.apply_delta(app_rows, np.asarray([120, 121, 122], np.int64))
    assert idx3.n_rows == 123 and idx3.staleness == idx2.staleness + 3
    # oracle probe: the folded index over the appended catalogue must
    # bit-match the exact kernel over the equivalent dense table
    dense = np.asarray(psi).copy()
    dense[5], dense[60] = patch_rows[0], patch_rows[1]
    dense = np.concatenate([dense, np.asarray(app_rows)])
    _, ei = topk_score(app_rows, jnp.asarray(dense), 3)
    _, i = idx3.topk(app_rows, 3, n_probe=4)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ei))
    assert set(np.asarray(ei).reshape(-1)) & {120, 121, 122}
    with pytest.raises(ValueError):
        idx3.apply_delta(app_rows[:1], np.asarray([999], np.int64))  # hole


def test_apply_delta_oracle_identity_after_fold():
    psi = _clustered(150, 8, 4, seed=23)
    idx = PsiIndex.build(psi, AnnConfig(n_clusters=4, seed=24))
    rng = np.random.default_rng(25)
    rows = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    ids = np.asarray([0, 75, 150, 151], np.int64)       # patch + append mix
    idx2 = idx.apply_delta(rows, ids)
    dense = np.asarray(psi).copy()
    dense[0], dense[75] = rows[0], rows[1]
    dense = np.concatenate([dense, np.asarray(rows[2:])])
    phi = _queries(5, 8, seed=26)
    es, ei = topk_score(phi, jnp.asarray(dense), 12)
    s, i = idx2.topk(phi, 12, n_probe=4)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ei))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(es))


def test_apply_delta_grows_full_block():
    # tiny catalogue, 1 cluster: block_rows starts at 8; 9th append must
    # trigger the +8-row repack and stay consistent
    psi = jnp.asarray(np.random.default_rng(27).normal(size=(8, 4)), jnp.float32)
    idx = PsiIndex.build(psi, AnnConfig(n_clusters=1, seed=28))
    assert idx.block_rows == 8
    rng = np.random.default_rng(29)
    rows = jnp.asarray(rng.normal(size=(3, 4)), jnp.float32)
    idx2 = idx.apply_delta(rows, np.asarray([8, 9, 10], np.int64))
    assert idx2.block_rows > 8 and idx2.n_rows == 11
    dense = np.concatenate([np.asarray(psi), np.asarray(rows)])
    phi = _queries(3, 4, seed=30)
    es, ei = topk_score(phi, jnp.asarray(dense), 6)
    s, i = idx2.topk(phi, 6, n_probe=1)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ei))


def test_needs_reindex_trigger():
    psi = _clustered(60, 8, 2, seed=31)
    idx = PsiIndex.build(psi, AnnConfig(n_clusters=2, seed=32, reindex_after=3))
    rows = jnp.asarray(np.random.default_rng(33).normal(size=(2, 8)), jnp.float32)
    idx2 = idx.apply_delta(rows, np.asarray([1, 2], np.int64))
    assert not idx2.needs_reindex()
    idx3 = idx2.apply_delta(rows, np.asarray([3, 4], np.int64))
    assert idx3.needs_reindex()      # 4 > reindex_after=3


# ------------------------------------------------------- sharded indexes

def _ivf_mesh(psi, n_shards, cfg, k):
    """Sharded, unreplicated IVF serving: the mesh's per-shard indexes."""
    return FaultTolerantRetrievalMesh(n_shards=n_shards, n_replicas=1, k=k,
                                      retrieval="ivf", ann=cfg,
                                      psi_table=psi)


def test_sharded_indexes_match_exact_cluster_topk():
    psi = _clustered(250, 8, 6, seed=34)
    cfg = AnnConfig(n_clusters=4, n_probe=4, seed=35)
    mesh = _ivf_mesh(psi, 3, cfg, 14)
    phi = _queries(6, 8, seed=36)
    es, ei = topk_score(phi, psi, 14)
    res = mesh.topk_phi(phi)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ei))
    np.testing.assert_array_equal(np.asarray(res.scores), np.asarray(es))


def test_fold_delta_indexes_rebuilds_when_stale():
    psi = _clustered(90, 8, 3, seed=37)
    table = shard_psi(psi, 3)
    cfg = AnnConfig(n_clusters=2, seed=38, reindex_after=1)
    idxs = build_shard_indexes(table, cfg)
    rows = jnp.asarray(np.random.default_rng(39).normal(size=(2, 8)), jnp.float32)
    ids = np.asarray([0, 40], np.int64)          # shards 0 and 1
    from repro.serve.publish import apply_delta
    table2 = shard_psi(jnp.asarray(apply_delta(np.asarray(psi), rows, ids)), 3)
    idxs2 = fold_delta_indexes(idxs, table2, rows, ids, cfg)
    # reindex_after=1 < 2 folded ids -> touched shards rebuilt fresh
    assert not idxs2[0].needs_reindex() and not idxs2[1].needs_reindex()
    # regardless of fold-vs-rebuild, results must match the exact table
    phi = _queries(4, 8, seed=40)
    dense = np.asarray(psi).copy()
    dense[0], dense[40] = rows[0], rows[1]
    es, ei = topk_score(phi, jnp.asarray(dense), 9)
    # the same fold through the mesh's publish_delta, served at n_probe=2
    mesh = _ivf_mesh(psi, 3, AnnConfig(n_clusters=2, n_probe=2, seed=38,
                                       reindex_after=1), 9)
    mesh.topk_phi(phi)                 # builds v1's indexes
    mesh.publish_delta(rows, ids)
    res = mesh.topk_phi(phi)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ei))


# ----------------------------------------------------- serving-tier wiring

def test_engine_ivf_oracle_matches_exact_engine():
    psi = _clustered(200, 8, 4, seed=41)
    phi = _queries(5, 8, seed=42)
    cfg = AnnConfig(n_clusters=4, n_probe=4, seed=43)
    ex = FaultTolerantRetrievalMesh(lambda q: q, n_shards=1, n_replicas=1,
                                    k=12, psi_table=psi)
    iv = FaultTolerantRetrievalMesh(lambda q: q, n_shards=1, n_replicas=1,
                                    k=12, psi_table=psi, retrieval="ivf",
                                    ann=cfg)
    a, b = ex.topk_phi(phi), iv.topk_phi(phi)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))
    # one shard indexes the whole table, as a standalone PsiIndex would
    idx = PsiIndex.build(psi, cfg, id_offset=0)
    s1, i1 = idx.topk(phi, 12)
    np.testing.assert_array_equal(np.asarray(b.ids), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(b.scores), np.asarray(s1))
    with pytest.raises(ValueError):
        FaultTolerantRetrievalMesh(lambda q: q, n_shards=1, n_replicas=1,
                                   retrieval="hnsw")


def test_cluster_ivf_publish_delta_and_exclusions():
    psi = _clustered(240, 8, 4, seed=44)
    phi = _queries(6, 8, seed=45)
    cfg = AnnConfig(n_clusters=4, n_probe=4, seed=46)
    cl_ex = FaultTolerantRetrievalMesh(n_shards=3, n_replicas=1, k=10)
    cl_iv = FaultTolerantRetrievalMesh(n_shards=3, n_replicas=1, k=10,
                                       retrieval="ivf", ann=cfg)
    cl_ex.publish(psi)
    cl_iv.publish(psi)
    rows = jnp.asarray(np.random.default_rng(47).normal(size=(3, 8)), jnp.float32)
    ids = np.asarray([2, 100, 210], np.int64)
    cl_ex.publish_delta(rows, ids)
    cl_iv.publish_delta(rows, ids)
    eids = jnp.asarray(np.tile(np.arange(20, dtype=np.int32), (6, 1)))
    a = cl_ex.topk_phi(phi, exclude_ids=eids)
    b = cl_iv.topk_phi(phi, exclude_ids=eids)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))
    assert not np.isin(np.asarray(b.ids), np.arange(20)).any()


def test_mesh_ivf_matches_exact_and_survives_faults():
    psi = _clustered(180, 8, 3, seed=48)
    phi = _queries(4, 8, seed=49)
    cfg = AnnConfig(n_clusters=3, n_probe=3, seed=50)
    m_ex = FaultTolerantRetrievalMesh(n_shards=3, n_replicas=2, k=8)
    m_iv = FaultTolerantRetrievalMesh(n_shards=3, n_replicas=2, k=8,
                                      retrieval="ivf", ann=cfg)
    m_ex.publish(psi)
    m_iv.publish(psi)
    a, b = m_ex.topk_phi(phi), m_iv.topk_phi(phi)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))
    assert b.coverage == 1.0
    # kill one replica of shard 0: the other replica must still serve ivf
    inj = FaultInjector()
    inj.fail(0, 0, "error")
    m_f = FaultTolerantRetrievalMesh(n_shards=3, n_replicas=2, k=8,
                                     retrieval="ivf", ann=cfg, injector=inj)
    m_f.publish(psi)
    c = m_f.topk_phi(phi)
    np.testing.assert_array_equal(np.asarray(c.ids), np.asarray(a.ids))
    assert c.coverage == 1.0


def test_empty_shard_index_is_none_and_served_as_empty():
    # 5 shards over 90 rows with rows_per=30 -> shards 3,4 are all padding
    psi = _clustered(90, 8, 3, seed=51)
    mesh = _ivf_mesh(psi, 5, AnnConfig(n_clusters=2, n_probe=2, seed=52), 7)
    table = mesh.table
    if any(table.valid_rows(s) == 0 for s in range(table.n_shards)):
        idxs = build_shard_indexes(table, AnnConfig(n_clusters=2, seed=52))
        assert any(ix is None for ix in idxs)
        phi = _queries(3, 8, seed=53)
        es, ei = topk_score(phi, psi, 7)
        res = mesh.topk_phi(phi)
        np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ei))


# ------------------------------------------------------ quant.py helpers

def test_quant_rows_roundtrip_and_shapes():
    x = np.random.default_rng(54).normal(size=(10, 6)).astype(np.float32)
    x[3] *= 100.0    # per-row scales must absorb wildly different norms
    q, s = int8_quantize_rows(jnp.asarray(x))
    assert q.shape == (10, 6) and q.dtype == jnp.int8 and s.shape == (10,)
    back = np.asarray(int8_dequantize_rows(q, s))
    rel = np.abs(back - x).max(axis=1) / np.abs(x).max(axis=1)
    assert rel.max() < 0.01
    with pytest.raises(ValueError):
        int8_quantize_rows(jnp.zeros((4,)))
