"""Streaming ranking-eval harness: dense-path parity, exclusion protocol,
and the per-epoch fit callback."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import ndcg_at_k, recall_at_k
from repro.core.models import mf
from repro.eval.ranking import fit_eval_callback, ranking_eval
from repro.kernels.topk_score.ops import exclude_ids_from_lists
from repro.kernels.topk_score.ref import exclude_ids_to_mask
from repro.sparse.interactions import build_interactions


def _setup(n_ctx=40, n_items=120, k=8, seed=0):
    rng = np.random.default_rng(seed)
    params = mf.init(jax.random.PRNGKey(seed), n_ctx, n_items, k)
    truth = rng.integers(0, n_items, size=n_ctx)
    excl = [rng.choice(n_items, size=int(rng.integers(0, 6)), replace=False)
            for _ in range(n_ctx)]
    return rng, params, truth, excl


def _dense_mask(excl, n_items):
    """The oracle's dense (B, n_items) exclusion from ragged id lists."""
    return exclude_ids_to_mask(exclude_ids_from_lists(excl), n_items)


def test_streaming_equals_dense_metrics():
    _, params, truth, excl = _setup()
    phi = mf.build_phi(params, jnp.arange(40))
    psi = mf.export_psi(params)
    res = ranking_eval(phi, psi, truth, k=10, batch_rows=13, exclude=excl,
                       block_items=32)
    mask = _dense_mask(excl, 120)
    dense = phi @ psi.T
    r = float(recall_at_k(dense, jnp.asarray(truth), 10, mask))
    n = float(ndcg_at_k(dense, jnp.asarray(truth), 10, mask))
    np.testing.assert_allclose(res["recall@10"], r, atol=1e-6)
    np.testing.assert_allclose(res["ndcg@10"], n, atol=1e-6)
    assert res["n_eval"] == 40 and res["k"] == 10


def test_streaming_eval_never_builds_full_mask_rows():
    """The exclusion protocol rides the kernel's (B, L) id-list form: the
    harness must produce dense-parity metrics WITHOUT ever calling the
    dense mask builder (the old (B, n_items) host-side path)."""
    import repro.eval.ranking as ranking_mod

    _, params, truth, excl = _setup(seed=5)
    phi = mf.build_phi(params, jnp.arange(40))
    psi = mf.export_psi(params)
    assert not hasattr(ranking_mod, "exclude_mask_from_lists")
    res = ranking_eval(phi, psi, truth, k=10, batch_rows=16, exclude=excl,
                       block_items=32)
    mask = _dense_mask(excl, 120)
    dense = phi @ psi.T
    r = float(recall_at_k(dense, jnp.asarray(truth), 10, mask))
    np.testing.assert_allclose(res["recall@10"], r, atol=1e-6)


def test_sharded_eval_matches_single_device():
    """cluster= streams the same batches through the sharded table; the
    merge contract makes the metrics identical at any shard count."""
    from repro.serve.mesh import FaultTolerantRetrievalMesh

    _, params, truth, excl = _setup(seed=6)
    phi = mf.build_phi(params, jnp.arange(40))
    psi = mf.export_psi(params)
    single = ranking_eval(phi, psi, truth, k=10, batch_rows=13, exclude=excl,
                          block_items=32)
    for n_shards in (1, 3):
        cl = FaultTolerantRetrievalMesh(n_shards=n_shards, n_replicas=1,
                                        k=10, block_items=32, psi_table=psi)
        sharded = ranking_eval(phi, None, truth, k=10, batch_rows=13,
                               exclude=excl, cluster=cl)
        np.testing.assert_allclose(sharded["recall@10"], single["recall@10"],
                                   atol=1e-6)
        np.testing.assert_allclose(sharded["ndcg@10"], single["ndcg@10"],
                                   atol=1e-6)


def test_no_exclude_and_single_batch():
    _, params, truth, _ = _setup(seed=1)
    phi = mf.build_phi(params, jnp.arange(40))
    res_a = ranking_eval(phi, mf.export_psi(params), truth, k=10, batch_rows=40)
    res_b = ranking_eval(phi, mf.export_psi(params), truth, k=10, batch_rows=7)
    np.testing.assert_allclose(res_a["recall@10"], res_b["recall@10"], atol=1e-6)
    np.testing.assert_allclose(res_a["ndcg@10"], res_b["ndcg@10"], atol=1e-6)


def test_fit_eval_callback_records_history_per_epoch():
    rng, params, truth, excl = _setup(seed=2)
    nnz = 300
    cells = rng.choice(40 * 120, size=nnz, replace=False)
    ctx, item = cells // 120, cells % 120
    y = rng.integers(1, 5, size=nnz).astype(np.float64)
    data = build_interactions(ctx, item, y, 1.0 + rng.random(nnz), 40, 120,
                              alpha0=0.3)
    cb = fit_eval_callback(
        lambda p: (mf.build_phi(p, jnp.arange(40)), mf.export_psi(p)),
        truth, k=10, exclude=excl, batch_rows=16,
    )
    hp = mf.MFHyperParams(k=8, alpha0=0.3, l2=0.05)
    mf.fit(params, data, hp, n_epochs=2, callback=cb)
    assert [h["epoch"] for h in cb.history] == [0, 1]
    for h in cb.history:
        assert 0.0 <= h["recall@10"] <= 1.0
        assert 0.0 <= h["ndcg@10"] <= 1.0


def test_every_skips_epochs():
    _, params, truth, _ = _setup(seed=3)
    cb = fit_eval_callback(
        lambda p: (mf.build_phi(p, jnp.arange(40)), mf.export_psi(p)),
        truth, k=5, every=2,
    )
    for ep in range(4):
        cb(ep, params)
    assert [h["epoch"] for h in cb.history] == [0, 2]
