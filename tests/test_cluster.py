"""Sharded retrieval cluster: bit-exact parity with the single-device
engine and the dense oracle at every shard count, cross-shard merge edges,
live publish/refresh, and the shard_map execution path."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _zoo import ZOO, model_phi_psi, _rand

from repro.core.models import mf
from repro.kernels import vmem
from repro.kernels.topk_score import topk_score_ref
from repro.serve.cluster import (
    ShardedRetrievalCluster,
    cluster_topk,
    resolve_cluster_block_items,
    shard_psi,
)
from repro.serve.engine import (
    RetrievalEngine,
    exclude_ids_from_lists,
    exclude_mask_from_lists,
)


@pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
def test_cluster_bit_identical_to_engine_any_shard_count(n_shards):
    """The acceptance criterion: ids AND scores bit-identical to the
    single-device engine and the dense lax.top_k oracle, with and without
    exclusion, at shard counts that do and don't divide n_items (101)."""
    rng = np.random.default_rng(0)
    phi, psi = _rand((9, 16), 1), _rand((101, 16), 2)
    engine = RetrievalEngine(psi, lambda p=phi: p, k=13, block_items=32)
    cl = ShardedRetrievalCluster(
        lambda p=phi: p, n_shards=n_shards, k=13, block_items=32,
        psi_table=psi,
    )
    es, ei = engine.topk()
    cs, ci = cl.topk()
    np.testing.assert_array_equal(np.asarray(ci), np.asarray(ei))
    assert bool((np.asarray(cs) == np.asarray(es)).all())  # BIT-identical
    ds, di = jax.lax.top_k(phi @ psi.T, 13)
    np.testing.assert_array_equal(np.asarray(ci), np.asarray(di))

    lists = [rng.choice(101, size=int(rng.integers(0, 8)), replace=False)
             for _ in range(9)]
    mask = exclude_mask_from_lists(lists, 101)
    eids = exclude_ids_from_lists(lists)
    es2, ei2 = engine.topk(exclude_mask=mask)
    for kwargs in (dict(exclude_mask=mask), dict(exclude_ids=eids)):
        cs2, ci2 = cl.topk(**kwargs)
        np.testing.assert_array_equal(np.asarray(ci2), np.asarray(ei2))
        assert bool((np.asarray(cs2) == np.asarray(es2)).all())


@pytest.mark.parametrize("name", ZOO)
def test_cluster_parity_all_models(name):
    """Every k-separable model through its export contract, sharded 3 ways
    (37 items ⇒ non-divisible), vs the dense oracle."""
    rng = np.random.default_rng(42)
    phi, psi = model_phi_psi(name, rng)
    cl = ShardedRetrievalCluster(
        lambda p=phi: p, n_shards=3, k=12, block_items=32, psi_table=psi
    )
    s, i = cl.topk()
    rs, ri = topk_score_ref(phi, psi, 12)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs), rtol=1e-5,
                               atol=1e-6)
    lists = [rng.choice(psi.shape[0], size=5, replace=False)
             for _ in range(phi.shape[0])]
    s2, i2 = cl.topk(exclude_ids=exclude_ids_from_lists(lists))
    rs2, ri2 = topk_score_ref(
        phi, psi, 12, exclude_mask_from_lists(lists, psi.shape[0])
    )
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(ri2))


def test_k_larger_than_one_shards_item_count():
    """K exceeding rows_per: every shard returns its whole range and the
    merge still ranks the global catalogue exactly."""
    phi, psi = _rand((4, 8), 3), _rand((10, 8), 4)
    table = shard_psi(psi, 3)  # rows_per=4 < K
    assert table.rows_per < 7
    s, i = cluster_topk(table, phi, 7, block_items=32)
    rs, ri = topk_score_ref(phi, psi, 7)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    # K even beyond n_items: inadmissible tail is (−inf, −1)
    s2, i2 = cluster_topk(table, phi, 15, block_items=32)
    assert bool((np.asarray(i2)[:, 10:] == -1).all())
    assert bool(np.isneginf(np.asarray(s2)[:, 10:]).all())


def test_global_tie_stability_across_shard_boundaries():
    """Duplicated ψ rows land in DIFFERENT shards ⇒ exact cross-shard score
    ties; the merged ranking must still be ascending-global-id."""
    base = _rand((30, 6), 5)
    psi = jnp.concatenate([base, base], axis=0)  # ids i and i+30 tie
    phi = _rand((5, 6), 6)
    rs, ri = topk_score_ref(phi, psi, 25)
    for n_shards in (2, 3, 4):  # boundaries split the tie pairs differently
        table = shard_psi(psi, n_shards)
        s, i = cluster_topk(table, phi, 25, block_items=32)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


def test_fully_excluded_shard_returns_neginf_slots():
    """A shard whose whole row range is excluded contributes only
    (−inf, −1) candidates; the merge must fill from the other shards and
    a fully-excluded CATALOGUE row must come back all (−inf, −1)."""
    phi, psi = _rand((3, 8), 7), _rand((24, 8), 8)
    table = shard_psi(psi, 3)  # shard 1 owns ids [8, 16)
    lists = [np.arange(8, 16), np.arange(8, 16), np.arange(24)]
    eids = exclude_ids_from_lists(lists)
    s, i = cluster_topk(table, phi, 24, exclude_ids=eids, block_items=32)
    got_i, got_s = np.asarray(i), np.asarray(s)
    # rows 0/1: shard 1's ids never appear; 16 admissible slots then −inf
    for r in (0, 1):
        real = got_i[r][got_i[r] >= 0]
        assert real.size == 16 and not np.isin(real, np.arange(8, 16)).any()
    # row 2: everything excluded — no id leaks at all
    assert bool((got_i[2] == -1).all()) and bool(np.isneginf(got_s[2]).all())
    rs, ri = topk_score_ref(
        phi, psi, 24, exclude_mask_from_lists(lists, 24)
    )
    np.testing.assert_array_equal(got_i, np.asarray(ri))


def test_publish_versioning_and_live_refresh():
    """fit(callback=PsiPublisher) refreshes the serving table per epoch:
    version bumps, results track the LATEST params, and a snapshot grabbed
    pre-publish still serves the old table (double buffer)."""
    from repro.serve.publish import PsiPublisher
    from repro.sparse.interactions import build_interactions

    rng = np.random.default_rng(9)
    n_ctx, n_items, k = 30, 50, 6
    params = mf.init(jax.random.PRNGKey(0), n_ctx, n_items, k)
    cl = ShardedRetrievalCluster(
        lambda ctx: mf.build_phi(params, ctx), n_shards=2, k=10,
        block_items=32,
    )
    with pytest.raises(RuntimeError, match="publish"):
        _ = cl.table  # serving before any publish must fail loudly
    pub = PsiPublisher(cl, mf.export_psi, every=1)

    nnz = 200
    cells = rng.choice(n_ctx * n_items, nnz, replace=False)
    data = build_interactions(
        cells // n_items, cells % n_items, rng.integers(1, 4, nnz),
        1.0 + rng.random(nnz), n_ctx, n_items, alpha0=0.3,
    )
    hp = mf.MFHyperParams(k=k, alpha0=0.3, l2=0.05)
    fitted = mf.fit(params, data, hp, n_epochs=2, callback=pub)
    assert [v for _, v in pub.versions] == [1, 2]
    assert cl.version == 2

    # the live table is epoch-2's ψ: cluster == fresh engine on the export
    phi = mf.build_phi(fitted, jnp.arange(8))
    engine = RetrievalEngine(mf.export_psi(fitted),
                             lambda ctx: mf.build_phi(fitted, ctx),
                             k=10, block_items=32)
    cs, ci = cl.topk_phi(phi)
    es, ei = engine.topk_phi(phi)
    np.testing.assert_array_equal(np.asarray(ci), np.asarray(ei))
    assert bool((np.asarray(cs) == np.asarray(es)).all())

    # double buffer: a snapshot held across a publish keeps serving v2
    old_table = cl.table
    cl.publish(jnp.zeros((n_items, k)))  # v3: degenerate table
    assert cl.version == 3 and old_table.version == 2
    s_old, i_old = cluster_topk(old_table, phi, 10, block_items=32)
    np.testing.assert_array_equal(np.asarray(i_old), np.asarray(ei))


def test_cluster_block_items_resolution_raises_not_shrinks(monkeypatch):
    """The merge scratch (S·K rows) busting the budget must surface as
    VmemBudgetError from the cluster's resolution — never a silent tile
    below one ψ block."""
    phi, psi = _rand((8, 16), 10), _rand((64, 16), 11)
    table = shard_psi(psi, 4)
    monkeypatch.setattr(vmem, "VMEM_BUDGET_BYTES", 200_000)
    with pytest.raises(vmem.VmemBudgetError):
        resolve_cluster_block_items(table, b=8, k=1024)
    with pytest.raises(vmem.VmemBudgetError):
        cluster_topk(table, phi, 1024)
    # an explicit block_items pin (the operator override) still works
    s, i = cluster_topk(table, phi, 8, block_items=128)
    rs, ri = topk_score_ref(phi, psi, 8)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


SHARD_MAP_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["REPRO_PALLAS_INTERPRET"] = "1"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np

    from repro.kernels.topk_score import topk_score_ref
    from repro.kernels.topk_score.ref import SCORE_ATOL, SCORE_RTOL
    from repro.serve.cluster import shard_map_topk, shard_psi
    from repro.serve.engine import exclude_ids_from_lists

    rng = np.random.default_rng(0)
    phi = jnp.asarray(rng.normal(size=(9, 16)), jnp.float32)
    psi = jnp.asarray(rng.normal(size=(101, 16)), jnp.float32)
    table = shard_psi(psi, 4, devices=jax.devices())
    mesh = jax.make_mesh((4,), ("shards",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    s, i = shard_map_topk(mesh, table, phi, 13, block_items=32)
    rs, ri = topk_score_ref(phi, psi, 13)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs),
                               rtol=SCORE_RTOL, atol=SCORE_ATOL)
    lists = [rng.choice(101, size=6, replace=False) for _ in range(9)]
    eids = exclude_ids_from_lists(lists)
    s2, i2 = shard_map_topk(mesh, table, phi, 13, exclude_ids=eids,
                            block_items=32)
    rs2, ri2 = topk_score_ref(phi, psi, 13, exclude_ids=eids)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(ri2))
    print("SHARD-MAP-TOPK-OK")
    """
)


@pytest.mark.slow
def test_shard_map_path_matches_oracle():
    """One shard_map over 4 forced host devices == the dense oracle (the
    pod-scale execution path; offsets from lax.axis_index)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SHARD_MAP_SCRIPT],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(__file__)),
        env={**env, "PYTHONPATH": "src"}, timeout=600,
    )
    assert "SHARD-MAP-TOPK-OK" in proc.stdout, (
        proc.stdout[-2000:] + proc.stderr[-3000:]
    )


def test_multi_device_placement_single_host():
    """devices= places shards round-robin (degenerate single-device here —
    the placement plumbing must still be parity-clean)."""
    phi, psi = _rand((5, 8), 12), _rand((40, 8), 13)
    cl = ShardedRetrievalCluster(
        lambda p=phi: p, n_shards=3, k=9, block_items=32,
        devices=jax.devices(), psi_table=psi,
    )
    s, i = cl.topk()
    rs, ri = topk_score_ref(phi, psi, 9)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
