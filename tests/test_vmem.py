"""Unit coverage for the shared VMEM-budget blocking policy
(``repro.kernels.vmem``): budget respected, ``n_rows`` cap, ``multiple``
rounding, and the fixed-bytes-overflow behavior (raise, don't silently
return a tile that overflows VMEM)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import vmem


def test_fit_block_rows_budget_respected():
    per_row = 1000
    rows = vmem.fit_block_rows(per_row, budget=100_000)
    assert rows * per_row <= 100_000
    assert rows % 8 == 0 and rows >= 8


def test_fit_block_rows_fixed_bytes_reduce_rows():
    per_row = 1000
    free = vmem.fit_block_rows(per_row, budget=100_000)
    with_fixed = vmem.fit_block_rows(per_row, fixed_bytes=50_000, budget=100_000)
    assert with_fixed < free
    assert 50_000 + with_fixed * per_row <= 100_000


def test_fit_block_rows_n_rows_cap():
    # a tiny problem must not be padded up to a huge tile...
    assert vmem.fit_block_rows(4, n_rows=10) == 16
    # ...and the cap rounds UP to the multiple so one grid step covers it
    assert vmem.fit_block_rows(4, n_rows=100, multiple=128, lo=128) == 128


def test_fit_block_rows_multiple_rounding():
    rows = vmem.fit_block_rows(1000, budget=100_000, multiple=16)
    assert rows % 16 == 0
    # 100 rows fit; floor to the multiple, not up
    assert rows == 96


def test_fit_block_rows_hi_clamp():
    assert vmem.fit_block_rows(1, budget=1 << 30, hi=2048) == 2048


def test_fit_block_rows_fixed_overflow_raises():
    """The old behavior silently returned the ``lo`` floor even when
    ``fixed_bytes`` alone exceeded the budget — reachable via
    ``topk_block_items`` at large block_b·k_pad and via the gather kernels'
    ψ slab. It must raise a clear error instead."""
    with pytest.raises(vmem.VmemBudgetError):
        vmem.fit_block_rows(1000, fixed_bytes=200_000, budget=100_000)
    # per-row cost alone busting the budget at lo rows also raises
    with pytest.raises(vmem.VmemBudgetError):
        vmem.fit_block_rows(100_000, budget=100_000, lo=8)


def test_cd_sweep_block_ctx_budget():
    d_pad, k_b = 1024, 8
    rows = vmem.cd_sweep_block_ctx(d_pad, k_b)
    per_row = 4 * ((k_b + 3) * d_pad + k_b * k_b + 4 * k_b)
    assert rows * per_row <= vmem.VMEM_BUDGET_BYTES
    assert rows >= 8


def test_cd_sweep_block_ctx_floors_at_pathological_d_pad():
    """The pre-gathered fit is the dispatch of last resort: a degree-skewed
    d_pad whose minimal tile busts the soft budget floors at lo rows (the
    pre-PR-4 behavior) instead of raising — and the dispatch resolver
    therefore never escalates."""
    rows = vmem.cd_sweep_block_ctx(d_pad=40_000, k_b=8)
    assert rows == 8
    use_gather, block_ctx = vmem.resolve_cd_sweep_dispatch(
        40_000, 8, n_src=50_000_000, n_rows=100
    )
    assert not use_gather and block_ctx == 8


def test_cd_sweep_gather_block_ctx_slab_is_fixed():
    """The gather variant charges the ψ slab as FIXED bytes: growing n_src
    shrinks the row tile only past the point where the slab eats into the
    budget, and a slab alone larger than the budget raises."""
    d_pad, k_b = 1024, 8
    small = vmem.cd_sweep_gather_block_ctx(d_pad, k_b, n_src=1_000)
    big = vmem.cd_sweep_gather_block_ctx(d_pad, k_b, n_src=100_000)
    assert small >= big
    with pytest.raises(vmem.VmemBudgetError):
        # 10M-row slab × 8 cols × 4 B ≈ 320 MB ≫ the 8 MiB budget
        vmem.cd_sweep_gather_block_ctx(d_pad, k_b, n_src=10_000_000)


def test_resolve_cd_sweep_dispatch_fallback():
    d_pad, k_b = 1024, 8
    use_gather, _ = vmem.resolve_cd_sweep_dispatch(d_pad, k_b, 1_000)
    assert use_gather
    # slab too big → pre-gathered fallback instead of an exception
    use_gather, block_ctx = vmem.resolve_cd_sweep_dispatch(
        d_pad, k_b, 10_000_000
    )
    assert not use_gather
    assert block_ctx == vmem.cd_sweep_block_ctx(d_pad, k_b)
    # explicit pregather pin skips the gather fit entirely
    use_gather, _ = vmem.resolve_cd_sweep_dispatch(
        d_pad, k_b, 1_000, prefer_gather=False
    )
    assert not use_gather
    # compiled backends must not default onto the interpret-only gather
    # path (its Mosaic lowering is a follow-up)
    use_gather, _ = vmem.resolve_cd_sweep_dispatch(
        d_pad, k_b, 1_000, interpret=False
    )
    assert not use_gather


def test_topk_block_items_overflow_raises():
    """Large block_b·k_pad: the fixed φ/top-k state alone busts the budget."""
    with pytest.raises(vmem.VmemBudgetError):
        vmem.topk_block_items(block_b=2048, d_pad=128, k_pad=65536)


def test_topk_block_items_exclude_id_tile_charged():
    """The exclude-ID variant's resident (block_b, L_pad) tiles and per-row
    hit column must shrink the ψ tile, not ride for free."""
    free = vmem.topk_block_items(block_b=128, d_pad=128, k_pad=128)
    with_ids = vmem.topk_block_items(block_b=128, d_pad=128, k_pad=128,
                                     excl_l_pad=256)
    assert with_ids < free
    with pytest.raises(vmem.VmemBudgetError):
        # a pathologically wide exclude list busts even the minimal tile
        # (the kernel wrapper's block_b-halving loop is the way out)
        vmem.topk_block_items(block_b=128, d_pad=128, k_pad=128,
                              excl_l_pad=8192)


def test_cluster_block_items_merge_scratch_is_fixed_cost():
    """The cross-shard merge scratch (S·K candidate score+id rows) is a
    FIXED cost growing with the shard count: more shards ⇒ same-or-smaller
    per-shard ψ tile, and a scratch alone over budget raises (the cluster
    PROPAGATES instead of shrinking below one ψ block)."""
    kw = dict(d_pad=128, k_pad=128, block_b=128)
    single = vmem.topk_block_items(**kw)
    s2 = vmem.cluster_block_items(n_shards=2, **kw)
    s16 = vmem.cluster_block_items(n_shards=16, **kw)
    assert s2 <= single and s16 <= s2
    with pytest.raises(vmem.VmemBudgetError):
        # 1024 shards × k_pad 8192 of merge scratch ≫ the budget
        vmem.cluster_block_items(block_b=128, d_pad=128, k_pad=8192,
                                 n_shards=1024)


def test_topk_score_shrinks_block_b_on_overflow(monkeypatch):
    """The kernel wrapper owns the shrinkable fixed dimension: under a tiny
    budget it must halve block_b until the tile fits and still produce
    oracle-exact top-k (not silently overflow VMEM)."""
    from repro.kernels.topk_score.kernel import topk_score_pallas
    from repro.kernels.topk_score.ref import topk_score_ref

    # small enough that block_b=128 would demand > budget fixed bytes
    monkeypatch.setattr(vmem, "VMEM_BUDGET_BYTES", 300_000)
    with pytest.raises(vmem.VmemBudgetError):
        vmem.topk_block_items(block_b=128, d_pad=128, k_pad=128)

    # 200 query rows keep the initial block_b at 128, forcing the shrink
    # loop (128 → 64 → 32 fits under the shrunken budget)
    rng = np.random.default_rng(0)
    phi = jnp.asarray(rng.normal(size=(200, 16)), jnp.float32)
    psi = jnp.asarray(rng.normal(size=(300, 16)), jnp.float32)
    scores, ids = topk_score_pallas(phi, psi, k=10, interpret=True)
    exp_scores, exp_ids = topk_score_ref(phi, psi, k=10)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(exp_ids))
    np.testing.assert_allclose(np.asarray(scores), np.asarray(exp_scores),
                               rtol=1e-5, atol=1e-6)


def test_gather_kernel_uses_budgeted_tile():
    """End-to-end: the gather sweep kernel resolves its own block_ctx from
    the budget and still matches the pre-gathered kernel."""
    from repro.kernels.cd_sweep.kernel import (
        cd_block_sweep_gather_pallas,
        cd_block_sweep_pallas,
    )
    from repro.kernels.cd_sweep.ref import gather_psi_blk

    rng = np.random.default_rng(3)
    c, d_pad, k_b, n_src = 50, 128, 4, 23
    tab = jnp.asarray(rng.normal(size=(n_src, k_b)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, n_src, (c, d_pad)), jnp.int32)
    alpha = jnp.asarray(rng.random((c, d_pad)), jnp.float32)
    e = jnp.asarray(rng.normal(size=(c, d_pad)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(c, k_b)), jnp.float32)
    r1 = jnp.asarray(rng.normal(size=(c, k_b)), jnp.float32)
    jb = rng.normal(size=(k_b, k_b))
    jb = jnp.asarray(jb @ jb.T + k_b * np.eye(k_b), jnp.float32)
    args = dict(alpha0=0.4, l2=0.05, eta=1.0)
    w1, e1 = cd_block_sweep_pallas(
        gather_psi_blk(tab, ids), alpha, e, w, r1, jb, interpret=True, **args
    )
    w2, e2 = cd_block_sweep_gather_pallas(
        tab, ids, alpha, e, w, r1, jb, interpret=True, **args
    )
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), rtol=1e-6, atol=1e-7)


def test_jit_shapes_stable_under_budget():
    """block_ctx resolution happens at trace time on static shapes — the
    same call twice must hit the jit cache (no per-call recomputation
    changing shapes)."""
    from repro.kernels.cd_sweep.ops import cd_block_sweep_gather

    rng = np.random.default_rng(4)
    c, d_pad, k_b, n_src = 20, 128, 2, 11
    tab = jnp.asarray(rng.normal(size=(n_src, k_b)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, n_src, (c, d_pad)), jnp.int32)
    alpha = jnp.asarray(rng.random((c, d_pad)), jnp.float32)
    e = jnp.asarray(rng.normal(size=(c, d_pad)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(c, k_b)), jnp.float32)
    r1 = jnp.asarray(rng.normal(size=(c, k_b)), jnp.float32)
    jb = jnp.eye(k_b, dtype=jnp.float32)
    w1, e1 = cd_block_sweep_gather(tab, ids, alpha, e, w, r1, jb,
                                   alpha0=0.4, l2=0.05)
    w2, e2 = cd_block_sweep_gather(tab, ids, alpha, jnp.asarray(e1), w, r1,
                                   jb, alpha0=0.4, l2=0.05)
    assert w2.shape == w1.shape and e2.shape == e1.shape
    assert bool(jnp.isfinite(w2).all())


def test_resolve_psi_dispatch_validates():
    """A typo'd psi_dispatch must raise, not silently select the
    k_b×-peak-HBM pre-gathered path."""
    from repro.core import sweeps

    assert sweeps.resolve_psi_dispatch("gather") is True
    assert sweeps.resolve_psi_dispatch("pregather") is False
    with pytest.raises(ValueError, match="psi_dispatch"):
        sweeps.resolve_psi_dispatch("Gather")
    with pytest.raises(ValueError, match="psi_dispatch"):
        sweeps.resolve_psi_dispatch("in-kernel")


def test_budget_constant_sane():
    assert vmem.VMEM_BUDGET_BYTES <= vmem.VMEM_BYTES
    assert vmem.VMEM_BUDGET_BYTES >= 1 << 20
