"""Exactness of iCD vs conventional CD on the full implicit matrix.

The paper's central claim (Lemma 1 + Lemma 2 + Lemma 3) is that iCD performs
the SAME Newton coordinate steps as conventional CD over all |C|·|I|
implicit examples, at a fraction of the cost. We verify trajectory-level
equality: same init + same sweep order ⇒ same parameters after each epoch.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import naive_cd
from repro.core.models import mf
from repro.sparse.interactions import build_interactions
from repro.sparse.segment import SORTED_TILE

jax.config.update("jax_enable_x64", False)


def make_problem(seed=0, n_ctx=13, n_items=9, nnz=37, alpha0=0.4):
    rng = np.random.default_rng(seed)
    pairs = rng.choice(n_ctx * n_items, size=nnz, replace=False)
    ctx, item = pairs // n_items, pairs % n_items
    y = rng.integers(1, 5, size=nnz).astype(np.float64)
    alpha = alpha0 + 1.0 + rng.random(nnz)  # α > α₀
    data = build_interactions(ctx, item, y, alpha, n_ctx, n_items, alpha0=alpha0)
    y_dense, a_dense = naive_cd.dense_from_observed(
        jnp.asarray(ctx), jnp.asarray(item), jnp.asarray(y, jnp.float32),
        jnp.asarray(alpha, jnp.float32), n_ctx, n_items, alpha0,
    )
    return data, y_dense, a_dense


@pytest.mark.parametrize("k", [1, 3, 8])
def test_mf_icd_matches_naive_cd_trajectory(k):
    data, y_dense, a_dense = make_problem()
    hp = mf.MFHyperParams(k=k, alpha0=0.4, l2=0.05, eta=1.0)
    params = mf.init(jax.random.PRNGKey(1), data.n_ctx, data.n_items, k)
    params_naive = params

    e = mf.residuals(params, data)
    for _ in range(3):
        params, e = mf.epoch(params, data, e, hp)
        params_naive = naive_cd.epoch_dense(params_naive, y_dense, a_dense, hp)
        np.testing.assert_allclose(params.w, params_naive.w, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(params.h, params_naive.h, rtol=2e-4, atol=2e-5)


def make_powerlaw_problem(seed=0, n_ctx=4000, n_items=40, alpha0=0.4):
    """Each context holds 1-10 distinct items drawn by Zipf(1.0) popularity,
    so the top item's run in the item-major layout spans several tiles of
    the sorted-run sums."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, n_items + 1)
    p /= p.sum()
    deg = rng.integers(1, 11, n_ctx)
    ctx = np.repeat(np.arange(n_ctx), deg)
    item = np.concatenate([rng.choice(n_items, d, replace=False, p=p) for d in deg])
    nnz = ctx.size
    y = rng.integers(1, 5, size=nnz).astype(np.float64)
    alpha = alpha0 + 1.0 + rng.random(nnz)
    data = build_interactions(ctx, item, y, alpha, n_ctx, n_items, alpha0=alpha0)
    y_dense, a_dense = naive_cd.dense_from_observed(
        jnp.asarray(ctx), jnp.asarray(item), jnp.asarray(y, jnp.float32),
        jnp.asarray(alpha, jnp.float32), n_ctx, n_items, alpha0,
    )
    return data, y_dense, a_dense


@pytest.mark.parametrize("k", [1, 4])
def test_mf_icd_matches_naive_cd_powerlaw(k):
    """The trajectory equality above on a power-law layout whose top item's
    run crosses several tiles: the residual patch copies each step across
    tile boundaries."""
    data, y_dense, a_dense = make_powerlaw_problem()
    assert np.diff(np.asarray(data.t_indptr)).max() > 2 * SORTED_TILE + 1
    hp = mf.MFHyperParams(k=k, alpha0=0.4, l2=0.05, eta=1.0)
    params = mf.init(jax.random.PRNGKey(1), data.n_ctx, data.n_items, k)
    params_naive = params

    e = mf.residuals(params, data)
    for _ in range(3):
        params, e = mf.epoch(params, data, e, hp)
        params_naive = naive_cd.epoch_dense(params_naive, y_dense, a_dense, hp)
        np.testing.assert_allclose(params.w, params_naive.w, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(params.h, params_naive.h, rtol=2e-4, atol=2e-5)


def _scoped_ops(text):
    """(opcode, element count, op_name) of each instruction of compiled HLO
    text that carries an op_name."""
    ops = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* (\w+)\(.*op_name=\"([^\"]*)\"", line)
        if m:
            dims = [int(d) for d in m.group(1).split(",") if d]
            ops.append((m.group(2), int(np.prod(dims)), m.group(3)))
    return ops


def test_epoch_patch_streams_the_runs():
    """In the compiled epoch no gather of one value per pair sits under
    ``icd.patch``: the step is copied along the sorted rows' runs. The
    per-pair column gathers under ``icd.gather`` are found, so the reading
    sees the ops it looks for."""
    data, _, _ = make_problem(seed=2, n_ctx=60, n_items=70, nnz=3001)
    hp = mf.MFHyperParams(k=2, alpha0=0.4, l2=0.05)
    params = mf.init(jax.random.PRNGKey(0), data.n_ctx, data.n_items, 2)
    e = mf.residuals(params, data)
    ops = _scoped_ops(mf.epoch.lower(params, data, e, hp).compile().as_text())
    per_pair = lambda scope, op: [o for o in ops if o[0] == op
                                  and o[1] == data.nnz and scope in o[2]]
    assert len(per_pair("icd.gather", "gather")) >= 2
    assert per_pair("icd.patch", "gather") == []


def test_mf_objective_monotone_decreasing():
    data, y_dense, a_dense = make_problem(seed=3, n_ctx=20, n_items=15, nnz=60)
    hp = mf.MFHyperParams(k=4, alpha0=0.4, l2=0.05)
    params = mf.init(jax.random.PRNGKey(2), data.n_ctx, data.n_items, 4)
    e = mf.residuals(params, data)
    prev = float(mf.objective(params, data, hp))
    for _ in range(6):
        params, e = mf.epoch(params, data, e, hp)
        cur = float(mf.objective(params, data, hp))
        assert cur <= prev + 1e-4, (cur, prev)
        prev = cur


def test_residual_cache_consistency():
    """The maintained residual cache must equal freshly computed residuals."""
    data, _, _ = make_problem(seed=5)
    hp = mf.MFHyperParams(k=5, alpha0=0.4, l2=0.1)
    params = mf.init(jax.random.PRNGKey(3), data.n_ctx, data.n_items, 5)
    e = mf.residuals(params, data)
    for _ in range(2):
        params, e = mf.epoch(params, data, e, hp)
    np.testing.assert_allclose(e, mf.residuals(params, data), rtol=1e-4, atol=1e-5)


def test_damped_step_also_converges():
    data, _, _ = make_problem(seed=7)
    hp = mf.MFHyperParams(k=3, alpha0=0.4, l2=0.05, eta=0.5)
    params = mf.init(jax.random.PRNGKey(4), data.n_ctx, data.n_items, 3)
    e = mf.residuals(params, data)
    start = float(mf.objective(params, data, hp))
    for _ in range(8):
        params, e = mf.epoch(params, data, e, hp)
    assert float(mf.objective(params, data, hp)) < start


def test_fit_compiles_one_epoch_program():
    """Unscheduled epochs pass the same static sweep index, so a 3-epoch
    fit traces and compiles the epoch once."""
    rng = np.random.default_rng(3)
    n_ctx, n_items, nnz = 9, 7, 30
    cells = rng.choice(n_ctx * n_items, nnz, replace=False)
    data = build_interactions(cells // n_items, cells % n_items,
                              np.ones(nnz), np.full(nnz, 2.0), n_ctx, n_items,
                              alpha0=1.0)
    hp = mf.MFHyperParams(k=3, alpha0=1.0, l2=0.1)
    params = mf.init(jax.random.PRNGKey(0), n_ctx, n_items, 3)
    before = mf.epoch._cache_size()
    mf.fit(params, data, hp, 3)
    assert mf.epoch._cache_size() - before == 1


def test_residuals_batched_gather_matches_direct():
    """Residuals run in fixed-size pair batches (full-width gathers would
    not fit one chip); a count that is not a batch multiple must still give
    the direct Σ φ·ψ − ȳ."""
    from repro.core import sweeps

    rng = np.random.default_rng(4)
    nnz = sweeps._RESID_BATCH + 123
    phi = jnp.asarray(rng.normal(size=(50, 4)), jnp.float32)
    psi = jnp.asarray(rng.normal(size=(40, 4)), jnp.float32)
    ctx = jnp.asarray(rng.integers(0, 50, nnz), jnp.int32)
    item = jnp.asarray(rng.integers(0, 40, nnz), jnp.int32)
    y = jnp.asarray(rng.normal(size=nnz), jnp.float32)
    got = sweeps.residuals_from_factors(phi, psi, ctx, item, y)
    want = np.sum(np.asarray(phi)[ctx] * np.asarray(psi)[item], axis=1) - y
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
