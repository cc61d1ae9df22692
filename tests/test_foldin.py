"""Closed-form fold-in (``core/foldin.py`` + the Model adapters): CD vs the
float64 normal-equations oracle on every zoo model (user AND item side),
the empty-history / l2=0 corners, FM's structurally-fixed extended columns,
and one-CD-sweep equivalence against ``mf._side_sweep`` restricted to one
row (fold-in IS the training sweep's per-row subproblem)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import foldin
from repro.core.models import mf
from repro.core.models.mf import _side_sweep
from repro.core.models.zoo import ZOO, zoo_model
from repro.core.gram import gram


def _history(rng, n, m=7):
    return rng.choice(n, size=min(m, n), replace=False)


@pytest.mark.parametrize("name", ZOO)
def test_fold_in_user_matches_exact_oracle(name):
    model, params, _ = zoo_model(name, np.random.default_rng(3))
    rng = np.random.default_rng(17)
    table = np.asarray(model.export_psi(params))
    ids = _history(rng, table.shape[0])
    y = rng.integers(1, 4, ids.size).astype(np.float32)
    alpha = (1.0 + rng.random(ids.size)).astype(np.float32)
    row = model.fold_in_user(params, ids, y, alpha, n_sweeps=512, tol=1e-9)
    free, init = model._user_free_init()
    hp = model._foldin_hp()
    exact = foldin.fold_in_exact(
        table, ids, y, alpha, alpha0=hp["alpha0"], l2=hp["l2"],
        free=free, init=init,
    )
    np.testing.assert_allclose(row, exact, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("name", ZOO)
def test_fold_in_item_matches_exact_oracle(name):
    model, params, _ = zoo_model(name, np.random.default_rng(3))
    rng = np.random.default_rng(23)
    table = np.asarray(model.phi_table(params))
    ids = _history(rng, table.shape[0])
    row = model.fold_in_item(params, ids, n_sweeps=512, tol=1e-9)
    free, init = model._item_free_init()
    hp = model._foldin_hp()
    exact = foldin.fold_in_exact(
        table, ids, None, None, alpha0=hp["alpha0"], l2=hp["l2"],
        free=free, init=init,
    )
    np.testing.assert_allclose(row, exact, rtol=2e-4, atol=2e-5)


def test_fm_fixed_columns_hold():
    """FM extended coordinates: the constant-1 column that pairs with the
    other side's spec column must come out EXACTLY 1 on a folded row."""
    model, params, _ = zoo_model("fm", np.random.default_rng(3))
    k = model.hp.k
    u = model.fold_in_user(params, [0, 4, 9])
    i = model.fold_in_item(params, [1, 2])
    assert u.shape == (k + 2,) and i.shape == (k + 2,)
    assert u[k + 1] == 1.0      # Φe's constant-1 (meets ψ_spec)
    assert i[k] == 1.0          # Ψe's constant-1 (meets φ_spec)
    # the free spec coordinate DID move (it's being solved, not pinned)
    assert u[k] != 0.0 and i[k + 1] != 0.0


def test_empty_history_l2_zero_stays_finite():
    """m=0, λ=0: the normal system is singular; the CD clamp must return
    finite numbers (the all-zero implicit-prior solution), not NaN/inf."""
    rng = np.random.default_rng(0)
    table = rng.normal(size=(11, 5)).astype(np.float32)
    res = foldin.fold_in_row(table, [], alpha0=0.5, l2=0.0)
    assert np.all(np.isfinite(res.row))
    np.testing.assert_allclose(res.row, np.zeros(5), atol=1e-7)
    # and with l2 > 0 the exact oracle agrees on the empty-history solve
    exact = foldin.fold_in_exact(table, [], alpha0=0.5, l2=0.1)
    got = foldin.fold_in_row(table, [], alpha0=0.5, l2=0.1)
    np.testing.assert_allclose(got.row, exact, atol=1e-6)


def test_one_sweep_matches_mf_side_sweep_single_row():
    """fold_in_row with n_sweeps=1 IS ``mf._side_sweep`` on a (1, k) side:
    same residual cache, same Gram contraction, same Newton step."""
    rng = np.random.default_rng(5)
    n_items, k, m = 13, 6, 8
    h = rng.normal(size=(n_items, k)).astype(np.float32)
    ids = rng.choice(n_items, size=m, replace=False)
    y = rng.integers(1, 4, m).astype(np.float32)
    alpha = (1.0 + rng.random(m)).astype(np.float32)
    hp = mf.MFHyperParams(k=k, alpha0=0.4, l2=0.07)

    got = foldin.fold_in_row(
        h, ids, y, alpha, alpha0=hp.alpha0, l2=hp.l2, eta=hp.eta, n_sweeps=1
    )
    h_j = jnp.asarray(h)
    side, _ = _side_sweep(
        jnp.zeros((1, k), jnp.float32), gram(h_j),
        lambda f: h_j[jnp.asarray(ids), f],
        jnp.zeros(m, jnp.int32), jnp.asarray([0, m], jnp.int32),
        jnp.asarray(alpha), jnp.asarray(-y), hp,
    )
    np.testing.assert_allclose(got.row, np.asarray(side[0]),
                               rtol=2e-5, atol=2e-6)


def test_fold_in_validation():
    table = np.zeros((4, 3), np.float32)
    with pytest.raises(ValueError):
        foldin.fold_in_row(table, [4], alpha0=1.0, l2=0.1)   # id out of range
    with pytest.raises(ValueError):
        foldin.fold_in_row(table, [0], y=np.ones(2), alpha0=1.0, l2=0.1)
    with pytest.raises(ValueError):
        foldin.fold_in_row(table, [0], alpha0=1.0, l2=0.1,
                           free=np.ones(2, bool))            # bad mask shape
