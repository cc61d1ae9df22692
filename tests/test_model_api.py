"""Unified Model protocol (``core/models/api.py``): all five zoo models run
the SAME lifecycle loop — init → objective → fit → epoch → export_psi →
build_phi → fold_in_user/item — with zero per-model signature branches at
the call site; plus serving integration (the mesh built from
``export_psi``/``build_phi``, fold-in through ``fold_in_user``) and the
Dataset.require error contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.design import make_design
from repro.core.models import fm, mf, mfsi, parafac, tucker
from repro.core.models.api import Dataset, Model, build_model
from repro.core.models.parafac import TensorContext
from repro.core.models.zoo import ZOO
from repro.serve.mesh import FaultTolerantRetrievalMesh
from repro.sparse.interactions import build_interactions

N_CTX, N_ITEMS, K = 10, 12, 4


def _interactions(rng, n_ctx=N_CTX, n_items=N_ITEMS, nnz=40, alpha0=0.3):
    cells = rng.choice(n_ctx * n_items, size=nnz, replace=False)
    return build_interactions(
        cells // n_items, cells % n_items, rng.integers(1, 4, nnz),
        alpha0 + 1.0 + rng.random(nnz), n_ctx, n_items, alpha0=alpha0,
    )


def _model(name):
    """(model, query): one construction recipe per zoo member; everything
    downstream of here is model-agnostic."""
    rng = np.random.default_rng(7)
    data = _interactions(rng)
    if name == "mf":
        return build_model(
            "mf", hp=mf.MFHyperParams(k=K, alpha0=0.3, l2=0.05),
            dataset=Dataset(data=data),
        ), jnp.arange(6)
    if name in ("mfsi", "fm"):
        x = make_design(
            [dict(name="id", ids=np.arange(N_CTX) % 7, vocab=7),
             dict(name="grp", ids=rng.integers(0, 3, N_CTX), vocab=3)], N_CTX)
        z = make_design(
            [dict(name="item_id", ids=np.arange(N_ITEMS), vocab=N_ITEMS),
             dict(name="genre", ids=rng.integers(0, 5, N_ITEMS), vocab=5)],
            N_ITEMS)
        hp = (mfsi.MFSIHyperParams(k=K, alpha0=0.3, l2=0.05) if name == "mfsi"
              else fm.FMHyperParams(k=K, alpha0=0.3, l2=0.05))
        return build_model(
            name, hp=hp, dataset=Dataset(data=data, x=x, z=z)
        ), jnp.arange(6)
    n_c1, n_c2 = 5, 4
    chosen = rng.choice(n_c1 * n_c2, size=N_CTX, replace=False)
    tc = TensorContext(c1=jnp.asarray(chosen // n_c2, jnp.int32),
                       c2=jnp.asarray(chosen % n_c2, jnp.int32),
                       n_c1=n_c1, n_c2=n_c2)
    hp = (parafac.PARAFACHyperParams(k=K, alpha0=0.3, l2=0.05)
          if name == "parafac"
          else tucker.TuckerHyperParams(k1=3, k2=2, k3=K, alpha0=0.3, l2=0.05))
    model = build_model(name, hp=hp, dataset=Dataset(data=data, tc=tc))
    q = (jnp.asarray([0, 1, 2, 3, 0, 1], jnp.int32),
         jnp.asarray([1, 0, 3, 2, 2, 0], jnp.int32))
    return model, q


@pytest.mark.parametrize("name", ZOO)
def test_uniform_lifecycle(name):
    model, query = _model(name)
    assert isinstance(model, Model)          # runtime_checkable protocol
    params = model.init(jax.random.PRNGKey(0))
    obj0 = float(model.objective(params))
    params = model.fit(params, n_epochs=3)
    assert float(model.objective(params)) < obj0
    # manual epoch with residual cache: same surface on every model
    e = model.residuals(params)
    params2, e2 = model.epoch(params, e)
    assert float(model.objective(params2)) <= float(model.objective(params))
    psi = model.export_psi(params)
    phi = model.build_phi(params, query)
    assert psi.shape[0] == N_ITEMS and phi.shape == (6, psi.shape[1])
    # fold-in rows live in the same export coordinates
    u = model.fold_in_user(params, [0, 3, 7])
    i = model.fold_in_item(params, [1, 2])
    assert u.shape == (psi.shape[1],) and i.shape == (psi.shape[1],)
    assert np.all(np.isfinite(u)) and np.all(np.isfinite(i))


@pytest.mark.parametrize("name", ZOO)
def test_engine_from_model(name):
    model, query = _model(name)
    params = model.fit(model.init(jax.random.PRNGKey(0)), n_epochs=2)
    psi = model.export_psi(params)
    mesh = FaultTolerantRetrievalMesh(
        lambda q: model.build_phi(params, q), n_shards=1, n_replicas=1, k=5,
        psi_table=psi)
    res = mesh.topk(query)                   # the model's own query space
    assert res.ids.shape == (6, 5) and res.coverage == 1.0
    # parity with the φ rows built by hand
    ref_res = mesh.topk_phi(model.build_phi(params, query))
    assert bool((res.ids == ref_res.ids).all())
    # request-time fold-in through the serving tier
    phi = jnp.asarray(model.fold_in_user(params, [0, 2], n_sweeps=32),
                      jnp.float32)[None, :]
    assert phi.shape == (1, psi.shape[1])
    assert mesh.topk_phi(phi).ids.shape == (1, 5)


def test_dataset_require_errors():
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match="missing"):
        build_model("mfsi", hp=mfsi.MFSIHyperParams(k=K), dataset=Dataset())
    with pytest.raises(ValueError, match="missing"):
        build_model("tucker", hp=tucker.TuckerHyperParams(k1=2, k2=2, k3=K),
                    dataset=Dataset(data=_interactions(rng)))
    with pytest.raises(ValueError, match="unknown model"):
        build_model("gcn", hp=None, dataset=Dataset())
    # data-less adapters exist (fold-in-only use) but training requires data
    m = build_model("mf", hp=mf.MFHyperParams(k=K), dataset=Dataset())
    with pytest.raises(ValueError, match="missing"):
        m.fit(mf.init(jax.random.PRNGKey(0), N_CTX, N_ITEMS, K), n_epochs=1)
