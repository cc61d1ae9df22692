"""Tiny reference instances of every k-separable model.

Shared by the kernel and serving-mesh parity tests and the serve bench: build
a small instance of each zoo model through the unified
:mod:`repro.core.models.api` ``Model`` protocol, so every consumer
exercises the same five models via ONE surface (no per-model signature
branches) and a new zoo member only has to be added HERE.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.design import make_design
from repro.core.models import fm, mf, mfsi, parafac, tucker
from repro.core.models.api import Dataset, build_model
from repro.core.models.parafac import TensorContext

ZOO = ("mf", "mfsi", "fm", "parafac", "tucker")


def rand_f32(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def zoo_model(name, rng, *, n_ctx=20, n_items=37, b=9, k=6):
    """A small instance of zoo model ``name`` through the unified API:
    returns ``(model, params, query)`` where ``model`` is the
    :class:`~repro.core.models.api.Model` adapter, ``params`` a seeded init,
    and ``query`` a B-row ``build_phi`` address in the model's own query
    space (ctx ids / design rows / a ``(c1, c2)`` pair tuple)."""
    if name == "mf":
        model = build_model("mf", hp=mf.MFHyperParams(k=k), dataset=Dataset())
        return model, mf.init(jax.random.PRNGKey(0), n_ctx, n_items, k), \
            jnp.arange(b)
    if name == "parafac":
        params = parafac.init(jax.random.PRNGKey(1), 8, 7, n_items, k)
        c1 = jnp.asarray(rng.integers(0, 8, b), jnp.int32)
        c2 = jnp.asarray(rng.integers(0, 7, b), jnp.int32)
        tc = TensorContext(c1=c1, c2=c2, n_c1=8, n_c2=7)
        model = build_model(
            "parafac", hp=parafac.PARAFACHyperParams(k=k), dataset=Dataset(tc=tc)
        )
        return model, params, (c1, c2)
    if name == "tucker":
        params = tucker.init(jax.random.PRNGKey(2), 8, 7, n_items, 4, 3, k)
        c1 = jnp.asarray(rng.integers(0, 8, b), jnp.int32)
        c2 = jnp.asarray(rng.integers(0, 7, b), jnp.int32)
        tc = TensorContext(c1=c1, c2=c2, n_c1=8, n_c2=7)
        model = build_model(
            "tucker", hp=tucker.TuckerHyperParams(k1=4, k2=3, k3=k),
            dataset=Dataset(tc=tc),
        )
        return model, params, (c1, c2)
    x = make_design(
        [dict(name="id", ids=np.arange(n_ctx) % 11, vocab=11),
         dict(name="grp", ids=rng.integers(0, 5, n_ctx), vocab=5)], n_ctx)
    z = make_design(
        [dict(name="item_id", ids=np.arange(n_items), vocab=n_items),
         dict(name="genre", ids=rng.integers(0, 7, n_items), vocab=7)], n_items)
    if name == "mfsi":
        model = build_model(
            "mfsi", hp=mfsi.MFSIHyperParams(k=k), dataset=Dataset(x=x, z=z)
        )
        return model, mfsi.init(jax.random.PRNGKey(3), x.p, z.p, k), \
            jnp.arange(b)
    if name != "fm":
        raise ValueError(f"unknown zoo model {name!r}")
    hp = fm.FMHyperParams(k=k)
    params = fm.init(jax.random.PRNGKey(4), x.p, z.p, k)
    # break the all-zero linear/bias init so ψ_spec is a real column
    params = params._replace(
        b=jnp.asarray(0.3), w_lin=rand_f32((x.p,), 10),
        h_lin=rand_f32((z.p,), 11),
    )
    model = build_model("fm", hp=hp, dataset=Dataset(x=x, z=z))
    return model, params, jnp.arange(b)


def model_phi_psi(name, rng, *, n_ctx=20, n_items=37, b=9, k=6):
    """A small instance of zoo model ``name``; returns (phi (B, D),
    psi (n_items, D)) through the model's export contract."""
    model, params, query = zoo_model(name, rng, n_ctx=n_ctx, n_items=n_items,
                                     b=b, k=k)
    return model.build_phi(params, query), model.export_psi(params)
