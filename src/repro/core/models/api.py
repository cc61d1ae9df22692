"""Unified ``Model`` protocol over the k-separable zoo (MF/MFSI/FM/PARAFAC/
Tucker).

The five model modules grew drifted entry points (``mf.fit(params, data,
hp, ...)`` vs ``fm.fit(params, x, z, data, hp, ...)`` vs ``tucker.fit(
params, tc, data, hp, ...)``; ``build_phi`` takes ctx ids / a Design / a
``(c1, c2)`` pair depending on the model). This module routes them through
ONE surface so the serving mesh, ranking eval, zoo helpers, and the
continual-learning tier never branch on per-model signatures:

    ds = Dataset(data=interactions, x=x, z=z)          # per-model bundle
    model = build_model("fm", hp=hp, dataset=ds)
    params = model.init(jax.random.PRNGKey(0))
    params = model.fit(params, n_epochs=5)             # data keyword-only
    psi = model.export_psi(params)                     # (n_items, D)
    phi = model.build_phi(params, query)               # (B, D) query rows
    phi_new = model.fold_in_user(params, item_ids)     # closed-form, no epoch
    psi_new = model.fold_in_item(params, ctx_ids)      # → serve publish_delta

``query`` is the model's natural address: context ids (MF), context-design
row ids (MFSI/FM), or a ``(c1, c2)`` pair tuple (PARAFAC/Tucker). Everything
else — which designs/tensor-context a model needs, FM's extended-column
conventions, which fold-in coordinates are structurally fixed — lives inside
the adapter.

Fold-in (the continual-learning path) solves ONE embedding row in export
coordinates against the frozen other side via :mod:`repro.core.foldin`:
``fold_in_user`` returns a φ row ready for the mesh's ``topk_phi``;
``fold_in_item`` returns a ψ row ready for the serving tier's
``publish_delta``. FM's constant-1 extended columns are held fixed
automatically (the ``free`` mask).

The module-level functions in ``mf.py``/``mfsi.py``/... remain the public
low-level API (existing tests/benches use them unmodified); the adapters
are thin delegates, not reimplementations.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Protocol, runtime_checkable

import jax
import numpy as np

from repro.core import foldin
from repro.core.design import Design
from repro.core.models import fm, mf, mfsi, parafac, tucker
from repro.core.models.parafac import TensorContext
from repro.sparse.interactions import Interactions

__all__ = [
    "Dataset", "Model", "build_model", "MODEL_TYPES",
    "MFModel", "MFSIModel", "FMModel", "PARAFACModel", "TuckerModel",
    "CtxMFModel",
]


@dataclasses.dataclass(frozen=True)
class Dataset:
    """Per-model data bundle: everything a model consumes besides params.

    ``data``  training interactions (always; fold-in works without it)
    ``x``/``z`` context/item feature designs (MFSI, FM)
    ``tc``    tensor context pair lists (PARAFAC, Tucker, CtxMF)
    ``confidence`` optional (nnz,) per-interaction confidence weights in
    ctx-major nnz order (e.g. from
    :func:`repro.core.implicit.frequency_confidence` /
    :func:`~repro.core.implicit.confidence_weights`); threaded as
    ``weights=`` through every adapter's ``fit``/``epoch`` unless the call
    overrides it. ``None`` keeps every training program bit-identical to
    the unweighted one.
    """

    data: Optional[Interactions] = None
    x: Optional[Design] = None
    z: Optional[Design] = None
    tc: Optional[TensorContext] = None
    confidence: Optional[jax.Array] = None

    def require(self, *fields: str) -> "Dataset":
        missing = [f for f in fields if getattr(self, f) is None]
        if missing:
            raise ValueError(f"Dataset is missing required field(s) {missing}")
        return self


@runtime_checkable
class Model(Protocol):
    """What every zoo adapter provides (see module docstring)."""

    name: str
    hp: object
    dataset: Dataset

    def init(self, key: jax.Array): ...
    def fit(self, params, *, n_epochs: int, data: Optional[Interactions] = None,
            callback: Optional[Callable] = None, schedule=None,
            weights=None): ...
    def epoch(self, params, e, *, data: Optional[Interactions] = None,
              schedule=None, sweep_index: int = 0, weights=None): ...
    def residuals(self, params, *, data: Optional[Interactions] = None): ...
    def objective(self, params, *, data: Optional[Interactions] = None): ...
    def export_psi(self, params): ...
    def build_phi(self, params, query): ...
    def phi_table(self, params): ...
    def fold_in_user(self, params, item_ids, y=None, alpha=None, *,
                     weights=None, n_sweeps: int = 64, tol: float = 1e-6): ...
    def fold_in_item(self, params, ctx_ids, y=None, alpha=None, *,
                     weights=None, n_sweeps: int = 64, tol: float = 1e-6): ...


class _ModelBase:
    """Shared adapter plumbing; subclasses bind one model module."""

    name = "?"

    def __init__(self, hp, dataset: Dataset):
        self.hp = hp
        self.dataset = dataset

    # -- data routing -----------------------------------------------------
    def _data(self, data: Optional[Interactions]) -> Interactions:
        if data is not None:
            return data
        self.dataset.require("data")
        return self.dataset.data

    def _weights(self, weights):
        """Per-interaction confidence for this call: an explicit ``weights``
        argument wins; otherwise the Dataset's ``confidence`` (None = the
        bit-identical unweighted program)."""
        return weights if weights is not None else self.dataset.confidence

    # -- fold-in ----------------------------------------------------------
    # Free/fixed masks over the D export coordinates; None = all free.
    def _user_free_init(self):
        return None, None

    def _item_free_init(self):
        return None, None

    def _foldin_hp(self):
        return dict(alpha0=self.hp.alpha0, l2=self.hp.l2, eta=self.hp.eta)

    def fold_in_user(self, params, item_ids, y=None, alpha=None, *,
                     weights=None, n_sweeps: int = 64,
                     tol: float = 1e-6) -> np.ndarray:
        """Closed-form φ row for an UNSEEN user from its item interactions:
        single-row CD against the frozen ψ export table. Returns (D,).
        ``weights`` (per-interaction confidence, e.g. frequency-derived)
        multiplies α in the single-row solve — continual learning inherits
        confidence."""
        free, init = self._user_free_init()
        table = np.asarray(self.export_psi(params))
        res = foldin.fold_in_row(
            table, item_ids, y, alpha, weights=weights, free=free, init=init,
            n_sweeps=n_sweeps, tol=tol, **self._foldin_hp(),
        )
        return res.row

    def fold_in_item(self, params, ctx_ids, y=None, alpha=None, *,
                     weights=None, n_sweeps: int = 64,
                     tol: float = 1e-6) -> np.ndarray:
        """Closed-form ψ row for a NEW item from the contexts that touched
        it (ids in the model's ``Interactions.ctx`` space): single-row CD
        against the frozen φ table. Returns (D,) — ready for the serving
        tier's ``publish_delta``. ``weights`` multiplies α like
        :meth:`fold_in_user`."""
        free, init = self._item_free_init()
        table = np.asarray(self.phi_table(params))
        res = foldin.fold_in_row(
            table, ctx_ids, y, alpha, weights=weights, free=free, init=init,
            n_sweeps=n_sweeps, tol=tol, **self._foldin_hp(),
        )
        return res.row


class MFModel(_ModelBase):
    name = "mf"

    def init(self, key):
        d = self._data(None)
        return mf.init(key, d.n_ctx, d.n_items, self.hp.k)

    def fit(self, params, *, n_epochs, data=None, callback=None, schedule=None,
            weights=None):
        return mf.fit(params, self._data(data), self.hp, n_epochs,
                      callback=callback, schedule=schedule,
                      weights=self._weights(weights))

    def epoch(self, params, e, *, data=None, schedule=None, sweep_index=0,
              weights=None):
        return mf.epoch(params, self._data(data), e, self.hp, schedule,
                        sweep_index, self._weights(weights))

    def residuals(self, params, *, data=None):
        return mf.residuals(params, self._data(data))

    def objective(self, params, *, data=None):
        return mf.objective(params, self._data(data), self.hp)

    def export_psi(self, params):
        return mf.export_psi(params)

    def build_phi(self, params, query):
        return mf.build_phi(params, query)

    def phi_table(self, params):
        return params.w


class MFSIModel(_ModelBase):
    name = "mfsi"

    def __init__(self, hp, dataset: Dataset):
        super().__init__(hp, dataset.require("x", "z"))

    def init(self, key):
        return mfsi.init(key, self.dataset.x.p, self.dataset.z.p, self.hp.k)

    def fit(self, params, *, n_epochs, data=None, callback=None, schedule=None,
            weights=None):
        ds = self.dataset
        return mfsi.fit(params, ds.x, ds.z, self._data(data), self.hp,
                        n_epochs, callback=callback, schedule=schedule,
                        weights=self._weights(weights))

    def epoch(self, params, e, *, data=None, schedule=None, sweep_index=0,
              weights=None):
        ds = self.dataset
        return mfsi.epoch(params, ds.x, ds.z, self._data(data), e, self.hp,
                          schedule, sweep_index, self._weights(weights))

    def residuals(self, params, *, data=None):
        ds = self.dataset
        return mfsi.residuals(params, ds.x, ds.z, self._data(data))

    def objective(self, params, *, data=None):
        ds = self.dataset
        return mfsi.objective(params, ds.x, ds.z, self._data(data), self.hp)

    def export_psi(self, params):
        return mfsi.export_psi(params, self.dataset.z)

    def build_phi(self, params, query):
        return mfsi.build_phi(params, self.dataset.x, query)

    def phi_table(self, params):
        return mfsi.phi(params, self.dataset.x)


class FMModel(_ModelBase):
    name = "fm"

    def __init__(self, hp, dataset: Dataset):
        super().__init__(hp, dataset.require("x", "z"))

    def init(self, key):
        return fm.init(key, self.dataset.x.p, self.dataset.z.p, self.hp.k)

    def fit(self, params, *, n_epochs, data=None, callback=None, schedule=None,
            weights=None):
        ds = self.dataset
        return fm.fit(params, ds.x, ds.z, self._data(data), self.hp,
                      n_epochs, callback=callback, schedule=schedule,
                      weights=self._weights(weights))

    def epoch(self, params, e, *, data=None, schedule=None, sweep_index=0,
              weights=None):
        ds = self.dataset
        return fm.epoch(params, ds.x, ds.z, self._data(data), e, self.hp,
                        schedule, sweep_index, self._weights(weights))

    def residuals(self, params, *, data=None):
        ds = self.dataset
        return fm.residuals(params, ds.x, ds.z, self._data(data), self.hp)

    def objective(self, params, *, data=None):
        ds = self.dataset
        return fm.objective(params, ds.x, ds.z, self._data(data), self.hp)

    def export_psi(self, params):
        return fm.export_psi(params, self.dataset.z, self.hp)

    def build_phi(self, params, query):
        return fm.build_phi(params, self.dataset.x, self.hp, query)

    def phi_table(self, params):
        return fm.phi_ext(params, self.dataset.x, self.hp)

    # FM extended columns: Φe = [Φ | φ_spec | 1], Ψe = [Ψ | 1 | ψ_spec].
    # A folded-in row solves the latent block plus ITS OWN spec column (it
    # meets the other side's constant-1) while the constant-1 column that
    # meets the other side's spec stays structurally fixed at 1.
    def _user_free_init(self):
        k = self.hp.k
        free = np.ones(k + 2, bool)
        free[k + 1] = False
        init = np.zeros(k + 2, np.float32)
        init[k + 1] = 1.0
        return free, init

    def _item_free_init(self):
        k = self.hp.k
        free = np.ones(k + 2, bool)
        free[k] = False
        init = np.zeros(k + 2, np.float32)
        init[k] = 1.0
        return free, init


class PARAFACModel(_ModelBase):
    name = "parafac"

    def __init__(self, hp, dataset: Dataset):
        super().__init__(hp, dataset.require("tc"))

    def init(self, key):
        d = self._data(None)
        tc = self.dataset.tc
        return parafac.init(key, tc.n_c1, tc.n_c2, d.n_items, self.hp.k)

    def fit(self, params, *, n_epochs, data=None, callback=None, schedule=None,
            weights=None):
        return parafac.fit(params, self.dataset.tc, self._data(data), self.hp,
                           n_epochs, callback=callback, schedule=schedule,
                           weights=self._weights(weights))

    def epoch(self, params, e, *, data=None, schedule=None, sweep_index=0,
              weights=None):
        return parafac.epoch(params, self.dataset.tc, self._data(data), e,
                             self.hp, schedule, sweep_index,
                             self._weights(weights))

    def residuals(self, params, *, data=None):
        return parafac.residuals(params, self.dataset.tc, self._data(data))

    def objective(self, params, *, data=None):
        return parafac.objective(params, self.dataset.tc, self._data(data),
                                 self.hp)

    def export_psi(self, params):
        return parafac.export_psi(params)

    def build_phi(self, params, query):
        c1, c2 = query
        return parafac.build_phi(params, c1, c2)

    def phi_table(self, params):
        return parafac.phi(params, self.dataset.tc)


class TuckerModel(_ModelBase):
    name = "tucker"

    def __init__(self, hp, dataset: Dataset):
        super().__init__(hp, dataset.require("tc"))

    def init(self, key):
        d = self._data(None)
        tc = self.dataset.tc
        return tucker.init(key, tc.n_c1, tc.n_c2, d.n_items,
                           self.hp.k1, self.hp.k2, self.hp.k3)

    def fit(self, params, *, n_epochs, data=None, callback=None, schedule=None,
            weights=None):
        return tucker.fit(params, self.dataset.tc, self._data(data), self.hp,
                          n_epochs, callback=callback, schedule=schedule,
                          weights=self._weights(weights))

    def epoch(self, params, e, *, data=None, schedule=None, sweep_index=0,
              weights=None):
        return tucker.epoch(params, self.dataset.tc, self._data(data), e,
                            self.hp, schedule, sweep_index,
                            self._weights(weights))

    def residuals(self, params, *, data=None):
        return tucker.residuals(params, self.dataset.tc, self._data(data))

    def objective(self, params, *, data=None):
        return tucker.objective(params, self.dataset.tc, self._data(data),
                                self.hp)

    def export_psi(self, params):
        return tucker.export_psi(params)

    def build_phi(self, params, query):
        c1, c2 = query
        return tucker.build_phi(params, c1, c2)

    def phi_table(self, params):
        return tucker.phi(params, self.dataset.tc)


class CtxMFModel(PARAFACModel):
    """Context-aware MF (GFF seasonal/session mode): PARAFAC with
    ``(c1, c2) = (user, context bucket)``. The query address is a
    ``(user_ids, bucket_ids)`` pair; ``tc``/``data.ctx`` come from
    :func:`repro.core.models.ctxmf.build_context`. All training and
    serving paths are the PARAFAC ones (incl. the fused rowpatch-kernel
    epoch) — only the naming and data-prep story differ."""

    name = "ctxmf"


MODEL_TYPES = {
    "mf": MFModel,
    "mfsi": MFSIModel,
    "fm": FMModel,
    "parafac": PARAFACModel,
    "tucker": TuckerModel,
    "ctxmf": CtxMFModel,
}


def build_model(name: str, *, hp, dataset: Dataset) -> Model:
    """Construct the adapter for zoo model ``name`` around its hyperparams
    and :class:`Dataset` bundle."""
    try:
        cls = MODEL_TYPES[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; zoo = {tuple(MODEL_TYPES)}"
        ) from None
    return cls(hp, dataset)
