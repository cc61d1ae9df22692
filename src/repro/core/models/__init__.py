"""k-separable model catalogue (paper §5) with exact iCD sweeps.

Every module exposes the same low-level surface:

- ``init(key, ...) -> params``            parameter pytree
- ``phi(params, ...) / psi(params, ...)`` the k-separable decomposition
- ``export_psi(params, ...) -> (I, D)``   ψ table for the serving mesh
- ``build_phi(params, <query>) -> (B, D)`` φ rows for a query batch (the
  serve/eval contract — column conventions in ``serve/README.md``)
- ``predict(params, ...)``                scores for (context, item) pairs
- ``epoch(params, data, hp, [schedule, sweep_index]) -> params`` one iCD
  epoch (ctx + item sweep); an optional
  :class:`~repro.core.sweeps.SweepSchedule` restricts it to a static
  subspace block plan (rotating / randomized / repeated k_b-blocks)
- ``objective(params, data, hp)``         Lemma-1 objective for monitoring

MF (eq. 15), MF with side information (eq. 20), FM ((k+2)-separable, eq. 26),
PARAFAC (eq. 34, sparse & dense context), Tucker (k₃-separable, eq. 40).

The UNIFIED surface over these modules is the ``Model`` protocol in
:mod:`repro.core.models.api`: ``build_model(name, hp=..., dataset=Dataset(
...))`` returns an adapter with data keyword-only methods (``fit``,
``epoch``, ``export_psi``, ``build_phi``) plus the continual-learning
entry points ``fold_in_user`` / ``fold_in_item`` (closed-form single-row
CD against the frozen other side — ``core/foldin.py``). The serving
mesh (built from ``export_psi``/``build_phi``), ranking eval
(``model_eval_callback`` / ``foldin_ranking_eval``), and the zoo helpers
all construct through it, so no consumer branches on per-model
signatures. The module-level functions here remain the public low-level
API — the adapters delegate, they do not reimplement.
"""

from repro.core.models import api, fm, mf, mfsi, parafac, tucker  # noqa: F401
from repro.core.models.api import Dataset, Model, build_model  # noqa: F401
