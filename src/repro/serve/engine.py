"""Model-agnostic retrieval engine over the fused score+top-K kernel.

The φ/ψ export contract
-----------------------

Every k-separable model (paper §4–5) scores an item as
``ŷ = ⟨φ(context), ψ(item)⟩``, so ONE retrieval path serves the whole zoo.
The uniform surface is the :class:`repro.core.models.api.Model` protocol
(``RetrievalEngine.from_model(model, params)`` is the one-call construction
path, and also enables request-time user fold-in); underneath, each model
module exports two functions the engine is built from:

  ``export_psi(params, ...) -> (n_items, D)``  the catalogue ψ table
  ``build_phi(params, <query>) -> (B, D)``     φ rows for a query batch

with D and the column conventions per model:

  model    D     export_psi                build_phi            columns
  -------  ----  ------------------------  -------------------  ------------
  MF       k     ``params.h``              ``w[ctx]``           ψ_f = h_{i,f}
  MFSI     k     ``Z·H`` (item design)     ``(X·W)[rows]``      eq. 21
  FM       k+2   ``psi_ext``: [Ψ | 1 | ψ_spec]
                                           ``phi_ext``:
                                           [Φ | φ_spec | 1]     eqs. 27–31
  PARAFAC  k     ``params.w``              ``u[c1]·v[c2]``      eq. 35
  Tucker   k3    ``params.w``              ``Σ b·u[c1]·v[c2]``  eq. 40

The FM alignment is the one to watch: Ψe's column k is the constant 1
(paired with φ_spec — the context bias/linear/pairwise bundle) and column
k+1 is ψ_spec (paired with Φe's constant 1), so the plain inner product
reproduces the full FM score including both special components.

The engine itself is just (ψ table, φ builder, blocking policy): ``topk``
streams ψ blocks through the Pallas kernel (``kernels/topk_score``) with a
running in-VMEM top-K merge — the ``(B, n_items)`` score matrix is never
materialized — and supports the seen-items-filtered serving protocol via
either exclusion form (below).

Exclusion forms
---------------

  * ``exclude_ids`` (B, L) int32, −1-padded per-row GLOBAL id lists
    (:func:`exclude_ids_from_lists`) — the web-scale form. The kernel
    builds each ψ-block-aligned (block_b, block_items) admissibility slice
    in-VMEM by comparing candidate ids against the row's list, so an
    exclude mask never materializes a full-catalogue row anywhere, and the
    same (global-id) lists serve every shard of a sharded table unchanged.
  * ``exclude_mask`` (B, n_items) bool (:func:`exclude_mask_from_lists`) —
    the legacy dense form; fine for query-batch-sized B at test scale and
    kept as the oracle-side representation.

Scaling past one device (serve/cluster.py, serve/batcher.py, serve/publish.py)
------------------------------------------------------------------------------

  * shard layout — the ψ table row-range partitions over S devices: shard
    s owns global ids [s·rows_per, (s+1)·rows_per), rows_per = ⌈n_items/S⌉,
    all shards padded to the uniform rows_per (only the last has padding;
    the kernel's ``n_valid`` meta keeps pad rows inadmissible). Each shard
    runs THIS engine's kernel with ``id_offset = s·rows_per`` so candidate
    ids come out global, and ``kernels.topk_score.topk_merge_shards`` ranks
    the S·K candidates by (−score, id) — reproducing the single-device
    tie-stable ascending-id policy bit-exactly at any shard count.
  * table versioning — serving tables are immutable, versioned snapshots
    (:class:`~repro.serve.cluster.PsiShardSet`); ``publish`` double-buffers
    the next snapshot and flips it live with one atomic reference swap, so
    a query reads one consistent version end-to-end and caches key on
    ``(query, version)`` — a publish invalidates them implicitly.
  * batcher flush protocol — single-row online queries are admitted to a
    queue and coalesced into kernel-shaped batches; a flush fires when the
    queue reaches ``max_batch`` rows (SIZE) or the oldest admission ages
    past ``max_delay`` (DEADLINE), whichever first; batches pad φ rows to a
    multiple of ``pad_to`` and right-pad per-request exclude-id lists with
    −1; results route back by ticket (``serve/batcher.py``).
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.topk_score.ops import topk_score
from repro.obs.metrics import resolve_registry
from repro.serve.cluster import TopKResult


def exclude_ids_from_lists(
    item_lists: Sequence, *, min_width: int = 1
) -> jax.Array:
    """(B, L) int32, −1-padded: ragged per-row GLOBAL excluded-id lists
    (train histories) in the kernel's exclude form. L is the widest row
    (≥ ``min_width``); host cost is O(Σ|list|) — never O(B·n_items)."""
    width = max(min_width, max((len(ids) for ids in item_lists), default=0))
    out = np.full((len(item_lists), width), -1, np.int32)
    for r, ids in enumerate(item_lists):
        ids = np.asarray(ids, np.int64).reshape(-1)
        out[r, : ids.size] = ids
    return jnp.asarray(out)


def exclude_mask_from_lists(
    item_lists: Sequence, n_items: int
) -> jax.Array:
    """(B, n_items) bool mask from ragged per-row item-id lists — the DENSE
    form: each row IS a full-catalogue row, so this is for query-batch-sized
    test/oracle use only; serving and eval pass
    :func:`exclude_ids_from_lists` instead."""
    mask = np.zeros((len(item_lists), n_items), dtype=bool)
    for r, ids in enumerate(item_lists):
        ids = np.asarray(ids, dtype=np.int64)
        if ids.size:
            mask[r, ids] = True
    return jnp.asarray(mask)


class RetrievalEngine:
    """Serve top-K retrieval for any k-separable model.

    Built from the model's exported ψ table and φ builder::

        engine = RetrievalEngine(mf.export_psi(params),
                                 lambda ctx: mf.build_phi(params, ctx))
        scores, ids = engine.topk(user_ids, k=100)

    ``topk`` semantics follow the kernel (see ``kernels/topk_score``):
    exact dense-``lax.top_k`` parity, ascending-id tie policy, (−inf, −1)
    on slots with no admissible candidate. The multi-device mirror with
    the same semantics (bit-exact) is
    :class:`repro.serve.cluster.ShardedRetrievalCluster`.
    """

    def __init__(
        self,
        psi_table: jax.Array,                      # (n_items, D)
        phi_fn: Callable[..., jax.Array],          # query -> (B, D)
        *,
        k: int = 100,
        block_items: Optional[int] = None,
        retrieval: str = "exact",
        ann=None,                                  # serve.ann.AnnConfig
        registry=None,
    ):
        self.psi = jnp.asarray(psi_table, jnp.float32)
        self.phi_fn = phi_fn
        self.k = k
        self.block_items = block_items
        self.model = None   # set by from_model: enables fold_in_phi
        self._params = None
        self.registry = resolve_registry(registry)
        if retrieval not in ("exact", "ivf"):
            raise ValueError(f"retrieval must be 'exact' or 'ivf', got {retrieval!r}")
        self.retrieval = retrieval
        self.index = None
        if retrieval == "ivf":
            # the engine's ψ is fixed at construction, so the IVF tier
            # (serve/ann.py) indexes it once, eagerly
            from repro.serve.ann import AnnConfig, PsiIndex

            self.ann = ann or AnnConfig()
            self.index = PsiIndex.build(self.psi, self.ann)
        else:
            self.ann = ann

    @classmethod
    def from_model(
        cls,
        model,
        params,
        *,
        k: int = 100,
        block_items: Optional[int] = None,
        retrieval: str = "exact",
        ann=None,
    ) -> "RetrievalEngine":
        """Build an engine from a :class:`repro.core.models.api.Model`
        adapter — the unified construction path (no per-model signature
        branches)::

            engine = RetrievalEngine.from_model(model, params, k=100)
            res = engine.topk(query)                  # model's query space
            phi = engine.fold_in_phi(unseen_history)  # request-time fold-in

        The engine keeps (model, params) so the serving tier can fold in
        an UNSEEN user at request time (:meth:`fold_in_phi`): the user's
        history rows are solved to a φ row against the frozen ψ table
        (closed-form single-row CD, ``core/foldin.py``) without touching
        training state.
        """
        eng = cls(
            model.export_psi(params),
            lambda *query: model.build_phi(
                params, query[0] if len(query) == 1 else query
            ),
            k=k, block_items=block_items, retrieval=retrieval, ann=ann,
        )
        eng.model = model
        eng._params = params
        return eng

    def fold_in_phi(self, item_ids, y=None, alpha=None, **kw) -> jax.Array:
        """(1, D) φ row for an unseen user folded in from their item
        history — closed-form, against the frozen ψ snapshot. Only
        available on engines built with :meth:`from_model`."""
        if self.model is None:
            raise RuntimeError(
                "fold_in_phi needs a Model adapter — build the engine with "
                "RetrievalEngine.from_model(model, params)"
            )
        row = self.model.fold_in_user(self._params, item_ids, y, alpha, **kw)
        return jnp.asarray(row, jnp.float32)[None, :]

    @property
    def n_items(self) -> int:
        return int(self.psi.shape[0])

    def phi(self, *query) -> jax.Array:
        """φ rows for a query batch — (B, D), D tiny; safe to materialize."""
        return jnp.asarray(self.phi_fn(*query), jnp.float32)

    def topk(
        self,
        *query,
        k: Optional[int] = None,
        exclude_mask: Optional[jax.Array] = None,
        exclude_ids: Optional[jax.Array] = None,
    ) -> TopKResult:
        """(scores, ids) :class:`~repro.serve.cluster.TopKResult`, both
        (B, k), for a query batch. A single-device engine has no failure
        modes to degrade over, so ``coverage`` is always 1.0 — the field
        exists so every serving tier (engine, cluster, mesh, batcher
        tickets, sharded eval) answers with ONE result contract."""
        return self.topk_phi(
            self.phi(*query), k=k, exclude_mask=exclude_mask,
            exclude_ids=exclude_ids,
        )

    def topk_phi(
        self,
        phi_rows: jax.Array,
        *,
        k: Optional[int] = None,
        exclude_mask: Optional[jax.Array] = None,
        exclude_ids: Optional[jax.Array] = None,
    ) -> TopKResult:
        """Like :meth:`topk` but from pre-built φ rows (the eval harness
        path, which batches a big φ matrix through here).

        ``retrieval='ivf'`` routes through the engine's
        :class:`~repro.serve.ann.PsiIndex` (centroid pruning + exact fused
        re-rank over the probed blocks); with ``ann.n_probe >=
        ann.n_clusters`` the index's oracle gate makes this bit-identical
        to the exact path. The IVF tier takes the web-scale ``exclude_ids``
        form only — the dense mask is indexed by catalogue position, which
        an approximate tier must not depend on."""
        if self.retrieval == "ivf":
            if exclude_mask is not None:
                raise ValueError(
                    "retrieval='ivf' takes exclude_ids (global id lists), "
                    "not a dense exclude_mask"
                )
            s, i = self.index.topk(
                phi_rows, k or self.k, exclude_ids=exclude_ids,
                block_items=self.block_items, registry=self.registry,
            )
            return TopKResult(s, i)
        s, i = topk_score(
            phi_rows, self.psi, k or self.k, exclude_mask,
            exclude_ids=exclude_ids, block_items=self.block_items,
        )
        return TopKResult(s, i)

    def scores(self, phi_rows: jax.Array) -> jax.Array:
        """Dense (B, n_items) scores — small batches / tests ONLY; serving
        and eval go through :meth:`topk`, which never materializes this."""
        return phi_rows @ self.psi.T


def bulk_score(forward: Callable, batch, chunk: int = 65536):
    """Offline scoring of a huge batch in fixed-size chunks (serve_bulk)."""
    n = jax.tree_util.tree_leaves(batch)[0].shape[0]
    outs = []
    for lo in range(0, n, chunk):
        piece = jax.tree_util.tree_map(lambda x: x[lo : lo + chunk], batch)
        outs.append(forward(piece))
    return jnp.concatenate(outs, axis=0)


def mf_retrieval_score_fn(user_vec: jax.Array, item_table: jax.Array):
    """The paper-native separable retrieval: one (k)·(k,N) matvec per id
    chunk — or a (B, k)·(k, N) matmul when ``user_vec`` is a (B, k) batch."""

    def score(ids):
        s = jnp.take(item_table, ids, axis=0) @ user_vec.T  # (c,) | (c, B)
        return s.T if s.ndim == 2 else s

    return score
