"""Streaming full-catalogue ranking evaluation (leave-one-out protocol).

Rendle's *Item Recommendation from Implicit Feedback* (2021) makes
sampled-free top-K ranking over the FULL catalogue the evaluation
standard: for every held-out (context, item) pair, rank all n_items and
score Recall@K / NDCG@K of the true item. The naive implementation is a
``(n_eval, n_items)`` score matrix — exactly the array that stops fitting
first at catalogue scale.

This harness never allocates it: evaluation contexts stream in batches of
``batch_rows`` φ rows through the fused ``kernels/topk_score`` kernel
(ψ-table blocks through VMEM, running top-K merge), so the largest live
arrays are the (batch_rows, D) φ tile, the (batch_rows, L) −1-padded
exclude-id tile, and the (batch_rows, K) results. Exclusion rides the
kernel's id-list form (``kernels.topk_score.ops.exclude_ids_from_lists``):
the ψ-block-aligned admissibility slices are built in-VMEM per block, so
an exclude mask never materializes a full-catalogue row — on host OR
device — at any ``n_items``. The per-row metric math is shared with the dense path
(``core.metrics.*_from_topk``), so streaming and dense evaluation are
numerically identical (parity-tested).

Past one device's HBM the same loop runs against a
``serve.mesh.FaultTolerantRetrievalMesh`` (``cluster=``): per batch the
mesh fans the φ tile over the ψ shards and K-way-merges the candidates
— bit-identical top-K to the single-table path, so the metrics are too.

Per-epoch use from the sweep loops: every model's ``fit`` already takes a
``callback(epoch, params)``; :func:`fit_eval_callback` adapts this harness
to that hook so training loops get a Recall/NDCG trajectory without
touching the sweep code.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.metrics import ndcg_from_topk, recall_from_topk
from repro.kernels.topk_score.ops import exclude_ids_from_lists, topk_score


def ranking_eval(
    phi: jnp.ndarray,             # (n_eval, D) φ rows of the eval contexts
    psi: Optional[jnp.ndarray],   # (n_items, D) ψ table; None with cluster=
    true_items: jnp.ndarray,      # (n_eval,) held-out item per context
    *,
    k: int = 100,
    batch_rows: int = 256,
    exclude: Optional[Sequence] = None,  # per-row id lists to mask (train items)
    block_items: Optional[int] = None,
    cluster=None,                 # serve.mesh.FaultTolerantRetrievalMesh
) -> Dict[str, float]:
    """Leave-one-out Recall@K / NDCG@K over the full catalogue, streamed.

    ``exclude`` is a length-``n_eval`` sequence of per-row item-id arrays
    (each row's training items); per batch they become the kernel's
    −1-padded (batch_rows, L) id tile — the full ``(n_eval, n_items)``
    mask, like the score matrix, never exists in any form.

    ``cluster=`` switches the top-K to the serving mesh
    (``cluster.topk_phi``; ``psi`` may be None) — the path past one
    device's HBM, bit-identical results by the mesh's merge contract. The
    returned metrics then carry the degradation contract — ``coverage``
    (the minimum over eval batches) and the union of ``dead_ranges`` — so
    an eval that ran against a partially-dead catalogue can never be
    mistaken for a full-catalogue number.
    """
    n_eval = int(phi.shape[0])
    true_items = jnp.asarray(true_items, jnp.int32)
    recall_sum = 0.0
    ndcg_sum = 0.0
    coverage = 1.0
    dead_ranges: set = set()
    for lo in range(0, n_eval, batch_rows):
        hi = min(lo + batch_rows, n_eval)
        eids = None
        if exclude is not None:
            eids = exclude_ids_from_lists(exclude[lo:hi])
        if cluster is not None:
            res = cluster.topk_phi(phi[lo:hi], k=k, exclude_ids=eids)
            top_ids = res.ids
            # degraded-mesh contract: metrics over a partially-dead
            # catalogue are labeled, never silently reported as full
            coverage = min(coverage, res.coverage)
            dead_ranges.update(res.dead_ranges)
        else:
            _, top_ids = topk_score(
                phi[lo:hi], psi, k, exclude_ids=eids, block_items=block_items
            )
        truth = true_items[lo:hi]
        b = hi - lo
        recall_sum += float(recall_from_topk(top_ids, truth)) * b
        ndcg_sum += float(ndcg_from_topk(top_ids, truth)) * b
    return {
        f"recall@{k}": recall_sum / max(1, n_eval),
        f"ndcg@{k}": ndcg_sum / max(1, n_eval),
        "k": k,
        "n_eval": n_eval,
        "coverage": coverage,
        "dead_ranges": tuple(sorted(dead_ranges)),
    }


def overlap_recall(approx_ids, oracle_ids) -> float:
    """Mean fraction of the exact oracle's admissible top-K found by an
    approximate retriever — THE metric of the IVF tier (``serve/ann.py``):
    recall@K against the exact path, not against held-out truth. −1 slots
    (inadmissible) in the oracle are ignored; rows whose oracle list is
    empty count as perfectly recalled."""
    approx_ids = np.asarray(approx_ids)
    oracle_ids = np.asarray(oracle_ids)
    total, hit = 0, 0
    for r in range(oracle_ids.shape[0]):
        truth = set(int(i) for i in oracle_ids[r] if i >= 0)
        if not truth:
            continue
        total += len(truth)
        hit += len(truth & set(int(i) for i in approx_ids[r]))
    return hit / total if total else 1.0


def ann_recall_curve(
    index,                        # serve.ann.PsiIndex
    phi: jnp.ndarray,             # (B, D) query rows
    psi: jnp.ndarray,             # (n_items, D) exact oracle table
    *,
    k: int = 100,
    n_probes: Sequence[int] = (1, 2, 4, 8),
    exclude: Optional[Sequence] = None,
) -> list:
    """Recall-vs-probe curve for one :class:`~repro.serve.ann.PsiIndex`:
    for each ``n_probe``, :func:`overlap_recall` of the index's top-K
    against the exact fused kernel over the same ψ table (the oracle the
    ROADMAP's recall-vs-speedup figure plots; the serve bench pairs each
    point with the analytic HBM-byte model). ``exclude`` takes the same
    per-row id lists as :func:`ranking_eval`."""
    eids = exclude_ids_from_lists(exclude) if exclude is not None else None
    _, oracle = topk_score(phi, psi, k, exclude_ids=eids)
    out = []
    for p in n_probes:
        _, ids = index.topk(phi, k, n_probe=int(p), exclude_ids=eids)
        out.append({
            "n_probe": int(p),
            f"recall@{k}": overlap_recall(ids, oracle),
        })
    return out


def fit_eval_callback(
    export: Callable,             # params -> (phi_eval, psi_table)
    true_items,
    *,
    k: int = 100,
    every: int = 1,
    exclude: Optional[Sequence] = None,
    batch_rows: int = 256,
    log: Optional[Callable[[str], None]] = None,
):
    """Adapt :func:`ranking_eval` to the models' ``fit(callback=...)`` hook.

    ``export(params)`` rebuilds the eval-context φ rows and ψ table from
    the current parameters (each model's ``build_phi``/``export_psi``).
    The returned callback appends one metrics dict per evaluated epoch to
    its ``history`` attribute::

        cb = fit_eval_callback(
            lambda p: (mf.build_phi(p, eval_ctx), mf.export_psi(p)),
            true_items, k=100, exclude=train_lists)
        mf.fit(params, data, hp, n_epochs, callback=cb)
        cb.history  # [{'epoch': 0, 'recall@100': ..., 'ndcg@100': ...}, ...]
    """
    history: list = []

    def callback(epoch: int, params) -> None:
        if epoch % every:
            return
        phi_eval, psi_table = export(params)
        res = ranking_eval(
            phi_eval, psi_table, jnp.asarray(np.asarray(true_items)),
            k=k, exclude=exclude, batch_rows=batch_rows,
        )
        res = {"epoch": epoch, **res}
        history.append(res)
        if log is not None:
            log(f"epoch {epoch}: recall@{k}={res[f'recall@{k}']:.4f} "
                f"ndcg@{k}={res[f'ndcg@{k}']:.4f}")

    callback.history = history
    return callback


def model_eval_callback(model, query, true_items, **kw):
    """:func:`fit_eval_callback` through the unified
    :class:`repro.core.models.api.Model` protocol — no per-model export
    plumbing::

        cb = model_eval_callback(model, eval_query, true_items, k=100)
        model.fit(params, n_epochs=5, callback=cb)
    """
    return fit_eval_callback(
        lambda p: (model.build_phi(p, query), model.export_psi(p)),
        true_items, **kw,
    )


def foldin_ranking_eval(
    model,
    params,
    histories: Sequence,          # per-user item-id arrays (observed history)
    true_items,                   # (n_eval,) held-out item per user
    *,
    k: int = 100,
    alpha=None,                   # per-event confidence, broadcast per user
    exclude_history: bool = True,
    batch_rows: int = 256,
    cluster=None,
    **foldin_kw,
) -> Dict[str, float]:
    """Cold-start ranking eval: every user is UNSEEN — their φ row comes
    from the closed-form fold-in (``model.fold_in_user`` against the frozen
    ψ table), then ranks the full catalogue exactly like the warm path.

    This measures what the serving tier actually does for a user with no
    trained embedding (``Model.fold_in_user``): solve the row
    from the observed ``histories[u]``, then retrieve. With
    ``exclude_history`` the folded-in items are masked at ranking time
    (the leave-one-out protocol — the true item must NOT be in the
    history).
    """
    phi_rows = np.stack([
        model.fold_in_user(
            params, np.asarray(h, np.int64),
            None if alpha is None else np.full(len(h), alpha, np.float32),
            **foldin_kw,
        )
        for h in histories
    ])
    psi = None if cluster is not None else model.export_psi(params)
    return ranking_eval(
        jnp.asarray(phi_rows), psi, jnp.asarray(np.asarray(true_items)),
        k=k, exclude=histories if exclude_history else None,
        batch_rows=batch_rows, cluster=cluster,
    )
