"""Continual learning under traffic: the full loop this repo's serving tier
exists for — train a warm model on the head of an interaction log, go live,
then replay the tail as arriving traffic and absorb it WITHOUT retraining:

  * an unseen user gets a φ row at request time (closed-form fold-in of
    their history against the frozen ψ snapshot — ``core/foldin.py``),
  * a brand-new item gets a ψ row folded in from its first interactions and
    enters the live catalogue through an incremental ``publish_delta``
    (version bump, batcher-cache invalidation, no full-table republish),
  * the warm side keeps improving with subspace-scheduled sweeps
    (``SweepSchedule``): each refresh updates only a rotating k_b-column
    block — a fraction of a full epoch's column updates — and republishes
    with the fold-in rows composed on top.

Everything runs through the unified ``Model`` protocol
(``core/models/api.py``), so swapping MF for FM/MFSI/PARAFAC/Tucker is a
one-line change.

    PYTHONPATH=src python examples/continual_learning.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.models import mf
from repro.core.models.api import Dataset, build_model
from repro.core.sweeps import SweepSchedule
from repro.data.loader import interaction_stream
from repro.data.synthetic import make_implicit_dataset
from repro.eval.ranking import foldin_ranking_eval
from repro.serve.mesh import FaultTolerantRetrievalMesh
from repro.serve.publish import PsiPublisher
from repro.sparse.interactions import build_interactions


def main():
    n_users, n_items, k = 300, 200, 16
    ds = make_implicit_dataset(n_users=n_users, n_items=n_items,
                               attr_strength=0.8, seed=0)
    events = ds.events                       # (nnz, 3) time-ordered
    split = int(0.8 * len(events))
    # the last 4 items are COLD: they never enter the warm training set
    head, n_warm_items = events[:split], n_items - 4
    hists = ds.user_histories()

    # --- warm phase: batch-train on the head of the log ------------------
    warm = head[head[:, 1] < n_warm_items]
    hp = mf.MFHyperParams(k=k, alpha0=0.3, l2=0.05)
    data = build_interactions(
        warm[:, 0], warm[:, 1], np.ones(len(warm)), np.full(len(warm), 2.0),
        n_users, n_warm_items, alpha0=hp.alpha0,
    )
    model = build_model("mf", hp=hp, dataset=Dataset(data=data))
    params = model.init(jax.random.PRNGKey(0))
    params = model.fit(params, n_epochs=6)
    print(f"warm: trained on {len(warm)} events, "
          f"{n_warm_items}/{n_items} items")

    # --- go live ---------------------------------------------------------
    # the published table composes the warm export with the fold-in rows,
    # so a full republish after a warm refresh keeps cold items live
    extra: dict = {}          # folded-in item id -> psi row

    def export(p):
        psi = np.asarray(model.export_psi(p))
        if extra:
            psi = np.concatenate(
                [psi, np.stack([extra[i] for i in sorted(extra)])]
            )
        return jnp.asarray(psi)

    mesh = FaultTolerantRetrievalMesh(
        lambda ctx: model.build_phi(params, ctx), n_shards=2, n_replicas=1,
        k=10,
    )
    pub = PsiPublisher(mesh, export, every=1)
    pub(0, params)
    print(f"live: psi v{mesh.version}, {mesh.n_items} items")

    # --- continual phase: replay the tail as arriving traffic ------------
    # cold items were OBSERVED in the head (just excluded from training),
    # so their early interactions are available to fold from
    item_hist: dict = {}      # interactions of not-yet-served items
    for u, i in head[head[:, 1] >= n_warm_items][:, :2]:
        item_hist.setdefault(int(i), []).append(int(u))
    folded_items = 0

    def flush_cold():
        # delta-append every cold item whose id is next in line and has
        # any history — appends must stay hole-free (see apply_delta)
        nonlocal folded_items
        while item_hist.get(mesh.n_items):
            i = mesh.n_items
            row = np.asarray(model.fold_in_item(params, item_hist[i]))
            extra[i] = row
            pub.publish_delta(row, i)
            folded_items += 1

    folded_users = 0
    for batch in interaction_stream(ds, batch_events=64, start=split):
        for u, i in zip(batch["ctx"], batch["item"]):
            u, i = int(u), int(i)
            if i >= n_warm_items:
                # new item: buffer its interactions, then fold in a psi
                # row and delta-publish it (no full-table republish)
                item_hist.setdefault(i, []).append(u)
                flush_cold()
            else:
                # request-time φ for the arriving user: closed-form against
                # the frozen warm ψ — no training state touched
                hist = hists[u][hists[u] < n_warm_items][:3]
                phi = model.fold_in_user(params, hist)
                res = mesh.topk_phi(jnp.asarray(phi, jnp.float32)[None])
                assert res.ids.shape[1] == 10
                folded_users += 1
        # subspace-scheduled warm refresh: ONE rotating k_b-block per
        # publish — a k_b/k fraction of a full epoch's column updates
        sched = SweepSchedule(kind="rotating", block=4, blocks_per_sweep=1)
        params, _ = model.epoch(params, model.residuals(params),
                                schedule=sched, sweep_index=mesh.version)
        pub(mesh.version, params)
    print(f"continual: {folded_users} fold-in queries answered, "
          f"{folded_items} items delta-published "
          f"(versions {[v for v, _ in pub.deltas]}), now at "
          f"v{mesh.version} with {mesh.n_items} items")

    # --- cold-start eval: every eval user folded in from scratch ---------
    observed, true_items = [], []
    for h in hists:
        seen = np.unique(h[:-1])
        seen = seen[seen < n_warm_items]
        if len(seen) and h[-1] < n_warm_items:
            observed.append(seen)
            true_items.append(int(h[-1]))
    res = foldin_ranking_eval(model, params, observed, true_items, k=10)
    print(f"fold-in eval: recall@10={res['recall@10']:.4f} "
          f"ndcg@10={res['ndcg@10']:.4f} over {res['n_eval']} users")


if __name__ == "__main__":
    main()
