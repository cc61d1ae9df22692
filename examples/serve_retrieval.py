"""Serving: the sharded online retrieval service end-to-end — train an
iCD-MF model, publish its ψ table into a multi-shard serving mesh at every
epoch boundary (double-buffered, versioned), answer micro-batched
single-row queries through the admission queue, run the streaming
leave-one-out ranking eval over the same sharded table, then harden it:
replicate the shards, kill replicas mid-traffic (bit-identical failover
under R=2, labeled degradation when a range loses every copy), heal, and
gate a ψ publish behind the canary staged rollout.

Every path is the paper-native k-separable product ⟨φ(context), ψ(item)⟩
(§5.1): per shard the fused Pallas score+top-k kernel streams ψ-table
blocks through VMEM with a running top-K merge (the (B, n_items) score
matrix is never materialized), and the cross-shard K-way merge reproduces
the dense top-k.

    PYTHONPATH=src python examples/serve_retrieval.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.models import mf
from repro.eval.ranking import ranking_eval
from repro.serve.batcher import MicroBatcher
from repro.serve.mesh import (
    FaultInjector,
    FaultTolerantRetrievalMesh,
    RetryPolicy,
)
from repro.serve.publish import PsiPublisher, StagedRollout
from repro.sparse.interactions import build_interactions


def main():
    n_users, n_items, k, n_shards = 1000, 50_000, 64, 4
    rng = np.random.default_rng(0)
    params = mf.init(jax.random.PRNGKey(0), n_users, n_items, k)

    # --- train → publish: live ψ refresh at every epoch boundary ---------
    nnz = 20_000
    cells = rng.choice(n_users * n_items, size=nnz, replace=False)
    data = build_interactions(
        cells // n_items, cells % n_items, rng.integers(1, 5, nnz),
        1.0 + rng.random(nnz), n_users, n_items, alpha0=0.1,
    )
    sharded = FaultTolerantRetrievalMesh(
        lambda ctx: mf.build_phi(params, ctx), n_shards=n_shards,
        n_replicas=1, k=100,
    )
    pub = PsiPublisher(sharded, mf.export_psi, every=1)
    hp = mf.MFHyperParams(k=k, alpha0=0.1, l2=0.05)
    params = mf.fit(params, data, hp, n_epochs=2, callback=pub)
    sharded.phi_fn = lambda ctx: mf.build_phi(params, ctx)
    print(f"published versions {[v for _, v in pub.versions]}: "
          f"{n_items} items over {n_shards} shards "
          f"(rows_per={sharded.table.rows_per})")

    # --- batched online queries over the sharded table -------------------
    for batch in (8, 64):
        ctx = jnp.arange(batch)
        _, warm_ids = sharded.topk(ctx)  # warmup (trace+compile)
        jax.block_until_ready(warm_ids)
        t0 = time.perf_counter()
        scores, ids = sharded.topk(ctx)
        jax.block_until_ready(ids)
        dt = time.perf_counter() - t0
        print(f"batch={batch:3d}: {dt * 1e3:7.2f} ms "
              f"({batch * n_items / dt / 1e6:.1f} M cand/s over "
              f"{n_shards} shards)")

    # --- sharded mesh vs dense lax.top_k ---------------------------------
    cs, ci = sharded.topk(jnp.arange(8))
    dense = jax.lax.top_k(params.w[:8] @ params.h.T, 100)[1]
    assert bool((ci == dense).all())
    print("mesh top-k == dense top-k ✓")

    # --- micro-batched single-row requests (the online p99 path) ---------
    batcher = MicroBatcher(
        lambda phi, eids: sharded.topk_phi(phi, exclude_ids=eids),
        max_batch=16, max_delay=2e-3,
        version_fn=lambda: sharded.version,
    )
    users = rng.integers(0, n_users, size=48)
    phi_all = np.asarray(mf.build_phi(params, jnp.arange(n_users)))
    t0 = time.perf_counter()
    tickets = [
        batcher.submit(phi_all[u], exclude=rng.choice(n_items, size=5),
                       key=("user", int(u)))
        for u in users
    ]
    batcher.flush()
    dt = time.perf_counter() - t0
    assert all(batcher.result(t) is not None for t in tickets)
    print(f"batcher: {len(users)} single-row requests in {dt * 1e3:.1f} ms, "
          f"{batcher.stats['flushes']} flushes "
          f"(size={batcher.stats['flush_by_size']} "
          f"forced={batcher.stats['flush_forced']}), "
          f"cache_hits={batcher.stats['cache_hits']} ✓")

    # --- streaming sharded eval: full catalogue, no (n_eval, n_items) ----
    n_eval = 512
    true_items = rng.integers(0, n_items, size=n_eval)
    res = ranking_eval(
        mf.build_phi(params, jnp.arange(n_eval)), None, true_items,
        k=100, batch_rows=256, cluster=sharded,
        exclude=[rng.choice(n_items, size=20, replace=False)
                 for _ in range(n_eval)],
    )
    print(f"streaming sharded eval: recall@100={res['recall@100']:.4f} "
          f"ndcg@100={res['ndcg@100']:.4f} over {res['n_eval']} contexts")

    # --- fault tolerance: replication, failover, graceful degradation ----
    # The same mesh with each ψ row-range on R=2 replicas; retries share
    # the batcher's max_delay latency budget.
    inj = FaultInjector()
    mesh = FaultTolerantRetrievalMesh(
        lambda ctx: mf.build_phi(params, ctx), n_shards=n_shards,
        n_replicas=2, k=100, injector=inj,
        retry=RetryPolicy(max_attempts=3, deadline=2e-3),
    )
    mesh.publish(mf.export_psi(params))
    base = mesh.topk(jnp.arange(8))
    inj.fail(1, 0, "error")  # kill one replica of shard 1 mid-traffic
    ft = mesh.topk(jnp.arange(8))
    assert ft.coverage == 1.0
    assert bool((ft.ids == base.ids).all())
    assert bool((ft.scores == base.scores).all())
    print("replica kill under R=2: failover bit-identical ✓")
    inj.fail(1, 1, "error")  # kill the other copy: the row range is gone
    deg = mesh.topk(jnp.arange(8))
    print(f"both replicas dead: query still completes, "
          f"coverage={deg.coverage:.4f}, dead item ranges={deg.dead_ranges}")
    inj.heal()
    mesh.heal()  # re-place the orphaned range from the authoritative copy
    healed = mesh.topk(jnp.arange(8))
    assert healed.coverage == 1.0 and bool((healed.ids == base.ids).all())
    print("heal(): replicas re-placed, full coverage restored ✓")

    # --- staged rollout: canary + mirrored traffic gate the ψ publish ----
    rollout = StagedRollout(
        mesh, mirror_phi=mf.build_phi(params, jnp.arange(16))
    )
    ok, _ = rollout.publish(mf.export_psi(params))
    bad = jnp.full((n_items, k), jnp.nan, jnp.float32)  # a broken export
    ok_bad, report = rollout.publish(bad)
    assert ok and not ok_bad and mesh.version == 2
    print(f"staged rollout: good table promoted (v{mesh.version}), NaN "
          f"table rolled back (checks={report['checks']}) ✓")

    # --- IVF approximate tier + quantized ψ (serve/ann.py) ---------------
    # Centroid pruning in front of the same fused kernel: n_probe of
    # n_clusters ψ blocks are exactly re-ranked; n_probe = n_clusters is
    # bit-identical to the exact path, and int8 per-row-scale storage
    # multiplies rows-per-shard while keeping relative score error small.
    from repro.eval.ranking import ann_recall_curve, overlap_recall
    from repro.serve.ann import AnnConfig, PsiIndex

    n_c = 32
    psi = mf.export_psi(params)

    def ivf_mesh(quant):
        return FaultTolerantRetrievalMesh(
            lambda ctx: mf.build_phi(params, ctx), n_shards=1, n_replicas=1,
            k=100, psi_table=psi, retrieval="ivf",
            ann=AnnConfig(n_clusters=n_c, n_probe=n_c, quant=quant),
        )

    os_, oi = ivf_mesh("none").topk(jnp.arange(8))
    assert bool((oi == ci).all()) and bool((os_ == cs).all())
    print(f"ivf oracle (n_probe=n_clusters={n_c}): bit-identical to exact ✓")
    curve = ann_recall_curve(
        PsiIndex.build(psi, AnnConfig(n_clusters=n_c)),
        mf.build_phi(params, jnp.arange(8)), psi, k=100,
        n_probes=(2, 4, 8, n_c),
    )
    print("ivf recall-vs-probe:",
          {pt["n_probe"]: round(pt["recall@100"], 3) for pt in curve})
    _, qi = ivf_mesh("int8").topk(jnp.arange(8))
    print(f"int8 ψ (per-row scales): id recall vs exact = "
          f"{overlap_recall(np.asarray(qi), np.asarray(ci)):.3f}, "
          f"~3.9x rows per shard at D=128 ✓")


if __name__ == "__main__":
    main()
