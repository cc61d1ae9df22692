"""Serving/eval bench for the fused score+top-K retrieval subsystem.

Tracks ``BENCH_topk_score.json`` at the repo root:

  * analytic HBM-traffic model — fused ``kernels/topk_score`` (ψ read once,
    scores never leave VMEM) vs the dense path (ψ read + (B, n_items)
    score matrix written AND re-read by ``lax.top_k``), plus the CLUSTER
    model: per-shard ψ reads + the cross-shard merge's S·K candidate
    traffic (the sharding overhead is the tiny merge term, not the ψ
    stream — sharding is ~free in bytes while multiplying HBM capacity);
  * measured CPU comparison of the two paths (interpret-mode kernels, so
    wall-clock is emulation-bound and informational only);
  * batcher p50/p99 queue+service latency under a synthetic open-loop
    arrival trace (simulated clock; service time from the analytic model
    so the numbers are not emulation-bound), with every routed result
    HARD-asserted against the per-row dense oracle;
  * HARD parity asserts — streaming kernel vs dense ``lax.top_k`` ids for
    every k-separable model, with and without exclude-id lists, the sharded
    serving mesh vs one kernel call on a single device (ids AND scores
    bit-identical at shard counts {1,2,3,4}), plus the streaming ranking-eval harness vs
    dense metrics. A broken kernel, merge, or export contract fails the
    whole bench (the CI serve-smoke gate);
  * HARD fault-tolerance asserts (``serve/mesh.py``) — replica kills under
    R=2 bit-identical to the healthy oracle, unreplicated kills complete
    with the coverage/dead-range contract, retry backoff bounded by the
    deadline budget;
  * HARD IVF/quantization asserts (``serve/ann.py``) — n_probe=n_clusters
    bit-identical to exact, recall@K >= 0.95 at >= 4x analytic byte
    reduction on the probe sweep, int8-per-row-scale ψ within 5% relative
    score error and >= 3x rows per HBM shard;
  * HARD observability asserts (``repro.obs``) — instrumented-vs-bare
    overhead < 3%, and one batched
    request under an injected replica kill exports a single
    ticket-correlated trace (request → queue → flush → dispatch →
    failover → merge) without changing a bit of the results.

Run: ``python -m benchmarks.run --quick`` (serve section) or
``python -m benchmarks.serve_bench --smoke``.
"""
from __future__ import annotations

import gc
import json
import os
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import HBM_BW


def topk_traffic_bytes(b: int, n_items: int, d: int, k: int) -> Dict[str, float]:
    """Analytic HBM bytes for one query batch (fp32). Dense: ψ table + φ +
    score-matrix write + score-matrix re-read (top_k). Fused: ψ table + φ
    + the final (B, K_pad) score/id blocks (running state rides VMEM)."""
    k_pad = -(-k // 128) * 128
    psi = 4.0 * n_items * d
    phi = 4.0 * b * d
    dense = psi + phi + 2 * 4.0 * b * n_items
    fused = psi + phi + 2 * 4.0 * b * k_pad
    return {
        "dense_bytes": dense,
        "fused_bytes": fused,
        "bytes_ratio": dense / fused,
        "dense_memory_s": dense / HBM_BW,
        "fused_memory_s": fused / HBM_BW,
    }


def cluster_traffic_bytes(
    b: int, n_items: int, d: int, k: int, n_shards: int
) -> Dict[str, float]:
    """Analytic HBM bytes for the SHARDED path: every shard streams its ψ
    slab once (total = one ψ read), φ replicates to S shards, and the
    cross-shard merge writes + re-reads the S·K_pad candidate score/id
    rows before the final (B, K_pad) result. Per-shard bytes bound the
    per-device time (shards run concurrently)."""
    k_pad = -(-k // 128) * 128
    psi = 4.0 * n_items * d                       # summed over shards
    phi = 4.0 * b * d * n_shards                  # replicated
    cand = 2 * 2 * 4.0 * b * k_pad * n_shards     # candidates: write + read
    final = 2 * 4.0 * b * k_pad
    total = psi + phi + cand + final
    single = topk_traffic_bytes(b, n_items, d, k)["fused_bytes"]
    per_shard = psi / n_shards + 4.0 * b * d + 2 * 4.0 * b * k_pad
    return {
        "cluster_bytes": total,
        "single_fused_bytes": single,
        "shard_overhead_ratio": total / single,
        "per_shard_bytes": per_shard,
        "per_shard_memory_s": per_shard / HBM_BW,
        "capacity_x": float(n_shards),  # ψ rows servable vs one device's HBM
    }


def _zoo_models(quick: bool):
    """Tiny (φ, ψ) exports for every k-separable model (the one shared
    builder in ``repro.core.models.zoo`` at bench shapes — used by the
    kernel-parity and cluster-parity sections)."""
    from repro.core.models.zoo import ZOO, model_phi_psi

    rng = np.random.default_rng(0)
    n_ctx, n_items, b, k = (24, 40, 8, 6) if quick else (128, 512, 32, 16)
    return {
        name: model_phi_psi(name, rng, n_ctx=n_ctx, n_items=n_items, b=b, k=k)
        for name in ZOO
    }


def _assert_topk_parity(name, phi, psi, k, exclude_ids=None, block_items=32):
    """Streaming kernel vs dense lax.top_k/oracle: ids exact, scores close."""
    from repro.kernels.topk_score import topk_score, topk_score_ref

    s, i = topk_score(phi, psi, k, exclude_ids=exclude_ids,
                      block_items=block_items)
    rs, ri = topk_score_ref(phi, psi, k, exclude_ids=exclude_ids)
    if not (np.asarray(i) == np.asarray(ri)).all():
        raise AssertionError(f"serve bench parity FAILED for {name}: top-k ids "
                             "diverge from the dense oracle")
    finite = np.isfinite(np.asarray(rs))
    if not np.allclose(np.asarray(s)[finite], np.asarray(rs)[finite],
                       rtol=1e-5, atol=1e-6):
        raise AssertionError(f"serve bench parity FAILED for {name}: top-k "
                             "scores diverge from the dense oracle")
    if exclude_ids is None:
        ds, di = jax.lax.top_k(phi @ psi.T, min(k, psi.shape[0]))
        if not (np.asarray(i)[:, : di.shape[1]] == np.asarray(di)).all():
            raise AssertionError(f"serve bench parity FAILED for {name}: ids "
                                 "diverge from dense lax.top_k")


def _zoo_parity(quick: bool) -> Dict[str, dict]:
    """Every model through its export_psi/build_phi contract, with and
    without exclusion, against the dense path."""
    from repro.kernels.topk_score.ops import exclude_ids_from_lists

    rng = np.random.default_rng(0)
    topk = 10 if quick else 100
    out = {}
    for name, (phi, psi) in _zoo_models(quick).items():
        excl = exclude_ids_from_lists(
            [rng.choice(psi.shape[0], size=min(5, psi.shape[0] // 2),
                        replace=False) for _ in range(phi.shape[0])],
        )
        kk = min(topk, psi.shape[0])
        _assert_topk_parity(name, phi, psi, kk)
        _assert_topk_parity(f"{name}+exclude", phi, psi, kk, excl)
        out[name] = {"parity_ok": True, "d": int(phi.shape[1]),
                     "n_items": int(psi.shape[0]), "k": kk}
    return out


def _cluster_parity(quick: bool) -> Dict[str, dict]:
    """Sharded serving mesh vs one kernel call on a single device vs the
    dense oracle: ids AND scores BIT-identical for every model at shard
    counts {1, 2, 3, 4}, with and without per-row exclusion — the
    acceptance gate of the sharded serving tier."""
    from repro.kernels.topk_score import topk_score, topk_score_ref
    from repro.kernels.topk_score.ops import exclude_ids_from_lists
    from repro.serve.mesh import FaultTolerantRetrievalMesh

    rng = np.random.default_rng(7)
    topk = 10 if quick else 100
    out = {}
    for name, (phi, psi) in _zoo_models(quick).items():
        kk = min(topk, psi.shape[0])
        es, ei = topk_score(phi, psi, kk, block_items=32)
        lists = [rng.choice(psi.shape[0], size=min(5, psi.shape[0] // 2),
                            replace=False) for _ in range(phi.shape[0])]
        eids = exclude_ids_from_lists(lists)
        es2, ei2 = topk_score(phi, psi, kk, exclude_ids=eids, block_items=32)
        rs2, ri2 = topk_score_ref(phi, psi, kk, exclude_ids=eids)
        for n_shards in (1, 2, 3, 4):
            cl = FaultTolerantRetrievalMesh(
                lambda p=phi: p, n_shards=n_shards, n_replicas=1, k=kk,
                block_items=32, psi_table=psi,
            )
            cs, ci = cl.topk_phi(phi)
            if not ((np.asarray(ci) == np.asarray(ei)).all()
                    and (np.asarray(cs) == np.asarray(es)).all()):
                raise AssertionError(
                    f"serve bench parity FAILED for {name}: mesh "
                    f"(n_shards={n_shards}) is not bit-identical to one "
                    "kernel call on a single device"
                )
            cs2, ci2 = cl.topk_phi(phi, exclude_ids=eids)
            if not ((np.asarray(ci2) == np.asarray(ri2)).all()
                    and (np.asarray(ci2) == np.asarray(ei2)).all()
                    and (np.asarray(cs2) == np.asarray(es2)).all()):
                raise AssertionError(
                    f"serve bench parity FAILED for {name}: sharded "
                    f"exclude path (n_shards={n_shards}) diverges"
                )
        out[name] = {"parity_ok": True, "shard_counts": [1, 2, 3, 4],
                     "k": kk, "n_items": int(psi.shape[0])}
    return out


def _batcher_bench(quick: bool) -> dict:
    """Open-loop single-row arrival trace through the micro-batcher over a
    sharded mesh (simulated clock). Queue wait comes from the flush
    policy; service time from the analytic per-shard traffic model (NOT
    interpret-mode wall clock). Every routed result is hard-asserted
    against the per-row dense oracle — the out-of-order-routing gate."""
    from repro.core.models import mf
    from repro.kernels.topk_score import topk_score_ref
    from repro.kernels.topk_score.ops import exclude_ids_from_lists
    from repro.serve.batcher import MicroBatcher
    from repro.serve.mesh import FaultTolerantRetrievalMesh

    rng = np.random.default_rng(11)
    n_ctx, n_items, k, kk = (64, 40, 8, 10) if quick else (512, 4096, 32, 100)
    n_requests = 64 if quick else 512
    n_shards, max_batch, max_delay = 2, 8, 2e-3
    params = mf.init(jax.random.PRNGKey(6), n_ctx, n_items, k)
    cluster = FaultTolerantRetrievalMesh(
        lambda ctx: mf.build_phi(params, ctx), n_shards=n_shards,
        n_replicas=1, k=min(kk, n_items), block_items=32,
        psi_table=mf.export_psi(params),
    )
    clock = {"t": 0.0}
    batcher = MicroBatcher(
        lambda phi, eids: cluster.topk_phi(phi, exclude_ids=eids),
        max_batch=max_batch, max_delay=max_delay, pad_to=8,
        clock=lambda: clock["t"], version_fn=lambda: cluster.version,
    )
    phi_all = np.asarray(mf.build_phi(params, jnp.arange(n_ctx)))
    psi = np.asarray(mf.export_psi(params))
    # analytic per-flush service time: per-shard stream + merge
    service_s = cluster_traffic_bytes(
        max_batch, n_items, phi_all.shape[1], min(kk, n_items), n_shards
    )["per_shard_memory_s"]

    # open-loop arrivals: exponential inter-arrival, mean = max_delay/4 ⇒
    # size flushes dominate, deadline bounds the tail
    arrivals = np.cumsum(rng.exponential(max_delay / 4, size=n_requests))
    users = rng.integers(0, n_ctx, size=n_requests)
    excls = [rng.choice(n_items, size=int(rng.integers(0, 4)), replace=False)
             for _ in range(n_requests)]
    submit_t, tickets = {}, []
    for t_arr, u, ex in zip(arrivals, users, excls):
        clock["t"] = float(t_arr)
        tk = batcher.submit(
            phi_all[u], exclude=ex,
            key=("user", int(u), tuple(np.sort(ex).tolist())),
        )
        submit_t[tk] = float(t_arr)
        tickets.append((tk, int(u), ex))
    clock["t"] = float(arrivals[-1]) + max_delay
    batcher.step()
    batcher.flush()

    lat = []
    for tk, u, ex in tickets:
        done = batcher.completed_at(tk)
        scores, ids = batcher.result(tk)
        # HARD routing assert: this ticket's rows == ITS user's oracle row
        rs, ri = topk_score_ref(
            phi_all[u : u + 1], psi, min(kk, n_items),
            exclude_ids=exclude_ids_from_lists([ex]),
        )
        if not (ids == np.asarray(ri)[0]).all():
            raise AssertionError(
                "serve bench FAILED: batcher routed the wrong result to a "
                f"ticket (user {u})"
            )
        lat.append(done - submit_t[tk] + service_s)
    lat = np.asarray(lat)
    return {
        "routing_ok": True,
        "trace": {
            "n_requests": n_requests, "n_shards": n_shards,
            "max_batch": max_batch, "max_delay_s": max_delay,
            "mean_interarrival_s": float(max_delay / 4),
        },
        "latency_p50_s": float(np.percentile(lat, 50)),
        "latency_p99_s": float(np.percentile(lat, 99)),
        "queue_p99_s": float(np.percentile(lat - service_s, 99)),
        "service_s_analytic": float(service_s),
        "stats": dict(batcher.stats),
        "note": "queue wait simulated-clock exact; service time analytic "
                "(interpret-mode wall clock is emulation-bound)",
    }


def _failover_bench(quick: bool) -> dict:
    """Fault-tolerance acceptance gate (serve/mesh.py), all HARD asserts:

      * R=2, kill each replica in turn mid-traffic ⇒ every answer stays
        BIT-identical (ids AND scores) to the healthy single-device oracle
        — failover must be invisible in results;
      * R=1, kill a shard ⇒ the query COMPLETES, reports coverage < 1 plus
        the exact dead row range, and the surviving ids equal the oracle
        restricted to the surviving ranges;
      * sticky timeouts under a deadline budget ⇒ total backoff never
        exceeds the budget (the batcher max_delay contract)."""
    from repro.kernels.topk_score import topk_score_ref
    from repro.serve.mesh import (
        FaultInjector,
        FaultTolerantRetrievalMesh,
        RetryPolicy,
    )

    rng = np.random.default_rng(17)
    n_ctx, n_items, d, kk = (9, 101, 16, 13) if quick else (32, 2048, 32, 50)
    n_shards, n_replicas = 4, 2
    phi = jnp.asarray(rng.normal(size=(n_ctx, d)), jnp.float32)
    psi = jnp.asarray(rng.normal(size=(n_items, d)), jnp.float32)
    rs_ref, ri_ref = topk_score_ref(phi, psi, kk)

    inj = FaultInjector()
    mesh = FaultTolerantRetrievalMesh(
        lambda p=phi: p, n_shards=n_shards, n_replicas=n_replicas, k=kk,
        block_items=32, injector=inj,
        retry=RetryPolicy(max_attempts=3, backoff_base=1e-4),
    )
    mesh.publish(psi)
    base = mesh.topk()
    if not (np.asarray(base.ids) == np.asarray(ri_ref)).all():
        raise AssertionError("serve bench FAILED: healthy mesh diverges "
                             "from the dense oracle")
    kills = 0
    for s in range(n_shards):
        for r in range(n_replicas):
            inj.fail(s, r, "error")
            # two queries: round-robin guarantees the kill is routed to
            for _ in range(2):
                res = mesh.topk()
                if res.coverage != 1.0 or not (
                    (np.asarray(res.ids) == np.asarray(base.ids)).all()
                    and (np.asarray(res.scores)
                         == np.asarray(base.scores)).all()
                ):
                    raise AssertionError(
                        "serve bench FAILED: failover parity — killing "
                        f"replica ({s},{r}) under R=2 changed the results"
                    )
            kills += 1
            inj.heal(s, r)
            mesh.replica_set.mark_live(s, r)
    failover_parity = True

    # unreplicated kill: labeled degradation, survivors oracle-exact
    inj2 = FaultInjector()
    mesh1 = FaultTolerantRetrievalMesh(
        lambda p=phi: p, n_shards=n_shards, n_replicas=1, k=kk,
        block_items=32, injector=inj2,
        retry=RetryPolicy(max_attempts=2, backoff_base=1e-4),
    )
    mesh1.publish(psi)
    inj2.fail(1, 0, "error")
    deg = mesh1.topk()
    table = mesh1.table
    lo, hi = table.rows_per, min(2 * table.rows_per, n_items)
    mask = np.zeros((n_ctx, n_items), bool)
    mask[:, lo:hi] = True
    ds_ref, di_ref = topk_score_ref(phi, psi, kk, jnp.asarray(mask))
    if (deg.coverage >= 1.0 or deg.dead_ranges != ((lo, hi),)
            or not (np.asarray(deg.ids) == np.asarray(di_ref)).all()):
        raise AssertionError(
            "serve bench FAILED: degraded-query contract — unreplicated "
            "shard kill must complete with coverage < 1, the dead row "
            "range, and oracle-exact survivors"
        )
    degraded_contract_ok = True

    # deadline budget: sticky timeouts may never sleep past the budget
    budget = 2e-3
    inj3 = FaultInjector()
    mesh3 = FaultTolerantRetrievalMesh(
        lambda p=phi: p, n_shards=2, n_replicas=2, k=kk, block_items=32,
        injector=inj3,
        retry=RetryPolicy(max_attempts=5, backoff_base=1e-3,
                          deadline=budget),
    )
    mesh3.publish(psi)
    inj3.fail(0, 0, "timeout", latency=1.5e-3)
    inj3.fail(0, 1, "timeout", latency=1.5e-3)
    mesh3.topk()
    if mesh3.stats["backoff_slept_s"] > budget:
        raise AssertionError(
            "serve bench FAILED: retry backoff "
            f"({mesh3.stats['backoff_slept_s']}s) exceeded the deadline "
            f"budget ({budget}s) — the batcher max_delay contract is broken"
        )
    deadline_ok = True
    return {
        "failover_parity": failover_parity,
        "degraded_contract_ok": degraded_contract_ok,
        "deadline_ok": deadline_ok,
        "replica_kills": kills,
        "mesh_stats": {k2: v for k2, v in mesh.stats.items()},
        "degraded_coverage": float(deg.coverage),
        "degraded_dead_ranges": [list(r) for r in deg.dead_ranges],
        "deadline_budget_s": budget,
        "backoff_slept_s": float(mesh3.stats["backoff_slept_s"]),
        "deadline_gaveups": int(mesh3.stats["deadline_gaveups"]),
    }


def _ann_clustered(n, d, n_centers, seed=0, spread=6.0):
    """Clustered ψ + centroid-seeking queries — the regime the IVF tier is
    built for. Fixed seeds: the recall gate must be deterministic."""
    rng = np.random.default_rng(seed)
    cents = rng.normal(size=(n_centers, d)) * spread
    per = -(-n // n_centers)
    rows = np.concatenate(
        [cents[i] + rng.normal(size=(per, d)) for i in range(n_centers)]
    )[:n]
    rng.shuffle(rows)
    return jnp.asarray(rows, jnp.float32), cents, rng


def _ann_bench(quick: bool) -> dict:
    """IVF + quantized-ψ acceptance gates (serve/ann.py), all HARD asserts:

      * ``ann_exact_parity`` — n_probe = n_clusters is BIT-identical (ids
        AND scores) to the exact fused kernel: the approximate tier
        degrades to exact, never to almost-exact;
      * ``ann_recall_floor`` — some point on the probe sweep reaches
        recall@K >= 0.95 against the exact oracle while the analytic
        HBM-byte model (centroid read + probed quantized blocks vs the
        full fp32 ψ stream) shows >= 4x fewer bytes;
      * ``quant_parity`` — the int8-per-row-scale index at oracle probe
        count returns >= 90% of the exact ids with scores within 5%
        RELATIVE error (per-row scales bound relative, not absolute,
        error — rows of very different norms are the point);
      * ``int8_capacity_x`` — ``vmem.shard_capacity_rows``: int8+scale
        rows per HBM byte >= 3x fp32 rows (the shard-capacity gate).
    """
    from repro.eval.ranking import ann_recall_curve, overlap_recall
    from repro.kernels.topk_score import topk_score
    from repro.kernels.vmem import psi_row_bytes, shard_capacity_rows
    from repro.serve.ann import AnnConfig, PsiIndex

    n, d, n_c, b, kk = (4096, 32, 16, 12, 100) if quick else (16384, 64, 32, 32, 100)
    psi, cents, rng = _ann_clustered(n, d, n_c, seed=23)
    phi = jnp.asarray(
        cents[rng.integers(0, n_c, size=b)] * 0.5
        + rng.normal(size=(b, d)) * 0.5,
        jnp.float32,
    )
    exact_s, exact_i = topk_score(phi, psi, kk)

    # --- exact-parity gate: oracle probe count, fp32 storage -------------
    idx32 = PsiIndex.build(psi, AnnConfig(n_clusters=n_c, seed=3))
    s, i = idx32.topk(phi, kk, n_probe=n_c)
    if not ((np.asarray(i) == np.asarray(exact_i)).all()
            and (np.asarray(s) == np.asarray(exact_s)).all()):
        raise AssertionError(
            "serve bench FAILED: IVF with n_probe=n_clusters is not "
            "bit-identical to the exact kernel"
        )
    ann_exact_parity = True

    # --- recall-vs-bytes sweep on the SHIPPED config (int8 + scales) -----
    idx8 = PsiIndex.build(psi, AnnConfig(n_clusters=n_c, quant="int8", seed=3))
    probes = sorted({1, 2, 4, max(1, n_c // 2), n_c})
    curve = ann_recall_curve(idx8, phi, psi, k=kk, n_probes=probes)
    exact_bytes = float(n * psi_row_bytes(d))            # full fp32 ψ stream
    sweep = []
    for pt in curve:
        p = pt["n_probe"]
        ivf_bytes = (
            float(n_c * d * 4)                           # centroid scoring
            + float(p * idx8.block_rows
                    * psi_row_bytes(d, psi_bytes=1, per_row_scale=True))
        )
        sweep.append({
            **pt,
            "ivf_bytes": ivf_bytes,
            "bytes_reduction_x": exact_bytes / ivf_bytes,
        })
    floor_pts = [pt for pt in sweep
                 if pt[f"recall@{kk}"] >= 0.95 and pt["bytes_reduction_x"] >= 4.0]
    if not floor_pts:
        raise AssertionError(
            "serve bench FAILED: no probe count reaches recall@"
            f"{kk} >= 0.95 at >= 4x analytic byte reduction; sweep={sweep}"
        )
    ann_recall_floor = True

    # --- quantized-score parity at oracle probes -------------------------
    s8, i8 = idx8.topk(phi, kk, n_probe=n_c)
    id_recall = overlap_recall(np.asarray(i8), np.asarray(exact_i))
    hit = np.asarray(i8) == np.asarray(exact_i)
    rel = (np.abs(np.asarray(s8) - np.asarray(exact_s))[hit]
           / np.maximum(np.abs(np.asarray(exact_s))[hit], 1e-3))
    if id_recall < 0.9 or rel.max() >= 0.05:
        raise AssertionError(
            "serve bench FAILED: int8 ψ quant parity — id recall "
            f"{id_recall:.3f} (need >= 0.9) / max relative score error "
            f"{rel.max():.4f} (need < 0.05)"
        )
    quant_parity = True

    # --- capacity gate: int8+scale rows per shard vs fp32 ----------------
    hbm = 16 * 2**30
    cap32 = shard_capacity_rows(hbm, 128)
    cap8 = shard_capacity_rows(hbm, 128, psi_bytes=1, per_row_scale=True)
    capacity_x = cap8 / cap32
    if capacity_x < 3.0:
        raise AssertionError(
            f"serve bench FAILED: int8 shard capacity {capacity_x:.2f}x "
            "fp32 (need >= 3x)"
        )
    return {
        "shape": dict(n_items=n, d=d, n_clusters=n_c, b=b, k=kk,
                      block_rows=int(idx8.block_rows)),
        "ann_exact_parity": ann_exact_parity,
        "ann_recall_floor": ann_recall_floor,
        "quant_parity": quant_parity,
        "recall_bytes_sweep": sweep,
        "best_floor_point": max(floor_pts, key=lambda p: p["bytes_reduction_x"]),
        "quant_id_recall": float(id_recall),
        "quant_max_rel_err": float(rel.max()),
        "int8_capacity_x": float(capacity_x),
        "capacity_rows": {"f32_D128_16GiB": cap32, "int8_D128_16GiB": cap8},
        "note": "bytes analytic (centroids + probed quantized blocks vs "
                "full fp32 stream); recall measured vs the exact kernel "
                "on fixed-seed clustered data",
    }


def _eval_harness_parity(quick: bool) -> dict:
    """Streaming ranking_eval (never a (n_eval, n_items) array) vs dense
    metrics over the same exclusion protocol — single-table AND sharded."""
    from repro.core.metrics import ndcg_at_k, recall_at_k
    from repro.core.models import mf
    from repro.eval.ranking import ranking_eval
    from repro.kernels.topk_score.ops import exclude_ids_from_lists
    from repro.kernels.topk_score.ref import exclude_ids_to_mask
    from repro.serve.mesh import FaultTolerantRetrievalMesh

    rng = np.random.default_rng(1)
    n_eval, n_items, k, topk = (32, 80, 8, 10) if quick else (256, 2048, 32, 100)
    params = mf.init(jax.random.PRNGKey(5), n_eval, n_items, k)
    truth = rng.integers(0, n_items, size=n_eval)
    excl = [rng.choice(n_items, size=4, replace=False) for _ in range(n_eval)]
    phi = mf.build_phi(params, jnp.arange(n_eval))
    psi = mf.export_psi(params)
    res = ranking_eval(phi, psi, truth, k=topk, batch_rows=max(8, n_eval // 3),
                       exclude=excl, block_items=32)
    mask = exclude_ids_to_mask(exclude_ids_from_lists(excl), n_items)
    dense = phi @ psi.T
    r = float(recall_at_k(dense, jnp.asarray(truth), topk, mask))
    n = float(ndcg_at_k(dense, jnp.asarray(truth), topk, mask))
    ok = (abs(res[f"recall@{topk}"] - r) < 1e-5
          and abs(res[f"ndcg@{topk}"] - n) < 1e-5)
    if not ok:
        raise AssertionError(
            f"serve bench parity FAILED for ranking_eval: streaming "
            f"({res}) vs dense (recall={r}, ndcg={n})"
        )
    # sharded eval over the mesh: same metrics past one device's HBM
    cl = FaultTolerantRetrievalMesh(n_shards=3, n_replicas=1, k=topk,
                                    block_items=32, psi_table=psi)
    res_sh = ranking_eval(phi, None, truth, k=topk,
                          batch_rows=max(8, n_eval // 3), exclude=excl,
                          cluster=cl)
    sharded_ok = (abs(res_sh[f"recall@{topk}"] - r) < 1e-5
                  and abs(res_sh[f"ndcg@{topk}"] - n) < 1e-5)
    if not sharded_ok:
        raise AssertionError(
            f"serve bench parity FAILED for SHARDED ranking_eval: "
            f"({res_sh}) vs dense (recall={r}, ndcg={n})"
        )
    return {"parity_ok": True, "sharded_parity_ok": True, **res}


def _obs_bench(quick: bool) -> dict:
    """Observability acceptance gates (repro.obs), all HARD asserts:

      * ``obs_overhead_ok`` — instrumented (live registry + tracer) vs
        bare (NULL_REGISTRY, no tracer) wall time over the same
        batcher→mesh traffic stays within 3% (median of interleaved
        rounds);
      * ``obs_trace_ok`` — one batched request under an injected replica
        kill yields a single ticket-correlated trace containing the whole
        story: request → queue → flush → dispatch → failover → merge —
        AND instrumentation is bit-invisible (ids and scores identical to
        the bare run).
    """
    from repro.obs import MetricsRegistry, Tracer, trace_for_ticket
    from repro.kernels.topk_score import topk_score
    from repro.obs.metrics import NULL_REGISTRY
    from repro.serve.batcher import MicroBatcher
    from repro.serve.mesh import (
        FaultInjector,
        FaultTolerantRetrievalMesh,
        RetryPolicy,
    )

    rng = np.random.default_rng(29)
    b, n_items, d, kk = (8, 96, 16, 10) if quick else (32, 2048, 32, 100)
    phi = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    psi = jnp.asarray(rng.normal(size=(n_items, d)), jnp.float32)

    # --- overhead gate: instrumented vs bare, same traffic ---------------
    # sized so the measurement is kernel-bound (production-shaped ψ, small
    # flush batches): per-request shard-kernel work is a few hundred µs
    # while the instrumentation hot path (span begin/end ≈ 2 µs, counter
    # inc ≈ 0.2 µs) is single-digit µs — the gate then measures the real
    # steady-state ratio instead of timer noise on a trivial workload
    n_requests = 48 if quick else 96
    n_rounds = 9
    n_items_o, d_o = (2048, 64) if quick else (4096, 64)
    phi_o = jnp.asarray(rng.normal(size=(b, d_o)), jnp.float32)
    psi_o = jnp.asarray(rng.normal(size=(n_items_o, d_o)), jnp.float32)
    phi_req = np.asarray(rng.normal(size=(n_requests, d_o)), np.float32)

    def build(registry, tracer):
        clock = {"t": 0.0}
        mesh = FaultTolerantRetrievalMesh(
            lambda p=phi_o: p, n_shards=2, n_replicas=2, k=kk,
            block_items=128, retry=RetryPolicy(max_attempts=2),
            registry=registry, tracer=tracer,
        )
        mesh.publish(psi_o)
        batcher = MicroBatcher(
            lambda rows, eids: mesh.topk_phi(rows, exclude_ids=eids),
            max_batch=4, max_delay=1e-3, pad_to=4,
            clock=lambda: clock["t"], version_fn=lambda: mesh.version,
            registry=registry, tracer=tracer,
        )
        return clock, batcher

    def run_requests(clock, batcher, base_t):
        tickets = []
        for r in range(n_requests):
            clock["t"] = base_t + r * 1e-4
            tickets.append(batcher.submit(phi_req[r]))
            batcher.step()
        clock["t"] = base_t + 1.0
        batcher.flush()
        return [np.asarray(batcher.result(t).ids) for t in tickets]

    # construction is one-time (family/child creation); the gate is the
    # STEADY-STATE per-request cost, so only the request loop is timed.
    # Rounds are INTERLEAVED bare/instrumented so both variants sample
    # the same noise environment (interpret-mode kernel jitter here is
    # ±10% per round — far larger than the instrumentation cost), and the
    # comparison statistic is the TRIMMED MEAN OF PAIRED DELTAS: the
    # adjacent bare/instrumented pair cancels slow drift, the min/max
    # delta pair is dropped to shed scheduler outliers, and averaging the
    # rest shrinks the fast jitter. Round 0 warms jit + child caches and
    # is discarded; GC is parked so a collection landing in one variant's
    # rounds doesn't masquerade as instrumentation cost. The measurement
    # (not the workload) is retried up to 3 attempts: true overhead is a
    # fraction of a percent, so one clean attempt under the gate is the
    # expected outcome and repeated failures mean a real regression.
    def measure_overhead():
        bare_cl, bare_b = build(NULL_REGISTRY, None)
        inst_cl, inst_b = build(MetricsRegistry(), Tracer())
        run_requests(bare_cl, bare_b, base_t=0.0)
        ins_ids = run_requests(inst_cl, inst_b, base_t=0.0)
        br_ids = None
        bare_ts, inst_ts = [], []
        gc.collect()
        gc.disable()
        try:
            for r in range(1, n_rounds + 1):
                t0 = time.perf_counter()
                br_ids = run_requests(bare_cl, bare_b, base_t=10.0 * r)
                bare_ts.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                ins_ids = run_requests(inst_cl, inst_b, base_t=10.0 * r)
                inst_ts.append(time.perf_counter() - t0)
        finally:
            gc.enable()
        deltas = sorted(i - b3 for b3, i in zip(bare_ts, inst_ts))[1:-1]
        bare_mean = sum(bare_ts) / len(bare_ts)
        extra = sum(deltas) / len(deltas)
        return extra / bare_mean, bare_mean, bare_mean + extra, br_ids, ins_ids

    for attempt in range(3):
        overhead, bare_s, instr_s, bare_ids, instr_ids = measure_overhead()
        if overhead < 0.03:
            break
    if overhead >= 0.03:
        raise AssertionError(
            f"serve bench FAILED: observability overhead {overhead:.2%} "
            f"(instrumented {instr_s:.4f}s vs bare {bare_s:.4f}s per "
            "round, 3 attempts; gate < 3%)"
        )
    obs_overhead_ok = True
    if any((a != b2).any() for a, b2 in zip(bare_ids, instr_ids)):
        raise AssertionError(
            "serve bench FAILED: instrumentation changed result ids — "
            "observability must be bit-invisible"
        )

    # --- trace gate: one correlated story through a replica kill ---------
    treg, tracer = MetricsRegistry(), Tracer()
    inj = FaultInjector()
    clock = {"t": 0.0}
    mesh = FaultTolerantRetrievalMesh(
        lambda p=phi: p, n_shards=2, n_replicas=2, k=kk, block_items=32,
        injector=inj, retry=RetryPolicy(max_attempts=2),
        registry=treg, tracer=tracer,
    )
    mesh.publish(psi)
    inj.fail(0, 0, "error")
    batcher = MicroBatcher(
        lambda rows, eids: mesh.topk_phi(rows, exclude_ids=eids),
        max_batch=4, max_delay=1e-3, pad_to=4,
        clock=lambda: clock["t"], version_fn=lambda: mesh.version,
        registry=treg, tracer=tracer,
    )
    phi_small = np.asarray(rng.normal(size=(4, d)), np.float32)
    tickets = [batcher.submit(phi_small[r]) for r in range(4)]
    batcher.flush()
    killed = mesh.topk_phi(phi)
    names = {s.name for s in trace_for_ticket(tracer, tickets[0])}
    need = {"request", "queue", "flush", "dispatch", "failover", "merge"}
    if not need <= names:
        raise AssertionError(
            f"serve bench FAILED: ticket trace spans {sorted(names)} miss "
            f"{sorted(need - names)}"
        )
    healthy_s, healthy_i = topk_score(phi, psi, kk, block_items=32)
    if not ((np.asarray(killed.ids) == np.asarray(healthy_i)).all()
            and (np.asarray(killed.scores)
                 == np.asarray(healthy_s)).all()):
        raise AssertionError(
            "serve bench FAILED: traced+killed mesh diverges from the "
            "healthy single-device kernel — failover must stay "
            "bit-invisible under instrumentation"
        )
    obs_trace_ok = True
    return {
        "obs_overhead_ok": obs_overhead_ok,
        "obs_trace_ok": obs_trace_ok,
        "overhead": {
            "bare_s": float(bare_s),
            "instrumented_s": float(instr_s),
            "overhead_frac": float(overhead),
            "gate": "< 0.03",
            "n_requests": n_requests,
            "n_rounds": n_rounds,
            "attempts": attempt + 1,
        },
        "trace": {
            "ticket_span_names": sorted(names),
            "n_spans": len(tracer.spans),
            "fault_burned_s": float(mesh.stats["fault_burned_s"]),
        },
    }


def _measure_cpu(quick: bool, n_rounds: int = 3) -> dict:
    """Wall-clock of dense matmul+top_k vs the streaming kernel (interpret
    mode on CPU ⇒ emulation-bound; informational, never gated)."""
    from repro.kernels.topk_score import topk_score

    rng = np.random.default_rng(2)
    b, n_items, d, k = (16, 4096, 16, 10) if quick else (64, 65536, 64, 100)
    phi = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    psi = jnp.asarray(rng.normal(size=(n_items, d)), jnp.float32)

    dense = jax.jit(lambda p, q: jax.lax.top_k(p @ q.T, k))
    jax.block_until_ready(dense(phi, psi))
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        jax.block_until_ready(dense(phi, psi))
    t_dense = (time.perf_counter() - t0) / n_rounds

    jax.block_until_ready(topk_score(phi, psi, k))
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        jax.block_until_ready(topk_score(phi, psi, k))
    t_fused = (time.perf_counter() - t0) / n_rounds
    return {
        "shape": dict(b=b, n_items=n_items, d=d, k=k),
        "dense_s": t_dense,
        "fused_s": t_fused,
        "note": "interpret-mode emulation; HBM advantage is the analytic row",
    }


def serve_topk_bench(quick: bool = True, out_path: Optional[str] = None) -> dict:
    """Fused retrieval vs dense baseline + the sharded cluster tier; writes
    BENCH_topk_score.json.

    The tracked repo-root JSON is always the quick-mode (CI smoke) shape;
    ``--full`` runs land in BENCH_topk_score_full.json."""
    if out_path is None:
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out_path = os.path.join(
            repo_root,
            "BENCH_topk_score.json" if quick else "BENCH_topk_score_full.json",
        )
    from repro.kernels import use_interpret

    analytic = {
        f"B={b}": topk_traffic_bytes(b=b, n_items=10_000_000, d=128, k=100)
        for b in (8, 64, 256, 1024)
    }
    analytic_cluster = {
        f"S={s}": cluster_traffic_bytes(
            b=256, n_items=10_000_000, d=128, k=100, n_shards=s
        )
        for s in (2, 4, 8, 16)
    }
    models = _zoo_parity(quick)
    cluster = _cluster_parity(quick)
    batcher = _batcher_bench(quick)
    failover = _failover_bench(quick)
    ann = _ann_bench(quick)
    eval_parity = _eval_harness_parity(quick)
    obs = _obs_bench(quick)
    measured = _measure_cpu(quick)
    results = {
        "kernel": "kernels/topk_score (fused score+top-K) vs dense "
                  "(B,n_items) matmul + lax.top_k; serve/cluster sharded "
                  "tier on top",
        "mode": "quick" if quick else "full",
        "backend": "interpret" if use_interpret() else "compiled",
        "analytic_web_scale": {
            "shape": "n_items=10M catalogue, D=128, K=100, fp32",
            **analytic,
        },
        "analytic_cluster": {
            "shape": "B=256, n_items=10M, D=128, K=100, fp32; per-shard ψ "
                     "stream + S·K merge candidates",
            **analytic_cluster,
        },
        "measured_cpu": measured,
        "models": models,
        "cluster": cluster,
        "batcher": batcher,
        "failover": failover,
        "ann": ann,
        "eval_harness": eval_parity,
        "obs": obs,
        "acceptance": {
            "bytes_ratio_at_B256": analytic["B=256"]["bytes_ratio"],
            "shard_overhead_at_S4": analytic_cluster["S=4"][
                "shard_overhead_ratio"
            ],
            "model_parity": {m: r["parity_ok"] for m, r in models.items()},
            "cluster_parity": all(r["parity_ok"] for r in cluster.values()),
            "batcher_routing_ok": batcher["routing_ok"],
            "failover_parity": failover["failover_parity"],
            "degraded_contract_ok": failover["degraded_contract_ok"],
            "retry_deadline_ok": failover["deadline_ok"],
            "eval_parity": eval_parity["parity_ok"],
            "sharded_eval_parity": eval_parity["sharded_parity_ok"],
            "ann_exact_parity": ann["ann_exact_parity"],
            "ann_recall_floor": ann["ann_recall_floor"],
            "quant_parity": ann["quant_parity"],
            "int8_capacity_x": ann["int8_capacity_x"],
            "obs_overhead_ok": obs["obs_overhead_ok"],
            "obs_trace_ok": obs["obs_trace_ok"],
            "target":">= 2x fewer HBM bytes per retrieval batch at B >= 256 "
                      "(analytic; scores never leave VMEM); streaming top-K "
                      "== dense lax.top_k ids for every k-separable model "
                      "incl. exclude-id lists; sharded mesh bit-identical "
                      "to one single-device kernel call at shard counts 1-4 "
                      "(<= 1.05x byte overhead at S=4); batcher routes "
                      "out-of-order requests exactly; streaming ranking-eval "
                      "== dense metrics without a (n_eval, n_items) array, "
                      "single-table and sharded; replica kill under R=2 "
                      "bit-identical (failover invisible), unreplicated kill "
                      "completes with coverage < 1 + dead ranges, retry "
                      "backoff never exceeds the deadline budget; IVF tier "
                      "n_probe=n_clusters bit-identical to exact, recall@K "
                      ">= 0.95 at >= 4x analytic byte reduction, int8 ψ "
                      "scores within 5% relative + >= 3x rows per shard; "
                      "observability: instrumented vs bare < 3% overhead, "
                      "one ticket-correlated trace through an injected "
                      "kill (request/queue/flush/dispatch/failover/merge) "
                      "with bit-invisible instrumentation",
            "met": analytic["B=256"]["bytes_ratio"] >= 2.0
                   and analytic_cluster["S=4"]["shard_overhead_ratio"] <= 1.05
                   and all(r["parity_ok"] for r in models.values())
                   and all(r["parity_ok"] for r in cluster.values())
                   and batcher["routing_ok"]
                   and failover["failover_parity"]
                   and failover["degraded_contract_ok"]
                   and failover["deadline_ok"]
                   and eval_parity["parity_ok"]
                   and eval_parity["sharded_parity_ok"]
                   and ann["ann_exact_parity"]
                   and ann["ann_recall_floor"]
                   and ann["quant_parity"]
                   and ann["int8_capacity_x"] >= 3.0
                   and obs["obs_overhead_ok"]
                   and obs["obs_trace_ok"],
        },
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--smoke", action="store_true",
                      help="quick shapes + hard parity gate (CI; the default)")
    mode.add_argument("--full", action="store_true")
    args = ap.parse_args()
    res = serve_topk_bench(quick=not args.full)
    print(json.dumps(res["acceptance"], indent=1))
    assert res["acceptance"]["met"], "serve bench acceptance gate not met"
