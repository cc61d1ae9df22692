#!/usr/bin/env python3
"""Chip smoke test: iCD-MF train-and-serve on a TPU at full width.

    python chip_smoke.py              # one chip: phases (a)-(d) below
    python chip_smoke.py --chips 4    # four chips: sharded retrieval only

One chip, the ``icd-mf`` config (200k users x 68k items, k=128, the
paper's §6 20M-interaction set, generated from ``--seed``):

  (a) train  - 3 epochs of ``build_model("mf", ...).fit``; the objective
               must be finite and decrease every epoch.
  (b) fused  - one ``mf_padded`` epoch through the compiled pre-gathered
               ``cd_sweep`` kernels on a slice that fits the padded layout,
               against the flat ``mf.epoch`` on the same slice.
  (c) serve  - the trained ψ in a 2-shard x 2-replica
               ``FaultTolerantRetrievalMesh`` behind the ``MicroBatcher``,
               one replica killed, 256 Zipf-chosen users each excluding
               their training items; every answer against the dense
               ``topk_score_ref`` on the chip, at coverage 1.0.
  (d) guards - a TPU must be present, Pallas must compile (no interpret
               mode), and the serving program must hold a
               ``tpu_custom_call``.

``--chips 4`` runs only the multi-chip serving paths — 4 shards x 1
replica, 2 shards x 2 replicas with one replica killed, and
``shard_map_topk`` over a 4-device mesh — each against one ``topk_score``
call over the whole table on device 0 (ids equal, scores bit-identical),
with the shard slabs on distinct devices.

Every check that fails exits non-zero. Only when all pass is the last line
of stdout ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Scores tolerance of phase (b): both epochs run the same fp32 Newton steps
# and differ only in summation order (segment sums vs padded-row reductions,
# XLA vs Pallas Gram, all products at HIGHEST precision). That roundoff,
# ~1e-7 relative per reduction, compounds over the 2·k sequential column
# updates of an epoch to well under 1e-4 of the parameters' scale, while a
# wrong kernel (a dropped column, a wrong Gauss–Seidel patch, a misrouted
# Ψ row) moves them by O(1) of it.
FUSED_RTOL = 1e-4
SLICE_USERS = 20_000
SLICE_LANES = 128            # padded slots per row on both sides
N_REQUESTS = 256


class CheckFailed(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"[chip_smoke] FAIL: {msg}")


def check(ok, msg: str) -> None:
    if not ok:
        raise CheckFailed(msg)
    print(f"  ok: {msg}", flush=True)


def now() -> float:
    return time.perf_counter()


# ------------------------------------------------------------------ guards
def guard_device(n_chips: int):
    """(d) Refuse anything but compiled Pallas on a TPU."""
    if "REPRO_PALLAS_INTERPRET" in os.environ:
        raise CheckFailed("REPRO_PALLAS_INTERPRET is set; the chip run "
                          "compiles every kernel")
    import jax

    from repro.kernels import use_interpret

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise CheckFailed(f"no TPU: jax.devices()[0].platform is "
                          f"{devs[0].platform!r}")
    if use_interpret():
        raise CheckFailed("repro.kernels.use_interpret() is true on a TPU")
    if len(devs) < n_chips:
        raise CheckFailed(f"--chips {n_chips} but JAX sees {len(devs)}")
    print(f"[d] device: {devs[0].device_kind} x{len(devs)}, Pallas compiled",
          flush=True)
    return devs


# ------------------------------------------------------------- (a) train
def train_phase(cfg, seed: int):
    import jax
    import numpy as np

    from repro.core.models import mf
    from repro.core.models.api import Dataset, build_model
    from repro.launch.train import build_dataset

    print(f"[a] train: {cfg.name} {cfg.n_ctx} users x {cfg.n_items} items, "
          f"nnz={cfg.nnz}, k={cfg.k}", flush=True)
    t0 = now()
    data = build_dataset(cfg, smoke=False, seed=seed)
    print(f"[a] data generated + laid out: {now() - t0:.3f}s", flush=True)
    hp = mf.MFHyperParams(k=cfg.k, alpha0=cfg.alpha0, l2=cfg.l2)
    model = build_model("mf", hp=hp, dataset=Dataset(data=data))
    params = model.init(jax.random.PRNGKey(seed))

    t0 = now()
    e0 = jax.block_until_ready(model.residuals(params))
    mf.epoch.lower(params, data, e0, hp, None, 0, None).compile()
    print(f"[a] epoch compile: {now() - t0:.3f}s", flush=True)
    objs = [float(model.objective(params))]
    times = []
    t_prev = now()

    def after_epoch(ep, p):
        nonlocal t_prev
        jax.block_until_ready(p)
        times.append(now() - t_prev)
        objs.append(float(model.objective(p)))
        print(f"[a] epoch {ep + 1}: {times[-1]:.3f}s, objective "
              f"{objs[-1]!r}", flush=True)
        t_prev = now()

    params = model.fit(params, n_epochs=3, callback=after_epoch)
    print(f"[a] objective before training {objs[0]!r}", flush=True)
    check(all(np.isfinite(objs)), "objective finite at every epoch")
    check(all(b < a for a, b in zip(objs, objs[1:])),
          "objective decreases every epoch")
    return model, params, data


# --------------------------------------------------------- (b) fused epoch
def slice_for_padding(data, n_users: int, lanes: int):
    """The ``n_users`` lowest-degree users' interactions with the items
    they touch at most ``lanes`` times, so both padded sides are
    ``lanes`` wide. Returns (user ids, sliced Interactions)."""
    import numpy as np

    from repro.sparse.interactions import build_interactions

    ctx, item = np.asarray(data.ctx), np.asarray(data.item)
    deg = np.bincount(ctx, minlength=data.n_ctx)
    users = np.sort(np.argsort(deg, kind="stable")[:n_users])
    keep = np.isin(ctx, users) & (deg[ctx] <= lanes)
    item_deg = np.bincount(item[keep], minlength=data.n_items)
    keep &= item_deg[item] <= lanes
    rows = np.searchsorted(users, ctx[keep])
    alpha, y = np.asarray(data.alpha)[keep], np.asarray(data.y)[keep]
    return users, build_interactions(rows, item[keep], y, alpha, n_users,
                                     data.n_items, rescale=False)


def fused_phase(params, data, hp):
    import jax
    import numpy as np

    from repro.core import sweeps
    from repro.core.models import mf, mf_padded
    from repro.kernels import vmem

    users, sl = slice_for_padding(data, SLICE_USERS, SLICE_LANES)
    p = mf.MFParams(w=params.w[users], h=params.h)
    pdata = mf_padded.pad_interactions(sl)
    d_c, d_i = pdata.alpha_c.shape[1], pdata.alpha_i.shape[1]
    grid_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(pdata))
    k_b = sweeps.resolve_block_k(hp.block_k, hp.k)
    tile_bytes = 4 * k_b * max(sl.n_ctx * d_c, sl.n_items * d_i)
    print(f"[b] slice: C={sl.n_ctx} users x {sl.n_items} items, "
          f"nnz={sl.nnz}; D_pad ctx={d_c} item={d_i}; padded grids "
          f"{grid_bytes} B, largest pre-gathered Ψ tile {tile_bytes} B",
          flush=True)
    check(d_c == SLICE_LANES and d_i == SLICE_LANES,
          f"both padded sides are {SLICE_LANES} lanes")
    use_gather, block_ctx = vmem.resolve_cd_sweep_dispatch(
        d_i, k_b, sl.n_ctx, n_rows=sl.n_items,
        prefer_gather=sweeps.resolve_psi_dispatch(hp.psi_dispatch))
    print(f"[b] dispatch: {'gather' if use_gather else 'pre-gathered'} "
          f"cd_block_sweep, k_b={k_b}, block_ctx={block_ctx}", flush=True)
    check(not use_gather, "compiled backend routes to the pre-gathered "
          "cd_sweep kernels")

    e_pad = mf_padded.residuals(p, pdata)
    t0 = now()
    compiled = mf_padded.epoch.lower(p, pdata, e_pad, hp).compile()
    print(f"[b] fused epoch compile: {now() - t0:.3f}s", flush=True)
    check("tpu_custom_call" in compiled.as_text(),
          "fused epoch program holds tpu_custom_call kernels")
    t0 = now()
    fused, _ = jax.block_until_ready(mf_padded.epoch(p, pdata, e_pad, hp))
    print(f"[b] fused epoch (first call): {now() - t0:.3f}s", flush=True)
    e = mf.residuals(p, sl)
    t0 = now()
    flat, _ = jax.block_until_ready(mf.epoch(p, sl, e, hp))
    print(f"[b] flat epoch (first call, incl. compile): {now() - t0:.3f}s",
          flush=True)
    for name in ("w", "h"):
        a = np.asarray(getattr(fused, name))
        b = np.asarray(getattr(flat, name))
        rel = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        print(f"[b] max|fused-flat|/max|flat| on {name}: {rel!r} "
              f"(tolerance {FUSED_RTOL})", flush=True)
        check(np.isfinite(a).all() and rel <= FUSED_RTOL,
              f"fused epoch matches the flat epoch on {name}")


# ------------------------------------------------------------- (c) serve
def user_histories(data, users):
    """Each user's training item ids, −1-padded to one width that is a
    multiple of 128 (one serving program for every flush)."""
    import numpy as np

    ctx, item = np.asarray(data.ctx), np.asarray(data.item)
    lo = np.searchsorted(ctx, users, side="left")
    hi = np.searchsorted(ctx, users, side="right")
    width = -(-int((hi - lo).max()) // 128) * 128
    out = np.full((len(users), width), -1, np.int32)
    for r, (a, b) in enumerate(zip(lo, hi)):
        out[r, : b - a] = item[a:b]
    return out


def serve_phase(model, params, data, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.models import mf
    from repro.kernels import use_interpret
    from repro.kernels.topk_score.kernel import topk_score_pallas
    from repro.kernels.topk_score.ref import topk_mismatches, topk_score_ref
    from repro.serve.batcher import MicroBatcher
    from repro.serve.cluster import resolve_cluster_block_items
    from repro.serve.mesh import (
        FaultInjector,
        FaultTolerantRetrievalMesh,
        RetryPolicy,
    )

    k = 100
    rng = np.random.default_rng(seed + 1)
    zipf = 1.0 / np.arange(1, data.n_ctx + 1) ** 1.1
    users = rng.permutation(data.n_ctx)[
        rng.choice(data.n_ctx, size=N_REQUESTS, p=zipf / zipf.sum())]
    excl = user_histories(data, users)
    print(f"[c] {N_REQUESTS} requests from {len(set(users.tolist()))} "
          f"Zipf-chosen users, exclude lists padded to {excl.shape[1]}",
          flush=True)

    psi = model.export_psi(params)
    injector = FaultInjector()
    mesh = FaultTolerantRetrievalMesh(
        lambda ctx: mf.build_phi(params, ctx), n_shards=2, n_replicas=2, k=k,
        injector=injector, retry=RetryPolicy(max_attempts=3),
    )
    mesh.publish(psi)
    injector.fail(0, 0, "error")
    print("[c] published psi into 2 shards x 2 replicas; killed replica "
          "(shard 0, replica 0)", flush=True)
    batch = 32
    batcher = MicroBatcher(
        lambda phi, eids: mesh.topk_phi(phi, exclude_ids=eids),
        max_batch=batch, max_delay=60.0, clock=now,
        version_fn=lambda: mesh.version,
    )

    table = mesh.table
    block_items = resolve_cluster_block_items(table, batch, k,
                                              excl_l=excl.shape[1])
    serving = jax.jit(lambda phi, slab, eids, nv: topk_score_pallas(
        phi, slab, k, exclude_ids=eids, id_offset=0, n_valid=nv,
        block_items=block_items, interpret=use_interpret()))
    hlo = serving.lower(
        jax.ShapeDtypeStruct((batch, psi.shape[1]), jnp.float32),
        jax.ShapeDtypeStruct(table.shards[0].shape, jnp.float32),
        jax.ShapeDtypeStruct((batch, excl.shape[1]), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
    ).compile().as_text()
    check("tpu_custom_call" in hlo,
          f"serving program (block_items={block_items}) holds a "
          "tpu_custom_call")

    phi_all = np.asarray(mf.build_phi(params, jnp.asarray(users)))
    t0 = now()
    tickets = [batcher.submit(phi_all[r], exclude=excl[r])
               for r in range(N_REQUESTS)]
    batcher.flush()
    got = [batcher.result(t) for t in tickets]
    jax.block_until_ready([(g.scores, g.ids) for g in got])
    dt = now() - t0
    bs, ms = batcher.stats, mesh.stats
    print(f"[c] served {N_REQUESTS} requests in {dt:.3f}s (first flush "
          f"compiles): {bs['flushes']} flushes, {ms['dispatches']} "
          f"dispatches, {ms['faults']} faults, {ms['failovers']} failovers",
          flush=True)
    scores = np.stack([np.asarray(g.scores) for g in got])
    ids = np.stack([np.asarray(g.ids) for g in got])
    coverage = min(g.coverage for g in got)

    ref = jax.jit(lambda phi, table, eids: topk_score_ref(
        phi, table, k, exclude_ids=eids))
    rs, ri = jax.block_until_ready(ref(jnp.asarray(phi_all), psi,
                                       jnp.asarray(excl)))
    bad = topk_mismatches(scores, ids, rs, ri)
    print(f"[c] vs dense topk_score_ref: {bad}, coverage={coverage!r}",
          flush=True)
    check(ms["faults"] >= 1 and ms["failovers"] >= 1,
          "the killed replica was hit and failed over")
    check(coverage == 1.0, "coverage 1.0 with one replica dead")
    check(bad == {"score_mismatches": 0, "id_mismatches": 0},
          "every answer matches the dense reference (ids exact outside "
          "near-ties, scores within tolerance)")
    leaked = [(r, i) for r in range(N_REQUESTS)
              for i in set(ids[r].tolist()) & set(excl[r].tolist()) - {-1}]
    check(not leaked, "no excluded (training) item is recommended")


# ------------------------------------------------------ --chips 4 (serving)
def four_chip_phase(cfg, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.topk_score import topk_score
    from repro.serve.cluster import TopKResult, shard_map_topk, shard_psi
    from repro.serve.mesh import FaultInjector, FaultTolerantRetrievalMesh

    k, b, n_excl = 100, N_REQUESTS, 128
    devs = jax.devices()[:4]
    rng = np.random.default_rng(seed)
    psi = jnp.asarray(0.1 * rng.normal(size=(cfg.n_items, cfg.k)), jnp.float32)
    phi = jnp.asarray(rng.normal(size=(b, cfg.k)), jnp.float32)
    eids = jnp.asarray(rng.integers(0, cfg.n_items, size=(b, n_excl)),
                       jnp.int32)
    print(f"[4] random psi {cfg.n_items}x{cfg.k} (the trained size), {b} "
          f"queries, exclude lists of {n_excl}, k={k}, devices "
          f"{[str(x) for x in devs]}", flush=True)

    def ready(res):
        jax.block_until_ready((res.scores, res.ids))
        return res

    t0 = now()
    base = ready(TopKResult(*topk_score(
        jax.device_put(phi, devs[0]), jax.device_put(psi, devs[0]), k,
        exclude_ids=jax.device_put(eids, devs[0]))))
    print(f"[4] single-device topk_score on {devs[0]}: {now() - t0:.3f}s",
          flush=True)

    def same(res, name):
        s, i = np.asarray(res.scores), np.asarray(res.ids)
        bs, bi = np.asarray(base.scores), np.asarray(base.ids)
        print(f"[4] {name}: ids differ at {int((i != bi).sum())} slots, "
              f"scores differ at {int((s != bs).sum())} slots, max "
              f"|diff| {float(np.nanmax(np.abs(np.where(np.isinf(bs), 0, s - bs))))!r}",
              flush=True)
        check(np.array_equal(i, bi), f"{name}: ids equal one device's")
        check(np.array_equal(s, bs), f"{name}: scores bit-identical")

    def slab_devices(mesh):
        return [next(iter(rep.slab.devices()))
                for row in mesh.replica_set.replicas for rep in row]

    m4 = FaultTolerantRetrievalMesh(None, n_shards=4, n_replicas=1, k=k,
                                    devices=devs, psi_table=psi)
    placed = slab_devices(m4)
    check(len(set(placed)) == 4,
          f"4 shard slabs on 4 distinct devices {[str(x) for x in placed]}")
    t0 = now()
    res = ready(m4.topk_phi(phi, exclude_ids=eids))
    print(f"[4] 4 shards x 1 replica: {now() - t0:.3f}s", flush=True)
    same(res, "4 shards x 1 replica")

    injector = FaultInjector()
    m22 = FaultTolerantRetrievalMesh(None, n_shards=2, n_replicas=2, k=k,
                                     devices=devs, injector=injector,
                                     psi_table=psi)
    placed = slab_devices(m22)
    check(all(placed[2 * s] != placed[2 * s + 1] for s in range(2)),
          f"replicas of a shard on distinct devices {[str(x) for x in placed]}")
    check(len(set(placed)) == 4,
          f"one slab per device {[str(x) for x in placed]}")
    injector.fail(0, 0, "error")
    t0 = now()
    res = ready(m22.topk_phi(phi, exclude_ids=eids))
    print(f"[4] 2 shards x 2 replicas, replica (0, 0) killed: "
          f"{now() - t0:.3f}s, coverage {res.coverage!r}, "
          f"failovers {m22.stats['failovers']}", flush=True)
    check(res.coverage == 1.0 and m22.stats["failovers"] >= 1,
          "failover kept coverage at 1.0")
    same(res, "2 shards x 2 replicas, one killed")

    table = shard_psi(psi, 4, devices=devs)
    check(len({next(iter(s.devices())) for s in table.shards}) == 4,
          "shard_map table slabs on 4 distinct devices")
    mesh = jax.make_mesh((4,), ("shards",), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,))
    t0 = now()
    res = ready(shard_map_topk(mesh, table, phi, k, exclude_ids=eids))
    print(f"[4] shard_map_topk over {mesh.devices.size} devices: "
          f"{now() - t0:.3f}s", flush=True)
    same(res, "shard_map_topk")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    print(f"[chip_smoke] compile cache: {use_compile_cache()}", flush=True)
    devs = guard_device(args.chips)
    t_start = now()
    cfg = get_config("icd-mf")
    if args.chips == 4:
        four_chip_phase(cfg, args.seed)
    else:
        model, params, data = train_phase(cfg, args.seed)
        print(f"[a] PASS ({now() - t_start:.3f}s so far)", flush=True)
        fused_phase(params, data, model.hp)
        print(f"[b] PASS ({now() - t_start:.3f}s so far)", flush=True)
        serve_phase(model, params, data, args.seed)
        print(f"[c] PASS ({now() - t_start:.3f}s so far)", flush=True)
    print(f"[chip_smoke] all phases passed in {now() - t_start:.3f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
