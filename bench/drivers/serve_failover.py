"""Open-loop top-K retrieval from a replicated, sharded mesh through the
loss of a replica in the middle of the window.

The serving stack is ``serve_open_loop``'s — ``MicroBatcher`` →
``FaultTolerantRetrievalMesh.topk_phi`` → ``cluster.shard_topk`` →
``topk_score`` — with the mesh's shards and replicas placed over the
cell's chips, the batch handed over on the host (``host_inputs``: each
dispatched replica's chip gets it), and auto-heal on. At ``kill_at_s``
into the window (half the window where that is shorter) the executor
arms a sticky ``FaultInjector`` error on the replica of shard 0 that is
not on the cell's first chip. Nothing else is told: the next dispatch
routed there fails, the replica is marked dead (``fail_threshold`` 1),
the flush fails over to the surviving copy, and the heal copies that copy
onto a chip with no dead replica and no live copy of the shard, routed
once resident.

Set-up makes ψ on the host, block by block from the seed (the block maker
of ``serve_open_loop.table_fn``: the same bits), and publishes it, so
each chip receives its own cut and no chip holds the whole table. φ comes
from ``query_rows`` over the rows the histories use. Every batch size is
warmed on every replica, and the cross-shard merge on every chip (after a
heal it may run on a chip that never merged before).

The plain reference (``bench/reference/topk.py``) judges the seeded
sample and every request answered from the kill to 1 s after the heal's
end. ``correct`` also needs every request answered with coverage 1
(``degraded`` 0) and the replication restored (``replicas_missing`` 0:
S·R routed replicas at the end).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import traffic
from bench.drivers.serve_open_loop import (
    _faulty,
    account,
    open_loop,
    padded_sizes,
    query_rows,
    stall_summary,
    table_fn,
)
from bench.reference import topk as ref_topk

FAULTS = ("altered_id", "half_batch", "drop_shard", "wrong_heal_slab")
AFTER_HEAL_S = 1.0    # judged past the heal's end


def host_table(block, n_blocks: int, n_items: int, d: int) -> np.ndarray:
    """ψ in host memory, filled block by block from the device's maker."""
    out = np.empty((n_items, d), np.float32)
    lo = 0
    for c in range(n_blocks):
        blk = np.asarray(block(c))
        out[lo:lo + blk.shape[0]] = blk
        lo += blk.shape[0]
    return out


def phi_of(psi: np.ndarray, history: np.ndarray, n_from: int,
           noise: float, seed: int) -> np.ndarray:
    """``query_rows`` over the ψ rows the histories use, the same bits as
    over the whole table, without putting the whole table on a device."""
    import jax.numpy as jnp

    used = history[:, :n_from]
    uniq, inv = np.unique(used, return_inverse=True)
    return query_rows(jnp.asarray(psi[uniq]), inv.reshape(used.shape),
                      n_from, noise, seed)


def warm_merges(devices, sizes, k: int, n_shards: int) -> None:
    """Compile the cross-shard merge (``jnp.stack`` and
    ``topk_merge_shards``) for every batch size on every chip."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.topk_score.ops import topk_merge_shards

    for dev in devices:
        for b in sizes:
            s = jax.device_put(np.zeros((b, k), np.float32), dev)
            i = jax.device_put(np.zeros((b, k), np.int32), dev)
            jax.block_until_ready(topk_merge_shards(
                jnp.stack([s] * n_shards), jnp.stack([i] * n_shards), k))


def build(h, n_requests: int) -> dict:
    """The serving stack and the inputs of ``n_requests`` requests (plus
    the warm-up's), published and warmed: everything before the window."""
    from repro.obs.trace import Tracer
    from repro.serve.batcher import MicroBatcher
    from repro.serve.mesh import (
        FaultInjector,
        FaultTolerantRetrievalMesh,
        RetryPolicy,
    )

    cfg, mix, seed = h.config, h.traffic, h.seed
    n_items, d, k = cfg["n_items"], cfg["dim"], mix["k"]
    max_batch, now = mix["max_batch"], time.perf_counter
    if h.fault not in (None,) + FAULTS:
        raise SystemExit(f"unknown fault {h.fault!r}; one of {FAULTS}")
    multi = len(h.devices) > 1
    tracer = Tracer(clock=now) if h.trace else None
    injector = FaultInjector()
    mesh = FaultTolerantRetrievalMesh(
        None, n_shards=cfg["shards"], n_replicas=cfg["replicas"], k=k,
        devices=h.devices if multi else None, clock=now, tracer=tracer,
        injector=injector, fail_threshold=1, auto_heal=cfg["auto_heal"],
        retry=RetryPolicy(max_attempts=1) if h.fault == "drop_shard"
        else None)
    kill = {"at": None, "armed": None, "victim": None}

    def execute(p, x):
        if kill["at"] is not None and kill["armed"] is None \
                and now() >= kill["at"]:
            rep = kill["victim"] = victim(mesh, h.devices[0].id)
            injector.fail(rep.shard, rep.idx, "error")
            kill["armed"] = now()
        return mesh.topk_phi(p, exclude_ids=x)

    run_fn = execute if h.fault in (None, "drop_shard", "wrong_heal_slab") \
        else _faulty(execute, h.fault)
    batcher = MicroBatcher(run_fn, max_batch=max_batch,
                           max_delay=mix["max_delay_ms"] * 1e-3,
                           pad_to=mix["pad_to"], clock=now, tracer=tracer,
                           version_fn=lambda: mesh.version, host_inputs=True)

    whole, block, n_blocks = table_fn(n_items, d, cfg["psi_sigma"], seed,
                                      mix["table_block"])
    del whole
    marks = {}
    with h.annotate("generate"):
        rq = traffic.requests(mix, n_items, n_requests + max_batch, seed)
        psi = host_table(block, n_blocks, n_items, d)
        hist_phi = phi_of(psi, rq["history"], mix["phi_from"],
                          mix["phi_noise"], seed)
        phi = hist_phi[rq["users"]]
        excl = rq["history"][rq["users"]]
        del hist_phi
    marks["inputs"] = now() - h.t_start
    with h.annotate("publish"):
        mesh.publish(psi)
    del psi
    marks["publish"] = now() - h.t_start
    if h.fault == "wrong_heal_slab":   # a heal copies another shard
        rs = mesh.replica_set
        rs.heal_source = lambda s: next(
            r for r in rs.live((s + 1) % rs.n_shards) if r.ready)
    sizes = padded_sizes(max_batch, mix["pad_to"])
    with h.annotate("warmup"):
        for b in sizes:
            for _ in range(cfg["replicas"]):   # each size on each replica
                tickets = [batcher.submit(phi[r], exclude=excl[r])
                           for r in range(n_requests, n_requests + b)]
                batcher.flush()
                for tk in tickets:
                    batcher.result(tk)
        if multi:
            warm_merges(h.devices, sizes, k, cfg["shards"])
    marks["warmup"] = now() - h.t_start
    return {"mesh": mesh, "batcher": batcher, "tracer": tracer,
            "kill": kill, "phi": phi, "excl": excl, "block": block,
            "n_blocks": n_blocks, "marks": marks}


def victim(mesh, first_chip: int):
    """The replica of shard 0 the kill takes: the last one not on the
    cell's first chip (the one whose trace the harness breaks down)."""
    row = mesh.replica_set.live(0)
    off = [r for r in row if r.device_id != first_chip]
    return (off or row)[-1]


def run(h) -> dict:
    cfg, mix, seed = h.config, h.traffic, h.seed
    n_items, d, k = cfg["n_items"], cfg["dim"], mix["k"]
    now = time.perf_counter
    due = traffic.arrivals(mix, h.seconds, seed)
    n = len(due)
    st = build(h, n)
    mesh, batcher, tracer, kill = (st[x] for x in
                                   ("mesh", "batcher", "tracer", "kill"))
    phi, excl, st_marks = st["phi"], st["excl"], st["marks"]
    h.log(f"set-up, seconds since start at the end of each part: "
          f"{st_marks}")
    slab_devices = sorted({r.device_id for row in mesh.replica_set.replicas
                           for r in row})
    span0 = len(tracer.spans) if tracer else 0
    stats0 = dict(batcher.stats), dict(mesh.stats)
    gc.collect()
    gc.freeze()     # set-up's objects stay out of the window's collections

    # ---------------------------------------------------------- window
    with h.window() as win:
        if mix.get("kill_at_s") is not None:
            kill["at"] = win.t0 + min(mix["kill_at_s"], h.seconds / 2)
        got = open_loop(batcher, phi, excl, due, k, win.t0, h.seconds,
                        mix["drain_s"], mix["max_delay_ms"] * 1e-3,
                        h.annotate)
        win.close()
    setup_s = win.t0 - h.t_start
    mem = h.memory_peak()
    acc = account(due, win.t0, win.t1, got)
    stalls = stall_summary(got["slow"])
    answered, failed = acc["answered"], acc["failed"]
    p50, p99 = acc["p50_ms"], acc["p99_ms"]
    bstats = {k_: v - stats0[0].get(k_, 0) for k_, v in batcher.stats.items()}
    mstats = {k_: v - stats0[1].get(k_, 0) for k_, v in mesh.stats.items()}
    spans = tracer.spans[span0:] if tracer else []
    healed = [r for row in mesh.replica_set.replicas for r in row
              if r.admitted_at is not None]
    heal_end = max((r.admitted_at for r in healed), default=None)
    live = sum(r.alive and r.ready and not r.canary   # routed replicas
               for row in mesh.replica_set.replicas for r in row)
    rep = kill["victim"]
    kill_info = {
        "armed_s": None if kill["armed"] is None else kill["armed"] - win.t0,
        "victim": None if rep is None else [rep.shard, rep.idx,
                                            rep.device_id],
        "victim_dead": None if rep is None else not rep.alive,
        "healed": [[r.shard, r.idx, r.device_id, r.admitted_at - win.t0]
                   for r in healed],
        "recover_s": None if kill["armed"] is None or heal_end is None
        else heal_end - kill["armed"],
        "replicas_live": live}
    h.log(f"{n} requests over {h.seconds} s, answered {int(answered.sum())}"
          f", degraded {acc['degraded']}, p50 {p50!r} ms, p99 {p99!r} "
          f"ms, window {win.seconds!r} s, setup {setup_s!r} s, flushes "
          f"{bstats['flushes']}, rows {bstats['flushed_rows']}")
    h.log(f"kill and heal: {kill_info}")
    h.log(f"host calls and collections over 0.02 s: {stalls}")
    block, n_blocks = st["block"], st["n_blocks"]
    kill["victim"] = None   # the replicas hold slabs: let them go
    del batcher, mesh, tracer, st, rep, healed
    gc.unfreeze()
    gc.collect()

    # ---------------------------------------------------------- reference
    t_ref = now()
    r_s = traffic.rng(seed, "sample")
    cap = mix["check_sample"]
    ok = np.flatnonzero(answered)
    idx = np.sort(r_s.choice(ok, size=min(cap, len(ok)), replace=False)) \
        if cap and len(ok) > cap else ok
    if kill["armed"] is not None:   # every answer from the kill on
        until = (win.t1 + mix["drain_s"] if heal_end is None
                 else heal_end + AFTER_HEAL_S)
        after = ok[(got["done"][ok] >= kill["armed"])
                   & (got["done"][ok] <= until)]
        idx = np.union1d(idx, after)
    ref_s, _ = ref_topk.topk(phi[idx], excl[idx], block, n_blocks, k)
    if h.control:   # the reference below the configured precision serves
        got_s, got_i = ref_topk.topk(phi[idx], excl[idx], block, n_blocks,
                                     k, precision=cfg["control_precision"])
    else:
        got_s, got_i = got["scores"][idx], got["ids"][idx]
    ref_got = ref_topk.scores_of(phi[idx], got_i, block, mix["table_block"],
                                 n_items)
    readings = ref_topk.compare(got_s, got_i, excl[idx], ref_s, ref_got)
    readings.update(degraded=acc["degraded"],
                    replicas_missing=cfg["shards"] * cfg["replicas"] - live)
    h.log(f"reference over {len(idx)} answers: {readings} "
          f"({now() - t_ref:.1f} s)")
    checks = {k_: (v, h.limits[k_]) for k_, v in readings.items()
              if k_ in h.limits}
    correct = bool(answered.all()) and all(
        v <= lim for v, lim in checks.values())

    flush_rows = [sp.attrs["batch"] for sp in spans if sp.name == "flush"]
    return {
        "correct": correct, "attempted": n, "failed": failed,
        "e2e": {"setup_s": setup_s, "serve_p50_ms": p50,
                "serve_p99_ms": p99},
        "checks": checks, "memory_peak_bytes": mem,
        "record": {"program_spans": spans, "flush_rows": flush_rows,
                   "n_items": n_items, "dim": d, "k": k,
                   "excl_l": mix["history_len"], "shards": cfg["shards"],
                   "device_ids": [dev.id for dev in h.devices],
                   "slab_devices": slab_devices},
        "info": {"compiles_in_window": win.compiles_inside,
                 "generator_late_p99_ms": acc["late_p99_ms"],
                 "stalls": stalls, "setup_marks": st_marks,
                 "batcher": bstats, "mesh": mstats, "kill": kill_info,
                 "readings": readings, "requests": n,
                 "checked": int(len(idx))},
    }
