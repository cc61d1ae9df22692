"""Open-loop top-K retrieval through the program's serving stack.

The window drives ``MicroBatcher.submit``/``step`` →
``FaultTolerantRetrievalMesh.topk_phi`` → ``cluster.shard_topk`` →
``topk_score`` from one thread, as a serving loop does: requests are
submitted when they are due (the traffic mix's arrivals), whether or not
earlier ones have been answered, and the batcher is stepped at its
deadlines in between.

Latency runs from a request's due time to the moment its result is in host
memory (the batcher's flush is synchronous: its results are host arrays
when the call that flushed returns; the harness stamps that return). A
request not answered within ``drain_s`` after the last arrival, or
answered with coverage < 1, counts in ``failed``.

Set-up makes the catalogue ψ on the device from the seed, publishes it,
drops its own reference, and warms every batch shape the batcher can form:
one flush for each size it pads a batch of 1 .. max_batch rows to.

Afterwards the plain reference (``bench/reference/topk.py``) regenerates ψ
block by block and judges a seeded sample of the answers.
"""
from __future__ import annotations

import gc
import time
from collections import deque

import numpy as np

from bench import traffic
from bench.reference import topk as ref_topk

FAULTS = ("altered_id", "half_batch")
SLOW_S = 0.02   # a host call of the loop this long is reported as a stall


def table_fn(n_items: int, d: int, sigma: float, seed: int, block: int):
    """(whole-table maker, block maker) of the catalogue ψ ~ N(0, σ²): block
    ``c`` comes from its own key, so the table can be made whole on the
    device in one call, and again block by block, with the same bits. The
    key and the block index are arguments, not constants of the program,
    so every seed runs the same compiled programs."""
    import jax
    import jax.numpy as jnp

    key = jax.random.key(traffic.jax_seed(seed, "factors"))
    sizes = [min(block, n_items - lo) for lo in range(0, n_items, block)]

    def blk(key, c, rows):
        return sigma * jax.random.normal(jax.random.fold_in(key, c),
                                         (rows, d), jnp.float32)

    whole = jax.jit(lambda key: jnp.concatenate(
        [blk(key, c, r) for c, r in enumerate(sizes)]))
    one = jax.jit(blk, static_argnums=2)
    return (lambda: whole(key)), (lambda c: one(key, c, sizes[c])), \
        len(sizes)


def query_rows(table, history: np.ndarray, n_from: int, noise: float,
               seed: int) -> np.ndarray:
    """φ of each history: the sum of the ψ rows of its first ``n_from``
    items plus N(0, noise²) — a user's query looks like what they used,
    so the items they excluded would otherwise rank near the top."""
    import jax
    import jax.numpy as jnp

    key = jax.random.key(traffic.jax_seed(seed, "noise"))

    @jax.jit
    def make(key, tab, ids, c):
        z = jax.random.normal(jax.random.fold_in(key, c),
                              (ids.shape[0], tab.shape[1]), jnp.float32)
        return jnp.take(tab, ids, axis=0).sum(axis=1) + noise * z

    out, step = [], 4096
    for c, lo in enumerate(range(0, history.shape[0], step)):
        ids = history[lo:lo + step, :n_from]
        ids = np.pad(ids, ((0, step - ids.shape[0]), (0, 0)))
        out.append(np.asarray(make(key, table, jnp.asarray(ids), c)))
    return np.concatenate(out)[: history.shape[0]]


def padded_sizes(max_batch: int, pad_to: int) -> list[int]:
    """A batch size for each row count the batcher pads 1 .. max_batch
    rows to (a multiple of ``pad_to``), at most ``max_batch``."""
    return sorted({min(-(-b // pad_to) * pad_to, max_batch)
                   for b in range(1, max_batch + 1)})


def open_loop(batcher, phi, excl, due, k, t0, seconds, drain_s, max_delay,
              annotate, *, clock=time.perf_counter,
              sleep=time.sleep) -> dict:
    """Submit request ``r`` at ``t0 + due[r]`` and step the batcher at its
    deadlines until every request is answered or ``drain_s`` has passed
    since ``seconds``. Returns per request: send and answer times (host
    clock), served scores and ids, and coverage; and ``slow``, the loop's
    host calls and Python's collections that took over ``SLOW_S``, as
    (seconds, what, start since ``t0``): where lateness comes from."""
    now = clock
    n = len(due)
    out = {"scores": np.full((n, k), np.nan, np.float32),
           "ids": np.full((n, k), -1, np.int32), "coverage": np.zeros(n),
           "sent": np.full(n, np.nan), "done": np.full(n, np.nan),
           "slow": []}
    pending = deque()
    gc_t = [0.0]

    def note(what, ta):
        d = now() - ta
        if d > SLOW_S:
            out["slow"].append((d, what, ta - t0))

    def on_gc(phase, info):
        if phase == "start":
            gc_t[0] = now()
        else:
            note(f"gc{info['generation']}", gc_t[0])

    def collect():
        while pending:
            tk, r = pending[0]
            res = batcher.result(tk)
            if res is None:
                return
            out["done"][r] = now()
            out["scores"][r], out["ids"][r] = res.scores, res.ids
            out["coverage"][r] = res.coverage
            pending.popleft()

    i = 0
    end = t0 + seconds + drain_s
    gc.callbacks.append(on_gc)
    try:
        while True:
            t = now()
            while i < n and t0 + due[i] <= t:
                with annotate("submit"):
                    tk = batcher.submit(phi[i], exclude=excl[i])
                note("submit", t)
                out["sent"][i] = t
                pending.append((tk, i))
                i += 1
                collect()
                t = now()
            if i >= n and not pending or t > end:
                break
            ta = now()
            with annotate("step"):
                batcher.step()
            note("step", ta)
            collect()
            if i >= n and not pending:
                break
            nxt = t0 + due[i] if i < n else end
            if pending:
                nxt = min(nxt, out["sent"][pending[0][1]] + max_delay)
            ta = now()
            wait = nxt - ta
            if wait > 1e-3:
                sleep(wait - 5e-4)
                note("sleep", ta + wait - 5e-4)   # the overshoot alone
    finally:
        gc.callbacks.remove(on_gc)
    return out


def stall_summary(slow, top: int = 6) -> dict:
    """Per kind of call, how many took over ``SLOW_S`` and their seconds;
    the ``top`` longest as (seconds, what, start since the window's)."""
    kinds = {}
    for d, what, _ in slow:
        n, tot = kinds.get(what, (0, 0.0))
        kinds[what] = (n + 1, tot + d)
    return {"over_s": SLOW_S, "by_kind": kinds,
            "longest": sorted(slow, reverse=True)[:top]}


def account(due, t0: float, t_end: float, got: dict) -> dict:
    """Latency of every request from its due time ``t0 + due`` to its
    answer; one never answered counts until ``t_end`` and in ``failed``,
    as does one answered with coverage < 1. Times in milliseconds."""
    answered = ~np.isnan(got["done"])
    lat = np.where(answered, got["done"], t_end) - (t0 + due)
    sent = ~np.isnan(got["sent"])
    late = got["sent"][sent] - (t0 + due[sent])
    degraded = answered & (got["coverage"] < 1.0)
    p50, p99 = np.percentile(lat, [50, 99]) * 1e3
    return {"answered": answered, "degraded": int(degraded.sum()),
            "failed": int((~answered).sum() + degraded.sum()),
            "p50_ms": float(p50), "p99_ms": float(p99),
            "late_p99_ms": float(np.percentile(late, 99)) * 1e3
            if len(late) else 0.0}


def run(h) -> dict:
    from repro.obs.trace import Tracer
    from repro.serve.batcher import MicroBatcher
    from repro.serve.mesh import FaultTolerantRetrievalMesh

    cfg, mix, seed = h.config, h.traffic, h.seed
    n_items, d = cfg["n_items"], cfg["dim"]
    k, max_batch = mix["k"], mix["max_batch"]
    if h.fault not in (None,) + FAULTS:
        raise SystemExit(f"unknown fault {h.fault!r}; one of {FAULTS}")
    now = time.perf_counter

    # ----------------------------------------------------------- inputs
    whole, block, n_blocks = table_fn(n_items, d, cfg["psi_sigma"], seed,
                                      mix["table_block"])
    with h.annotate("generate"):
        due = traffic.arrivals(mix, h.seconds, seed)
        n = len(due)
        rq = traffic.requests(mix, n_items, n + max_batch, seed)
        psi = whole()
        hist_phi = query_rows(psi, rq["history"], mix["phi_from"],
                              mix["phi_noise"], seed)
        phi = hist_phi[rq["users"]]
        excl = rq["history"][rq["users"]]
        del hist_phi
    marks = {"inputs": now() - h.t_start}

    # ---------------------------------------------------- serving stack
    tracer = Tracer(clock=now) if h.trace else None
    devices = h.devices if len(h.devices) > 1 else None
    mesh = FaultTolerantRetrievalMesh(
        None, n_shards=cfg["shards"], n_replicas=cfg["replicas"], k=k,
        devices=devices, clock=now, tracer=tracer)
    with h.annotate("publish"):
        mesh.publish(psi)
    del psi
    marks["publish"] = now() - h.t_start
    execute = lambda p, x: mesh.topk_phi(p, exclude_ids=x)
    if h.fault is not None:
        execute = _faulty(execute, h.fault)
    batcher = MicroBatcher(execute, max_batch=max_batch,
                           max_delay=mix["max_delay_ms"] * 1e-3,
                           pad_to=mix["pad_to"], clock=now, tracer=tracer,
                           version_fn=lambda: mesh.version)
    with h.annotate("warmup"):
        for b in padded_sizes(max_batch, mix["pad_to"]):
            tickets = [batcher.submit(phi[r], exclude=excl[r])
                       for r in range(n, n + b)]
            batcher.flush()
            for tk in tickets:
                batcher.result(tk)
    marks["warmup"] = now() - h.t_start
    h.log(f"set-up, seconds since start at the end of each part: {marks}")
    span0 = len(tracer.spans) if tracer else 0
    stats0 = dict(batcher.stats), dict(mesh.stats)
    gc.collect()
    gc.freeze()     # set-up's objects stay out of the window's collections

    # ---------------------------------------------------------- window
    with h.window() as win:
        got = open_loop(batcher, phi, excl, due, k, win.t0, h.seconds,
                        mix["drain_s"], mix["max_delay_ms"] * 1e-3,
                        h.annotate)
        win.close()
    served_s, served_i = got["scores"], got["ids"]
    setup_s = win.t0 - h.t_start
    mem = h.memory_peak()
    acc = account(due, win.t0, win.t1, got)
    stalls = stall_summary(got["slow"])
    answered, failed = acc["answered"], acc["failed"]
    p50, p99 = acc["p50_ms"], acc["p99_ms"]
    bstats = {k_: v - stats0[0].get(k_, 0) for k_, v in batcher.stats.items()}
    mstats = {k_: v - stats0[1].get(k_, 0) for k_, v in mesh.stats.items()}
    spans = tracer.spans[span0:] if tracer else []
    h.log(f"{n} requests over {h.seconds} s, answered {int(answered.sum())}"
          f", degraded {acc['degraded']}, p50 {p50!r} ms, p99 {p99!r} "
          f"ms, window {win.seconds!r} s, setup {setup_s!r} s, flushes "
          f"{bstats['flushes']}, rows {bstats['flushed_rows']}")
    h.log(f"host calls and collections over {SLOW_S} s: {stalls}")
    del batcher, mesh, execute, tracer
    gc.unfreeze()
    gc.collect()

    # ---------------------------------------------------------- reference
    t_ref = now()
    r_s = traffic.rng(seed, "sample")
    cap = mix["check_sample"]
    ok = np.flatnonzero(answered)
    idx = np.sort(r_s.choice(ok, size=min(cap, len(ok)), replace=False)) \
        if cap and len(ok) > cap else ok
    ref_s, _ = ref_topk.topk(phi[idx], excl[idx], block, n_blocks, k)
    if h.control:   # the reference below the configured precision serves
        got_s, got_i = ref_topk.topk(phi[idx], excl[idx], block, n_blocks,
                                     k, precision=cfg["control_precision"])
    else:
        got_s, got_i = served_s[idx], served_i[idx]
    ref_got = ref_topk.scores_of(phi[idx], got_i, block, mix["table_block"],
                                 n_items)
    readings = ref_topk.compare(got_s, got_i, excl[idx], ref_s, ref_got)
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
                   "excl_l": mix["history_len"], "shards": cfg["shards"]},
        "info": {"compiles_in_window": win.compiles_inside,
                 "generator_late_p99_ms": acc["late_p99_ms"],
                 "stalls": stalls, "setup_marks": marks, "batcher": bstats,
                 "mesh": mstats, "readings": readings,
                 "requests": n, "checked": int(len(idx))},
    }


def _faulty(execute, fault: str):
    """The executor with one planted fault: ``altered_id`` changes one id
    of every answer where it is produced; ``half_batch`` answers the first
    half of each batch and gives its rows to the second half."""
    import jax.numpy as jnp

    from repro.serve.cluster import TopKResult

    def run(phi, eids):
        res = execute(phi, eids)
        s, i = jnp.asarray(res.scores), jnp.asarray(res.ids)
        if fault == "altered_id":
            i = i.at[:, -1].set((i[:, -1] + 1) % 2**20)
        else:
            half = -(-phi.shape[0] // 2)
            s = jnp.concatenate([s[:half], s[:phi.shape[0] - half]])
            i = jnp.concatenate([i[:half], i[:phi.shape[0] - half]])
        return TopKResult(s, i, res.coverage, res.dead_ranges)

    return run
