"""Observability spine (repro/obs): registry + tracing under simulated
clocks, instrumentation back-compat on the serving components, and the
bit-identity guard (metrics/tracing must never change results).

Everything runs on injected clocks — no sleeps, no wall-time flakiness —
except the checks that spans reach a real profiler trace on the CPU.
"""
import glob
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs.export import (
    chrome_trace,
    metrics_jsonl,
    prometheus_text,
    write_metrics,
    write_trace,
)
from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    NULL_REGISTRY,
    MetricsRegistry,
    StatsView,
    default_registry,
    resolve_registry,
)
from repro.obs import trace as obs_trace
from repro.obs.trace import Tracer, trace_for_ticket
from repro.serve.batcher import MicroBatcher
from repro.serve.mesh import (
    FaultInjector,
    FaultTolerantRetrievalMesh,
    RetryPolicy,
)


# ------------------------------------------------------------------ registry
class TestRegistry:
    def test_counter_gauge_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total", "help", labels=("who",))
        c.labels(who="a").inc()
        c.labels(who="a").inc(2.5)
        c.labels(who="b").inc()
        assert reg.get("x_total", who="a") == 3.5
        assert reg.get("x_total", who="b") == 1.0
        g = reg.gauge("depth")
        g.set(7)
        g.dec(2)
        assert reg.get("depth") == 5.0

    def test_counter_rejects_negative(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("c_total").inc(-1)

    def test_family_reregistration_must_match(self):
        reg = MetricsRegistry()
        reg.counter("n_total", labels=("a",))
        # same shape: returns the same family
        assert reg.counter("n_total", labels=("a",)) is reg.counter(
            "n_total", labels=("a",))
        with pytest.raises(ValueError):
            reg.gauge("n_total", labels=("a",))          # kind mismatch
        with pytest.raises(ValueError):
            reg.counter("n_total", labels=("b",))        # label mismatch

    def test_label_validation(self):
        reg = MetricsRegistry()
        fam = reg.counter("y_total", labels=("who",))
        with pytest.raises(ValueError):
            fam.labels(nope="x")

    def test_histogram_bucket_edges(self):
        # observations land in the FIRST bucket whose edge >= v (le
        # semantics); one implicit overflow bucket past the last edge
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0)).labels()
        for v in (0.05, 0.1, 0.10001, 1.0, 5.0, 11.0, 1e9):
            h.observe(v)
        assert h.counts == [2, 2, 1, 2]   # le edges are inclusive;
        # 0.05/0.1 -> le-0.1, 0.10001/1.0 -> le-1, 5.0 -> le-10,
        # 11.0/1e9 -> the implicit overflow bucket
        assert h.count == 7
        assert h.sum == pytest.approx(0.05 + 0.1 + 0.10001 + 1.0 + 5.0
                                      + 11.0 + 1e9)

    def test_histogram_rejects_unsorted_edges(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.histogram("bad_seconds", buckets=(1.0, 0.5)).labels()

    def test_quantile_interpolation(self):
        # 10 observations uniform in the (0, 1] bucket: the Prometheus
        # linear-interpolation rule puts p50 at rank 5 of 10 -> 0.5
        reg = MetricsRegistry()
        h = reg.histogram("q_seconds", buckets=(1.0, 2.0)).labels()
        for _ in range(10):
            h.observe(0.7)
        assert h.quantile(0.5) == pytest.approx(0.5)
        assert h.quantile(1.0) == pytest.approx(1.0)

    def test_p99_small_samples_and_overflow_clamp(self):
        reg = MetricsRegistry()
        h = reg.histogram("p_seconds", buckets=(1e-3, 1e-2)).labels()
        assert np.isnan(h.quantile(0.99))            # empty -> NaN
        h.observe(5e-4)
        # single sample: every quantile interpolates inside its bucket
        assert 0.0 < h.quantile(0.99) <= 1e-3
        h.observe(1.0)                               # overflow bucket
        assert h.quantile(0.99) == 1e-2              # clamps to last edge
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_percentiles_keys(self):
        reg = MetricsRegistry()
        h = reg.histogram("pp_seconds").labels()
        h.observe(1e-4)
        assert set(h.percentiles()) == {"p50", "p90", "p99"}

    def test_simulated_clock_timer(self):
        clock = {"t": 100.0}
        reg = MetricsRegistry(clock=lambda: clock["t"])
        h = reg.histogram("t_seconds", buckets=DEFAULT_BUCKETS).labels()
        with reg.timer(h):
            clock["t"] += 0.25
        assert h.count == 1
        assert h.sum == pytest.approx(0.25)

    def test_default_and_null_registry(self):
        assert resolve_registry(None) is default_registry()
        reg = MetricsRegistry()
        assert resolve_registry(reg) is reg
        # NULL is falsy (components use truthiness to skip recording)
        # and absorbs the whole API as no-ops
        assert not NULL_REGISTRY
        NULL_REGISTRY.counter("whatever_total").labels(a=1).inc()
        NULL_REGISTRY.histogram("h_seconds").observe(3.0)

    def test_stats_view_is_live_mapping(self):
        reg = MetricsRegistry()
        c = reg.counter("sv_total").labels()
        view = StatsView({"n": lambda: int(c.value)})
        assert dict(view) == {"n": 0}
        c.inc(3)
        assert view["n"] == 3 and len(view) == 1


# -------------------------------------------------------------------- tracing
class TestTracing:
    def test_span_nesting_auto_parent(self):
        clock = {"t": 0.0}
        tr = Tracer(clock=lambda: clock["t"])
        with tr.span("outer") as outer:
            clock["t"] = 1.0
            with tr.span("inner", detail=7) as inner:
                clock["t"] = 2.0
        assert inner.parent_id == outer.span_id
        assert outer.parent_id is None
        assert outer.t0 == 0.0 and outer.t1 == 2.0
        assert inner.duration == pytest.approx(1.0)
        assert inner.attrs["detail"] == 7
        assert tr.current is None

    def test_begin_end_and_activate(self):
        tr = Tracer(clock=lambda: 0.0)
        fs = tr.begin("flush", parent=None)
        with tr.activate(fs):
            with tr.span("dispatch") as d:
                pass
        tr.end(fs, coverage=1.0)
        assert d.parent_id == fs.span_id
        assert fs.attrs["coverage"] == 1.0
        assert [s.name for s in tr.subtree(fs)] == ["flush", "dispatch"]

    def test_ticket_correlation_out_of_order(self):
        # two tickets whose flushes interleave: each ticket's trace pulls
        # its own request/queue spans PLUS the flush subtree it references
        tr = Tracer(clock=lambda: 0.0)
        rq1 = tr.begin("request", parent=None, ticket=1)
        rq2 = tr.begin("request", parent=None, ticket=2)
        fs2 = tr.begin("flush", parent=None)       # ticket 2 flushes FIRST
        with tr.activate(fs2):
            tr.end(tr.begin("dispatch", shard=0))
        tr.end(fs2)
        tr.end(rq2, flush_span=fs2.span_id)
        fs1 = tr.begin("flush", parent=None)
        with tr.activate(fs1):
            tr.end(tr.begin("failover", shard=0))
        tr.end(fs1)
        tr.end(rq1, flush_span=fs1.span_id)
        names1 = {s.name for s in trace_for_ticket(tr, 1)}
        names2 = {s.name for s in trace_for_ticket(tr, 2)}
        assert names1 == {"request", "flush", "failover"}
        assert names2 == {"request", "flush", "dispatch"}
        # and the shared-flush case: both tickets see the shared spans
        assert fs1.span_id in {s.span_id for s in trace_for_ticket(tr, 1)}
        assert trace_for_ticket(tr, 99) == []


def _profiled(tmp_path, body):
    """Run ``body`` under a profiler trace on the CPU; returns the trace's
    ``repro.*`` host events as ``{name: [(start_ns, duration_ns, stats)]}``."""
    d = str(tmp_path / "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(obs_trace.PROFILER_PREFIX):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns, dict(e.stats)))
    return out


class TestProfilerSpans:
    def test_no_annotation_without_tracer(self, monkeypatch):
        made = []

        class Counting:
            def __init__(self, name, **attrs):
                made.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(obs_trace, "TraceAnnotation", Counting)
        clock = {"t": 0.0}

        def serve(tracer):
            b = MicroBatcher(_fake_topk, max_batch=2, max_delay=1.0,
                             clock=lambda: clock["t"], version_fn=lambda: 0,
                             registry=NULL_REGISTRY, tracer=tracer)
            for _ in range(3):
                b.submit(np.ones(8, np.float32))
            b.flush()

        serve(None)
        assert made == []
        serve(Tracer(clock=lambda: clock["t"]))
        assert {"repro.request", "repro.queue", "repro.flush",
                "repro.assemble", "repro.transfer", "repro.wait",
                "repro.route"} == set(made)

    def test_unnested_spans_reach_the_profiler(self, tmp_path):
        tr = Tracer(clock=time.perf_counter)

        def body():
            a = tr.begin("first", parent=None, batch=3, reason="size")
            b = tr.begin("second", parent=None)
            time.sleep(0.02)
            tr.end(a)        # the first begun ends first: not LIFO
            time.sleep(0.01)
            tr.end(b)

        ev = _profiled(tmp_path, body)
        (a0, ad, a_stats), = ev["repro.first"]
        (b0, bd, _), = ev["repro.second"]
        sa, sb = tr.spans
        assert ad == pytest.approx(sa.duration * 1e9, abs=5e6)
        assert bd == pytest.approx(sb.duration * 1e9, abs=5e6)
        assert a0 <= b0 < a0 + ad < b0 + bd
        assert ad >= 0.02e9 and bd >= 0.03e9
        assert a_stats["batch"] == 3 and a_stats["reason"] == "size"

    @pytest.mark.parametrize("n_shards, middle", [
        (1, ["dispatch"]), (2, ["dispatch", "dispatch", "merge"])])
    def test_flush_phases_nest_in_order(self, n_shards, middle):
        tr = Tracer()
        phi, _, mesh = _mesh_pair(n_shards=n_shards, n_replicas=1,
                                  tracer=tr)
        b = MicroBatcher(
            lambda rows, eids: mesh.topk_phi(rows, exclude_ids=eids),
            max_batch=4, max_delay=1.0, version_fn=lambda: mesh.version,
            tracer=tr)
        for r in range(3):
            b.submit(np.asarray(phi[r]), exclude=[r, r + 1])
        b.flush()
        fl, = [s for s in tr.spans if s.name == "flush"]
        kids = [s for s in tr.spans if s.parent_id == fl.span_id]
        assert [s.name for s in kids] == [
            "assemble", "transfer", *middle, "wait", "route"]
        assert all(fl.t0 <= s.t0 <= s.t1 <= fl.t1 for s in kids)
        assert all(x.t1 <= y.t0 for x, y in zip(kids, kids[1:]))
        assert fl.attrs["batch"] == 3 and fl.attrs["batch_padded"] == 8


class TestStepScopes:
    @pytest.mark.parametrize("scheduled", [False, True])
    def test_epoch_lowering_carries_every_icd_scope(self, scheduled):
        from repro.core.models import mf
        from repro.core.sweeps import SweepSchedule
        from repro.sparse.interactions import build_interactions

        rng = np.random.default_rng(0)
        ctx, item = rng.integers(0, 20, 200), rng.integers(0, 15, 200)
        data = build_interactions(ctx, item, np.ones(200),
                                  np.full(200, 2.0), 20, 15)
        params = mf.init(jax.random.key(0), 20, 15, 4)
        e = mf.residuals(params, data)
        sched = SweepSchedule("full", block=2, blocks_per_sweep=1) \
            if scheduled else None
        text = mf.epoch.lower(params, data, e, mf.MFHyperParams(k=4),
                              sched).as_text(debug_info=True)
        assert set(re.findall(r"icd\.[a-z]+", text)) == {
            "icd.gram", "icd.gather", "icd.segsum", "icd.implicit",
            "icd.newton", "icd.patch", "icd.permute"}


# ------------------------------------------- component instrumentation
def _fake_topk(rows, eids):
    b = int(rows.shape[0])
    scores = np.tile(np.arange(3, 0, -1, dtype=np.float32), (b, 1))
    ids = np.tile(np.arange(3, dtype=np.int32), (b, 1))
    return scores, ids


class TestBatcherInstrumentation:
    def _batcher(self, clock, registry=None, tracer=None, **kw):
        kw.setdefault("max_batch", 4)
        kw.setdefault("max_delay", 1.0)
        return MicroBatcher(
            _fake_topk, clock=lambda: clock["t"],
            version_fn=lambda: 0, registry=registry, tracer=tracer, **kw)

    def test_stats_backcompat_keys_and_types(self):
        clock = {"t": 0.0}
        b = self._batcher(clock, registry=MetricsRegistry())
        for _ in range(4):
            b.submit(np.ones(8, np.float32))
        assert b.stats["submitted"] == 4 and b.stats["flushes"] == 1
        assert b.stats["flush_by_size"] == 1
        # the old dict exposed ints; the registry-backed view must too
        assert all(isinstance(v, int) for v in dict(b.stats).values())

    def test_drained_counter(self):
        clock = {"t": 0.0}
        b = self._batcher(clock, registry=MetricsRegistry())
        b.submit(np.ones(8, np.float32))
        leftovers = b.drain()
        assert len(leftovers) == 1 and b.closed
        assert b.stats["drained"] == 1
        assert b.stats["flushes"] == 1   # drained flushes count as flushes

    def test_registry_series_behind_stats(self):
        clock = {"t": 0.0}
        reg = MetricsRegistry(clock=lambda: clock["t"])
        b = self._batcher(clock, registry=reg)
        b.submit(np.ones(8, np.float32))
        clock["t"] = 5.0
        b.flush()
        # queue latency observed under the simulated clock: exactly 5s
        fam = reg.counter("serve_batcher_submitted_total",
                          labels=("instance",))
        assert sum(ch.value for ch in fam.children()) == 1
        hist = next(iter(
            reg.histogram("serve_batcher_queue_latency_seconds",
                          labels=("instance",)).children()))
        assert hist.count == 1 and hist.sum == pytest.approx(5.0)

    def test_ticket_correlated_trace(self):
        clock = {"t": 0.0}
        tr = Tracer(clock=lambda: clock["t"])
        b = self._batcher(clock, registry=MetricsRegistry(), tracer=tr)
        t1 = b.submit(np.ones(8, np.float32))
        t2 = b.submit(np.ones(8, np.float32))
        clock["t"] = 2.0
        b.flush()
        for t in (t1, t2):
            names = [s.name for s in trace_for_ticket(tr, t)]
            assert names.count("request") == 1
            assert {"request", "queue", "flush"} <= set(names)
        rq = next(s for s in tr.spans
                  if s.name == "request" and s.attrs["ticket"] == t1)
        assert rq.attrs["coverage"] == 1.0 and rq.t1 == 2.0


def _mesh_pair(n_shards=2, n_replicas=2, k=7, **kw):
    rng = np.random.default_rng(11)
    phi = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
    psi = jnp.asarray(rng.normal(size=(96, 16)), jnp.float32)
    mesh = FaultTolerantRetrievalMesh(
        lambda p=phi: p, n_shards=n_shards, n_replicas=n_replicas, k=k,
        block_items=32, **kw)
    mesh.publish(psi)
    return phi, psi, mesh


class TestMeshInstrumentation:
    def test_stats_backcompat_and_counter_names(self):
        reg = MetricsRegistry()
        phi, _, mesh = _mesh_pair(registry=reg)
        mesh.topk_phi(phi)
        assert mesh.stats["queries"] == 1
        assert mesh.stats["dispatches"] == 2          # one per shard
        assert isinstance(mesh.stats["queries"], int)
        assert isinstance(mesh.stats["backoff_slept_s"], float)
        fam = reg.counter("serve_mesh_queries_total", labels=("instance",))
        assert sum(ch.value for ch in fam.children()) == 1

    def test_fault_burned_latency_recorded(self):
        # an injected timeout carries burned deadline budget; the retry
        # loop must aggregate it into fault_burned_s (satellite #2)
        reg = MetricsRegistry()
        inj = FaultInjector()
        clock = {"t": 0.0}
        phi, _, mesh = _mesh_pair(
            registry=reg, injector=inj, clock=lambda: clock["t"],
            retry=RetryPolicy(max_attempts=2, backoff_base=1e-4))
        inj.fail(0, 0, "timeout", latency=0.125, count=1)
        res = mesh.topk_phi(phi)
        assert res.coverage == 1.0                    # failover covered it
        assert mesh.stats["faults"] == 1
        assert mesh.stats["fault_burned_s"] >= 0.125
        fam = reg.counter("serve_mesh_fault_burned_seconds_total",
                          labels=("instance",))
        assert sum(ch.value for ch in fam.children()) >= 0.125

    def test_degraded_counting_through_batcher(self):
        # kill BOTH replicas of shard 0: the mesh serves degraded, the
        # batcher counts every routed row as degraded, nothing is cached
        reg = MetricsRegistry()
        inj = FaultInjector()
        phi, _, mesh = _mesh_pair(
            registry=reg, injector=inj,
            retry=RetryPolicy(max_attempts=1))
        inj.fail(0, 0, "error")
        inj.fail(0, 1, "error")
        clock = {"t": 0.0}
        b = MicroBatcher(
            lambda rows, eids: mesh.topk_phi(rows, exclude_ids=eids),
            max_batch=4, max_delay=1.0, clock=lambda: clock["t"],
            version_fn=lambda: mesh.version, registry=reg)
        tickets = [b.submit(np.ones(16, np.float32), key=("u", i))
                   for i in range(3)]
        b.flush()
        res = b.result(tickets[0])
        assert res.coverage < 1.0
        assert mesh.stats["degraded_queries"] == 1
        assert b.stats["degraded_results"] == 3
        assert b.stats["cache_hits"] == 0

    def test_bit_identity_guard(self, tmp_path):
        # the whole point of opt-in observability: a fully instrumented
        # mesh returns bit-identical results to a bare one
        phi, _, bare = _mesh_pair(registry=NULL_REGISTRY)
        tr = Tracer()
        _, _, instr = _mesh_pair(registry=MetricsRegistry(), tracer=tr)
        r0, r1 = bare.topk_phi(phi), instr.topk_phi(phi)
        np.testing.assert_array_equal(np.asarray(r0.ids),
                                      np.asarray(r1.ids))
        np.testing.assert_array_equal(np.asarray(r0.scores),
                                      np.asarray(r1.scores))

        # and through the batcher's flush: tracer off against on, the
        # latter with its spans going into a running profiler trace
        def served(mesh, tracer):
            b = MicroBatcher(
                lambda rows, eids: mesh.topk_phi(rows, exclude_ids=eids),
                max_batch=4, max_delay=1.0, version_fn=lambda: mesh.version,
                registry=NULL_REGISTRY, tracer=tracer)
            tickets = [b.submit(np.asarray(phi[r]), exclude=[r])
                       for r in range(4)]
            return [b.result(t) for t in tickets]

        off, on = served(bare, None), []
        ev = _profiled(tmp_path, lambda: on.extend(served(instr, tr)))
        assert len(ev["repro.flush"]) == 1
        for a, b in zip(off, on):
            np.testing.assert_array_equal(a.ids, b.ids)
            np.testing.assert_array_equal(a.scores, b.scores)

    def test_replica_latency_histogram_exists(self):
        reg = MetricsRegistry()
        phi, _, mesh = _mesh_pair(registry=reg)
        mesh.topk_phi(phi)
        fam = reg.histogram("serve_mesh_replica_latency_seconds",
                            labels=("instance", "shard", "replica"))
        assert sum(ch.count for ch in fam.children()) == 2


def _walk_rounds_brute(ids, n_rows, block_items, off, block_b):
    """The exclude-id walk's rounds, tile by tile and row by row."""
    b, l = ids.shape
    block_b = min(block_b, -(-b // 8) * 8)
    n_tiles = -(-n_rows // block_items)
    rounds = 0
    for lo in range(0, b, block_b):
        for t in range(n_tiles):
            base = off + t * block_items
            inside = (ids[lo:lo + block_b] >= base) & (
                ids[lo:lo + block_b] < base + block_items)
            rounds += int(inside.sum(axis=1).max())
    return rounds, -(-b // block_b) * n_tiles * (-(-l // 128) * 128)


class TestExclWalkRounds:
    @pytest.mark.parametrize("b, l, n_rows, block_items, off, block_b", [
        (5, 128, 640, 128, 0, 128),       # one row block, lists of a lane
        (21, 200, 1000, 256, 0, 8),       # three row blocks, two lanes
        (16, 60, 900, 128, 500, 16),      # a shard: ids below and above it
    ])
    def test_matches_brute_force(self, b, l, n_rows, block_items, off,
                                 block_b):
        from repro.kernels.topk_score import excl_walk_rounds

        rng = np.random.default_rng(b * l)
        ids = rng.integers(-1, off + n_rows + 300, size=(b, l))
        ids[rng.random((b, l)) < 0.25] = -1
        ids[0, : min(l, 40)] = off + np.arange(min(l, 40))   # one tile
        got = excl_walk_rounds(ids, n_rows, block_items, id_offset=off,
                               block_b=block_b)
        assert got == _walk_rounds_brute(ids, n_rows, block_items, off,
                                         block_b)
        assert 0 < got[0] <= got[1]

    def test_traced_dispatch_carries_rounds_untraced_counts_none(
            self, monkeypatch):
        from repro.serve import mesh as mesh_mod

        calls = []
        real = mesh_mod.excl_walk_rounds
        monkeypatch.setattr(mesh_mod, "excl_walk_rounds",
                            lambda *a, **kw: calls.append(1) or real(*a, **kw))

        def flush(tracer):
            phi, _, mesh = _mesh_pair(tracer=tracer)
            b = MicroBatcher(
                lambda rows, eids: mesh.topk_phi(rows, exclude_ids=eids),
                max_batch=4, max_delay=1.0, version_fn=lambda: mesh.version,
                registry=NULL_REGISTRY, tracer=tracer, host_inputs=True)
            for r in range(3):
                b.submit(np.asarray(phi[r]), exclude=[r, 50 + r, 90])
            b.flush()

        flush(None)
        assert calls == []
        tr = Tracer()
        flush(tr)
        disp = [sp for sp in tr.spans if sp.name == "dispatch"]
        assert len(disp) == 2 == len(calls)        # one per shard
        for sp in disp:
            assert 0 < sp.attrs["excl_rounds"] <= sp.attrs["excl_rounds_full"]
        # 48-row shards in two 32-item tiles. Shard 0: each row's id r in
        # tile 0, and 50 + r in its padded tail tile [32, 64), which the
        # walk visits too. Shard 1: 50 + r in tile 0, 90 in tile 1.
        assert [sp.attrs["excl_rounds"] for sp in disp] == [2, 2]
        assert disp[0].attrs["excl_rounds_full"] == 2 * 128


# ------------------------------------------------------------------- export
class TestExport:
    def _populated(self):
        clock = {"t": 0.0}
        reg = MetricsRegistry(clock=lambda: clock["t"])
        reg.counter("a_total", "a help", labels=("who",)).labels(
            who="x").inc(2)
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0)).labels()
        h.observe(0.05)
        h.observe(0.5)
        return reg

    def test_jsonl_schema(self):
        recs = [json.loads(line)
                for line in metrics_jsonl(self._populated()).splitlines()]
        by_name = {r["name"]: r for r in recs}
        a = by_name["a_total"]
        assert a["type"] == "counter" and a["value"] == 2.0
        assert a["labels"] == {"who": "x"}
        lat = by_name["lat_seconds"]
        assert lat["count"] == 2 and lat["buckets"]["+Inf"] == 2
        assert lat["buckets"]["0.1"] == 1
        assert {"p50", "p90", "p99"} <= set(lat)

    def test_jsonl_empty_histogram_is_strict_json(self):
        reg = MetricsRegistry()
        reg.histogram("empty_seconds").labels()
        rec = json.loads(metrics_jsonl(reg))
        assert rec["p99"] is None         # NaN must not leak into JSON

    def test_prometheus_text(self):
        text = prometheus_text(self._populated())
        assert "# TYPE a_total counter" in text
        assert 'a_total{who="x"} 2.0' in text
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert "lat_seconds_count 2" in text

    def test_write_metrics_picks_format(self, tmp_path):
        reg = self._populated()
        p1 = write_metrics(str(tmp_path / "m.jsonl"), reg)
        p2 = write_metrics(str(tmp_path / "m.prom"), reg)
        assert json.loads(open(p1).readline())["name"]
        assert open(p2).read().startswith("# HELP")

    def test_chrome_trace_schema(self, tmp_path):
        clock = {"t": 0.0}
        tr = Tracer(clock=lambda: clock["t"])
        with tr.span("outer", ticket=3):
            clock["t"] = 0.002
            with tr.span("inner"):
                clock["t"] = 0.003
        doc = chrome_trace(tr)
        events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert [e["name"] for e in events] == ["outer", "inner"]
        outer, inner = events
        assert outer["ts"] == 0.0 and outer["dur"] == pytest.approx(3000.0)
        assert inner["args"]["parent_id"] == outer["args"]["span_id"]
        assert events[0]["args"]["ticket"] == 3
        path = write_trace(str(tmp_path / "t.json"), tr)
        assert json.load(open(path))["displayTimeUnit"] == "ms"
