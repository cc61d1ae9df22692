"""End-to-end observability: train -> publish -> serve under injected
faults -> export metrics (JSONL + Prometheus text) and a Perfetto trace.

One registry and one tracer (``repro.obs``) thread through every layer:

  * training — ``fit_metrics_callback`` records epoch wall time, the loss
    trajectory and SweepSchedule block visits, composed with a
    ``PsiPublisher`` that snapshots ψ into
    the live mesh at each epoch boundary;
  * serving — the ``MicroBatcher`` and ``FaultTolerantRetrievalMesh``
    share the registry (queue depth, flush reasons, cache hits, dispatch/
    failover/retry counters, per-replica latency histograms) and the
    tracer, so one batched request under an
    injected replica kill exports as a single correlated trace:
    submit -> queue -> flush -> dispatch -> failover -> merge;
  * export — ``results/obs/metrics.jsonl``, ``metrics.prom``, and
    ``trace.json`` (open the last in Perfetto / chrome://tracing).

    PYTHONPATH=src python examples/observability.py
"""
import json
import os
import time

import jax
import numpy as np

from repro.core.models.api import Dataset, build_model
from repro.core.models.mf import MFHyperParams
from repro.core.sweeps import SweepSchedule
from repro.data.synthetic import make_implicit_dataset
from repro.obs import (
    MetricsRegistry,
    Tracer,
    compose_callbacks,
    fit_metrics_callback,
    metrics_jsonl,
    trace_for_ticket,
    write_metrics,
    write_trace,
)
from repro.serve.batcher import MicroBatcher
from repro.serve.mesh import (
    FaultInjector,
    FaultTolerantRetrievalMesh,
    RetryPolicy,
)
from repro.serve.publish import PsiPublisher
from repro.sparse.interactions import build_interactions

OUT_DIR = os.path.join("results", "obs")


def main():
    registry = MetricsRegistry(clock=time.perf_counter)
    tracer = Tracer(clock=time.perf_counter)

    # --- train: metrics callback + live psi publishes --------------------
    n_users, n_items, k, k_b = 200, 120, 16, 4
    ds = make_implicit_dataset(n_users=n_users, n_items=n_items, seed=0)
    ev = ds.events
    data = build_interactions(
        ev[:, 0], ev[:, 1], np.ones(len(ev)), np.full(len(ev), 2.0),
        n_users, n_items, alpha0=0.3,
    )
    hp = MFHyperParams(k=k, alpha0=0.3, l2=0.05)
    model = build_model("mf", hp=hp, dataset=Dataset(data=data))
    params = model.init(jax.random.PRNGKey(0))

    injector = FaultInjector()
    mesh = FaultTolerantRetrievalMesh(
        lambda ctx: model.build_phi(params, ctx),
        n_shards=2, n_replicas=2, k=10, injector=injector,
        retry=RetryPolicy(max_attempts=3, deadline=5e-3),
        registry=registry, tracer=tracer,
    )
    schedule = SweepSchedule(kind="rotating", block=k_b)
    publisher = PsiPublisher(mesh, model.export_psi, every=1,
                             registry=registry)
    cb = compose_callbacks(
        fit_metrics_callback(
            registry=registry, objective=model.objective,
            schedule=schedule, n_dims=k, block=k_b,
        ),
        publisher,
    )
    params = model.fit(params, n_epochs=4, callback=cb, schedule=schedule)
    metrics_cb = cb.callbacks[0]
    losses = [loss for _, _, loss in metrics_cb.history]
    print(f"train: {len(metrics_cb.history)} epochs, loss "
          f"{losses[0]:.4f} -> {losses[-1]:.4f}; "
          f"psi versions published: {[v for _, v in publisher.versions]}")

    # --- serve under an injected replica kill ----------------------------
    injector.fail(0, 0, "error")     # sticky: replica (0,0) dies; R=2
    batcher = MicroBatcher(
        lambda phi, eids: mesh.topk_phi(phi, exclude_ids=eids),
        max_batch=8, max_delay=5e-3, clock=time.perf_counter,
        version_fn=lambda: mesh.version,
        registry=registry, tracer=tracer,
    )
    phi_all = np.asarray(model.build_phi(params, np.arange(n_users)))
    tickets = [batcher.submit(phi_all[u], key=("user", int(u)))
               for u in range(8)]
    batcher.step()
    batcher.flush()
    res = batcher.result(tickets[0])
    batcher.drain()
    ms = mesh.stats
    print(f"serve: {ms['dispatches']} dispatches, {ms['faults']} fault(s), "
          f"{ms['failovers']} failover(s), "
          f"coverage={res.coverage:.4f} (kill was invisible: R=2)")
    assert ms["faults"] >= 1 and ms["failovers"] >= 1
    assert res.coverage == 1.0

    # one ticket's whole story, correlated across layers
    span_names = {s.name for s in trace_for_ticket(tracer, tickets[0])}
    print(f"trace[ticket {tickets[0]}]: spans {sorted(span_names)}")
    assert {"request", "queue", "flush", "dispatch", "merge"} <= span_names

    # --- export ----------------------------------------------------------
    os.makedirs(OUT_DIR, exist_ok=True)
    jsonl_path = os.path.join(OUT_DIR, "metrics.jsonl")
    prom_path = os.path.join(OUT_DIR, "metrics.prom")
    trace_path = os.path.join(OUT_DIR, "trace.json")
    write_metrics(jsonl_path, registry)
    write_metrics(prom_path, registry)
    write_trace(trace_path, tracer)
    n_lines = len(metrics_jsonl(registry).splitlines())
    with open(trace_path) as fh:
        n_events = len(json.load(fh)["traceEvents"])
    print(f"export: {n_lines} metric series -> {jsonl_path} / {prom_path}; "
          f"{n_events} trace events -> {trace_path} "
          "(open in Perfetto / chrome://tracing)")


if __name__ == "__main__":
    main()
