"""Benchmark harness — one entry per paper table/figure + the roofline.

  python -m benchmarks.run              # everything (quick mode)
  python -m benchmarks.run --full       # paper-scale synthetic runs
  python -m benchmarks.run --only fig8

Prints ``name,value,derived`` CSV lines and writes JSON to
results/experiments/.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def _emit(name: str, seconds: float, derived: str):
    print(f"{name},{seconds * 1e6:.1f},{derived}")


def run_figure(name, fn, out_dir, quick, registry=None):
    t0 = time.perf_counter()
    res = fn(quick=quick)
    dt = time.perf_counter() - t0
    if registry is not None:
        registry.histogram(
            "bench_section_seconds", "wall time per benchmark section",
            ("section",),
        ).labels(section=name).observe(dt)
    if isinstance(res, dict):
        res = {**res, "bench_seconds": dt}
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{name}.json"), "w") as f:
        json.dump(res, f, indent=1, default=str)
    return res, dt


def fig7(quick):
    from benchmarks.experiments import paper_dataset, relative_to_popularity, run_cold_start

    res = run_cold_start(paper_dataset(quick), quick=quick)
    return {"absolute": res, "relative_to_popularity": relative_to_popularity(res)}


def fig6a(quick):
    from benchmarks.experiments import paper_dataset, relative_to_popularity, run_offline

    res = run_offline(paper_dataset(quick), quick=quick)
    return {"absolute": res, "relative_to_popularity": relative_to_popularity(res)}


def fig6b(quick):
    from benchmarks.experiments import paper_dataset, relative_to_popularity, run_instant

    res = run_instant(paper_dataset(quick), quick=quick)
    return {"absolute": res, "relative_to_popularity": relative_to_popularity(res)}


def fig8(quick):
    from benchmarks import fig8_cost

    return fig8_cost.run(quick=quick)


def kernels(quick):
    """Micro-bench the Pallas kernels (interpret mode ⇒ timing is not
    meaningful on CPU; we report the oracle-XLA timings + shapes covered)."""
    import jax

    from repro.kernels.gram.ref import gram_ref

    out = {}
    for rows, k in ((4096, 128), (65536, 128)):
        x = jax.random.normal(jax.random.PRNGKey(0), (rows, k))
        f = jax.jit(gram_ref)
        f(x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(5):
            f(x).block_until_ready()
        out[f"gram_xla_{rows}x{k}"] = (time.perf_counter() - t0) / 5
    return out


def cd_sweep(quick):
    """Fused block-sweep vs per-column iCD kernel; also refreshes the
    tracked BENCH_cd_sweep.json at the repo root."""
    from benchmarks.roofline_bench import cd_sweep_bench

    return cd_sweep_bench(quick=quick)


def serve(quick):
    """Fused score+top-K retrieval vs the dense path; hard kernel-vs-oracle
    parity for the whole model zoo + the streaming eval harness; refreshes
    the tracked BENCH_topk_score.json at the repo root."""
    from benchmarks.serve_bench import serve_topk_bench

    return serve_topk_bench(quick=quick)


def roofline(quick):
    from benchmarks.roofline_bench import load_table, markdown_table

    rows = load_table()
    ok = [r for r in rows if r["status"] == "ok"]
    return {
        "n_cells": len(rows),
        "n_ok": len(ok),
        "table_single_pod": markdown_table(rows, "16x16"),
        "table_multi_pod": markdown_table(rows, "2x16x16"),
    }


def grid(quick):
    """Model × confidence × context experiments grid: trains every cell on
    the MovieLens-class log, streams Recall/NDCG through eval/ranking, and
    hard-gates weighted parity + the frequency/context quality wins;
    results merge into BENCH_cd_sweep.json under ``quality``."""
    from benchmarks.experiments import run_grid

    return run_grid(quick=quick)


def continual(quick):
    """Continual-learning gates: fold-in parity (all zoo models + the mesh
    round-trip), full-schedule bit equivalence, delta-publish semantics,
    and the subspace-scheduling updates-to-quality curve — each section
    hard-asserts; results merge into BENCH_cd_sweep.json."""
    from benchmarks.continual_bench import continual_bench

    return continual_bench(quick=quick)


FIGURES = {
    "fig7_coldstart": fig7,
    "fig6a_offline": fig6a,
    "fig6b_instant": fig6b,
    "fig8_cost": fig8,
    "kernels": kernels,
    "cd_sweep": cd_sweep,
    "serve": serve,
    "continual": continual,
    "grid": grid,
    "roofline": roofline,
}

# dataset-free, seconds-fast subset — the smoke gate for CI / pre-commit
QUICK_SET = ("kernels", "cd_sweep", "serve", "continual", "grid", "roofline")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help=f"smoke subset only: {', '.join(QUICK_SET)}")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="results/experiments")
    args = ap.parse_args()
    quick = not args.full

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    # surface the Pallas backend so CI logs show what produced the numbers
    from repro.kernels import use_interpret

    interp = use_interpret()
    import jax

    print(f"# pallas_backend={'interpret' if interp else 'compiled'} "
          f"(use_interpret()={interp}) jax_default_backend={jax.default_backend()}")
    print("# name,seconds_us,derived")

    from repro.obs import MetricsRegistry

    registry = MetricsRegistry(clock=time.perf_counter)
    ran = []
    for name, fn in FIGURES.items():
        if args.quick and name not in QUICK_SET:
            continue
        if args.only and args.only not in name:
            continue
        res, dt = run_figure(name, fn, args.out, quick, registry=registry)
        ran.append(name)
        _emit(name, dt, json.dumps(res, default=str)[:160].replace(",", ";"))

    if args.quick and ran:
        # per-section wall time read back from the obs registry (each
        # section observed exactly once, so the histogram mean IS the
        # section's wall time)
        print("# section wall-time summary (bench_section_seconds):")
        total = 0.0
        for name in ran:
            s = registry.get("bench_section_seconds", section=name)
            total += s
            print(f"#   {name:<16s} {s:8.2f}s")
        print(f"#   {'total':<16s} {total:8.2f}s")


if __name__ == "__main__":
    main()
