#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json`` (which names its driver,
``bench/drivers/<driver>.py``), the limits its outputs are held to in
``bench/limits/<workload>.json``, and each per-layer metric's reader in
``bench/metrics/<metric>.py``.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
the per-layer metrics, ``busy_s``/``window_s`` and a ``breakdown``.

The run refuses (exit 2, no result) anything but compiled kernels on a TPU
with as many chips as the cell asks for. ``--control 1`` puts the plain
reference, in the precision below the configuration's, in the program's
place; ``--fault <name>`` plants one of its driver module's faults. Both exist to
show that the comparison fails them; the benchmark's own runs use neither.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

OUT_DIR = ROOT / ".bench_runs"


class Refused(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"[bench] refused: {msg}")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    """The cell's entry, configuration, traffic mix, limits and metrics."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())

    def here(m):
        return workload in m.get("workloads", [workload])

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "limits": {k: v["limit"] for k, v in limits["limits"].items()},
        "end_to_end": [m for m in spec["end_to_end"] if here(m)],
        "per_layer": [m for m in spec["per_layer"] if here(m)],
    }


# ------------------------------------------------------------------ device
def guard_device(chips: int):
    """The TPU and compiled kernels, or refuse. Returns the devices used."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if env in ("1", "true", "yes"):
        raise Refused("REPRO_PALLAS_INTERPRET forces interpret mode")
    import jax

    from repro.kernels import use_interpret

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    if use_interpret():
        raise Refused("Pallas kernels would run in interpret mode")
    return devs[:chips]


def use_compile_cache() -> str:
    """JAX's persistent cache at the fixed ``<checkout>/.jax_cache`` (or
    ``JAX_COMPILATION_CACHE_DIR`` where set), every program cached."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts programs lowered for compilation (cache hits included): any
    inside the measured window is a shape that set-up did not warm. Also
    counts the compilations that asked the persistent cache, and those it
    answered: after a cell's first run in a checkout, every one."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    ASKED = "/jax/compilation_cache/compile_requests_use_cache"
    FOUND = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        from jax import monitoring

        self.n = self.asked = self.found = 0
        monitoring.register_event_duration_secs_listener(self._on)
        monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1

    def _on_event(self, event, **kw):
        if event == self.ASKED:
            self.asked += 1
        elif event == self.FOUND:
            self.found += 1

    def __call__(self) -> int:
        return self.n


# ------------------------------------------------------------------ harness
class Harness:
    """What a driver is given: the cell, the run's options, the measured
    window, and the time the process started."""

    def __init__(self, args, cell: dict, devices, compiles):
        from bench import tracing

        self.workload = args.workload
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.control = bool(args.control)
        self.fault = args.fault
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.limits = cell["limits"]
        self.devices = devices
        self.compiles = compiles
        self.t_start = T_START
        self.annotate = tracing.annotate
        self.trace_dir = str(OUT_DIR / f"trace.{args.workload}") \
            if self.trace else None

    def window(self):
        from bench import tracing

        return tracing.Window(self.trace_dir, self.compiles)

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", flush=True)

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest of the cell's devices."""
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)


def per_layer(cell: dict, rec: dict, devices) -> tuple[dict, dict, dict]:
    """(metrics, device additions, breakdown) of a traced run."""
    from bench import tracing

    ops, spans = tracing.read_trace(rec["trace_dir"])
    win = tracing.window_of(spans)
    dev_ops = [ops.get(d.id, []) for d in devices]
    rec.update(ops=dev_ops, spans=spans, window_ns=win)
    extra, brk = {}, None
    if win is not None and any(dev_ops):
        lo, hi = win
        busy = [tracing.busy_ns(e, lo, hi) for e in dev_ops]
        rec["busy_s"] = sum(busy) / len(busy) * 1e-9
        rec["window_s"] = (hi - lo) * 1e-9
        extra = {"busy_s": rec["busy_s"], "window_s": rec["window_s"]}
        brk = tracing.breakdown(dev_ops[0], spans, lo, hi)
    metrics = {}
    for m in cell["per_layer"]:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, extra, brk


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    devices = guard_device(cell["cell"]["chips"])
    from bench import counts

    cache = use_compile_cache()
    compiles = CompileCounter()
    import jax

    OUT_DIR.mkdir(exist_ok=True)
    d0 = devices[0]
    print(f"[bench] {args.workload} seed {args.seed} on {d0.device_kind} "
          f"x{len(jax.devices())}, compile cache {cache}", flush=True)
    h = Harness(args, cell, devices, compiles)
    driver = load_module(BENCH / "drivers" / f"{cell['traffic']['driver']}.py")
    res = driver.run(h)

    res["info"]["persistent_cache"] = {"asked": compiles.asked,
                                       "found": compiles.found}
    for name in ("compiles_in_window", "generator_late_p99_ms",
                 "persistent_cache"):
        if name in res["info"]:
            print(f"[bench] {name}: {res['info'][name]!r}", flush=True)
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"]}
    if args.trace:
        res["record"]["trace_dir"] = h.trace_dir
        res["record"]["peak"] = counts.peaks(d0.device_kind)
        metrics, extra, brk = per_layer(cell, res["record"], devices)
        device.update(extra)
        out.update(metrics=metrics, device=device)
        if brk is not None:
            out["breakdown"] = brk
    else:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        out.update(metrics={k: {"value": v, "unit": units[k]}
                            for k, v in res["e2e"].items() if k in units},
                   device=device)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in res["checks"].items()}

    run_file = OUT_DIR / f"{args.workload}.{args.seed}.{args.trace}.json"
    run_file.write_text(json.dumps({**out, "info": res["info"]}, indent=1))
    print(f"correct: {res['correct']}", file=sys.stderr)
    for k, (v, lim) in res["checks"].items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
