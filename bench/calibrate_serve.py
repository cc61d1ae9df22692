#!/usr/bin/env python3
"""A serving cell's knee and its readings on many seeds, in one process.

    python3 bench/calibrate_serve.py --workload retr15m_ha.serve_kill \\
        --sweep 200 40 520 --sweep-seconds 6 --share 0.8 \\
        --seeds 1 2 3 --seconds 40 --control-seeds 4 5 6 \\
        --fault-seeds 7 8 --short-seconds 6 [--write-rate]

The sweep (drivers with ``build``, such as ``serve_failover``) publishes
the cell's table once, with no kill, and offers the open loop rates from
the first to the last in steps, each for ``--sweep-seconds``, until a rate
is not sustained: a request still unanswered 1 s after the window, or a
median latency over the last third of the requests above 1.5 times that
over the first third (the queue grows). It then tries half a step above
the last rate sustained. The knee is the highest rate sustained, and the
runs after it offer ``--share`` of it (``--write-rate`` also writes that
rate into the traffic mix's file).

Then whole runs through the cell's driver, as ``bench/run.py --trace 0``
runs them: sound on each of ``--seeds`` (``--seconds``), the control on
each of ``--control-seeds`` and each planted fault on ``--fault-seeds`` in
turn (``--short-seconds``). Prints one JSON line per sweep step and per
run. The limits in ``bench/limits/`` are set from such readings.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import run as harness  # noqa: E402
from bench import traffic  # noqa: E402
from bench.tracing import annotate  # noqa: E402

GROWTH = 1.5      # last third's median over the first third's: not sustained
LATE_S = 1.0      # answered this long after the window at the latest


def harness_for(cell, devices, compiles, seed, seconds, *, control=False,
                fault=None, mix=None):
    args = argparse.Namespace(workload=cell["cell"]["name"], seed=seed,
                              seconds=seconds, trace=0, control=int(control),
                              fault=fault)
    h = harness.Harness(args, {**cell, "traffic": mix or cell["traffic"]},
                        devices, compiles)
    h.t_start = time.perf_counter()
    return h


def sweep_step(st, mix, rate, seconds, seed) -> dict:
    """One rate of the sweep on the published stack ``st``."""
    from bench.drivers.serve_open_loop import open_loop

    due = traffic.arrivals({**mix, "rate": rate}, seconds, seed)
    t0 = time.perf_counter() + 0.05
    got = open_loop(st["batcher"], st["phi"], st["excl"], due, mix["k"], t0,
                    seconds, LATE_S, mix["max_delay_ms"] * 1e-3, annotate)
    while st["batcher"].n_queued:       # what the window left queued
        st["batcher"].flush()
    lat = np.where(np.isnan(got["done"]), np.inf, got["done"] - t0 - due)
    third = max(1, len(due) // 3)
    first, last = np.median(lat[:third]), np.median(lat[-third:])
    answered = int(np.isfinite(lat).sum())
    return {"kind": "sweep", "rate": rate, "requests": len(due),
            "answered": answered,
            "p50_ms": float(np.median(lat)) * 1e3,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "first_third_p50_ms": float(first) * 1e3,
            "last_third_p50_ms": float(last) * 1e3,
            "sustained": bool(answered == len(due) and last <= GROWTH * first)}


def sweep(drv, cell, devices, compiles, seed, lo, step, hi, seconds):
    mix = {**cell["traffic"], "kill_at_s": None}
    h = harness_for(cell, devices, compiles, seed, seconds, mix=mix)
    n = len(traffic.arrivals({**mix, "rate": hi + step}, seconds, seed))
    st = drv.build(h, n)
    best, rate = None, lo
    while rate <= hi:
        line = sweep_step(st, mix, rate, seconds, seed)
        print(json.dumps(line), flush=True)
        if not line["sustained"]:
            break
        best, rate = rate, rate + step
    if best is not None and rate <= hi:
        line = sweep_step(st, mix, best + step / 2, seconds, seed)
        print(json.dumps(line), flush=True)
        if line["sustained"]:
            best += step / 2
    del st
    gc.collect()    # the sweep's slabs go before the runs place theirs
    return best


def one_run(drv, cell, devices, compiles, seed, seconds, kind, **kw):
    h = harness_for(cell, devices, compiles, seed, seconds, **kw)
    res = drv.run(h)
    return {"kind": kind, "seed": seed, "seconds": seconds,
            "rate": cell["traffic"]["rate"], "correct": res["correct"],
            "failed": res["failed"], "attempted": res["attempted"],
            "e2e": res["e2e"], "memory_peak_bytes": res["memory_peak_bytes"],
            "checks": {k: v[0] for k, v in res["checks"].items()},
            "info": {k: res["info"].get(k) for k in (
                "kill", "compiles_in_window", "generator_late_p99_ms",
                "setup_marks", "checked", "mesh")}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sweep", type=float, nargs=3,
                    metavar=("FIRST", "STEP", "LAST"))
    ap.add_argument("--sweep-seconds", type=float, default=6.0)
    ap.add_argument("--share", type=float, default=0.8)
    ap.add_argument("--write-rate", action="store_true")
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--short-seconds", type=float, default=6.0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    devices = harness.guard_device(cell["cell"]["chips"])
    harness.use_compile_cache()
    compiles = harness.CompileCounter()
    drv = harness.load_module(
        harness.BENCH / "drivers" / f"{cell['traffic']['driver']}.py")
    if args.sweep:
        if not hasattr(drv, "build"):
            raise harness.Refused(f"{args.workload}'s driver cannot sweep")
        seed = (args.seeds or [0])[0]
        knee = sweep(drv, cell, devices, compiles, seed, *args.sweep,
                     args.sweep_seconds)
        if knee is None:
            raise SystemExit("no rate of the sweep was sustained")
        rate = int(round(args.share * knee))
        print(json.dumps({"kind": "knee", "knee": knee, "rate": rate}),
              flush=True)
        cell["traffic"] = {**cell["traffic"], "rate": rate}
        if args.write_rate:
            path = harness.BENCH / "traffic" / f"{cell['cell']['traffic']}.json"
            mix = json.loads(path.read_text())
            mix["rate"] = rate
            path.write_text(json.dumps(mix, indent=2) + "\n")
    runs = [(s, args.seconds, "sound", {}) for s in args.seeds]
    runs += [(s, args.short_seconds, "control", {"control": True})
             for s in args.control_seeds]
    faults = getattr(drv, "FAULTS", ())
    if args.fault_seeds:
        runs += [(args.fault_seeds[i % len(args.fault_seeds)],
                  args.short_seconds, f, {"fault": f})
                 for i, f in enumerate(faults)]
    for seed, seconds, kind, kw in runs:
        print(json.dumps(one_run(drv, cell, devices, compiles, seed, seconds,
                                 kind, **kw)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
