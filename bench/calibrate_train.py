#!/usr/bin/env python3
"""Readings of a training cell's comparison on many seeds in one process,
with no measured window: the program (sound), the control and each planted
fault, each against the plain reference. The limits in ``bench/limits/``
are set from such readings (the sound runs' largest, the control's and
the faults' smallest).

    python3 bench/calibrate_train.py --workload mf_yt.train --seeds 1 2 3 \\
        [--control 1] [--fault half_batch]

Prints one JSON line per seed: ``{"seed", "sound", "control"?, <fault>?}``,
each a dict of the numbers ``train_step.readings`` gives.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import run as harness  # noqa: E402
from bench.drivers import train_step  # noqa: E402
from bench.tracing import annotate  # noqa: E402


def seed_readings(cfg: dict, mix: dict, seed: int, control: bool,
                  faults: list[str]) -> dict:
    import numpy as np

    k_b, n = mix["block"], mix["check_steps"]
    t = time.perf_counter()
    inp = train_step.make_inputs(cfg, seed, annotate)
    loss_of = train_step.loss_fn(inp, cfg)
    p0 = (np.asarray(inp["w0"]), np.asarray(inp["h0"]))
    follow = lambda steps: train_step.follow(steps, loss_of, n, annotate)
    ref = follow(train_step.ReferenceSteps(inp, cfg, k_b, "highest"))
    out = {"seed": seed, "losses_ref": ref[0]}
    runs = [("sound", None)] + [(f, f) for f in faults]
    for name, fault in runs:
        steps = train_step.program_steps(inp, cfg, k_b, fault, annotate)
        out[name] = train_step.readings(p0, follow(steps), ref, n)
        del steps
    if control:
        steps = train_step.ReferenceSteps(inp, cfg, k_b,
                                          cfg["control_precision"])
        out["control"] = train_step.readings(p0, follow(steps), ref, n)
    out["seconds"] = time.perf_counter() - t
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", action="append", default=[],
                    choices=train_step.FAULTS)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    if cell["traffic"]["driver"] != "train_step":
        raise harness.Refused(f"{args.workload} is not a training cell")
    harness.guard_device(cell["cell"]["chips"])
    harness.use_compile_cache()
    for seed in args.seeds:
        print(json.dumps(seed_readings(cell["config"], cell["traffic"], seed,
                                       bool(args.control), args.fault)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
