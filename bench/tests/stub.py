"""A harness for running a driver on the CPU at a small size: what
``bench/run.py`` hands a driver, without the look for a chip."""
from __future__ import annotations

import jax

from bench import tracing

TRAIN_CONFIG = {
    "n_ctx": 300, "n_items": 400, "nnz": 3000, "k": 16, "alpha0": 1.0,
    "l2": 0.1, "y_observed": 1.0, "alpha_observed": 3.0, "init_sigma": 0.1,
    "control_precision": "bf16x3",
}
TRAIN_MIX = {"driver": "train_step", "block": 8, "check_steps": 3}

SERVE_CONFIG = {"n_items": 3000, "dim": 128, "psi_sigma": 0.1, "shards": 1,
                "replicas": 1, "control_precision": "bf16x3"}
SERVE_MIX = {
    "driver": "serve_open_loop", "rate": 200, "k": 10, "max_batch": 8,
    "max_delay_ms": 5, "pad_to": 8, "n_users": 50, "user_zipf": 1.1,
    "history_len": 16, "history_zipf": 1.0, "phi_from": 4,
    "phi_noise": 0.1, "drain_s": 30, "check_sample": 0, "table_block": 1024,
}


class StubHarness:
    def __init__(self, config, traffic, limits, *, seed=7, seconds=0.5,
                 control=False, fault=None, trace=False):
        self.workload = "stub"
        self.config, self.traffic, self.limits = config, traffic, limits
        self.seed, self.seconds = seed, seconds
        self.control, self.fault, self.trace = control, fault, trace
        self.devices = jax.devices()[:1]
        self.t_start = 0.0
        self.trace_dir = None
        self.annotate = tracing.annotate
        self.lines = []

    def window(self):
        return tracing.Window(None, lambda: 0)

    def log(self, msg):
        self.lines.append(msg)

    def memory_peak(self):
        return 0
