"""Training steps of iCD-MF through the program's ``MFModel.epoch``.

One step is ``MFModel.epoch(params, e, schedule=SweepSchedule("full",
block=k_b, blocks_per_sweep=1))``: k_b columns of W, then the same k_b
columns of H, with both Gram matrices and the residual cache. Between steps
the latent columns of W and H are rolled together by k_b; a joint
permutation of the latent dimensions leaves every prediction, the residual
cache and the objective unchanged, so successive steps visit the subspaces
in the order of the rotating schedule while one program is compiled.

Set-up makes the interactions (the benchmark's generator, the program's
layout) and the initial factors (on the device, from the seed), then runs
the first ``check_steps`` steps through the same step object the window
drives; those are the steps the plain reference follows. The window runs
steps until the first step boundary after ``--seconds``.

``train_epoch_s`` is the window's seconds over the column updates it
completed, times 2k: the whole window expressed as one epoch.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import counts, traffic
from bench.reference import mf_cd

FAULTS = ("unchanged", "half_batch")


def init_factors(n_ctx: int, n_items: int, k: int, sigma: float, seed: int):
    """(W0, H0) ~ N(0, σ²), made on the device in one call from the seed."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kw, kh = jax.random.split(key)
        return (sigma * jax.random.normal(kw, (n_ctx, k), jnp.float32),
                sigma * jax.random.normal(kh, (n_items, k), jnp.float32))

    return make(jax.random.key(traffic.jax_seed(seed, "factors")))


def leaf_numbers(p0, a, b) -> tuple[float, float]:
    """(gap of norms, norm of the difference) between two results ``a``
    (the program's) and ``b`` (the reference's) started from ``p0``, each
    over the worst leaf, as shares of max(that leaf's reference change, the
    median leaf's). Leaves whose reference change is under a thousandth of
    the median leaf's are left out (they move by rounding alone)."""
    f64 = lambda x: np.asarray(x, np.float64)
    ref = [np.linalg.norm(f64(y) - f64(x)) for x, y in zip(p0, b)]
    med = float(np.median(ref))
    gap = diff = 0.0
    for x, pa, pb, r in zip(p0, a, b, ref):
        if r < 1e-3 * med:
            continue
        scale = max(r, med)
        na = np.linalg.norm(f64(pa) - f64(x))
        gap = max(gap, abs(na - r) / scale)
        diff = max(diff, np.linalg.norm(f64(pa) - f64(pb)) / scale)
    return gap, diff


class RolledSteps:
    """The step the window drives: ``model.epoch`` over the first ``k_b``
    latent columns of W and H, then both rolled by ``k_b`` so that the next
    step meets the next subspace. ``state`` is ``[params, e]``;
    :meth:`current` gives (W, H, e) back in the original column order.
    ``skip`` plants the fault of a step that returns its state unchanged."""

    def __init__(self, model, params, k_b: int, *, skip: bool = False):
        import jax
        import jax.numpy as jnp

        from repro.core.sweeps import SweepSchedule

        self.model, self.k_b, self.skip, self.done = model, k_b, skip, 0
        self.sched = SweepSchedule("full", block=k_b, blocks_per_sweep=1)
        self.roll = jax.jit(
            lambda w, h, s: (jnp.roll(w, s, 1), jnp.roll(h, s, 1)))
        self.state = [params, model.residuals(params)]

    def step(self) -> None:
        params, e = self.state
        if not self.skip:
            params, e = self.model.epoch(params, e, schedule=self.sched)
        w, h = self.roll(params.w, params.h, -self.k_b)
        self.state[:] = [type(params)(w, h), e]
        self.done += 1

    def current(self):
        params, e = self.state
        w, h = self.roll(params.w, params.h, self.k_b * self.done)
        return w, h, e


class ReferenceSteps:
    """The plain reference (``bench/reference/mf_cd.py``) as a step object
    with RolledSteps' interface: columns [f0, f0+k_b) of W then H, f0
    rotating through the subspaces, every product at ``precision``."""

    def __init__(self, inp: dict, cfg: dict, k_b: int, precision: str):
        import jax.numpy as jnp

        self.ctx, self.item = jnp.asarray(inp["ctx"]), jnp.asarray(inp["item"])
        self.abar = jnp.asarray(inp["abar"])
        self.cfg, self.k_b, self.precision, self.done = cfg, k_b, precision, 0
        w, h = inp["w0"], inp["h0"]
        self.state = [w, h, mf_cd.residuals(w, h, self.ctx, self.item,
                                            jnp.asarray(inp["ybar"]))]

    def step(self) -> None:
        f0 = (self.done % (self.cfg["k"] // self.k_b)) * self.k_b
        self.state[:] = mf_cd.step(
            *self.state, self.ctx, self.item, self.abar, f0, n_cols=self.k_b,
            alpha0=self.cfg["alpha0"], l2=self.cfg["l2"],
            precision=self.precision)
        self.done += 1

    def current(self):
        return tuple(self.state)


def make_inputs(cfg: dict, seed: int, annotate) -> dict:
    """The interactions (host, sorted by user) with their rescaled targets
    and weights, and the initial factors (device), all from the seed."""
    n_ctx, n_items, nnz = cfg["n_ctx"], cfg["n_items"], cfg["nnz"]
    with annotate("generate"):
        ctx, item = traffic.powerlaw_interactions(n_ctx, n_items, nnz, seed)
    y = np.full(nnz, cfg["y_observed"], np.float32)
    alpha = np.full(nnz, cfg["alpha_observed"], np.float32)
    ybar, abar = mf_cd.rescale(y, alpha, cfg["alpha0"])
    w0, h0 = init_factors(n_ctx, n_items, cfg["k"], cfg["init_sigma"], seed)
    return {"ctx": ctx, "item": item, "y": y, "alpha": alpha, "ybar": ybar,
            "abar": abar, "w0": w0, "h0": h0}


def program_steps(inp: dict, cfg: dict, k_b: int, fault, annotate):
    """The program's step object on the program's own data layout, with
    ``fault`` (None or one of FAULTS) planted."""
    import jax.numpy as jnp

    from repro.core.models import mf
    from repro.core.models.api import Dataset, build_model
    from repro.sparse.interactions import build_interactions

    with annotate("layout"):
        data = build_interactions(inp["ctx"], inp["item"], inp["y"],
                                  inp["alpha"], cfg["n_ctx"], cfg["n_items"],
                                  alpha0=cfg["alpha0"])
    if fault == "half_batch":
        # half of the pairs left out, the other half weighted double
        keep = (np.arange(data.nnz) % 2 == 0).astype(np.float32) * 2.0
        data = dataclasses.replace(data, alpha=data.alpha * jnp.asarray(keep))
    hp = mf.MFHyperParams(k=cfg["k"], alpha0=cfg["alpha0"], l2=cfg["l2"])
    model = build_model("mf", hp=hp, dataset=Dataset(data=data))
    return RolledSteps(model, mf.MFParams(inp["w0"], inp["h0"]), k_b,
                       skip=fault == "unchanged")


def follow(steps, loss_of, n: int, annotate) -> tuple[list, dict]:
    """Run ``n`` steps; the loss before the first and after each, and W, H
    (host, column order) after the first and the last."""
    losses, snaps = [loss_of(*steps.current())], {}
    for s in range(1, n + 1):
        with annotate("step"):
            steps.step()
            w, h, e = steps.current()
            losses.append(loss_of(w, h, e))
        if s in (1, n):
            snaps[s] = (np.asarray(w), np.asarray(h))
    return losses, snaps


def readings(p0, got: tuple, ref: tuple, n: int) -> dict:
    """The compared numbers of ``got`` = (losses, snaps) against the
    reference's: each step's loss, as a share of the reference's decrease
    from its starting loss to that step; the first step's change and the
    change after ``n`` steps, per leaf (W, H)."""
    (losses, snaps), (ref_losses, ref_snaps) = got, ref
    g1, d1 = leaf_numbers(p0, snaps[1], ref_snaps[1])
    gn, dn = leaf_numbers(p0, snaps[n], ref_snaps[n])
    l0 = ref_losses[0]
    return {"loss_gap": max(abs(a - b) / abs(l0 - b)
                            for a, b in zip(losses[1:], ref_losses[1:])),
            "step1_norm_gap": g1, "change_norm_gap": gn,
            "step1_diff": d1, "change_diff": dn}


def loss_fn(inp: dict, cfg: dict):
    """The objective of (W, H, e), in float64 on the host."""
    abar = np.asarray(inp["abar"], np.float64)
    return lambda w, h, e: mf_cd.objective(w, h, e, abar, cfg["alpha0"],
                                           cfg["l2"])


def run(h) -> dict:
    import jax
    import jax.numpy as jnp

    cfg, mix, seed = h.config, h.traffic, h.seed
    k, k_b, n_check = cfg["k"], mix["block"], mix["check_steps"]
    if h.fault not in (None,) + FAULTS:
        raise SystemExit(f"unknown fault {h.fault!r}; one of {FAULTS}")

    t = time.perf_counter
    inp = make_inputs(cfg, seed, h.annotate)
    marks = {"inputs": t() - h.t_start}
    loss_of = loss_fn(inp, cfg)
    if h.control:   # the plain reference below the configured precision
        steps = ReferenceSteps(inp, cfg, k_b, cfg["control_precision"])
    else:
        steps = program_steps(inp, cfg, k_b, h.fault, h.annotate)
    marks["layout"] = t() - h.t_start
    p0 = (np.asarray(inp["w0"]), np.asarray(inp["h0"]))
    got = follow(steps, loss_of, n_check, h.annotate)
    marks["steps"] = t() - h.t_start
    h.log(f"set-up steps done, losses {got[0]}; seconds since start at "
          f"the end of each part: {marks}")

    # --------------------------------------------------------------- window
    n_steps = 0
    with h.window() as win:
        while True:
            with h.annotate("step"):
                steps.step()
                jax.block_until_ready(steps.state)
            n_steps += 1
            if time.perf_counter() - win.t0 >= h.seconds:
                break
        win.close()
    setup_s = win.t0 - h.t_start
    finite = bool(np.isfinite(float(jnp.sum(steps.state[-1]))))
    mem = h.memory_peak()
    epoch_s = win.seconds / (2 * k_b * n_steps) * 2 * k
    h.log(f"window {win.seconds!r} s, {n_steps} steps, train_epoch_s "
          f"{epoch_s!r}, setup_s {setup_s!r}")
    del steps

    # ------------------------------------------------------------ reference
    t_ref = time.perf_counter()
    ref = follow(ReferenceSteps(inp, cfg, k_b, "highest"), loss_of, n_check,
                 h.annotate)
    h.log(f"reference losses {ref[0]} ({time.perf_counter() - t_ref:.1f} s)")
    read = readings(p0, got, ref, n_check)
    h.log(f"readings {read}")
    checks = {k_: (v, h.limits[k_]) for k_, v in read.items()
              if k_ in h.limits}
    correct = finite and all(v <= lim for v, lim in checks.values())

    flops = counts.mf_step_flops(cfg["n_ctx"], cfg["n_items"], cfg["nnz"],
                                 k, k_b)
    return {
        "correct": correct, "attempted": n_steps,
        "failed": 0 if finite else n_steps,
        "e2e": {"setup_s": setup_s, "train_epoch_s": epoch_s},
        "checks": checks, "memory_peak_bytes": mem,
        "record": {"steps": n_steps, "window_host_s": win.seconds,
                   "flops_per_step": flops},
        "info": {"compiles_in_window": win.compiles_inside,
                 "readings": read, "setup_marks": marks,
                 "losses": got[0], "ref_losses": ref[0],
                 "reference_s": time.perf_counter() - t_ref},
    }
