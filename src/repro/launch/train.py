"""End-to-end training CLI: one registry config through ``build_model``.

  python -m repro.launch.train --arch icd-mf --smoke --steps 30
  python -m repro.launch.train --arch icd-mf --steps 3

``--smoke`` trains the reduced config on the small attribute-driven
generator (:func:`repro.data.synthetic.make_implicit_dataset`). Without it
the full config's interaction count is generated by the vectorised
power-law generator (:func:`repro.data.synthetic.make_powerlaw_interactions`)
— the paper's §6 scale for ``icd-mf``. ``icd-fm`` needs the A+P+H feature
fields, which no generator builds yet, so it is refused.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config


def build_dataset(cfg, *, smoke: bool, seed: int):
    """The training ``Interactions`` for ``cfg`` (observed y=1, raw
    confidence α₀+2, Lemma-1 rescaled)."""
    from repro.data.synthetic import (
        make_implicit_dataset,
        make_powerlaw_interactions,
    )
    from repro.sparse.interactions import build_interactions

    if smoke:
        ev = make_implicit_dataset(n_users=cfg.n_ctx, n_items=cfg.n_items,
                                   seed=seed).events
        pairs = np.unique(ev[:, 0] * cfg.n_items + ev[:, 1])
        ctx, item = pairs // cfg.n_items, pairs % cfg.n_items
    else:
        ctx, item = make_powerlaw_interactions(cfg.n_ctx, cfg.n_items,
                                               cfg.nnz, seed=seed)
    nnz = len(ctx)
    return build_interactions(
        ctx, item, np.ones(nnz), np.full(nnz, cfg.alpha0 + 2.0),
        cfg.n_ctx, cfg.n_items, alpha0=cfg.alpha0,
    )


def train(cfg, *, smoke: bool, epochs: int, seed: int):
    from repro.core.models import mf
    from repro.core.models.api import Dataset, build_model

    if cfg.model != "mf":
        raise SystemExit(
            f"--arch {cfg.name}: no generator builds the {cfg.model!r} "
            "feature fields yet; only icd-mf trains from this CLI")
    t0 = time.perf_counter()
    data = build_dataset(cfg, smoke=smoke, seed=seed)
    print(f"[train] data: {data.n_ctx} users x {data.n_items} items, "
          f"{data.nnz} interactions ({time.perf_counter() - t0:.1f}s)")
    hp = mf.MFHyperParams(k=cfg.k, alpha0=cfg.alpha0, l2=cfg.l2)
    model = build_model(cfg.model, hp=hp, dataset=Dataset(data=data))
    params = model.init(jax.random.PRNGKey(seed))

    def report(ep, p):
        obj = float(model.objective(p))
        print(f"[train] epoch {ep + 1} objective {obj:.6g} "
              f"({time.perf_counter() - t0:.1f}s)")

    t0 = time.perf_counter()
    return model.fit(params, n_epochs=epochs, callback=report)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=20, help="epochs")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    print(f"[train] arch={cfg.name} smoke={args.smoke} "
          f"device={jax.devices()[0].device_kind}")
    train(cfg, smoke=args.smoke, epochs=args.steps, seed=args.seed)


if __name__ == "__main__":
    main()
