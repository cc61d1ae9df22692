"""The training cell's rolled step: a joint roll of W's and H's latent
columns by k_b after each block-0 step is the rotating schedule, so 16
rolled steps equal 16 steps of SweepSchedule("rotating", block=8,
blocks_per_sweep=1) within float32 rounding — with one program compiled
where the schedule compiles 16."""
import jax
import numpy as np

from bench import traffic
from bench.drivers.train_step import RolledSteps, init_factors
from repro.core.models import mf
from repro.core.models.api import Dataset, build_model
from repro.core.sweeps import SweepSchedule
from repro.sparse.interactions import build_interactions

K, K_B, STEPS = 128, 8, 16


def test_rolled_steps_equal_the_rotating_schedule():
    n_ctx, n_items, nnz = 60, 400, 600
    ctx, item = traffic.powerlaw_interactions(n_ctx, n_items, nnz, seed=5)
    data = build_interactions(ctx, item, np.ones(nnz), np.full(nnz, 3.0),
                              n_ctx, n_items, alpha0=1.0)
    hp = mf.MFHyperParams(k=K, alpha0=1.0, l2=0.1)
    model = build_model("mf", hp=hp, dataset=Dataset(data=data))
    w0, h0 = init_factors(n_ctx, n_items, K, 0.1, seed=5)

    rolled = RolledSteps(model, mf.MFParams(w0, h0), K_B)
    for _ in range(STEPS):
        rolled.step()
    w_r, h_r, e_r = rolled.current()

    sched = SweepSchedule("rotating", block=K_B, blocks_per_sweep=1)
    params = mf.MFParams(w0, h0)
    e = model.residuals(params)
    for s in range(STEPS):
        params, e = model.epoch(params, e, schedule=sched, sweep_index=s)

    for got, want in ((w_r, params.w), (h_r, params.h), (e_r, e)):
        got, want = np.asarray(got), np.asarray(want)
        scale = np.abs(want).max()
        assert np.abs(got - want).max() <= 1e-5 * scale
    # and the step moved the factors: the comparison is not of two copies
    assert np.abs(np.asarray(params.w) - np.asarray(w0)).max() > 1e-2
    assert jax.tree_util.tree_leaves(rolled.state)
