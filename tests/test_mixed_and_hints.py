"""Mixed-precision (ZeRO-1 building block) + sharding hints no-op behavior."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models.hints import constrain, sharding_hints
from repro.optim import adamw, apply_updates
from repro.optim.mixed import mixed_precision


def test_mixed_precision_tracks_fp32_trajectory():
    """bf16 live params + fp32 master must follow the pure-fp32 AdamW
    trajectory to bf16 resolution."""
    target = jnp.asarray([0.33, -1.7, 2.4, 0.01])

    def loss(p):
        return jnp.sum((p.astype(jnp.float32) - target) ** 2)

    opt32 = adamw(0.05)
    p32 = jnp.zeros(4, jnp.float32)
    s32 = opt32.init(p32)

    optmx = mixed_precision(adamw(0.05))
    pmx = jnp.zeros(4, jnp.bfloat16)
    smx = optmx.init(pmx)

    for _ in range(150):
        g32 = jax.grad(loss)(p32)
        u, s32 = opt32.update(g32, s32, p32)
        p32 = apply_updates(p32, u)

        gmx = jax.grad(loss)(pmx).astype(jnp.float32)
        u, smx = optmx.update(gmx, smx, pmx)
        pmx = apply_updates(pmx, u)

    # master should match fp32 closely; live bf16 within bf16 eps
    np.testing.assert_allclose(np.asarray(smx["master"]), np.asarray(p32),
                               atol=2e-2)
    np.testing.assert_allclose(np.asarray(pmx, dtype=np.float32),
                               np.asarray(p32), atol=5e-2)


def test_hints_noop_without_context():
    x = jnp.ones((4, 4))
    np.testing.assert_array_equal(constrain(x, ("a", None)), x)


def test_hints_apply_inside_mesh():
    # sharding hints are GSPMD constraints: the mesh axes must be Auto
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    def f(x):
        return constrain(x, ("expert", None)) * 2

    with mesh, sharding_hints(expert="model"):
        out = jax.jit(f)(jnp.ones((4, 4)))
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((4, 4)))


def test_hints_restore_previous_mapping():
    from repro.models.hints import _current

    with sharding_hints(a="model"):
        with sharding_hints(b="data"):
            assert _current() == {"b": "data"}
        assert _current() == {"a": "model"}  # outer mapping restored
    assert _current() is None
