"""Distributed semantics tests.

These run in a SUBPROCESS with ``--xla_force_host_platform_device_count=8``
so the main pytest process keeps its single-device view (the dry-run is the
only place that forces 512). Covered:

  * sharded_gram (shard_map + psum) == global gram
  * pjit'd iCD-MF epoch on a (4,2) mesh == single-device epoch
  * elastic resharding: checkpoint from an 8-device mesh restores onto a
    4-device mesh (simulated node loss) and training continues bit-exact
  * int8 EF compressed psum across shards ≈ uncompressed mean
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map
    from jax.sharding import AxisType
    from functools import partial
    import sys
    sys.path.insert(0, "src")

    assert len(jax.devices()) == 8

    # ---- 1. sharded gram == global gram ---------------------------------
    from repro.core.gram import gram, sharded_gram
    mesh = jax.make_mesh((8,), ("rows",), axis_types=(AxisType.Auto,))
    m = jax.random.normal(jax.random.PRNGKey(0), (64, 6))
    f = shard_map(partial(sharded_gram, axis_name="rows"), mesh=mesh,
                  in_specs=P("rows", None), out_specs=P())
    np.testing.assert_allclose(f(m), gram(m), rtol=1e-5, atol=1e-5)
    print("sharded_gram OK")

    # ---- 2. pjit iCD-MF epoch == single-device --------------------------
    from repro.core.models import mf
    from repro.sparse.interactions import build_interactions
    rng = np.random.default_rng(0)
    n_ctx, n_items, nnz = 32, 24, 128
    cells = rng.choice(n_ctx * n_items, nnz, replace=False)
    ctx, item = cells // n_items, cells % n_items
    data = build_interactions(ctx, item, np.ones(nnz), np.full(nnz, 1.5),
                              n_ctx, n_items, alpha0=0.5)
    hp = mf.MFHyperParams(k=4, alpha0=0.5, l2=0.1)
    params = mf.init(jax.random.PRNGKey(1), n_ctx, n_items, 4)
    e = mf.residuals(params, data)
    ref_p, ref_e = mf.epoch(params, data, e, hp)

    mesh2 = jax.make_mesh((4, 2), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2)
    dsh = lambda spec: NamedSharding(mesh2, spec)
    p_sh = mf.MFParams(w=dsh(P("data", None)), h=dsh(P("model", None)))
    import dataclasses
    # pair arrays over "data"; the run offsets (one per row) replicate
    d_sh = dataclasses.replace(
        jax.tree_util.tree_map(lambda _: dsh(P("data")), data),
        indptr=dsh(P()), t_indptr=dsh(P()))
    d_sharded = jax.device_put(data, d_sh)
    p_sharded = jax.device_put(params, p_sh)
    e_sharded = jax.device_put(e, dsh(P("data")))
    with mesh2:
        got_p, got_e = jax.jit(
            lambda p, d, ee: mf.epoch(p, d, ee, hp),
            in_shardings=(p_sh, d_sh, dsh(P("data"))),
            out_shardings=(p_sh, dsh(P("data"))),
        )(p_sharded, d_sharded, e_sharded)
    np.testing.assert_allclose(np.asarray(got_p.w), np.asarray(ref_p.w),
                               rtol=5e-4, atol=5e-5)
    np.testing.assert_allclose(np.asarray(got_p.h), np.asarray(ref_p.h),
                               rtol=5e-4, atol=5e-5)
    print("pjit iCD epoch OK")

    # ---- 3. elastic resharding restore ----------------------------------
    import tempfile
    from repro.checkpoint import Checkpointer
    from repro.runtime.elastic import ElasticMeshManager
    state = {"w": jax.device_put(jnp.arange(32.0).reshape(8, 4),
                                 dsh(P("data", None)))}
    tmp = tempfile.mkdtemp()
    ck = Checkpointer(tmp)
    ck.save(1, state, blocking=True)
    mgr = ElasticMeshManager(model_axis=2)
    small = mgr.on_failure([d.id for d in jax.devices()[4:]])  # lose 4 devices
    assert small.devices.size == 4
    sh2 = NamedSharding(small, P("data", None))
    restored = ck.restore(1, state, {"w": sh2})
    np.testing.assert_allclose(np.asarray(restored["w"]), np.asarray(state["w"]))
    assert restored["w"].sharding.mesh.devices.size == 4
    print("elastic reshard OK")

    # ---- 4. compressed psum ---------------------------------------------
    from repro.optim.compression import compressed_psum
    g = jax.random.normal(jax.random.PRNGKey(2), (8, 128))
    err0 = jnp.zeros((8, 128))
    f = shard_map(partial(compressed_psum, axis_name="rows"), mesh=mesh,
                  in_specs=(P("rows", None), P("rows", None)),
                  out_specs=(P(None, None), P("rows", None)))
    # note: out mean is replicated; per-shard err returned sharded
    mean_hat, err = f(g, err0)
    true_mean = jnp.mean(g, axis=0, keepdims=True)
    np.testing.assert_allclose(np.asarray(mean_hat)[0], np.asarray(true_mean)[0],
                               atol=0.05)
    print("compressed psum OK")
    print("ALL-DISTRIBUTED-OK")
    """
)


@pytest.mark.slow
def test_distributed_semantics():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
        env={**env, "PYTHONPATH": "src"}, timeout=600,
    )
    assert "ALL-DISTRIBUTED-OK" in proc.stdout, proc.stdout + proc.stderr
