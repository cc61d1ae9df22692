"""The 2-shard × 2-replica mesh over four devices through the loss of a
replica, on four virtual CPU devices: the dense reference's ids, and the
same score bits before, during and after the loss and the heal; nothing
new on the lost device; the heal copies a live replica onto a device that
is neither tainted nor the surviving copy's; a copy still in flight is not
routed, nor does any query go to its target device meanwhile; no query
puts two shards on one device while another replica is free. Once with
the replica on device 0 lost, once with one off it."""
import os
import subprocess
import sys
import textwrap

import pytest

HA_SUBPROCESS_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["REPRO_PALLAS_INTERPRET"] = "1"
    import gc
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np

    from repro.kernels.topk_score import topk_score_ref
    from repro.kernels.topk_score.ref import SCORE_ATOL, SCORE_RTOL
    from repro.obs.trace import Tracer
    from repro.serve.batcher import MicroBatcher
    from repro.serve.mesh import FaultInjector, FaultTolerantRetrievalMesh

    K, B = 13, 8
    rng = np.random.default_rng(int(sys.argv[1]))
    psi = rng.normal(size=(203, 16)).astype(np.float32)
    phi = rng.normal(size=(24, 16)).astype(np.float32)
    excl = rng.integers(0, 203, size=(24, 5)).astype(np.int32)
    ref_s, ref_i = (np.asarray(a) for a in topk_score_ref(
        jnp.asarray(phi), jnp.asarray(psi), K, exclude_ids=jnp.asarray(excl)))
    devices = jax.devices()
    assert len(devices) == 4

    def run(victim_on_dev0):
        inj, tracer = FaultInjector(), Tracer()
        mesh = FaultTolerantRetrievalMesh(
            None, n_shards=2, n_replicas=2, k=K, block_items=32,
            devices=devices, injector=inj, auto_heal=True, tracer=tracer)
        mesh.publish(psi)        # a host table: cut on the host
        rs = mesh.replica_set
        slabs = [r.device_id for row in rs.replicas for r in row]
        assert sorted(slabs) == [0, 1, 2, 3], slabs   # one slab per device
        batcher = MicroBatcher(
            lambda p, x: mesh.topk_phi(p, exclude_ids=x), max_batch=B,
            pad_to=8, tracer=tracer, host_inputs=True)

        healthy = {}

        def serve(rows):
            tickets = [batcher.submit(phi[r], exclude=excl[r]) for r in rows]
            batcher.flush()
            for r, t in zip(rows, tickets):
                res = batcher.result(t)
                s, i = np.asarray(res.scores), np.asarray(res.ids)
                assert res.coverage == 1.0
                assert (i == ref_i[r]).all(), r
                np.testing.assert_allclose(s, ref_s[r], rtol=SCORE_RTOL,
                                           atol=SCORE_ATOL)
                # the same bits as before the loss: failover is invisible
                assert (healthy.setdefault(r, s) == s).all(), r

        for q in range(3):
            serve(range(8 * q, 8 * q + 8))
        victim = next(r for r in rs.replicas[0]
                      if (r.device_id == 0) == victim_on_dev0)
        survivor = next(r for r in rs.replicas[0] if r is not victim)
        lost = victim.device
        gc.collect()
        before = {id(a) for a in jax.live_arrays()
                  if a.committed and lost in a.devices()}
        puts = []
        real_put = jax.device_put

        def spy(x, device=None, *a, **kw):
            puts.append(device)
            return real_put(x, device, *a, **kw)

        gate = [False]
        rs.is_ready = lambda slab: gate[0]   # the heal's copy in flight
        inj.fail(0, victim.idx, "error")
        jax.device_put = spy
        try:
            for q in range(6):
                serve(range(8 * (q % 3), 8 * (q % 3) + 8))
            assert not victim.alive
            new = rs.replicas[0][-1]
            assert new.idx not in (victim.idx, survivor.idx) and new.alive
            assert not new.ready and new.served == 0, "routed while copying"
            assert all(rs.pick(0) is survivor for _ in range(4))
            heal = [sp for sp in tracer.spans if sp.name == "heal"]
            assert len(heal) == 1 and heal[0].t1 is None
            assert heal[0].attrs["src_device"] == survivor.device_id
            assert new.device_id not in (victim.device_id,
                                         survivor.device_id)
            gate[0] = True
            for q in range(6):
                serve(range(8 * (q % 3), 8 * (q % 3) + 8))
            assert new.ready and new.served > 0 and heal[0].t1 is not None
        finally:
            jax.device_put = real_put
        assert all(d is None or d != lost for d in puts), puts
        gc.collect()
        after = {id(a) for a in jax.live_arrays()
                 if a.committed and lost in a.devices()}
        assert after <= before, "new arrays on the lost device"
        by_flush = {}
        for sp in tracer.spans:
            if sp.name == "dispatch" and sp.attrs.get("outcome") == "ok":
                by_flush.setdefault(sp.parent_id, []).append(
                    sp.attrs["device"])
        for devs in by_flush.values():
            assert len(devs) == 2 and devs[0] != devs[1], devs
        # no query sent work to the heal's target while its copy ran
        target = new.device_id
        for sp in tracer.spans:
            if sp.name == "dispatch" and sp.t0 < heal[0].t1:
                assert sp.t0 < heal[0].t0 or sp.attrs["device"] != target
        live = [r for row in rs.replicas for r in row if r.alive and r.ready]
        assert len(live) == 4 and mesh.stats["degraded_queries"] == 0

    run(victim_on_dev0=True)
    run(victim_on_dev0=False)
    print("MESH-HA-OK")
    """
)


@pytest.mark.parametrize("seed", [0, 1])
def test_two_by_two_mesh_through_a_replica_loss(seed):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-c", HA_SUBPROCESS_SCRIPT, str(seed)],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**env, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
        timeout=300,
    )
    assert "MESH-HA-OK" in proc.stdout, (
        proc.stdout[-2000:] + proc.stderr[-3000:]
    )
