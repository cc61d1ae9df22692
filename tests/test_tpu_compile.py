"""The main-path Pallas kernels compile for a TPU v5e.

Nothing runs here: each kernel is lowered and compiled by the TPU compiler
for a described (not attached) v5e chip, at the widths the deployment uses,
and the compiled program must hold the Mosaic kernel (``tpu_custom_call``).
This is what interpret-mode tests cannot show — an op Mosaic does not lower,
a tile it refuses, or more VMEM than a kernel may use. The topology is
described inside a fixture (never at import), and the tests skip where no
TPU compiler is installed.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.cd_sweep import kernel as cd
from repro.kernels.gram.kernel import gram_pallas
from repro.kernels.topk_score.kernel import topk_score_pallas

N_ITEMS = 68_000          # the §6 catalogue
D = 128                   # embedding width (k=128)
K = 100
B = 128
K_B = 8                   # cd_sweep columns per fused block
C = 4096                  # padded rows in one cd_sweep dispatch


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile cannot be read back without the chip
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield jax.sharding.SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


def compile_holds_kernel(fn, *shapes):
    hlo = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in hlo


def _s(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("variant", ["plain", "exclude_ids", "int8",
                                     "exclude_ids_l200"])
def test_topk_score_compiles(one_chip, variant):
    s = lambda shape, dt=jnp.float32: _s(one_chip, shape, dt)  # noqa: E731
    phi, psi = s((B, D)), s((N_ITEMS, D))
    if variant == "plain":
        fn = lambda p, q: topk_score_pallas(p, q, K, interpret=False)  # noqa: E731
        compile_holds_kernel(fn, phi, psi)
    elif variant.startswith("exclude_ids"):
        # 68k items are 17 tiles of 4,096; 200 ids are two lanes of the list
        fn = lambda p, q, e: topk_score_pallas(  # noqa: E731
            p, q, K, exclude_ids=e, interpret=False)
        l = 200 if variant.endswith("l200") else 128
        compile_holds_kernel(fn, phi, psi, s((B, l), jnp.int32))
    else:
        fn = lambda p, q, sc: topk_score_pallas(  # noqa: E731
            p, q, K, psi_scale=sc, interpret=False)
        compile_holds_kernel(fn, phi, s((N_ITEMS, D), jnp.int8),
                             s((N_ITEMS,)))


@pytest.mark.parametrize("d_pad", [128, 512])
@pytest.mark.parametrize("kernel", ["sweep", "rowpatch", "slab_reduce",
                                    "resid_patch"])
def test_pregathered_cd_sweep_compiles(one_chip, kernel, d_pad):
    s = lambda shape, dt=jnp.float32: _s(one_chip, shape, dt)  # noqa: E731
    psi_blk, grid, slab = s((C, K_B, d_pad)), s((C, d_pad)), s((C, K_B))
    hp = dict(alpha0=1.0, l2=0.1, interpret=False)
    if kernel == "sweep":
        compile_holds_kernel(
            lambda *a: cd.cd_block_sweep_pallas(*a, **hp),
            psi_blk, grid, grid, slab, slab, s((K_B, K_B)))
    elif kernel == "rowpatch":
        compile_holds_kernel(
            lambda *a: cd.cd_block_sweep_rowpatch_pallas(*a, **hp),
            psi_blk, grid, grid, slab, slab, s((C, K_B, K_B)))
    elif kernel == "slab_reduce":
        compile_holds_kernel(
            lambda *a: cd.cd_slab_reduce_pallas(*a, interpret=False),
            psi_blk, grid, grid)
    else:
        compile_holds_kernel(
            lambda *a: cd.cd_resid_patch_pallas(*a, interpret=False),
            psi_blk, grid, slab)


def test_gram_compiles(one_chip):
    compile_holds_kernel(lambda m: gram_pallas(m, interpret=False),
                         _s(one_chip, (N_ITEMS, D)))
