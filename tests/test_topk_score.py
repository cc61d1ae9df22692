"""Fused score+top-K kernel: oracle parity, edge cases, and the engine
contract across the whole k-separable model zoo."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _zoo import ZOO, model_phi_psi, _rand

from repro.kernels.topk_score import topk_merge_shards, topk_score, topk_score_ref
from repro.kernels.topk_score.ref import SCORE_ATOL, SCORE_RTOL
from repro.serve.engine import (
    RetrievalEngine,
    exclude_ids_from_lists,
    exclude_mask_from_lists,
)


def test_matches_ref_and_dense_topk_nondivisible_blocks():
    phi, psi = _rand((9, 24), 0), _rand((301, 24), 1)
    s, i = topk_score(phi, psi, 17, block_items=128)  # 301 % 128 != 0
    rs, ri = topk_score_ref(phi, psi, 17)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs), rtol=1e-6, atol=1e-6)
    ds, di = jax.lax.top_k(phi @ psi.T, 17)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(di))
    np.testing.assert_allclose(np.asarray(s), np.asarray(ds), rtol=1e-6, atol=1e-6)


def test_batch_larger_than_block_b():
    phi, psi = _rand((50, 8), 2), _rand((200, 8), 3)
    s, i = topk_score(phi, psi, 10, block_b=16, block_items=64)
    ds, di = jax.lax.top_k(phi @ psi.T, 10)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(di))
    np.testing.assert_allclose(np.asarray(s), np.asarray(ds), rtol=1e-6, atol=1e-6)


def test_tied_scores_rank_ascending_id():
    # duplicated ψ rows across different blocks ⇒ exact score ties
    base = _rand((40, 6), 4)
    psi = jnp.concatenate([base, base, base], axis=0)  # ids i, i+40, i+80 tie
    phi = _rand((5, 6), 5)
    s, i = topk_score(phi, psi, 30, block_items=64)
    rs, ri = topk_score_ref(phi, psi, 30)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    # dense lax.top_k over the id-ordered row is the documented tie policy
    ds, di = jax.lax.top_k(phi @ psi.T, 30)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(di))


def test_exclude_mask_and_fully_masked_row():
    rng = np.random.default_rng(6)
    phi, psi = _rand((7, 12), 6), _rand((90, 12), 7)
    excl = jnp.asarray(rng.random((7, 90)) < 0.4)
    excl = excl.at[2, :].set(True)  # row 2: nothing admissible
    s, i = topk_score(phi, psi, 12, excl, block_items=32)
    rs, ri = topk_score_ref(phi, psi, 12, excl)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    # excluded ids never leak; fully-masked row is all (−inf, −1)
    assert bool((np.asarray(i)[2] == -1).all())
    assert bool(np.isneginf(np.asarray(s)[2]).all())
    got = np.asarray(i)
    mask = np.asarray(excl)
    for r in range(7):
        real = got[r][got[r] >= 0]
        assert not mask[r, real].any()


def test_exclude_ids_matches_mask_path():
    """The web-scale id-list exclusion form (in-kernel block-aligned mask
    slices, no (B, n_items) array) must agree with the dense-mask form."""
    rng = np.random.default_rng(16)
    phi, psi = _rand((7, 12), 6), _rand((90, 12), 7)
    lists = [rng.choice(90, size=int(rng.integers(0, 9)), replace=False)
             for _ in range(7)]
    eids = exclude_ids_from_lists(lists)
    mask = exclude_mask_from_lists(lists, 90)
    s_ids, i_ids = topk_score(phi, psi, 12, exclude_ids=eids, block_items=32)
    s_m, i_m = topk_score(phi, psi, 12, mask, block_items=32)
    np.testing.assert_array_equal(np.asarray(i_ids), np.asarray(i_m))
    np.testing.assert_array_equal(np.asarray(s_ids), np.asarray(s_m))
    rs, ri = topk_score_ref(phi, psi, 12, exclude_ids=eids)
    np.testing.assert_array_equal(np.asarray(i_ids), np.asarray(ri))


def test_id_offset_and_n_valid_shard_semantics():
    """A row-range shard (id_offset, n_valid) emits GLOBAL ids and keeps
    pad rows inadmissible — the kernel contract serve/cluster builds on."""
    phi, psi = _rand((5, 8), 12), _rand((64, 8), 13)
    # shard owning global rows [40, 64), padded to 32 rows
    shard = jnp.pad(psi[40:], ((0, 8), (0, 0)))
    s, i = topk_score(phi, shard, 30, id_offset=40, n_valid=24, block_items=32)
    rs, ri = topk_score_ref(phi, psi[40:], 30)
    ri_global = np.where(np.asarray(ri) >= 0, np.asarray(ri) + 40, -1)
    np.testing.assert_array_equal(np.asarray(i), ri_global)
    # kernel vs reference: the stated fp32 score contract (ref.py)
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs),
                               rtol=SCORE_RTOL, atol=SCORE_ATOL)
    # pad rows (global id >= 64) never surface
    assert (np.asarray(i) < 64).all()
    # traced offsets hit the same jit cache (one program serves all shards)
    s2, i2 = topk_score(phi, shard, 30, id_offset=jnp.int32(40),
                        n_valid=jnp.int32(24), block_items=32)
    np.testing.assert_array_equal(np.asarray(i2), ri_global)


def test_k_larger_than_n_items():
    phi, psi = _rand((3, 5), 8), _rand((11, 5), 9)
    s, i = topk_score(phi, psi, 20, block_items=128)
    rs, ri = topk_score_ref(phi, psi, 20)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    assert bool((np.asarray(i)[:, 11:] == -1).all())
    assert bool(np.isneginf(np.asarray(s)[:, 11:]).all())
    # the 11 real slots are the full catalogue, exactly ranked
    ds, di = jax.lax.top_k(phi @ psi.T, 11)
    np.testing.assert_array_equal(np.asarray(i)[:, :11], np.asarray(di))


def test_merge_shards_is_tie_stable_and_pads_inadmissible():
    """topk_merge_shards alone: score-ordered per-shard lists with cross-
    shard ties must come out in ascending GLOBAL id; −inf slots are −1."""
    # two shards, one row; shard 1 has a tie (score 1.0) with shard 0
    s0 = jnp.asarray([[[1.0, 0.5, -jnp.inf]]])
    i0 = jnp.asarray([[[7, 2, -1]]], jnp.int32)
    s1 = jnp.asarray([[[1.0, 0.25, -jnp.inf]]])
    i1 = jnp.asarray([[[3, 9, -1]]], jnp.int32)
    ms, mi = topk_merge_shards(jnp.concatenate([s0, s1]),
                               jnp.concatenate([i0, i1]), 5)
    # tie at 1.0: id 3 (shard 1) precedes id 7 (shard 0)
    np.testing.assert_array_equal(np.asarray(mi)[0], [3, 7, 2, 9, -1])
    np.testing.assert_array_equal(
        np.asarray(ms)[0], [1.0, 1.0, 0.5, 0.25, -np.inf])
    # k larger than the candidate pool pads with (−inf, −1)
    ms2, mi2 = topk_merge_shards(jnp.concatenate([s0, s1]),
                                 jnp.concatenate([i0, i1]), 8)
    assert bool((np.asarray(mi2)[0, 4:] == -1).all())
    assert bool(np.isneginf(np.asarray(ms2)[0, 4:]).all())


@pytest.mark.parametrize("name", ZOO)
def test_streaming_matches_dense_topk_all_models(name):
    """The acceptance check: fused kernel == dense lax.top_k for the zoo,
    with and without an exclude mask, through the RetrievalEngine."""
    rng = np.random.default_rng(42)
    phi, psi = model_phi_psi(name, rng)
    # model predict ⇔ ⟨φ, ψ⟩ consistency is covered by each model's own
    # tests; here we pin streaming top-k to the dense path over Φ·Ψᵀ
    engine = RetrievalEngine(psi, lambda p=phi: p, k=12, block_items=32)
    s, i = engine.topk()
    ds, di = jax.lax.top_k(engine.scores(phi), 12)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(di))
    np.testing.assert_allclose(np.asarray(s), np.asarray(ds), rtol=1e-5, atol=1e-6)

    excl_lists = [rng.choice(psi.shape[0], size=5, replace=False)
                  for _ in range(phi.shape[0])]
    mask = exclude_mask_from_lists(excl_lists, psi.shape[0])
    s2, i2 = engine.topk(exclude_mask=mask)
    rs2, ri2 = topk_score_ref(phi, psi, 12, mask)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(ri2))
    got = np.asarray(i2)
    m = np.asarray(mask)
    for r in range(got.shape[0]):
        real = got[r][got[r] >= 0]
        assert not m[r, real].any()
    # the id-list exclusion form agrees with the mask form bit-for-bit
    s3, i3 = engine.topk(exclude_ids=exclude_ids_from_lists(excl_lists))
    np.testing.assert_array_equal(np.asarray(i3), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s3), np.asarray(s2))


def test_nan_scores_rank_like_dense_top_k():
    """A NaN ψ row ranks first, as in lax.top_k's total order — so a bad
    table surfaces as non-finite scores instead of vanishing."""
    phi, psi = _rand((4, 8), 30), _rand((150, 8), 31)
    psi = psi.at[77].set(jnp.nan)
    s, i = topk_score(phi, psi, 10, block_items=128)
    rs, ri = topk_score_ref(phi, psi, 10)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    assert (np.asarray(i)[:, 0] == 77).all()
    assert np.isnan(np.asarray(s)[:, 0]).all()


def test_exclude_ids_wider_than_one_lane_tile():
    """Exclude lists longer than 128 ids (several lane tiles of the list)
    agree with the dense-mask oracle."""
    rng = np.random.default_rng(32)
    phi, psi = _rand((6, 16), 33), _rand((700, 16), 34)
    lists = [rng.choice(700, size=int(n), replace=False)
             for n in rng.integers(150, 300, size=6)]
    eids = exclude_ids_from_lists(lists)
    assert eids.shape[1] > 128
    s, i = topk_score(phi, psi, 20, exclude_ids=eids, block_items=128)
    rs, ri = topk_score_ref(phi, psi, 20, exclude_ids=eids)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs),
                               rtol=SCORE_RTOL, atol=SCORE_ATOL)


def test_topk_mismatches_forgives_only_near_ties():
    from repro.kernels.topk_score.ref import topk_mismatches

    ref_s = np.asarray([[3.0, 2.0, 2.0 + 1e-7, -np.inf]], np.float32)
    ref_i = np.asarray([[5, 9, 4, -1]], np.int32)
    # a near-tie ranked the other way, within the score tolerance: clean
    got_i = np.asarray([[5, 4, 9, -1]], np.int32)
    assert topk_mismatches(ref_s, got_i, ref_s, ref_i) == {
        "score_mismatches": 0, "id_mismatches": 0}
    # a clear winner swapped out, and a score off by 1e-3: both counted
    bad_s = ref_s.copy()
    bad_s[0, 0] += 1e-3
    got_i = np.asarray([[7, 9, 4, -1]], np.int32)
    assert topk_mismatches(bad_s, got_i, ref_s, ref_i) == {
        "score_mismatches": 1, "id_mismatches": 1}


def _walk_case(case):
    """Inputs of one exclude-id walk case: (phi, psi, psi_scale, ids,
    id_offset, n_valid, block_b); tiles are 128 items."""
    rng = np.random.default_rng(41)
    b, n_rows, off, n_valid, l, block_b = 5, 640, 0, 640, 128, 128
    if case == "several_row_blocks":
        b, block_b = 21, 8
    if case == "n_valid_short":
        n_valid = 555
    if case == "other_shards":
        off = 1000
    if case == "l_not_lane_multiple":
        l = 200
    ids = np.full((b, l), -1, np.int64)
    for r in range(b):
        ids[r, : l - 7 * r % 40] = rng.choice(
            np.arange(off, off + n_rows), size=l - 7 * r % 40, replace=False)
    if case == "one_tile":        # every id of row 1 in tile 2: all rounds
        ids[1] = np.arange(256, 384)
    if case == "straddle":        # ids on both sides of tile boundaries
        ids[:, :12] = np.r_[122:134][None] + 128 * (np.arange(b) % 4)[:, None]
    if case == "unsorted_duplicated":
        ids[:, 40:80] = ids[:, :40]
        ids = rng.permuted(ids, axis=1)
    if case == "all_pad_rows":
        ids[[0, 3]] = -1
    if case == "other_shards":    # ids of the shards below and above
        ids[:, ::3] = rng.integers(0, 3000, size=ids[:, ::3].shape)
    phi, psi = _rand((b, 32), 42), _rand((n_rows, 32), 43)
    scale = None
    if case == "bf16":
        psi = psi.astype(jnp.bfloat16)
    if case == "int8":
        from repro.core.quant import int8_quantize_rows

        psi, scale = int8_quantize_rows(psi)
    return phi, psi, scale, jnp.asarray(ids, jnp.int32), off, n_valid, block_b


@pytest.mark.parametrize("case", [
    "one_tile", "straddle", "unsorted_duplicated", "all_pad_rows",
    "other_shards", "n_valid_short", "l_not_lane_multiple",
    "several_row_blocks", "bf16", "int8"])
def test_exclude_id_walk_matches_mask_and_ref(case):
    """The exclude-id form, which walks only each tile's ids, returns what
    the dense-mask form and the dense reference return, bit for bit."""
    phi, psi, scale, ids, off, n_valid, block_b = _walk_case(case)
    k, n_rows = 40, psi.shape[0]
    kw = dict(psi_scale=scale, id_offset=off, n_valid=n_valid,
              block_b=block_b, block_items=128)
    s, i = topk_score(phi, psi, k, exclude_ids=ids, **kw)
    local = np.asarray(ids) - off
    in_shard = (np.asarray(ids) >= 0) & (local >= 0) & (local < n_rows)
    mask = np.zeros((phi.shape[0], n_rows), np.int8)
    mask[np.nonzero(in_shard)[0], local[in_shard]] = 1
    ms, mi = topk_score(phi, psi, k, jnp.asarray(mask), **kw)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(mi))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(ms))
    deq = psi.astype(jnp.float32)
    if scale is not None:
        deq = deq * scale[:, None]
    rs, ri = topk_score_ref(phi, deq[:n_valid], k,
                            exclude_ids=np.where(in_shard, local, -1))
    ri = np.asarray(ri)
    np.testing.assert_array_equal(np.asarray(i), np.where(ri >= 0, ri + off,
                                                          -1))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(rs))
