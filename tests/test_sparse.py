"""Sparse substrate: CSR, segment ops, EmbeddingBag, neighbor sampler."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need hypothesis; CI installs it
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.sparse import (
    build_adjacency,
    coo_to_csr,
    csr_row_ids,
    embedding_bag,
    multi_hot_lookup,
    neighbor_sampler,
)
from repro.sparse.csr import transpose_csr_host
from repro.sparse.sampler import sample_neighbors
from repro.sparse.segment import SORTED_TILE, run_offsets, segment_broadcast_sorted


def test_csr_roundtrip_and_row_ids():
    rng = np.random.default_rng(0)
    n_rows, n_cols, nnz = 7, 5, 12
    cells = rng.choice(n_rows * n_cols, nnz, replace=False)
    row, col = cells // n_cols, cells % n_cols
    data = rng.normal(size=nnz)
    csr = coo_to_csr(row, col, data, n_rows, n_cols)
    assert csr.nnz == nnz
    rid = np.asarray(csr_row_ids(csr))
    dense = np.zeros((n_rows, n_cols))
    dense[rid, np.asarray(csr.indices)] = np.asarray(csr.data)
    expect = np.zeros((n_rows, n_cols))
    expect[row, col] = data
    np.testing.assert_allclose(dense, expect)
    # transpose twice = identity (as dense)
    t2 = transpose_csr_host(transpose_csr_host(csr))
    dense2 = np.zeros((n_rows, n_cols))
    dense2[np.asarray(csr_row_ids(t2)), np.asarray(t2.indices)] = np.asarray(t2.data)
    np.testing.assert_allclose(dense2, expect)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), n_rows=st.integers(1, 10), vocab=st.integers(1, 12),
       dim=st.integers(1, 6), nnz=st.integers(1, 40))
def test_embedding_bag_matches_loop(seed, n_rows, vocab, dim, nnz):
    rng = np.random.default_rng(seed)
    table = rng.normal(size=(vocab, dim)).astype(np.float32)
    ids = rng.integers(0, vocab, nnz)
    rows = rng.integers(0, n_rows, nnz)
    weights = rng.normal(size=nnz).astype(np.float32)
    got = embedding_bag(jnp.asarray(table), jnp.asarray(ids), jnp.asarray(rows),
                        n_rows, jnp.asarray(weights))
    expect = np.zeros((n_rows, dim), np.float32)
    for i, r, w in zip(ids, rows, weights):
        expect[r] += w * table[i]
    np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-5)


def test_multi_hot_lookup_mean():
    table = jnp.asarray(np.arange(12, dtype=np.float32).reshape(6, 2))
    ids = jnp.asarray([[0, 1, 2], [3, 3, 0]])
    mask = jnp.asarray([[1, 1, 0], [1, 0, 0]], jnp.float32)
    got = multi_hot_lookup(table, ids, mask, combiner="mean")
    expect = np.stack([(np.arange(2) * 0 + table[0] + table[1]) / 2, table[3]])
    np.testing.assert_allclose(got, np.asarray(expect))


def test_neighbor_sampler_validity():
    rng = np.random.default_rng(1)
    n_nodes, n_edges = 50, 400
    src = rng.integers(0, n_nodes, n_edges)
    dst = rng.integers(0, n_nodes, n_edges)
    adj = build_adjacency(src, dst, n_nodes)
    seeds = jnp.asarray(rng.integers(0, n_nodes, 16), jnp.int32)
    frontiers = neighbor_sampler(jax.random.PRNGKey(0), adj, seeds, [5, 3])
    assert frontiers[0].shape == (16,)
    assert frontiers[1].shape == (16 * 5,)
    assert frontiers[2].shape == (16 * 5 * 3,)
    # validity: every sampled neighbor must be a true neighbor (or self-loop
    # fallback for isolated nodes)
    indptr, indices = np.asarray(adj.indptr), np.asarray(adj.indices)
    neigh_sets = [set(indices[indptr[v]:indptr[v + 1]]) for v in range(n_nodes)]
    parents = np.asarray(frontiers[0])
    children = np.asarray(frontiers[1]).reshape(16, 5)
    for p, kids in zip(parents, children):
        for kid in kids:
            assert kid in neigh_sets[p] or (len(neigh_sets[p]) == 0 and kid == p)


def test_sampler_isolated_nodes_self_loop():
    adj = coo_to_csr(np.array([0]), np.array([1]), None, 4, 4)  # node 2,3 isolated
    seeds = jnp.asarray([2, 3, 0], jnp.int32)
    neigh = sample_neighbors(jax.random.PRNGKey(0), adj, seeds, 4)
    assert np.all(np.asarray(neigh[0]) == 2)
    assert np.all(np.asarray(neigh[1]) == 3)
    assert np.all(np.asarray(neigh[2]) == 1)


# --- sorted-run broadcast -----------------------------------------------------
SORTED_CASES = ("empty_rows", "long_row", "singletons", "ragged_tail",
                "nnz0", "nnz1", "zipf")


def _sorted_layout(case, rng, tile=SORTED_TILE):
    """(sorted ids, n_rows) of one layout, sized against the tile."""
    if case == "empty_rows":        # empty rows first, in the middle, last
        counts = [0, 0, 3, 0, tile + 1, 0, 0, 2, 1, 0, 0]
    elif case == "long_row":        # one run across several tiles
        counts = [2, 3 * tile + 5, 1, 0, 4]
    elif case == "singletons":      # every pair its own row
        counts = [1] * (2 * tile + 3)
    elif case == "ragged_tail":     # nnz not a multiple of the tile
        counts = list(rng.integers(0, 4, size=tile))
        counts[-1] += 1 + (sum(counts) % tile == tile - 1)
    elif case == "nnz0":
        counts = [0, 0, 0]
    elif case == "nnz1":
        counts = [0, 1, 0]
    else:                           # Zipf(1.0) row popularity, as items are
        n_rows = 50
        p = 1.0 / np.arange(1, n_rows + 1)
        ids = rng.choice(n_rows, size=4 * tile + 7, p=p / p.sum())
        return np.sort(ids).astype(np.int32), n_rows
    return np.repeat(np.arange(len(counts)), counts).astype(np.int32), len(counts)


@pytest.mark.parametrize("case", SORTED_CASES)
def test_segment_broadcast_sorted_is_take(case):
    """Each pair gets its row's value: ``jnp.take`` bit for bit, signed
    zeros and NaNs included."""
    rng = np.random.default_rng(len(case))
    ids, n_rows = _sorted_layout(case, rng)
    vals = rng.normal(size=n_rows).astype(np.float32)
    vals[::5] = -0.0
    vals[3::7] = np.nan
    got = np.asarray(segment_broadcast_sorted(
        jnp.asarray(vals), jnp.asarray(ids),
        jnp.asarray(run_offsets(ids, n_rows))))
    want = np.asarray(jnp.take(jnp.asarray(vals), jnp.asarray(ids)))
    assert got.shape == want.shape == (ids.size,)
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))
