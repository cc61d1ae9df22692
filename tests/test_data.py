"""Data pipeline: synthetic generator structure + hosted loaders + design."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# property tests need hypothesis (CI installs it); only they skip without it
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised in bare containers
    HAVE_HYPOTHESIS = False

from repro.core.design import design_matmul, make_design, to_dense
from repro.data import loader
from repro.data.loader import (
    frequency_interactions,
    interaction_stream,
    load_movielens,
    split_by_time,
)
from repro.data.synthetic import make_implicit_dataset


def test_synthetic_dataset_structure():
    ds = make_implicit_dataset(n_users=50, n_items=40, seed=3)
    assert ds.events.shape[1] == 3
    assert ds.events[:, 0].max() < 50 and ds.events[:, 1].max() < 40
    # time-ordered
    assert np.all(np.diff(ds.events[:, 2]) > 0)
    # every user has events within the configured range
    hists = ds.user_histories()
    assert len(hists) == 50
    assert all(len(h) >= 1 for h in hists)
    # attributes in range
    assert ds.age.max() < ds.n_age and ds.country.max() < ds.n_country


def test_attribute_signal_exists():
    """Users sharing attributes must have more similar item distributions
    than random pairs — the mechanism behind the Figure-7 reproduction."""
    ds = make_implicit_dataset(n_users=300, n_items=200, attr_strength=0.95,
                               pop_strength=0.3, taste_strength=2.5, seed=0)
    hists = ds.user_histories()

    def dist(u):
        v = np.bincount(hists[u], minlength=200).astype(float)
        return v / max(v.sum(), 1)

    key = [(a, c) for a, c in zip(ds.age, ds.country)]
    same, diff = [], []
    rng = np.random.default_rng(0)
    for _ in range(3000):
        u, v = rng.integers(0, 300, 2)
        if u == v:
            continue
        sim = float(dist(u) @ dist(v))
        (same if key[u] == key[v] else diff).append(sim)
    if len(same) > 10:
        assert np.mean(same) > np.mean(diff)


def test_interaction_stream_replays_event_log_in_order():
    ds = make_implicit_dataset(n_users=40, n_items=30, seed=7)
    batches = list(interaction_stream(ds, batch_events=64))
    # finite replay: every event appears exactly once, in arrival order
    assert sum(len(b["item"]) for b in batches) == len(ds.events)
    assert all(len(b["item"]) == 64 for b in batches[:-1])
    ctx = np.concatenate([b["ctx"] for b in batches])
    item = np.concatenate([b["item"] for b in batches])
    t = np.concatenate([b["t"] for b in batches])
    np.testing.assert_array_equal(ctx, ds.events[:, 0])
    np.testing.assert_array_equal(item, ds.events[:, 1])
    np.testing.assert_array_equal(t, ds.events[:, 2])
    assert np.all(np.diff(t) > 0)
    # start= resumes mid-log (the warm-start boundary of the continual loop)
    tail = list(interaction_stream(ds, batch_events=64, start=128))
    np.testing.assert_array_equal(
        np.concatenate([b["item"] for b in tail]), ds.events[128:, 1]
    )


@pytest.mark.parametrize("n_hosts,n", [(4, 10), (3, 7), (4, 3), (2, 64), (5, 5)])
def test_host_slice_partial_batches(monkeypatch, n_hosts, n):
    """Regression: the balanced host split must PARTITION every batch size —
    disjoint, in-order, nothing dropped. The old ``n // n_hosts`` truncation
    dropped the tail of final partial batches (n=10, H=4 lost 2 events) and
    emptied hosts when n < H."""
    monkeypatch.setattr(jax, "process_count", lambda: n_hosts)
    parts = []
    for i in range(n_hosts):
        monkeypatch.setattr(jax, "process_index", lambda i=i: i)
        parts.append(loader._host_slice(n))
    covered = np.concatenate([np.arange(n)[s] for s in parts])
    np.testing.assert_array_equal(covered, np.arange(n))
    sizes = [s.stop - s.start for s in parts]
    assert max(sizes) - min(sizes) <= 1


def test_interaction_stream_multihost_covers_final_partial(monkeypatch):
    """The per-host slices of every streamed batch (incl. the final partial
    one) must reassemble to the full event log."""
    ds = make_implicit_dataset(n_users=20, n_items=15, seed=11)
    n_hosts = 4
    monkeypatch.setattr(jax, "process_count", lambda: n_hosts)
    per_host = []
    for i in range(n_hosts):
        monkeypatch.setattr(jax, "process_index", lambda i=i: i)
        per_host.append(list(interaction_stream(ds, batch_events=64)))
    n_batches = len(per_host[0])
    assert all(len(b) == n_batches for b in per_host)
    items = np.concatenate(
        [np.concatenate([per_host[i][b]["item"] for i in range(n_hosts)])
         for b in range(n_batches)]
    )
    np.testing.assert_array_equal(items, ds.events[:, 1])


def test_load_movielens_synthetic_fallback_and_cache(tmp_path):
    cache = str(tmp_path / "cache")
    log = load_movielens(cache_dir=cache, n_users=30, n_items=25, seed=4)
    assert log.n_events > 0
    assert log.user.max() < log.n_users and log.item.max() < log.n_items
    assert (tmp_path / "cache" / "ml-synth.data").exists()
    # second load reads the cache file and is bit-identical
    log2 = load_movielens(cache_dir=cache)
    np.testing.assert_array_equal(log.user, log2.user)
    np.testing.assert_array_equal(log.item, log2.item)
    np.testing.assert_array_equal(log.t, log2.t)


def test_load_movielens_parses_ratings_file(tmp_path):
    # ml-100k u.data layout: 1-indexed ids, rating, timestamp
    f = tmp_path / "u.data"
    f.write_text("1\t5\t3\t100\n2\t5\t4\t50\n1\t9\t1\t75\n")
    log = load_movielens(str(f))
    assert (log.n_users, log.n_items) == (2, 2)  # ids remapped dense
    np.testing.assert_array_equal(log.user, [0, 1, 0])
    np.testing.assert_array_equal(log.item, [0, 0, 1])
    np.testing.assert_array_equal(log.value, [3.0, 4.0, 1.0])
    np.testing.assert_array_equal(log.t, [100, 50, 75])
    with pytest.raises(FileNotFoundError):
        load_movielens(str(tmp_path / "missing.data"))


def test_split_by_time_instant_protocol(tmp_path):
    log = load_movielens(cache_dir=str(tmp_path), n_users=30, n_items=25, seed=5)
    train, test = split_by_time(log, holdout_fraction=0.25)
    assert train.n_events + test.n_events == log.n_events
    assert train.t.max() <= test.t.min()        # strict global time cutoff
    assert test.n_users == log.n_users and test.n_items == log.n_items


def test_frequency_interactions_alignment(tmp_path):
    """Weights must land in data's ctx-major nnz order: training with
    (uniform α, weights=w) must equal building with α_raw directly — checked
    via the rescale identity on each cell."""
    log = load_movielens(cache_dir=str(tmp_path), n_users=25, n_items=20, seed=6)
    data, weights, counts = frequency_interactions(
        log, alpha0=0.5, base_alpha=2.0, beta=1.0, mode="linear"
    )
    assert weights.shape == (data.nnz,) == counts.shape
    # dedupe really collapsed repeats: total value mass is preserved
    assert counts.sum() == pytest.approx(float(log.value.sum()))
    # alignment: cell (ctx, item) carries the weight of ITS OWN count
    key_data = np.asarray(data.ctx).astype(np.int64) * log.n_items + np.asarray(
        data.item
    )
    key_log = log.user * log.n_items + log.item
    count_of = {}
    for k, v in zip(key_log, log.value):
        count_of[k] = count_of.get(k, 0.0) + float(v)
    expect_w = (1.0 + np.array([count_of[k] for k in key_data])) / 2.0
    np.testing.assert_allclose(weights, expect_w, rtol=1e-6)
    # and the uniform base data is Lemma-1 rescaled from α=2, α₀=0.5
    np.testing.assert_allclose(np.asarray(data.alpha), 1.5, rtol=1e-6)


def _design_matmul_case(seed, n):
    rng = np.random.default_rng(seed)
    design = make_design(
        [
            dict(name="a", ids=rng.integers(0, 5, n), vocab=5),
            dict(name="b", ids=rng.integers(0, 3, n), vocab=3,
                 weights=rng.normal(size=n).astype(np.float32)),
        ],
        n,
    )
    w = jnp.asarray(rng.normal(size=(design.p, 4)), jnp.float32)
    np.testing.assert_allclose(
        design_matmul(design, w), to_dense(design) @ w, rtol=2e-4, atol=2e-5
    )


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 500), n=st.integers(1, 12))
    def test_design_matmul_matches_dense(seed, n):
        _design_matmul_case(seed, n)
else:
    @pytest.mark.parametrize("seed,n", [(0, 1), (1, 5), (2, 12)])
    def test_design_matmul_matches_dense(seed, n):
        _design_matmul_case(seed, n)


def test_powerlaw_interactions_distinct_exact_and_seeded():
    """The deployment-scale generator: exactly nnz distinct (user, item)
    pairs, sorted, degrees in [1, cap] with a heavy tail, Zipf-skewed item
    popularity, and the same pairs for the same seed."""
    from repro.data.synthetic import MAX_DEGREE_SHARE, make_powerlaw_interactions

    n_users, n_items, nnz = 3000, 2000, 90_000
    u, i = make_powerlaw_interactions(n_users, n_items, nnz, seed=5)
    keys = u.astype(np.int64) * n_items + i
    assert len(u) == nnz and np.all(np.diff(keys) > 0)   # sorted, distinct
    assert u.min() >= 0 and u.max() < n_users and i.max() < n_items
    deg = np.bincount(u, minlength=n_users)
    assert deg.min() >= 1 and deg.max() <= MAX_DEGREE_SHARE * n_items
    assert deg.max() > 4 * np.median(deg)                # power-law tail
    pop = np.sort(np.bincount(i, minlength=n_items))[::-1]
    assert pop[0] > 20 * np.median(pop)                  # Zipf head
    u2, i2 = make_powerlaw_interactions(n_users, n_items, nnz, seed=5)
    np.testing.assert_array_equal(u, u2)
    np.testing.assert_array_equal(i, i2)
    with pytest.raises(ValueError):
        make_powerlaw_interactions(10, 100, 10 * 6, seed=0)  # > cap of 5
