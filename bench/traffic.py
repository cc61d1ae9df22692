"""The one traffic generator: every input of a run, made from ``--seed``.

A traffic mix is a data file (``bench/traffic/<name>.json``) of parameters
that these functions read; a new mix is a new file, not new code. Streams
that must not move together draw from ``rng(seed, stream)``, so adding a
stream never changes another.

What is the same for every seed and what the seed changes:

* arrivals: every seed gets the same number of requests and the same
  sequence of exponential gaps (their quantiles, in one fixed shuffled
  order), started at a point the seed picks — the same clumps of
  arrivals, at other times of the window, so the work of a run is fixed
  and only its order moves;
* interactions: ``powerlaw_interactions`` is a copy of the program's
  ``data.synthetic.make_powerlaw_interactions`` (exact nnz distinct pairs,
  Zipf items, Pareto(1.2) user degrees), kept here so that the benchmark's
  inputs cannot change with the program.
"""
from __future__ import annotations

import numpy as np

# Shapes of the deployment-scale interaction generator.
ITEM_ZIPF = 1.0
USER_PARETO = 1.2
MAX_DEGREE_SHARE = 0.05

_STREAMS = {"interactions": 1, "factors": 2, "arrivals": 3, "users": 4,
            "history": 5, "sample": 6, "noise": 7}


def rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), _STREAMS[stream]])


def jax_seed(seed: int, stream: str) -> int:
    """A 31-bit seed for ``jax.random.key`` derived from a seed of any size."""
    return int(rng(seed, stream).integers(0, 2**31 - 1))


# ------------------------------------------------------------ interactions
def powerlaw_degrees(r: np.random.Generator, n_users: int, nnz: int,
                     max_degree: int) -> np.ndarray:
    """Per-user degrees from a Pareto(USER_PARETO) tail, scaled so they sum
    to exactly ``nnz``, each in [1, ``max_degree``]."""
    if not n_users <= nnz <= n_users * max_degree:
        raise ValueError(f"cannot place {nnz} interactions on {n_users} "
                         f"users with degrees in [1, {max_degree}]")
    raw = (1.0 - r.random(n_users)) ** (-1.0 / USER_PARETO)

    def degrees(c):
        return np.clip(np.floor(c * raw), 1, max_degree).astype(np.int64)

    lo, hi = 0.0, nnz / raw.min()
    for _ in range(100):                    # bisect the scale onto nnz
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if degrees(mid).sum() <= nnz else (lo, mid)
    deg = degrees(lo)
    short = nnz - int(deg.sum())            # 0 <= short < n_users
    room = np.flatnonzero(deg < max_degree)
    deg[r.choice(room, size=short, replace=False)] += 1
    return deg


def powerlaw_interactions(n_users: int, n_items: int, nnz: int,
                          seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``nnz`` distinct (user, item) pairs, sorted by (user, item): user
    degrees from :func:`powerlaw_degrees`, item popularity Zipf over a
    random id order, each user's items by stratified inverse-CDF sampling,
    repeats refilled uniformly until every user holds its degree."""
    r = rng(seed, "interactions")
    max_degree = max(1, int(MAX_DEGREE_SHARE * n_items))
    deg = powerlaw_degrees(r, n_users, nnz, max_degree)
    cdf = np.cumsum(1.0 / np.arange(1, n_items + 1) ** ITEM_ZIPF)
    cdf /= cdf[-1]
    rank_to_item = r.permutation(n_items)

    users = np.repeat(np.arange(n_users, dtype=np.int64), deg)
    starts = np.repeat(np.cumsum(deg) - deg, deg)
    slot = np.arange(nnz) - starts
    phase = np.repeat(r.random(n_users), deg)
    x = (phase + (slot + r.random(nnz)) / np.repeat(deg, deg)) % 1.0
    ranks = np.minimum(np.searchsorted(cdf, x, side="right"), n_items - 1)
    keys = np.unique(users * n_items + rank_to_item[ranks])
    del users, starts, slot, phase, x, ranks
    while len(keys) < nnz:
        have = np.bincount(keys // n_items, minlength=n_users)
        fill = np.repeat(np.arange(n_users, dtype=np.int64), deg - have)
        fill = fill * n_items + r.integers(0, n_items, len(fill))
        keys = np.unique(np.concatenate([keys, fill]))
    return (keys // n_items).astype(np.int32), (keys % n_items).astype(np.int32)


# ---------------------------------------------------------------- arrivals
def gap_order(n: int, seed: int) -> np.ndarray:
    """``n`` unit-mean exponential gaps: the distribution's quantiles in one
    shuffled order, the same for every seed, rotated to start where the
    seed picks. A shuffle per seed would give each seed its own clumps of
    arrivals, and the tail of the latency would follow the seed."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    rng(0, "arrivals").shuffle(gaps)
    return np.roll(gaps, -int(rng(seed, "arrivals").integers(n)))


def arrivals(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of an open loop.

    ``mix["rate"]`` is the mean offered rate (requests/s). A ``"phases"``
    list of ``[duration_s, rate_multiple]`` repeats through the window
    (bursts); without it the rate is steady. Operational time (expected
    arrivals so far) runs at the phase's rate; the count is its value at
    ``seconds`` for every seed, and the gaps in operational time are
    :func:`gap_order`'s."""
    phases = mix.get("phases") or [[1.0, 1.0]]
    edges_t = np.concatenate([[0.0], np.cumsum([d for d, _ in phases])])
    edges_op = mix["rate"] * np.concatenate(
        [[0.0], np.cumsum([d * m for d, m in phases])])
    period, per_op = edges_t[-1], edges_op[-1]

    def op_at(t):
        cycles, rem = np.divmod(t, period)
        return cycles * per_op + np.interp(rem, edges_t, edges_op)

    n = int(round(op_at(seconds)))
    gaps = gap_order(n, seed)
    op = np.cumsum(gaps) * ((op_at(seconds) - 0.5) / gaps.sum())
    cycles, rem = np.divmod(op, per_op)
    return cycles * period + np.interp(rem, edges_op, edges_t)


# --------------------------------------------------------------- histories
def zipf_lists(n_rows: int, n_items: int, length: int, exponent: float,
               r: np.random.Generator, rank_to_item: np.ndarray) -> np.ndarray:
    """(n_rows, length) int32: per row ``length`` DISTINCT item ids drawn
    from a Zipf(``exponent``) popularity over ``rank_to_item`` order."""
    cdf = np.cumsum(1.0 / np.arange(1, n_items + 1) ** exponent)
    cdf /= cdf[-1]
    out = np.empty((n_rows, length), np.int64)
    todo = np.arange(n_rows)
    draws = 2 * length
    while len(todo):
        ranks = np.searchsorted(cdf, r.random((len(todo), draws)),
                                side="right").clip(max=n_items - 1)
        srt = np.sort(ranks, axis=1)
        dup = np.zeros_like(srt, bool)
        dup[:, 1:] = srt[:, 1:] == srt[:, :-1]
        keys = np.where(dup, np.inf, r.random(srt.shape))
        pick = np.take_along_axis(srt, np.argsort(keys, axis=1), axis=1)
        ok = (~dup).sum(axis=1) >= length
        out[todo[ok]] = pick[ok, :length]
        todo = todo[~ok]
        draws *= 2
    return rank_to_item[out].astype(np.int32)


def requests(mix: dict, n_items: int, n_requests: int, seed: int) -> dict:
    """Who sends each request and what it excludes.

    ``mix["n_users"] == 0``: every request is a user of its own. Otherwise
    requests come from ``n_users`` users chosen by Zipf(``user_zipf``) over
    a random order, and a user's requests share one history. Returns
    ``users`` (n_requests,) — the history row of each request — and
    ``history`` (n_histories, L) distinct item ids."""
    r_users, r_hist = rng(seed, "users"), rng(seed, "history")
    if mix.get("n_users", 0):
        n_users = mix["n_users"]
        p = 1.0 / np.arange(1, n_users + 1) ** mix["user_zipf"]
        p /= p.sum()
        rank = r_users.choice(n_users, size=n_requests, p=p)
        users = r_users.permutation(n_users)[rank]
        uniq, users = np.unique(users, return_inverse=True)
        n_hist = len(uniq)
    else:
        users = np.arange(n_requests)
        n_hist = n_requests
    rank_to_item = r_hist.permutation(n_items)
    history = zipf_lists(n_hist, n_items, mix["history_len"],
                         mix["history_zipf"], r_hist, rank_to_item)
    return {"users": users.astype(np.int64), "history": history}
