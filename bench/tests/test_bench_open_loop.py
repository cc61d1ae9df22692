"""The open loop's arithmetic under a fake clock: latency runs from the
due time to the answer, and a request never answered or answered with
coverage < 1 counts as failed."""
import contextlib

import numpy as np
import pytest

from bench import traffic
from bench.drivers.serve_open_loop import (
    account,
    open_loop,
    padded_sizes,
    stall_summary,
)
from repro.serve.batcher import MicroBatcher
from repro.serve.cluster import TopKResult

K = 3


class FakeClock:
    """Fake seconds; every reading moves it on by a microsecond, as the
    loop's own work takes time."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-6
        return self.t

    def sleep(self, dt):
        self.t += dt


def fake_service(clock, service_s, coverage=lambda call: 1.0):
    """An executor that takes ``service_s`` of fake time per flush and
    answers row r with ids (r, r, r); ``coverage(call)`` per flush."""
    calls = []

    def run(phi, eids):
        calls.append(phi.shape[0])
        clock.t += service_s
        ids = np.repeat(np.arange(phi.shape[0])[:, None], K, 1)
        return TopKResult(np.zeros((phi.shape[0], K), np.float32),
                          ids.astype(np.int32), coverage(len(calls)), ())

    return run, calls


def drive(due, service_s, *, coverage=lambda call: 1.0, drain_s=5.0,
          seconds=1.0, max_batch=4, max_delay=0.005):
    clock = FakeClock()
    run, calls = fake_service(clock, service_s, coverage)
    b = MicroBatcher(run, max_batch=max_batch, max_delay=max_delay,
                     pad_to=1, clock=clock)
    n = len(due)
    phi = np.zeros((n, 2), np.float32)
    excl = np.full((n, 1), -1, np.int32)
    t0 = clock()
    got = open_loop(b, phi, excl, np.asarray(due, float), K, t0, seconds,
                    drain_s, max_delay, lambda name: contextlib.nullcontext(),
                    clock=clock, sleep=clock.sleep)
    return got, t0, clock, calls


def test_latency_runs_from_the_due_time():
    # one request every 100 ms, each served alone by its 5 ms deadline
    due = [0.0, 0.1, 0.2, 0.3]
    got, t0, clock, calls = drive(due, service_s=0.002)
    acc = account(np.asarray(due), t0, clock(), got)
    lat = got["done"] - (t0 + np.asarray(due))
    # deadline 5 ms (+ the loop's sleep granularity) + 2 ms service
    assert np.all(lat >= 0.007 - 1e-9) and np.all(lat <= 0.0085)
    assert acc["failed"] == 0 and acc["answered"].all()
    assert acc["p50_ms"] == pytest.approx(np.median(lat) * 1e3)
    assert calls == [1, 1, 1, 1]


def test_a_stall_delays_later_requests_from_their_due_time():
    # a flush of 300 ms: the three requests due during it wait for it,
    # and their latency counts from when they were due, not when sent
    due = [0.0, 0.05, 0.10, 0.15]
    got, t0, clock, _ = drive(due, service_s=0.3, max_batch=1)
    lat = got["done"] - (t0 + np.asarray(due))
    assert lat[0] == pytest.approx(0.3, abs=1e-4)
    assert lat[1] == pytest.approx(0.6 - 0.05, abs=1e-4)
    assert np.all(np.diff(got["done"]) > 0)
    late = got["sent"] - (t0 + np.asarray(due))
    assert late[1] == pytest.approx(0.25, abs=1e-4)
    assert late[0] == pytest.approx(0.0, abs=1e-4)
    # each flush shows in the loop's list of slow calls, where it was made
    stalls = stall_summary(got["slow"])
    assert sum(n for n, _ in stalls["by_kind"].values()) == 4
    d, what, at = stalls["longest"][0]
    assert d == pytest.approx(0.3, abs=1e-4) and what in ("submit", "step")


def test_degraded_answers_count_as_failed():
    due = [0.0, 0.1, 0.2]
    got, t0, clock, _ = drive(
        due, 0.001, coverage=lambda call: 0.5 if call == 2 else 1.0)
    acc = account(np.asarray(due), t0, clock(), got)
    assert acc["degraded"] == 1 and acc["failed"] == 1


def test_unanswered_requests_count_as_failed_until_the_end():
    # the first flush (two requests) outlasts the drain: the third request
    # is sent after the loop's end and never answered
    due = [0.0, 0.001, 0.002]
    got, t0, clock, _ = drive(due, service_s=2.0, max_batch=2,
                              drain_s=0.5, seconds=0.01)
    acc = account(np.asarray(due), t0, clock(), got)
    unanswered = np.isnan(got["done"])
    assert unanswered.tolist() == [False, False, True]
    assert acc["failed"] == 1
    # their latency runs to the end of the loop
    assert acc["p99_ms"] == pytest.approx(
        (clock() - t0 - due[-1]) * 1e3, rel=0.02)


def test_arrivals_keep_their_count_and_gaps_for_every_seed():
    mix = {"rate": 500.0}
    a, b = traffic.arrivals(mix, 4.0, 1), traffic.arrivals(mix, 4.0, 2**40)
    assert len(a) == len(b) == 2000
    assert np.all(np.diff(a) > 0) and a[-1] < 4.0
    np.testing.assert_allclose(np.sort(np.diff(a)), np.sort(np.diff(b)),
                               rtol=0.2, atol=2e-4)
    burst = traffic.arrivals({"rate": 500.0,
                              "phases": [[0.8, 0.5], [0.2, 3.0]]}, 4.0, 3)
    in_burst = (burst % 1.0) >= 0.8
    assert len(burst) == 2000
    assert in_burst.sum() == pytest.approx(0.6 * 2000, rel=0.05)


@pytest.mark.parametrize("max_batch, pad_to, sizes", [
    (64, 8, [8, 16, 24, 32, 40, 48, 56, 64]),
    (60, 8, [8, 16, 24, 32, 40, 48, 56, 60]),
    (3, 1, [1, 2, 3]),
])
def test_warmup_flushes_one_batch_per_padded_size(max_batch, pad_to, sizes):
    assert padded_sizes(max_batch, pad_to) == sizes
    # every row count 1 .. max_batch pads to the size of one warmed batch
    pad = lambda b: -(-b // pad_to) * pad_to
    assert {pad(b) for b in range(1, max_batch + 1)} == {pad(b)
                                                         for b in sizes}


def test_every_seed_meets_the_same_clumps_of_arrivals():
    # one gap sequence for every seed, started at a point the seed picks
    n = 1000
    a, b = traffic.gap_order(n, 1), traffic.gap_order(n, 2**40 + 1)
    assert not np.array_equal(a, b)
    start = int(np.flatnonzero(a == b[0])[0])
    np.testing.assert_array_equal(np.roll(a, -start), b)
