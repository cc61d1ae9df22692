"""The comparison that decides ``correct``, shown to fail: each driver runs
at a small size on the CPU (the look for a chip skipped), once sound, once
with the plain reference in the precision below the configuration's in the
program's place (the control), and once with each fault the cell can have
planted in the timed path. Sound comes out correct; the control and every
fault come out not correct, under the cell's own limits."""
import json
from pathlib import Path

import pytest

from bench.drivers import serve_open_loop, train_step
from bench.tests.stub import (
    SERVE_CONFIG,
    SERVE_MIX,
    TRAIN_CONFIG,
    TRAIN_MIX,
    StubHarness,
)

LIMITS = Path(__file__).resolve().parents[1] / "limits"
SERVE_CELLS = ("retr15m.serve", "mf_yt.serve")


def limits(cell):
    data = json.loads((LIMITS / f"{cell}.json").read_text())
    return {k: v["limit"] for k, v in data["limits"].items()}


def train(**kw):
    h = StubHarness(TRAIN_CONFIG, TRAIN_MIX, limits("mf_yt.train"), **kw)
    return train_step.run(h)


def serve(cell, **kw):
    h = StubHarness(SERVE_CONFIG, SERVE_MIX, limits(cell), **kw)
    return serve_open_loop.run(h)


def test_train_sound_run_is_correct():
    res = train(seed=2**31 + 7)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_train_control_is_not_correct():
    res = train(seed=11, control=True)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", train_step.FAULTS)
def test_train_fault_is_not_correct(fault):
    res = train(seed=12, fault=fault)
    assert not res["correct"], (fault, res["checks"])


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_serve_sound_run_is_correct(cell):
    res = serve(cell, seed=2**33 + 5)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == res["info"]["checked"]


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_serve_control_is_not_correct(cell):
    res = serve(cell, seed=21, control=True)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", serve_open_loop.FAULTS)
@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_serve_fault_is_not_correct(cell, fault):
    res = serve(cell, seed=22, fault=fault)
    assert not res["correct"], (fault, res["checks"])
