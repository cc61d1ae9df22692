"""bench/calibrate_train.py on the CPU at a small size, with the look for a
chip replaced: one line per seed, the sound readings under the cell's
limits, and the control and each fault over at least one of them."""
import json

import jax
import pytest

from bench import calibrate_train, run
from bench.drivers import train_step
from bench.tests.stub import TRAIN_CONFIG, TRAIN_MIX


def test_readings_per_seed(monkeypatch, capsys):
    lim = json.loads((run.BENCH / "limits" / "mf_yt.train.json").read_text())
    limits = {k: v["limit"] for k, v in lim["limits"].items()}
    cell = {"cell": {"name": "mf_yt.train", "chips": 1},
            "config": TRAIN_CONFIG, "traffic": TRAIN_MIX, "limits": limits}
    monkeypatch.setattr(run, "load_cell", lambda w: cell)
    monkeypatch.setattr(run, "guard_device", lambda chips: jax.devices()[:1])
    monkeypatch.setattr(run, "use_compile_cache", lambda: "off")
    faults = [a for f in train_step.FAULTS for a in ("--fault", f)]
    assert calibrate_train.main(["--workload", "mf_yt.train", "--seeds",
                                 "5", str(2**31 + 9), "--control", "1",
                                 *faults]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["seed"] for x in lines] == [5, 2**31 + 9]
    for x in lines:
        assert all(x["sound"][k] <= v for k, v in limits.items()), x
        for bad in ("control",) + train_step.FAULTS:
            assert any(x[bad][k] > v for k, v in limits.items()), (bad, x)


def test_refuses_a_serving_cell(monkeypatch):
    monkeypatch.setattr(run, "guard_device", lambda chips: jax.devices()[:1])
    with pytest.raises(SystemExit):
        calibrate_train.main(["--workload", "mf_yt.serve", "--seeds", "1"])
