"""bench/run.py end to end on the CPU at a small size, with the look for a
chip replaced: the result line's keys, the metrics each mode reports, and
the compared numbers last on standard error and in the line."""
import json

import jax
import pytest

from bench import counts, run
from bench.tests.stub import SERVE_CONFIG, SERVE_MIX, TRAIN_CONFIG, TRAIN_MIX

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())


def cell(name, config, mix):
    here = lambda m: name in m.get("workloads", [name])
    lim = json.loads((run.BENCH / "limits" / f"{name}.json").read_text())
    return {"cell": {"name": name, "chips": 1}, "config": config,
            "traffic": mix,
            "limits": {k: v["limit"] for k, v in lim["limits"].items()},
            "end_to_end": [m for m in SPEC["end_to_end"] if here(m)],
            "per_layer": [m for m in SPEC["per_layer"] if here(m)]}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", ["mf_yt.train", "mf_yt.serve"])
def test_result_line(monkeypatch, capsys, tmp_path, name, trace):
    config, mix = ((TRAIN_CONFIG, TRAIN_MIX) if name.endswith("train")
                   else (SERVE_CONFIG, SERVE_MIX))
    monkeypatch.setattr(run, "load_cell", lambda w: cell(name, config, mix))
    monkeypatch.setattr(run, "guard_device", lambda chips: jax.devices()[:1])
    monkeypatch.setattr(run, "OUT_DIR", tmp_path)
    monkeypatch.setattr(run, "use_compile_cache", lambda: "off")
    v5e = counts.peaks("TPU v5 lite")
    monkeypatch.setattr(counts, "peaks", lambda kind: v5e)
    assert run.main(["--workload", name, "--seed", str(2**31 + 3),
                     "--seconds", "0.5", "--trace", str(trace)]) == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "checks" and res["correct"] is True
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    c = cell(name, config, mix)
    if trace:
        names = {m["name"] for m in c["per_layer"]}
        assert set(res["metrics"]) <= names     # device readers read nothing
        if name.endswith("serve"):
            assert {"serve.queue_ms", "serve.flush_ms",
                    "serve.mfu"} <= set(res["metrics"])
    else:
        assert set(res["metrics"]) == {m["name"] for m in c["end_to_end"]}
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") for line in tail)
    assert "compiles_in_window: 0" in out
