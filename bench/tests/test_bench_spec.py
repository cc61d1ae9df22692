"""BENCHMARK.json against the files the harness finds by name, and the
harness's refusals: no chip, and a directory that holds only the benchmark."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                   r"projection|head|expansion|per_tok|^dim$|^k$")


def test_names_and_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for part in ("configs", "workloads", "end_to_end",
                                    "per_layer") for x in SPEC[part]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert 1 <= SPEC["run_seconds"] <= 51


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        cfg = configs[w["config"]]
        data = json.loads((ROOT / cfg["file"]).read_text())
        assert data["name"] == cfg["name"]
        assert data["reduced"] == cfg["reduced"]
        assert not any(WIDTH.search(r) for r in cfg["reduced"])
        mix = json.loads(
            (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / "drivers" / f"{mix['driver']}.py").exists()
        limits = json.loads(
            (ROOT / "bench" / "limits" / f"{w['name']}.json").read_text())
        for name, lim in limits["limits"].items():
            if lim.get("exact"):        # an exact count: limit 0
                assert lim["lower"] == lim["limit"] == 0 < lim["upper"]
            else:
                assert lim["lower"] < lim["limit"] < lim["upper"], name
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)


def test_every_metric_is_read_where_it_is_listed():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in cells
            moved = next(x for x in SPEC["end_to_end"]
                         if x["name"] == m["moves"])
            assert w in moved.get("workloads", cells)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_refuses_without_a_chip():
    r = _run(ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "refused" in r.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
