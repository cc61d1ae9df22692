"""The replica-loss driver at a small size on the CPU: a sound run is
correct with nothing degraded and the replication restored; each planted
fault comes out not correct; the two mesh readers give hand-computed
values on a synthetic record, and nothing where there is nothing to
read."""
import importlib.util
import json
from pathlib import Path

import pytest

from bench.drivers import serve_failover
from bench.tests.stub import SERVE_CONFIG, SERVE_MIX, StubHarness
from repro.obs.trace import Span

BENCH = Path(__file__).resolve().parents[1]
CONFIG = {**SERVE_CONFIG, "shards": 2, "replicas": 2, "auto_heal": True}
MIX = {**SERVE_MIX, "driver": "serve_failover", "n_users": 0,
       "kill_at_s": 20}


def limits():
    data = json.loads(
        (BENCH / "limits" / "retr15m_ha.serve_kill.json").read_text())
    return {k: v["limit"] for k, v in data["limits"].items()}


def serve(**kw):
    h = StubHarness(CONFIG, MIX, limits(), seconds=1.0, **kw)
    return serve_failover.run(h)


def test_failover_sound_run_is_correct():
    res = serve(seed=2**33 + 9, trace=True)
    kill = res["info"]["kill"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and kill["victim_dead"]
    assert kill["replicas_live"] == 4 and len(kill["healed"]) == 1
    assert res["checks"]["degraded"][0] == 0
    assert res["checks"]["replicas_missing"][0] == 0
    assert res["info"]["checked"] == res["attempted"]   # check_sample 0
    assert reader("serve.recover_ms")(res["record"]) > 0


@pytest.mark.parametrize("fault", serve_failover.FAULTS)
def test_failover_fault_is_not_correct(fault):
    res = serve(seed=31, fault=fault)
    assert not res["correct"], (fault, res["checks"])


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(name, t0, t1, **attrs):
    sp = Span(0, None, name, t0, attrs)
    sp.t1 = t1
    return sp


def record():
    spans = [span("dispatch", 0.1 * i, 0.1 * i + 0.01, device=d,
                  outcome="ok") for i, d in enumerate([0, 2, 1, 3, 0, 2])]
    spans += [span("dispatch", 1.0, 1.001, device=1,
                   outcome="ReplicaFailure"),
              span("heal", 1.0005, 1.2505, shard=0, replica=2),
              span("dispatch", 1.01, 1.02, device=0, outcome="ok"),
              span("dispatch", 1.01, 1.02, device=3, outcome="ok")]
    return {"program_spans": spans, "slab_devices": [0, 1, 2, 3]}


def test_recover_ms_reads_kill_to_heal_end():
    assert reader("serve.recover_ms")(record()) == pytest.approx(250.5)
    rec = record()
    rec["program_spans"] = [sp for sp in rec["program_spans"]
                            if sp.name != "heal"]
    assert reader("serve.recover_ms")(rec) is None
    rec["program_spans"] = [sp for sp in record()["program_spans"]
                            if sp.attrs.get("outcome", "ok") == "ok"]
    assert reader("serve.recover_ms")(rec) is None


def test_device_skew_reads_busiest_chip_over_mean():
    # ok dispatches by chip: 0 -> 3, 1 -> 1, 2 -> 2, 3 -> 2; mean 2
    assert reader("serve.device_skew")(record()) == pytest.approx(150.0)
    assert reader("serve.device_skew")({"program_spans": []}) is None
    even = {"program_spans": [span("dispatch", 0, 1, device=d, outcome="ok")
                              for d in (0, 1, 2, 3)],
            "slab_devices": [0, 1, 2, 3]}
    assert reader("serve.device_skew")(even) == pytest.approx(100.0)


def test_calibrate_serve_sweeps_then_runs(monkeypatch, capsys):
    import jax

    from bench import calibrate_serve, run

    cell = {"cell": {"name": "retr15m_ha.serve_kill", "chips": 1,
                     "traffic": "poisson_r15m_kill"},
            "config": CONFIG, "traffic": {**MIX, "rate": 50},
            "limits": limits()}
    monkeypatch.setattr(run, "load_cell", lambda w: cell)
    monkeypatch.setattr(run, "guard_device", lambda chips: jax.devices()[:1])
    monkeypatch.setattr(run, "use_compile_cache", lambda: "off")
    # the CPU's pace is no test: a step is sustained when all is answered
    monkeypatch.setattr(calibrate_serve, "GROWTH", float("inf"))
    assert calibrate_serve.main([
        "--workload", "retr15m_ha.serve_kill", "--sweep", "100", "100", "200",
        "--sweep-seconds", "0.5", "--seeds", str(2**31 + 3), "--seconds",
        "1", "--control-seeds", "4", "--fault-seeds", "5",
        "--short-seconds", "1"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    kinds = [x["kind"] for x in lines]
    assert kinds[:3] == ["sweep", "sweep", "knee"]
    knee = lines[2]
    assert knee == {"kind": "knee", "knee": 200, "rate": 160}
    runs = lines[kinds.index("knee") + 1:]
    assert [x["kind"] for x in runs] == ["sound", "control",
                                         *serve_failover.FAULTS]
    assert all(x["rate"] == knee["rate"] for x in runs)
    assert runs[0]["correct"] and runs[0]["info"]["kill"]["replicas_live"] == 4
    assert not any(x["correct"] for x in runs[1:]), [
        (x["kind"], x["checks"], x["info"]["kill"]) for x in runs]
