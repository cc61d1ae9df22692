"""Examples must stay runnable (subset; full set exercised in CI shell)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(__file__))


def _run(script, timeout=500):
    env = dict(os.environ, PYTHONPATH=f"src:{os.environ.get('PYTHONPATH', '')}")
    return subprocess.run(
        [sys.executable, os.path.join("examples", script)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )


@pytest.mark.slow
def test_quickstart_runs_and_beats_popularity():
    p = _run("quickstart.py")
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
    assert "Recall@10" in p.stdout


@pytest.mark.slow
def test_serve_retrieval_example():
    p = _run("serve_retrieval.py")
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
    assert "mesh top-k == dense top-k" in p.stdout
    assert "batcher:" in p.stdout
    assert "streaming sharded eval" in p.stdout
