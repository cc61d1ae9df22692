"""Entry-point plumbing: the training CLI and the compile-cache helper."""
import jax
import pytest

from repro.configs import get_smoke_config
from repro.launch import compile_cache
from repro.launch.train import train


def test_train_cli_runs_mf_through_build_model(capsys):
    train(get_smoke_config("icd-mf"), smoke=True, epochs=3, seed=0)
    objs = [float(line.split("objective ")[1].split()[0])
            for line in capsys.readouterr().out.splitlines()
            if "objective" in line]
    assert len(objs) == 3 and objs[0] > objs[1] > objs[2]


def test_train_cli_refuses_fm_without_feature_generator():
    with pytest.raises(SystemExit, match="feature fields"):
        train(get_smoke_config("icd-fm"), smoke=True, epochs=1, seed=0)


def test_compile_cache_env_wins_else_fixed_repo_path(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None  # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        assert path == str(compile_cache.REPO_ROOT / ".jax_cache")
        assert (compile_cache.REPO_ROOT / "chip_smoke.py").exists()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
