"""Bring-up guards that run on the CPU: the chip smoke refuses to run
without a TPU, and the persistent compile cache stays in one place."""
import importlib.util
import pathlib

import jax
import pytest

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--four-chips"]],
                         ids=["one-chip", "four-chips"])
def test_chip_smoke_refuses_cpu(argv, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert _chip_smoke().main(argv) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out          # no result line on a CPU
    assert "no TPU" in out.err


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env_var(monkeypatch, tmp_path,
                                       cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: no other directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch,
                                                   cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path   # stable
