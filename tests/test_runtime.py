"""The persistent compilation cache every entry point turns on: JAX's own
``JAX_COMPILATION_CACHE_DIR`` when set, else one fixed path in the repo."""
import jax
import pytest

from repro import runtime


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_defaults_to_the_repo(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.enable_compile_cache()
    assert path == str(runtime.REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert runtime.REPO_CACHE_DIR.parent.joinpath("pyproject.toml").exists()


def test_cache_env_var_is_left_to_jax(monkeypatch, tmp_path,
                                      cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
