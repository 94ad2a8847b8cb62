"""The entry points' persistent compile cache location."""
import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.DEFAULT_DIR.parent.joinpath("chip_smoke.py").exists()
    assert compile_cache.enable_compile_cache() == path      # stable
