"""The persistent compilation cache: one helper, one fixed directory."""
import jax
import pytest

from repro.launch import cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_defaults_to_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = cache.enable_compilation_cache()
    assert path == str(cache.CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == path
    assert cache.CHECKOUT_CACHE.parent.joinpath("pyproject.toml").exists()
    assert cache.enable_compilation_cache() == path    # stable across calls


def test_cache_env_dir_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert cache.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
