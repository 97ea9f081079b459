"""The entry points' compile-cache placement (repro.launch.cache)."""
import jax
import pytest

from repro.launch import cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_used_and_no_other_is_set(monkeypatch, tmp_path,
                                             restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = cache.use_compile_cache()
    assert path == str(cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert cache.DEFAULT_DIR.name == ".jax_cache"
    assert (cache.DEFAULT_DIR.parent / "pyproject.toml").exists()
    assert cache.use_compile_cache() == path     # same dir on every call


def test_outside_a_checkout_needs_the_env_dir(monkeypatch, tmp_path,
                                              restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cache, "CHECKOUT", tmp_path)
    before = jax.config.jax_compilation_cache_dir
    with pytest.raises(RuntimeError, match="JAX_COMPILATION_CACHE_DIR"):
        cache.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before
