"""The persistent compile cache stays at one fixed path."""

import jax
import pytest

from repro.utils.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache


@pytest.fixture
def cache_dir_config():
    """Restore JAX's cache directory after the test: nothing compiles in
    between, so no cache is opened."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == enable_compile_cache() == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == first
    assert DEFAULT_CACHE_DIR.name == ".jax_cache"
    assert (DEFAULT_CACHE_DIR.parent / "src" / "repro").is_dir()
