"""Where the scripts' persistent compilation cache goes."""

import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    """Restore jax_compilation_cache_dir after the test (nothing compiles
    in between, so no cache is ever opened)."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_nothing_is_set(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_uses_one_fixed_dir_in_the_checkout(monkeypatch,
                                                  cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.use_compile_cache()
    assert got == compile_cache.use_compile_cache()       # stable
    assert jax.config.jax_compilation_cache_dir == got
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
