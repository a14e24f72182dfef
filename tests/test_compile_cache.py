"""Where the persistent compilation cache goes."""

from __future__ import annotations

import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    """Leave JAX's cache directory as the test found it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_names_the_cache_dir(monkeypatch, cache_config, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    path = compile_cache.enable_compile_cache(tmp_path / "checkout")
    assert path == str(tmp_path / "cc")
    # JAX reads the variable itself; the code sets no other directory.
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_the_checkout(monkeypatch, cache_config,
                                            tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache(tmp_path)
    assert path == os.path.join(str(tmp_path), ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # A fixed path: a second call (another run) lands in the same place.
    assert compile_cache.enable_compile_cache(tmp_path) == path
