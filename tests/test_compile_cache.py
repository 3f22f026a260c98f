"""Where the persistent compilation cache goes (runtime/compile_cache.py)."""

from pathlib import Path

import pytest

import jax
from jax.experimental.compilation_cache import compilation_cache

from repro.runtime import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_cache_dir_follows_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_cache_dir_defaults_to_repo(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache() == expected
    assert jax.config.jax_compilation_cache_dir == expected
