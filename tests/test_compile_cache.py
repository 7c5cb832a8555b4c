"""The compile-cache helper: the environment wins, else the checkout."""

from pathlib import Path

import jax

from repro.compile_cache import CACHE_ENV, enable_compile_cache


def test_env_set_leaves_jax_alone(monkeypatch, tmp_path):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_env_unset_uses_checkout_cache(monkeypatch):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        checkout = Path(__file__).resolve().parents[1]
        assert path == str(checkout / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
