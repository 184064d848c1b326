"""Where the persistent compilation cache goes (launch/compile_cache)."""

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_dir_in_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = enable_compile_cache()
        assert got == str(CHECKOUT_CACHE_DIR) == jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == got  # not a temp, pid or time path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    checkout = CHECKOUT_CACHE_DIR.parent
    assert CHECKOUT_CACHE_DIR.name == ".jax_cache"
    assert (checkout / "pyproject.toml").is_file()
    assert ".jax_cache/" in (checkout / ".gitignore").read_text().split()


def test_outside_a_checkout_raises(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "CHECKOUT_CACHE_DIR", tmp_path / ".jax_cache")
    with pytest.raises(RuntimeError, match="not a source checkout"):
        enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before
