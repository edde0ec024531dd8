"""Where the entry points put JAX's persistent compilation cache."""
import os

import jax

from repro.launch import compile_cache


def test_cache_dir_from_the_environment_is_left_to_jax(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_and_git_ignored_off_cpu(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache("cpu") is None
    assert jax.config.jax_compilation_cache_dir == before
    try:
        path = compile_cache.enable_compile_cache("tpu")
        assert path == str(compile_cache.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = compile_cache.CACHE_DIR.parent
    assert (root / "pyproject.toml").exists()          # the checkout root
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    assert os.path.basename(path) == ".jax_cache"
