"""Where the entry points keep JAX's persistent compilation cache."""
from pathlib import Path

import jax

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_env_var_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_dir_at_the_repo_root(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compile_cache()
        assert Path(first) == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == first
        assert compile_cache.enable_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

