"""The persistent compile cache lands where the entry points put it."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax

from repro.launch import cache


def test_default_dir_is_fixed_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = cache.use_compile_cache()
        assert got == str(cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        root = cache.DEFAULT_DIR.parent
        assert (root / "src" / "repro" / "launch" / "cache.py").is_file()
        with open(root / ".gitignore") as fh:
            assert ".jax_cache/" in fh.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_cache_lands_in_env_dir(tmp_path):
    """A fresh process with the variable set writes its entries there."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 3)(jnp.arange(8.0)).block_until_ready()
    """)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"),
               JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert any((tmp_path / "jc").iterdir())
