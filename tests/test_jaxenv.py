"""repro.jaxenv: the scoped float64 switch and the compile-cache placement."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import jaxenv

SRC = str(Path(__file__).resolve().parents[1] / "src")


def test_x64_is_scoped():
    before = jax.config.jax_enable_x64
    with jaxenv.x64():
        assert jnp.zeros(1).dtype == jnp.float64
    assert jax.config.jax_enable_x64 == before


def test_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(jaxenv.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = jaxenv.enable_compile_cache()
        assert path == str(jaxenv.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == path
        assert jaxenv.CHECKOUT_CACHE.parent == Path(SRC).parent
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_cache_env_dir_is_the_only_one_written(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, a compile lands there and the
    checkout's own cache directory is left alone."""
    cache = tmp_path / "cc"
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.jaxenv import enable_compile_cache
        print(enable_compile_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
    """)
    before = (sorted(jaxenv.CHECKOUT_CACHE.rglob("*"))
              if jaxenv.CHECKOUT_CACHE.exists() else None)
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               **{jaxenv.CACHE_ENV: str(cache)})
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(cache)
    assert any(p.is_file() for p in cache.rglob("*"))
    after = (sorted(jaxenv.CHECKOUT_CACHE.rglob("*"))
             if jaxenv.CHECKOUT_CACHE.exists() else None)
    assert after == before
