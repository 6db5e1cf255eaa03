"""The persistent compilation cache the entry points turn on
(``repro.utils.enable_compile_cache``): placed from outside through
``JAX_COMPILATION_CACHE_DIR`` when that is set, else at one fixed,
gitignored path inside the checkout."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.utils import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_default_path_is_fixed_and_gitignored(monkeypatch,
                                               restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path          # no per-call names
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_env_dir_is_used_and_nothing_else_is_set(monkeypatch, tmp_path,
                                                 restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_env_dir_receives_the_compiled_programs(tmp_path):
    """End to end in a fresh process: JAX reads the variable itself, and
    a compiled program lands in that directory."""
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.utils import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
    """)
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert any(tmp_path.iterdir()), "no cache entry was written"
