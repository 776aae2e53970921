"""`repro.compile_cache`: where the entry points keep compiled programs.

`JAX_COMPILATION_CACHE_DIR`, when set, is JAX's own and nothing else is
configured; otherwise the cache goes to one fixed directory inside the
checkout.  Each test restores JAX's setting, so the suite keeps the cache
off.  Two tests compile a small function and look for the cache entry on
disk: one in a child process started with the variable set, one in this
process with the in-repo directory pointed at a temporary one.
"""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax._src import compilation_cache

from repro import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]

COMPILE_ONE = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print("CACHE", enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(8.0)).block_until_ready()
"""


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_dir_is_used_and_nothing_else_is_set(monkeypatch,
                                                 restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_without_env_the_cache_is_one_fixed_repo_dir(monkeypatch,
                                                     restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path     # same every call


def test_env_dir_receives_the_compiled_entry(tmp_path):
    src = str(REPO / "src")
    pythonpath = src + os.pathsep + os.environ.get("PYTHONPATH", "")
    result = subprocess.run(
        [sys.executable, "-c", COMPILE_ONE],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "PYTHONPATH": pythonpath.rstrip(os.pathsep),
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert f"CACHE {tmp_path}" in result.stdout, result.stderr[-2000:]
    assert any(tmp_path.iterdir()), "no compiled entry in the env cache dir"


_affine_cos = jax.jit(lambda x: jnp.cos(x) * 5.0 - 2.0)


@pytest.fixture
def fresh_cache():
    """Let this test's compiles initialise the persistent cache, and leave
    it uninitialised (so off again) for the tests that follow."""
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    compilation_cache.reset_cache()
    yield
    compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


def test_repo_dir_receives_the_compiled_entry(monkeypatch, restore_cache_dir,
                                              fresh_cache, tmp_path):
    cache = tmp_path / ".jax_cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "REPO_CACHE_DIR", cache)
    assert compile_cache.enable_compile_cache() == str(cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _affine_cos(jnp.arange(16.0)).block_until_ready()
    assert cache.is_dir() and any(cache.iterdir()), \
        "no compiled entry in the in-repo cache dir"
