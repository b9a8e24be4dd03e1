"""`launch.compilecache`: one persistent-cache placement for every entry
point. A set ``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in
code; otherwise the cache is a fixed, git-ignored directory inside the
checkout, never a temporary or per-process name."""

import os
import tempfile
from pathlib import Path

import jax
import pytest

from repro.launch import compilecache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_nothing_is_set_in_code(monkeypatch,
                                                 restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/jax-cache")
    assert compilecache.enable_compile_cache() == "/somewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_ignored_directory_in_the_checkout(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compilecache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert str(os.getpid()) not in path
    assert not path.startswith(tempfile.gettempdir())
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
    # a second process (or call) lands on the very same directory
    assert compilecache.enable_compile_cache() == path
