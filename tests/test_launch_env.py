"""repro.launch.env: one compile-cache directory, flags appended."""

import os
from pathlib import Path

import jax
import pytest

from repro.launch import env

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_placed_cache_dir_is_respected(monkeypatch, tmp_path, cache_dir_config):
    """With JAX_COMPILATION_CACHE_DIR set, the helper reports that directory
    and configures no other: JAX reads the variable itself."""
    monkeypatch.setenv(env.CACHE_ENV, str(tmp_path))
    assert env.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == cache_dir_config


def test_default_cache_dir_is_one_fixed_path(monkeypatch, tmp_path, cache_dir_config):
    """Without the variable the cache sits at one git-ignored path inside
    the checkout, whatever the working directory, TMPDIR or process."""
    monkeypatch.delenv(env.CACHE_ENV, raising=False)
    first = env.configure_compile_cache()
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    second = env.configure_compile_cache()
    assert first == second == env.DEFAULT_CACHE_DIR
    assert Path(first) == REPO / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


@pytest.mark.parametrize("var", ["XLA_FLAGS", "LIBTPU_INIT_ARGS"])
def test_append_flag_keeps_what_is_there(monkeypatch, var):
    monkeypatch.setenv(var, "--kept=1")
    env.append_flag(var, "--added=2")
    assert os.environ[var] == "--kept=1 --added=2"
    monkeypatch.delenv(var)
    env.append_flag(var, "--only")
    assert os.environ[var] == "--only"
