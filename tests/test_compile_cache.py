"""``utils/platform.enable_compile_cache``: JAX's persistent compile
cache at a fixed path unless ``JAX_COMPILATION_CACHE_DIR`` names one."""

from __future__ import annotations

from pathlib import Path

import jax
import pytest

from mpitest_tpu.utils import platform

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_unset_uses_the_repo_cache(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = platform.enable_compile_cache()
    assert got == str(platform.REPO_CACHE_DIR)
    assert platform.REPO_CACHE_DIR.name == ".jax_cache"
    assert platform.REPO_CACHE_DIR.parent == REPO
    assert jax.config.jax_compilation_cache_dir == got


def test_set_is_left_to_jax(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert platform.enable_compile_cache() == str(tmp_path)
    # nothing set in code: JAX reads the variable itself
    assert jax.config.jax_compilation_cache_dir == before
