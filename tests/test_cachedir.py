"""Cache placement: the environment names the place, else a fixed path in
the checkout that git ignores."""

from __future__ import annotations

import jax
import pytest

from repro.cachedir import REPO_ROOT, enable_compile_cache
from repro.core import _iosim_c


@pytest.fixture
def compile_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def _ignored():
    return (REPO_ROOT / ".gitignore").read_text().split()


def test_compile_cache_env_var_is_left_to_jax(monkeypatch, tmp_path,
                                              compile_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_ignored_dir(monkeypatch,
                                                       compile_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(REPO_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in _ignored()


def test_annealer_helper_builds_in_repro_cache_or_the_checkout(monkeypatch,
                                                               tmp_path):
    monkeypatch.setenv("REPRO_CACHE", str(tmp_path / "c"))
    assert _iosim_c._cache_dir() == str(tmp_path / "c")
    assert (tmp_path / "c").is_dir()
    monkeypatch.delenv("REPRO_CACHE")
    assert _iosim_c._cache_dir() == str(REPO_ROOT / ".repro_cache")
    assert ".repro_cache/" in _ignored()
