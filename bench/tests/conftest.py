"""The benchmark's CPU tests import it as the package ``bench``, and the
program from ``src``, whatever directory pytest starts in."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def jax_cache_off(monkeypatch, tmp_path):
    """A run of a cell turns on the persistent compilation cache in the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` names one; set after JAX
    has started, that variable leaves the cache off.  The threshold the
    run lowers is put back."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)
