"""Fixed on-disk cache locations inside the checkout.

A persistent cache only hits when its directory stays put, so both caches
this project keeps live at fixed paths under the repository root unless the
caller's environment names a place:

  * JAX's compilation cache — ``JAX_COMPILATION_CACHE_DIR`` when set (JAX
    reads it itself), else ``<repo>/.jax_cache``;
  * the annealer's C helper build (``core/_iosim_c.py``) — ``REPRO_CACHE``
    when set, else ``<repo>/.repro_cache``.

Both default directories are listed in ``.gitignore``.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and nothing
    is changed here.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    Call once per process, from an entry point, before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
