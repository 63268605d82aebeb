"""JAX's persistent compilation cache for the repo's entry points.

``enable_compile_cache()`` is called once by ``chip_smoke.py`` and by the
launchers in ``launch/``, before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this sets
nothing else; otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout (listed in ``.gitignore``), so a later run from the same checkout
finds it.  Tests never call it.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The directory the cache uses: the environment's, else the checkout's
    ``.jax_cache/``."""
    return os.environ.get(ENV) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return it."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
