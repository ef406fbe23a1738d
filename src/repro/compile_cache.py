"""One fixed home for JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``repro.launch.*``)
call :func:`use_compile_cache` before their first compile.  Importing
``repro`` configures nothing, so library users and the tests keep JAX's own
defaults.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: The checkout root (``src/repro/compile_cache.py`` -> ``.``).
CHECKOUT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is changed.  Otherwise the cache lives in
    ``<checkout>/.jax_cache``: a fixed path, never one built from a
    temporary name, a process id or the time, because the directory is what
    lets a later process find the entries again.  Returns the directory in
    use.
    """
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
