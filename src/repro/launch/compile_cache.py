"""Where the command-line entry points keep JAX's persistent compile cache.

``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.tune`` and
``benchmarks.run`` call :func:`use_compile_cache` first thing in ``main`` —
never at import, so importing the library changes no JAX setting.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...). A
# fixed path: the cache directory is part of what a later run must match.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives in ``.jax_cache`` at the
    root of the checkout, so a second process in the same checkout reuses
    the first one's compiled programs.
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
