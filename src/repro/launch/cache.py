"""Where JAX keeps its persistent compilation cache.

Each entry point (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
calls :func:`use_compile_cache` once, at start; nothing calls it at import.
"""

from __future__ import annotations

import os
import pathlib

# fixed and inside the checkout: a cache is found again only at the path
# it was written to, so the path never comes from a temporary name
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Place the cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout (git-ignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
