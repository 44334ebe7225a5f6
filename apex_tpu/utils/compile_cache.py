"""Where JAX's persistent compilation cache lives — decided in one place.

The directory is part of every cache key's story: a path that moves
(``/tmp``, a pid, a timestamp) never hits. So it is placed from OUTSIDE
when ``JAX_COMPILATION_CACHE_DIR`` is set — JAX reads that variable
itself and this module then sets no directory in code — and otherwise at
one fixed, git-ignored path inside the checkout.
"""

from __future__ import annotations

import os

__all__ = ["default_cache_dir", "enable_compile_cache"]

# programs that compile faster than this are not worth a cache file
MIN_COMPILE_SECS = 1.0


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — the checkout being the directory that
    holds the ``apex_tpu`` package."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.
    Call before the first compilation."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = default_cache_dir()
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return path
