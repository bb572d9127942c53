"""JAX's persistent compilation cache for the entry points.

The cache key includes the directory, so it lives at one fixed path:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself), else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns
    its directory.  Called by entry points, never at import."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
