"""JAX's persistent compilation cache, at one fixed place.

`JAX_COMPILATION_CACHE_DIR`, where it is set, is read by JAX itself and
wins: no other path is set in code. Otherwise the cache lives at
`<checkout>/.jax_cache` (listed in `.gitignore`). The path is part of what
the cache is keyed on, so it must not move between runs.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its
    directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
