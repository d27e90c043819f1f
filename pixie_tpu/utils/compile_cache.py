"""JAX's persistent compilation cache: the one place that points it.

``JAX_COMPILATION_CACHE_DIR`` wins when the environment sets it;
otherwise the cache lives at the fixed ``<repo>/.jax_cache`` (the path is
part of the cache key, so a directory that moves never hits).
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO_ROOT, ".jax_cache"
    )


def enable() -> str:
    """Point JAX's persistent compilation cache at ``cache_dir()``."""
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
