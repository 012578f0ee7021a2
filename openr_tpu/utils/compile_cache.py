"""JAX's persistent compilation cache, placed from outside.

One helper for every program that compiles for the chip (`main()`):
where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it and nothing
here sets another directory; otherwise the
cache lives at a fixed path inside the checkout (`<repo>/.jax_cache`,
listed in .gitignore).  The path is part of the cache key, so it is
never temporary, per-process or time-based.  Every executable is
written, however fast it compiled or small it is: JAX's defaults skip
programs that compile in under a second, and a chip run then compiles
most of its programs again on every call.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path

