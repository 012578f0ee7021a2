"""JAX's persistent compilation cache and a count of what went through it.

Copied from openr_tpu/utils/compile_cache.py so that the yardstick does
not move with the program.  The cache lives at `<checkout>/.jax_cache`,
a fixed path (the path is part of the cache key), and every executable
is written however fast it compiled, so a cell's second run finds every
program it needs.
"""

from __future__ import annotations

import os
import threading

_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_HITS = "/jax/compilation_cache/cache_hits"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def configure(root: str) -> str:
    """Point JAX (and the program, which reads the variable) at the
    checkout's cache directory; call before JAX is imported."""
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileStats:
    """Cache requests and hits, and executables obtained (compiled, or
    loaded from the cache: JAX times both under one event)."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self._lock = threading.Lock()
        self.requests = 0
        self.hits = 0
        self.executables = 0
        self.compile_or_load_s = 0.0

        def on_event(event: str, **_kw) -> None:
            with self._lock:
                if event == _REQUESTS:
                    self.requests += 1
                elif event == _HITS:
                    self.hits += 1

        def on_duration(event: str, secs: float, **_kw) -> None:
            if event == _BACKEND_COMPILE:
                with self._lock:
                    self.executables += 1
                    self.compile_or_load_s += secs

        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "cache_requests": self.requests,
                "cache_hits": self.hits,
                "executables": self.executables,
                "compile_or_load_s": self.compile_or_load_s,
            }
