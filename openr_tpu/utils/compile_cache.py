"""JAX's persistent compilation cache, placed from outside.

One helper for every program that compiles for the chip (`main()`,
`chip_smoke.py`, `bench.py`): where `JAX_COMPILATION_CACHE_DIR` is set,
JAX reads it and nothing here sets another directory; otherwise the
cache lives at a fixed path inside the checkout (`<repo>/.jax_cache`,
listed in .gitignore).  The path is part of the cache key, so it is
never temporary, per-process or time-based.  Every executable is
written, however fast it compiled or small it is: JAX's defaults skip
programs that compile in under a second, and a chip run then compiles
most of its programs again on every call.

`CompileStats` counts what went through the cache (requests, hits) and
the executables obtained with their seconds (a backend compile or a
cache load; JAX times both under one event) from its monitoring events.
"""

from __future__ import annotations

import os
import threading

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")

_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_HITS = "/jax/compilation_cache/cache_hits"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


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


class CompileStats:
    """Cache requests, hits, and executables obtained (compiled or loaded
    from the cache) with their seconds since creation, read from
    `jax.monitoring` events (process-wide listeners)."""

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
