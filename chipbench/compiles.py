"""JAX's persistent compilation cache and a count of compiles.

The cache sits where ``JAX_COMPILATION_CACHE_DIR`` says when it is set,
and otherwise at the fixed ``<checkout>/.jax_cache``: the directory is
part of what a later run must find again, so it never moves. Every
compile is written to it, however short, so that a warm run loads the
small eager programs too.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def enable_cache(checkout: Path) -> str:
    """Turn the persistent cache on; returns its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(checkout / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts executables obtained, their seconds and persistent-cache
    hits, process-wide, through JAX's monitoring events. JAX times a
    persistent-cache load under the same event as a compile, so
    ``count`` holds both and ``count - cache_hits`` is what compiled.
    ``names`` lists the function of each executable obtained, in order."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.names = []
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, fun_name="?", **_kw):
            if name == event:
                self.count += 1
                self.seconds += secs
                self.names.append(fun_name)

        def on_event(name, **_kw):
            if name == CACHE_HIT_EVENT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
