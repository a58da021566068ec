"""Compilations and compile-cache hits inside a block of code, from JAX's
monitoring events (a hit records its retrieval, not a compile)."""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def compile_seconds():
    """Yields a dict: ``s`` (trace, lowering and backend-compile seconds),
    ``compiles`` (backend compilations) and ``cache_hits``."""
    import jax

    box = {"s": 0.0, "compiles": 0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            box["s"] += duration
            if event == "/jax/core/compile/backend_compile_duration":
                box["compiles"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            box["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield box
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
