"""XLA backend compiles and persistent-cache traffic of the process.

Copied from chip_smoke.py's ``_watch_compiles`` (PR 22): the
``jax.monitoring`` listeners see every backend compile, eager ops
included, which the engine's own ``compile_count`` does not.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def watch_compiles():
    """Yield ``(cache, compiles)``: persistent-cache hit/miss counts and
    ``(seconds, name)`` of every XLA backend compile while the block
    runs."""
    import jax
    cache = {"hits": 0, "misses": 0}
    compiles: list[tuple[float, str]] = []

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    def on_duration(event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append((secs, fun_name))

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield cache, compiles
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_duration)
