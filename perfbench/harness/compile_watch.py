"""JAX's own compile events (copied from ``chip_smoke.py``'s watcher).

A ``backend_compile_duration`` event is one executable built — by the compiler
or loaded from the persistent cache alike; ``cache_retrieval_time_sec`` is the
part of it a cache hit spent loading.  The window is correct only if no such
event falls inside it.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

BUILT = "/jax/core/compile/backend_compile_duration"
Event = Tuple[str, Optional[str], float]   # (event, fun_name, seconds)


def watch() -> List[Event]:
    """Register the listener; the returned list grows as JAX compiles."""
    import jax

    seen: List[Event] = []

    def listener(event, secs, **kw):
        if event.startswith(("/jax/core/compile/", "/jax/compilation_cache/")):
            seen.append((event, kw.get("fun_name"), float(secs)))

    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


def built(events: List[Event]) -> List[Event]:
    return [e for e in events if e[0] == BUILT]
