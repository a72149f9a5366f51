"""Where JAX runs and where its compiled programs are kept.

Two substrates, chosen explicitly by whoever starts the process:

- the CPU test substrate (``force_cpu_platform``): N virtual host devices,
  Pallas kernels interpreted *because this function asked for it* — tests and
  the multi-chip dry run call it before the first backend/device use;
- the chip: a train worker asked for TPU pins the platform itself and raises
  if the backend that comes up is anything else (``train/jax_config.py``).

Single authoritative implementation — do not copy this dance elsewhere.
"""

from __future__ import annotations

import logging
import os
import sys
import threading

from ray_tpu._private import flight_recorder

logger = logging.getLogger(__name__)

# <checkout>/.jax_cache: derived from this file's own location, so every
# process of every run in one checkout agrees on it (the path is part of what
# makes a cache entry findable again; a temp, pid or session directory never
# hits).
_DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")

# glibc gives every thread but the first its own arena, built from 64 MiB
# heaps, and unmaps such a heap the moment it is entirely free unless
# M_TOP_PAD covers it.  Loading a compiled program from the cache allocates
# and frees tens of MB over and over; off the main thread — where every
# ray_tpu worker runs its tasks — each round unmapped and re-faulted the heap:
# 7.0 s to load the GPT-2-small step against 2.5 s on the main thread, 2.05 s
# with this pad on any thread (chip runs, PR 21).  Costs address space, and
# at most this much freed memory kept per arena.
_M_TOP_PAD = -2  # <malloc.h>
_HEAP_PAD_BYTES = 64 << 20


def force_cpu_platform(n_devices: int = 8) -> None:
    """Force JAX onto ``n_devices`` virtual CPU devices, with Pallas kernels
    interpreted (inherited by the worker processes this one starts).

    Must be called before any jax device/backend use.  Safe to call more than
    once with the same ``n_devices``; the flag append is idempotent.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and return
    the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of it stands
    and nothing is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache``.  Call it in the process that compiles, before
    the first compile.  Either way the allocator is told to keep the loader's
    freed heaps (see ``_HEAP_PAD_BYTES``).
    """
    import ctypes

    import jax

    ctypes.CDLL(None).mallopt(_M_TOP_PAD, _HEAP_PAD_BYTES)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_COMPILE_CACHE)
    return _DEFAULT_COMPILE_CACHE


# --- the program's one listener of JAX's compile events --------------------
_COMPILE_EVENTS = ("/jax/core/compile/", "/jax/compilation_cache/")
_TIME_SAVED = "compile_time_saved_sec"  # an estimate, not time that passed
_BUILT = "backend_compile_duration"     # one executable compiled or loaded
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
# an unrolled twelve-layer trace fires hundreds of sub-millisecond events;
# what is shorter than this is neither recorded nor counted
_COMPILE_MIN_S = 0.05
_RECOMPILE_WARN_S = 1.0
_watching = False
# JAX reports a hit or a miss (a miss only where it writes the entry) before
# the build's own duration event, on the compiling thread
_cache = threading.local()


def watch_compiles() -> None:
    """Register, once a process, the listener that turns ``jax.monitoring``'s
    compile events into flight-recorder records (``compile``,
    ``compile.cache``), the ``train_compiles_total`` /
    ``train_compile_seconds`` instruments, and a warning where a function is
    built after the session's first ``train.report``."""
    global _watching
    if _watching:
        return
    _watching = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_compile_seconds)
    jax.monitoring.register_event_listener(_on_cache_event)


def _on_cache_event(event: str, **kw) -> None:
    state = _CACHE_EVENTS.get(event)
    if state is None:
        return
    _cache.state = state
    if flight_recorder.RECORDING:
        flight_recorder.record("compile.cache", state)


def _on_compile_seconds(event: str, seconds: float, **kw) -> None:
    if not event.startswith(_COMPILE_EVENTS):
        return
    stage = event.rsplit("/", 1)[-1]
    if flight_recorder.RECORDING and event.startswith(_COMPILE_EVENTS[0]):
        # trace, lowering and build-or-load of the thread that builds, the
        # short ones too: a round's ``compile`` (_TrainSession.report)
        flight_recorder.add_span("compile", seconds)
    cache = None
    if stage == _BUILT:     # taken whatever the build's length: it is this one's
        cache, _cache.state = getattr(_cache, "state", "uncached"), "uncached"
    if seconds < _COMPILE_MIN_S or stage == _TIME_SAVED:
        return
    fun_name = str(kw.get("fun_name") or "")
    if flight_recorder.RECORDING:
        flight_recorder.mark("compile", seconds, f"{stage}|{fun_name}")
    from ray_tpu.train._metrics import train_metrics

    metrics = train_metrics()
    metrics["compile_seconds"].observe(seconds, {"stage": stage})
    if stage != _BUILT:
        return
    metrics["compiles"].inc(1, {"fun_name": fun_name, "cache": cache})
    session = sys.modules.get("ray_tpu.train._session")
    session = session and session.get_session()
    if seconds >= _RECOMPILE_WARN_S and session and session.report_step:
        logger.warning(
            "%s was built inside the loop (%.1f s, compile cache: %s), after "
            "train.report step %d: a jitted function ran for the first time "
            "or met a new shape, dtype or static argument",
            fun_name, seconds, cache, session.report_step)
