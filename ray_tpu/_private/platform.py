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

import contextlib
import glob
import logging
import os
import sys
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

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


def import_jax():
    """``import jax``.  Where it is a worker process's first, the mark
    ``bringup.worker.jax_import`` holds it: in a train worker that is the
    actor's class load (``ray_tpu.train`` reaches ``util/collective/xla.py``),
    so the mark is ``bringup.worker.actor``'s child.  A driver's import is
    none of a worker's start and writes nothing."""
    from ray_tpu._private.worker import global_worker_core

    first = "jax" not in sys.modules and getattr(
        global_worker_core(), "mode", None) == "worker"
    with flight_recorder.timed("bringup.worker.jax_import") if first \
            else contextlib.nullcontext():
        import jax
    return jax


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and return
    the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of it stands
    and nothing is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache``.  Call it in the process that compiles, before
    the first compile.  Either way the allocator is told to keep the loader's
    freed heaps (see ``_HEAP_PAD_BYTES``).  What the directory holds now goes
    into the flight recorder (``record_compile_cache``).
    """
    import ctypes

    import jax

    global _cache_entries
    ctypes.CDLL(None).mallopt(_M_TOP_PAD, _HEAP_PAD_BYTES)
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = _DEFAULT_COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", where)
    _cache_entries = (where, None)
    record_compile_cache("start")
    return where


# the directory ``enable_compile_cache`` turned on and the entries the last
# record found there (None before the first); None in a process that keeps
# no cache
_cache_entries: Optional[Tuple[str, Optional[set]]] = None


def record_compile_cache(when: str) -> None:
    """``compile.cache_dir|<when>|bytes=<n> entries=<n> max=<n> written=<n>
    evicted=<n>`` into the flight recorder: what the compile cache's
    directory holds (one scan of it), the cap JAX evicts under
    (``JAX_COMPILATION_CACHE_MAX_SIZE``; -1 is none) and the entries that
    came and went since this process's previous record.  Nothing where the
    recorder is off or the process keeps no cache."""
    global _cache_entries
    if _cache_entries is None or not flight_recorder.RECORDING:
        return
    import jax

    where, known = _cache_entries
    found: Dict[str, int] = {}
    try:
        with os.scandir(where) as entries:
            for entry in entries:
                found[entry.name] = entry.stat().st_size
    except OSError:
        pass    # not made yet, or an entry evicted under the scan
    # an entry is a file, with a second one for its time of last use
    names = {n for n in found if not n.endswith("-atime")}
    known = names if known is None else known
    flight_recorder.record("compile.cache_dir", (
        f"{when}|bytes={sum(found.values())} entries={len(names)} "
        f"max={jax.config.jax_compilation_cache_max_size} "
        f"written={len(names - known)} evicted={len(known - names)}"))
    _cache_entries = (where, names)


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


# --- the chip on arrival, and what of the client's making Python sees ------
ARRIVAL = "bringup.worker.chip_on_arrival"
# a chip is a device file: /dev/vfio/<n> on the chip machines, /dev/accel<n>
# on a TPU VM
_CHIP_FILES = ("/dev/accel*", "/dev/vfio/*")
# the client's making in the installed JAX (jax/_src/xla_bridge.py):
# make_tpu_client dlopens libtpu through xla_client's
# load_pjrt_plugin_dynamically, then initialises the plugin and asks it for
# the client; both are Python functions, the rest is one native call
_CLIENT_SEAMS = {
    "load_pjrt_plugin_dynamically": "bringup.worker.tpu_client.plugin_load",
    "make_tpu_client": "bringup.worker.tpu_client.client"}


def chip_holders() -> List[Tuple[int, str, str]]:
    """Other processes that hold one of the chips' device files open, as
    ``(pid, state, /proc/<pid>/fd/<n>)``, the state the letter of
    ``/proc/<pid>/stat`` (``R``, ``S``, ``D`` ...): one walk of ``/proc``,
    none on a machine without such files."""
    chips = {path for pattern in _CHIP_FILES for path in glob.glob(pattern)}
    if not chips:
        return []
    holders = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        try:
            for fd in os.listdir(f"/proc/{pid}/fd"):
                link = f"/proc/{pid}/fd/{fd}"
                if os.readlink(link) in chips:
                    with open(f"/proc/{pid}/stat") as f:
                        state = f.read().rpartition(")")[2].split()[0]
                    holders.append((int(pid), state, link))
                    break
        except (OSError, IndexError):
            continue    # gone meanwhile, or another user's
    return holders


@contextlib.contextmanager
def chip_on_arrival() -> Iterator[None]:
    """Around the TPU client's making: the chip's state when this process
    came for it, as one record — ``bringup.worker.chip_on_arrival|free``
    before the block, or ``|held|<pid>|<state>|<seconds>`` when the first
    holder found let go or the block ended, the seconds since the walk those
    it still held the chip for (looked at every 50 ms: one ``readlink``).  A
    held chip is also one WARNING, at once: a worker that dies in the block
    leaves that."""
    t0 = time.perf_counter()
    holders = chip_holders()
    if not holders:
        flight_recorder.record(ARRIVAL, "free")
        yield
        return
    pid, state, link = holders[0]
    logger.warning(
        "the chip is held on arrival by %s", ", ".join(
            f"pid {p} (state {s})" for p, s, _ in holders))
    chip, over = os.readlink(link), threading.Event()

    def follow() -> None:
        while not over.wait(0.05):
            try:
                if os.readlink(link) != chip:
                    break
            except OSError:
                break
        flight_recorder.record(
            ARRIVAL, f"held|{pid}|{state}|{time.perf_counter() - t0:.6f}")

    follower = threading.Thread(target=follow, daemon=True,
                                name="chip-on-arrival")
    follower.start()
    try:
        yield
    finally:
        over.set()
        follower.join(1.0)


@contextlib.contextmanager
def client_seams() -> Iterator[None]:
    """Around ``jax.default_backend()``: the calls of ``_CLIENT_SEAMS`` that
    this thread makes inside the block, each a mark from its call to its
    return (``sys.setprofile`` for the block's length; a few hundred Python
    calls pass through it).  Where the installed JAX has no function of
    these names there is no mark, and ``bringup.worker.tpu_client``'s own
    cost is the split."""
    began: Dict[str, flight_recorder.Usage] = {}

    def seam(frame, event, arg):
        kind = _CLIENT_SEAMS.get(frame.f_code.co_name)
        if kind is None:
            return
        if event == "call":
            began.setdefault(kind, flight_recorder.usage())
        elif event == "return" and kind in began:
            flight_recorder.mark_since(kind, began.pop(kind))

    outer = sys.getprofile()
    sys.setprofile(seam)
    try:
        yield
    finally:
        sys.setprofile(outer)
