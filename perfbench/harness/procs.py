"""Every process a run starts is stopped and waited for before the run ends.

The system under test starts its own processes (GCS, nodelet, workers) and
stops them in ``ray_tpu.shutdown()``; what that leaves behind would outlive
the run: the ``multiprocessing`` resource tracker of every process that used
the shared-memory object store (the run's own process among them, where the
cell feeds from a dataset), a worker the nodelet spawned while it was already
stopping, a killed process nobody waited for (PERF.md, sections 6 and 7).  So
the benchmark's process adopts every orphan among its descendants before the
runtime starts (``adopt_orphans``) and, on every path out of the run, stops
and reaps whatever is still there (``stop_all``).  Linux only, like the
chip's host.
"""

from __future__ import annotations

import ctypes
import os
import signal
import sys
import time
from typing import Dict, List

PR_SET_CHILD_SUBREAPER = 36     # <linux/prctl.h>


def adopt_orphans() -> None:
    """From now on a descendant whose parent dies becomes this process's
    child and not init's, so that ``stop_all`` can find it and wait for it."""
    libc = ctypes.CDLL(None, use_errno=True)
    if libc.prctl(PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0) != 0:
        err = ctypes.get_errno()
        raise OSError(err, "prctl(PR_SET_CHILD_SUBREAPER): " + os.strerror(err))


def _table() -> Dict[int, tuple]:
    """pid -> (parent pid, state, command) of every process ``/proc`` shows."""
    table = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat", "rb") as f:
                stat = f.read().decode(errors="replace")
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue            # ended while we were reading
        if ")" not in stat:
            continue
        # "pid (comm) state ppid ...": comm may hold spaces and parentheses
        comm_end = stat.rindex(")")
        state, ppid = stat[comm_end + 2:].split()[:2]
        table[int(entry)] = (int(ppid), state,
                             cmd.strip() or stat[stat.index("(") + 1:comm_end])
    return table


def descendants() -> Dict[int, tuple]:
    """Every process below this one, as ``_table`` rows."""
    table = _table()
    children: Dict[int, List[int]] = {}
    for pid, (ppid, _, _) in table.items():
        children.setdefault(ppid, []).append(pid)
    found, queue = {}, [os.getpid()]
    while queue:
        for pid in children.get(queue.pop(), ()):
            if pid not in found:
                found[pid] = table[pid]
                queue.append(pid)
    return found


def _reap() -> None:
    while True:
        try:
            pid, _ = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            return
        if pid == 0:
            return


def _stop_own_resource_tracker() -> None:
    """``multiprocessing``'s resource tracker of this process — started when
    the runtime puts a block into shared memory, as the dataset cells do —
    ignores SIGTERM and ends only when its pipe closes, which the interpreter
    does at exit without waiting for it.  Close it here, the way the
    interpreter would, and wait: the tracker unlinks what was leaked and
    ends.  Where this Python has no such hook, ``stop_all`` kills it."""
    tracker = getattr(sys.modules.get("multiprocessing.resource_tracker"),
                      "_resource_tracker", None)
    stop = getattr(tracker, "_stop", None)
    if stop is None:
        return
    try:
        stop()
    except Exception:   # noqa: BLE001 — stop_all deals with what is left
        pass


def stop_all(grace_s: float = 2.0, limit_s: float = 30.0) -> List[str]:
    """Stop every descendant of this process and wait until each has ended:
    SIGTERM, after ``grace_s`` SIGKILL (the resource trackers of killed workers
    ignore SIGTERM and end on their own within the grace, once they have
    unlinked what their worker left in shared memory).  Returns the commands
    of those that were still running when it was called (zombies are only
    reaped).  Raises
    ``TimeoutError`` if one is still there after ``limit_s``."""
    _stop_own_resource_tracker()
    t0 = time.monotonic()
    running: Dict[int, str] = {}
    while True:
        _reap()
        left = descendants()
        if not left:
            return list(running.values())
        waited = time.monotonic() - t0
        if waited > limit_s:
            raise TimeoutError(f"still there after {limit_s:.0f} s: " + "; ".join(
                f"{pid} {cmd[:80]}" for pid, (_, _, cmd) in left.items()))
        for pid, (_, state, cmd) in left.items():
            if state == "Z":
                continue        # ended; reaped once it is this process's child
            if pid not in running:
                running[pid], sig = cmd, signal.SIGTERM
            elif waited >= grace_s:
                sig = signal.SIGKILL
            else:
                continue
            try:
                os.kill(pid, sig)
            except ProcessLookupError:
                pass
        time.sleep(0.02)
