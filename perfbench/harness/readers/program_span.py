"""Host time of the program's own profiler spans (``ray_tpu/*``, written by
``ray_tpu.util.tracing.profiler_span`` and ``ShardedPretrainer.step``), on the
clock of the device's operations.

The reduced trace keeps only the benchmark's ``bench/*`` spans, so these are
read from the profiler's own file, which the worker leaves beside it, and cut
to the traced window.  A span is named exactly (``span``) or by the start of
its name (``prefix``).  ``as_``:

``ms_per_step``       the spans' time in the window over the traced steps, on
                      whichever thread they ran: a sum, so it sees the block
                      pull that happens once in 32 steps and a median hides;
``self_ms_per_step``  the same, less what spans nested under them cover;
``idle_pct``          device-idle time, as a share of the window and averaged
                      over the devices, whose innermost cover among the loop
                      thread's spans is one of these; with neither ``span``
                      nor ``prefix``, the idle time that no program span
                      covers — the user's loop, ``float(loss)`` included.

The loop thread is the one that holds the ``ray_tpu/step`` spans; spans of
other threads cover nothing.  ``None`` where the trace holds no such span (a
program without them, a cell that never enters that code).
"""

from __future__ import annotations

import functools
import glob
import os
from typing import Dict, List, Optional, Tuple

from perfbench.harness.trace_reduce import (Op, Trace, gaps, label_gaps,
                                            self_seconds, total, union)

HOST_PLANE = "/host:CPU"
PREFIX = "ray_tpu/"
STEP = "ray_tpu/step"
UNCOVERED = "none"

Span = Tuple[str, float, float]         # (name, start, end), seconds


@functools.lru_cache(maxsize=2)
def load(xplane_path: str) -> Tuple[Tuple[Span, ...], ...]:
    """The ``ray_tpu/*`` spans of the profiler's file, thread by thread, the
    loop thread first (no thread with a step span: an empty one first).
    Reading the file initialises no backend."""
    from jax.profiler import ProfileData

    threads: List[Tuple[Span, ...]] = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans = tuple(sorted(
                ((e.name, e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9)
                 for e in line.events if e.name.startswith(PREFIX)),
                key=lambda s: (s[1], -s[2])))
            if spans:
                threads.append(spans)
    steps = lambda spans: sum(1 for s in spans if s[0] == STEP)  # noqa: E731
    threads.sort(key=steps, reverse=True)
    if not threads or not steps(threads[0]):
        threads.insert(0, ())
    return tuple(threads)


def xplane_of(ctx) -> Optional[str]:
    """The profiler's file of the traced run: beside the reduced trace."""
    reduced = ctx.measured.get("trace")
    if not reduced:
        return None
    files = glob.glob(os.path.join(os.path.dirname(reduced["file"]),
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def _matcher(span: Optional[str], prefix: Optional[str]):
    if span is not None:
        return lambda name: name == span
    return lambda name: name.startswith(prefix)


def span_seconds(threads, matches, own: bool) -> Optional[float]:
    """Seconds of the matching spans over all threads; with ``own``, without
    what the spans nested under them cover.  ``None`` if none matches."""
    found, secs = False, 0.0
    for spans in threads:
        if own:
            ops = [Op(n, "span", "", a, b) for n, a, b in spans]
            for op, own_s in self_seconds(ops):
                if matches(op.name):
                    found, secs = True, secs + own_s
        else:
            mine = [(a, b) for n, a, b in spans if matches(n)]
            found, secs = found or bool(mine), secs + total(union(mine))
    return secs if found else None


def idle_seconds(ops_by_device, window, loop_spans) -> Dict[int, Dict[str, float]]:
    """Per device, its idle seconds in ``window`` by the innermost loop-thread
    span that covered them; ``UNCOVERED`` takes the rest."""
    return {d: label_gaps(gaps(ops, window), loop_spans, fallback=UNCOVERED)
            for d, ops in ops_by_device.items()}


def read(ctx, as_: str, span: Optional[str] = None,
         prefix: Optional[str] = None):
    path = xplane_of(ctx) if ctx.trace is not None else None
    if path is None:
        return None
    window = ctx.trace.window()
    threads = [Trace(spans=list(spans)).clipped(window).spans
               for spans in load(path)]
    if as_ == "idle_pct":
        if not ctx.devices or not threads[0]:
            return None         # no step span: which thread is the loop's?
        by_device = idle_seconds(ctx.trace.ops, window, threads[0])
        if span is None and prefix is None:
            matches = lambda name: name == UNCOVERED  # noqa: E731
        else:
            matches = _matcher(span, prefix)
            if not any(matches(n) for n, _, _ in threads[0]):
                return None
        shares = [sum(s for n, s in named.items() if matches(n))
                  / ctx.window_s for named in by_device.values()]
        return 100.0 * sum(shares) / len(shares)
    if as_ not in ("ms_per_step", "self_ms_per_step"):
        raise ValueError(f"unknown quantity {as_!r}")
    if span is None and prefix is None:
        raise ValueError(f"{as_} wants a span or a prefix")
    secs = span_seconds(threads, _matcher(span, prefix),
                        own=as_ == "self_ms_per_step")
    return None if secs is None else secs / ctx.traced_steps * 1e3
