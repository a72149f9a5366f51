"""The benchmark's own host spans, around the calls its loop makes into the
program.  Each is kept twice: on the host clock, for medians over the whole
window, and as a ``TraceAnnotation`` (``bench/<name>``), so that in a traced
sub-window it lies on the device trace's clock and an idle gap can be named by
the span that covered it."""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List, Tuple

from perfbench.harness.trace_reduce import SPAN_PREFIX


class Spans:
    def __init__(self):
        self.seen: Dict[str, List[Tuple[float, float]]] = {}

    @contextmanager
    def __call__(self, name: str):
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation(SPAN_PREFIX + name):
            yield
        self.seen.setdefault(name, []).append((t0, time.perf_counter()))

    def clear(self) -> None:
        self.seen.clear()

    def durations_ms(self, name: str) -> List[float]:
        return [(b - a) * 1e3 for a, b in self.seen.get(name, [])]
