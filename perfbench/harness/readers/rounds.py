"""A run's steady state as the program wrote it into its flight-recorder
rings: the train worker's report rounds (``train.rounds``: consecutive rounds
summed into one record, each with the rounds and steps it covers, its longest
round and its seconds by the loop thread's own spans), its stalled rounds
(``train.stall``) and the driver's side of the rounds
(``train.driver_rounds``), through ``flight_recorder.round_timeline`` — the
one function of the program this reader calls.  The session is the newest
under ``RAY_TPU_TMPDIR``, as ``bringup`` finds it; the train worker is the
process whose ring holds the last ``train.rounds`` record.  ``None`` where
the program has no such function or the session no such record.

Read over the **whole window**, not the traced steps: the last ``steps``
steps (the worker's count) before the final report, counted back from the
end by the records' own step counts.  The final report's round has no step
and is left out; a record that straddles the window's start counts by the
share of its steps that lie inside.  ``as_``:

``worst_excess_s``  the window's longest round less its median round;
``stalled``         the ``train.stall`` records that end inside the window;
``left_out``        how many rounds the two above left out (below);
``ms_per_step``     the seconds under the names ``of``, on ``side``
                    (``worker``, or ``driver``: its records by the share of
                    their time inside the window), over the window's steps.

A round in which the benchmark itself called ``jax.profiler.start_trace`` or
``stop_trace`` is the benchmark's, not the program's (0.4 to 5.6 s of
``start_trace`` in every traced run: ``PERF.md`` section 6, PR 50).  The
traced loop stamps the two calls on the recorder's clock (``time.time()``;
``kinds/train_loop.py::_Profiler.calls``, brought back beside the reduced
trace), and ``worst_excess_s`` and ``stalled`` leave out every record that
overlaps one of them — a stalled round is a record of its own; a round that
was not is left out with the rounds summed beside it.  The seconds by name
(``ms_per_step``) are left whole: the profiler's calls run under none of the
program's spans.
"""

from __future__ import annotations

import functools
import statistics
from typing import List, Optional, Sequence, Tuple

from perfbench.harness.readers.bringup import newest_session

ROUNDS, STALL, DRIVER = "train.rounds", "train.stall", "train.driver_rounds"


@functools.lru_cache(maxsize=2)
def timeline(session_dir: str) -> Optional[list]:
    try:
        from ray_tpu._private import flight_recorder
    except ImportError:
        return None
    read = getattr(flight_recorder, "round_timeline", None)
    return read(session_dir) if read else None


def window(records: Sequence, steps: int) -> List[Tuple[object, float]]:
    """Of one worker's ``train.rounds`` records, oldest first, those that
    hold the last ``steps`` steps, each with the share of it inside."""
    held: List[Tuple[object, float]] = []
    left = float(steps)
    for record in reversed(records):
        if left <= 0:
            break
        n = record.counts.get("steps", 0)
        if not n:
            if held:                # a round without a step inside the window
                held.append((record, 1.0))
            continue                # the final report's round, after it
        share = min(1.0, left / n)
        held.append((record, share))
        left -= n * share
    return held[::-1]


def profiler_calls(ctx) -> List[Tuple[float, float]]:
    """The benchmark's own profiler calls in this run, as intervals on the
    recorder's clock; none where the run was not traced."""
    traced = ctx.measured.get("trace") or {}
    return [(float(a), float(b)) for a, b in traced.get("profiler_calls", ())]


def _overlaps(record, calls: Sequence[Tuple[float, float]]) -> bool:
    return any(record.start < b and a < record.end for a, b in calls)


def read(ctx, as_: str, of: Sequence[str] = (), side: str = "worker",
         session_dir: Optional[str] = None):
    session_dir = session_dir or newest_session()
    rounds = timeline(session_dir) if session_dir else None
    mine = [r for r in rounds or () if r.kind == ROUNDS]
    if not mine:
        return None
    worker = mine[-1].process
    steps = ctx.measured["steps"]
    held = window([r for r in mine if r.process == worker], steps)
    if not held:
        return None
    first, share = held[0]
    opened = first.end - share * (first.end - first.start)
    closed = held[-1][0].end
    calls = profiler_calls(ctx)
    if as_ == "stalled":
        return float(sum(1 for r in rounds if r.kind == STALL
                         and r.process == worker and opened < r.end <= closed
                         and not _overlaps(r, calls)))
    if as_ == "left_out":
        return float(sum(record.counts.get("rounds", 1)
                         for record, _ in held if _overlaps(record, calls)))
    if as_ == "worst_excess_s":
        each = [s for record, share in held if not _overlaps(record, calls)
                for s in (record.each() if share == 1.0 else
                          [(record.end - record.start)
                           / record.counts.get("rounds", 1)])]
        return max(each) - statistics.median(each) if each else None
    if as_ != "ms_per_step":
        raise ValueError(f"as_ must be worst_excess_s, stalled, left_out or "
                         f"ms_per_step, got {as_!r}")
    if side == "worker":
        parts = held
    elif side == "driver":
        parts = [(r, max(0.0, min(r.end, closed) - max(r.start, opened))
                  / (r.end - r.start))
                 for r in rounds if r.kind == DRIVER and r.end > r.start]
        if not parts:
            return None
    else:
        raise ValueError(f"side must be worker or driver, got {side!r}")
    seconds = sum(record.seconds.get(name, 0.0) * share
                  for record, share in parts for name in of)
    return seconds / steps * 1e3
