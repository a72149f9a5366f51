"""A job's start as the program wrote it into its flight-recorder rings:
``bringup.*`` marks (an interval a record, on ``time.time()``, the clock
``run.py``'s ``t_start`` is on) and the train worker's ``compile`` /
``compile.cache`` records, through ``flight_recorder.bringup_timeline`` — the
one function of the program this reader calls.  The session is the newest
``session_*`` under ``RAY_TPU_TMPDIR``; its rings are still on disk when the
runtime is down.  Nothing where the program has no such function, the session
has no ring, or no record matches.

``mark``: a record kind or a list of kinds.  ``as_``: ``seconds`` (the
seconds of the clock the matching records cover: their union, so a jitted
function traced inside another's trace is not counted twice) or ``count`` (how
many; 0 where the train worker's ring was found and none matches).  The
timeline's uncovered seconds are inside reader ``start_account``'s
``unnamed``.  For ``compile`` records ``stage`` keeps
those stages and ``less`` takes the seconds those cover away; for
``compile.cache`` records ``cache`` keeps ``hit`` or ``miss``.  Records of a worker process count only from the train worker — the
process whose ring holds ``train_fn_enter`` — and the nodelet's
``worker_spawn`` only for that worker.
"""

from __future__ import annotations

import functools
import glob
import os
from typing import List, Optional, Sequence, Tuple, Union

ENTERED = "bringup.worker.train_fn_enter"
OF_A_WORKER = ("bringup.worker.", "bringup.state_init", "compile")


@functools.lru_cache(maxsize=2)
def timeline(session_dir: str) -> Optional[Tuple[List[tuple], Optional[float]]]:
    try:
        from ray_tpu._private import flight_recorder
    except ImportError:
        return None
    read = getattr(flight_recorder, "bringup_timeline", None)
    return read(session_dir) if read else None


def newest_session() -> Optional[str]:
    root = os.environ.get("RAY_TPU_TMPDIR", "/tmp/ray_tpu")
    sessions = glob.glob(os.path.join(root, "session_*"))
    return max(sessions, key=os.path.getmtime) if sessions else None


def _names(value: Union[None, str, Sequence[str]]) -> Tuple[str, ...]:
    return (value,) if isinstance(value, str) else tuple(value or ())


def _covered(spans: List[Tuple[float, float]]) -> float:
    total, reached = 0.0, float("-inf")
    for start, end in sorted(spans):
        total += max(0.0, end - max(start, reached))
        reached = max(reached, end)
    return total


def read(ctx, mark=None, as_: str = "seconds", stage=None, less=None,
         cache: Optional[str] = None, session_dir: Optional[str] = None):
    session_dir = session_dir or newest_session()
    found = timeline(session_dir) if session_dir else None
    if not found or not found[0]:
        return None
    marks = found[0]
    workers = [m[0] for m in marks if m[1] == ENTERED]
    worker = workers[-1] if workers else None
    kinds, stages, lessened = _names(mark), _names(stage), _names(less)
    kept: List[Tuple[float, float]] = []
    taken: List[Tuple[float, float]] = []
    for name, kind, start, end, detail in marks:
        if kind not in kinds:
            continue
        if kind.startswith(OF_A_WORKER) and name != worker:
            continue
        if kind == "bringup.worker_spawn" and detail != worker:
            continue
        into = kept
        if kind == "compile":
            this = detail.partition("|")[0]
            if this in lessened:
                into = taken
            elif this not in stages:
                continue
        if kind == "compile.cache" and cache and detail != cache:
            continue
        into.append((start, end))
    if as_ == "count":
        return float(len(kept)) if worker else None
    if as_ != "seconds":
        raise ValueError(f"as_ must be seconds or count, got {as_!r}")
    return _covered(kept) - _covered(taken) if kept else None
