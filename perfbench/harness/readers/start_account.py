"""A start, accounted by the program: ``flight_recorder.start_account`` — the
one function of the program this reader calls — over the newest ``session_*``
under ``RAY_TPU_TMPDIR`` (reader ``bringup`` says which), whose rings are
still on disk when the runtime is down.  The interval runs from the beginning
of the first ``bringup.*`` mark to the train worker's first ``train.report``.

``key``: a path into the account, its parts joined by ``/`` — ``total``,
``unnamed``, ``named/<mark or compile|stage>`` (the seconds that name holds
and none of its children), ``marks/<kind>/<seconds | cpu | off_cpu | majflt |
inblock>`` (a whole mark and what it cost).  Nothing where the program has no
such function (the parent of PR 68), the session has no first report, or the
path ends nowhere.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

from perfbench.harness.readers.bringup import newest_session


@functools.lru_cache(maxsize=2)
def account(session_dir: str) -> Optional[Dict[str, Any]]:
    try:
        from ray_tpu._private import flight_recorder
    except ImportError:
        return None
    read = getattr(flight_recorder, "start_account", None)
    return read(session_dir) if read else None


def read(ctx, key: str, session_dir: Optional[str] = None) -> Optional[float]:
    session_dir = session_dir or newest_session()
    found: Any = account(session_dir) if session_dir else None
    for part in key.split("/"):
        if not isinstance(found, dict) or part not in found:
            return None
        found = found[part]
    return float(found) if isinstance(found, (int, float)) else None
