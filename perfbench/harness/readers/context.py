"""What a reader is given: everything the traced run collected, nothing it
has to fetch."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from perfbench.harness.manifest import Cell
from perfbench.harness.trace_reduce import Trace


@dataclass
class Context:
    cell: Cell
    peak: Dict[str, float]               # the device kind's row of peaks.json
    measured: Dict[str, Any]             # the worker's last report
    trace: Optional[Trace]               # clipped to the traced window
    traced_steps: int

    @property
    def window_s(self) -> float:
        a, b = self.trace.window()
        return b - a

    @property
    def devices(self) -> List[int]:
        return sorted(self.trace.ops)
