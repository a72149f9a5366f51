"""BENCHMARK.json and the data files it names.

A cell is found by its name alone: its configuration file is the ``file`` of
its ``config`` entry, its traffic file is ``traffic/<traffic>.json``, and a
per-layer metric's reader is named in ``layer_metrics/<metric>.json``.  A later
PR adds a cell, a metric, a traffic kind or a reader by adding files and
entries; nothing in this module lists one.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def peaks() -> Dict[str, Dict[str, Any]]:
    """Published peaks keyed by ``device_kind``; a kind that is not here is an
    error where it is looked up, never a default."""
    return _load(os.path.join(BENCH_DIR, "harness", "peaks.json"))


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]       # the configuration file, as it is run
    traffic: Dict[str, Any]      # the traffic file
    end_to_end: List[Dict[str, Any]]   # BENCHMARK.json entries this cell reports
    per_layer: List[Dict[str, Any]]    # each with its metric file under "file"


def _reported_in(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str) -> Cell:
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (has: "
                       f"{[w['name'] for w in bench['workloads']]})")
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    config = _load(os.path.join(ROOT, config_entry["file"]))
    traffic = _load(os.path.join(BENCH_DIR, "traffic",
                                 entry["traffic"] + ".json"))
    end_to_end = [m for m in bench["end_to_end"] if _reported_in(m, name)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [
        dict(m, file=_load(os.path.join(BENCH_DIR, "layer_metrics",
                                        m["name"] + ".json")))
        for m in bench["per_layer"]
        if _reported_in(m, name) and m["moves"] in reported]
    return Cell(name, int(entry["chips"]), config, traffic, end_to_end,
                per_layer)
