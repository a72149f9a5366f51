"""One run of one cell: the cell's traffic kind drives the system under test
(``kinds/<kind>.py::run``) and brings back measurements; here they become the
line the benchmark prints.

This process never initializes a JAX backend: a chip belongs to one process,
and that process is the program's worker.
"""

from __future__ import annotations

import glob
import importlib
import os
import shutil
import sys
from typing import Any, Dict, List, Optional

from perfbench.harness import manifest
from perfbench.harness.readers import device_busy, memory_peak, rounds
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import (Trace, gaps, label_gaps,
                                            self_seconds)

TRACE_DIR = os.path.join(manifest.ROOT, ".perfbench_trace")
MEMORY_KEYS = ("peak_bytes_in_use", "peak_bytes_reserved")


class Refused(Exception):
    """The run is not a measurement: no line is printed."""


def kind_of(cell: manifest.Cell):
    """The module of the cell's traffic kind: see ``kinds/__init__.py``."""
    return importlib.import_module(
        f"perfbench.harness.kinds.{cell.traffic['kind']}")


def run_cell(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> Dict[str, Any]:
    """Drive the cell once, as its traffic kind does, and return the
    measurements the system under test brought back."""
    trace_dir = os.path.join(TRACE_DIR, cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return kind_of(cell).run(cell, seed=seed, seconds=seconds, trace=trace,
                             t_start=t_start, trace_dir=trace_dir)


def backend_initialized() -> bool:
    """Whether this process holds a JAX backend: the benchmark's own process
    must not (a chip belongs to one process, the train worker)."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def show_worker_logs(tail: int = 60) -> None:
    """Worker output is not echoed to the driver; on a failure its end goes
    to stderr, so that one chip call says what went wrong."""
    root = os.environ.get("RAY_TPU_TMPDIR", "/tmp/ray_tpu")
    for path in sorted(glob.glob(os.path.join(root, "session_*", "logs",
                                              "worker-*")))[-4:]:
        try:
            with open(path, errors="replace") as f:
                lines = f.readlines()[-tail:]
        except OSError:
            continue
        print(f"--- {path}", file=sys.stderr)
        sys.stderr.writelines(lines)


def _read_metric(ctx: Context, metric_file: Dict[str, Any]) -> Optional[float]:
    reader = importlib.import_module(
        f"perfbench.harness.readers.{metric_file['reader']}")
    return reader.read(ctx, **metric_file.get("args", {}))


_NOT_MODULES = {"checkpoint", "rematted_computation", "while", "body", "cond",
                "closed_call", "pallas_call", "shard_map"}


def _group(op) -> str:
    """A name for the breakdown.  From the name path the trace prints: the
    flax modules (``h_3/attn/wq`` -> ``h_*/attn/wq``), with ``bwd`` where the
    path is a transpose and ``remat`` where it is recomputation; where the
    path names no module (loss, optimizer), its wrappers and primitive; where
    the trace has no path, the opcode."""
    import re

    if not op.path:
        return op.kind
    parts = op.path.split("/")[1:]          # without "jit(<step>)"
    modules = [p for p in parts[:-1] if "(" not in p and p not in _NOT_MODULES]
    if not modules:
        return "/".join(parts)[-120:]
    label = re.sub(r"\bh_\d+\b", "h_*", "/".join(modules))
    if "rematted_computation" in parts:
        label += " remat"
    elif any(p.startswith("transpose(") for p in parts):
        label += " bwd"
    return label[-120:]


def _breakdown(ctx: Context, top: int = 10) -> Dict[str, List]:
    n = len(ctx.devices)
    by_op: Dict[str, float] = {}
    by_span: Dict[str, float] = {}
    for d in ctx.devices:
        ops = ctx.trace.ops[d]
        for op, secs in self_seconds(ops):
            group = _group(op)
            by_op[group] = by_op.get(group, 0.0) + secs / n
        idle = gaps(ops, ctx.trace.window())
        for name, secs in label_gaps(idle, ctx.trace.spans).items():
            by_span[name] = by_span.get(name, 0.0) + secs / n
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {"device_ops": [[k, v] for k, v in rank(by_op)],
            "idle_gaps": [[k, v] for k, v in rank(by_span)]}


def verdict(cell: manifest.Cell, m: Dict[str, Any]) -> Dict[str, bool]:
    """Every condition of ``correct``, by name: the kind's own, and those of
    every kind."""
    return dict(
        kind_of(cell).verdict(cell, m),
        no_compile_in_window=not m["compiled_in_window"],
        device_is_the_cells=(m["device"]["platform"] == "tpu"
                             and m["device"]["count"] == cell.chips))


def result_line(cell: manifest.Cell, m: Dict[str, Any], trace: bool
                ) -> Dict[str, Any]:
    """The JSON object a run prints last.  Raises ``Refused`` where the device
    is not a TPU with an entry in ``peaks.json``: a number from anything else
    is never written under the name of a device metric."""
    kind = kind_of(cell)
    device = dict(m["device"])
    peak = manifest.peaks().get(device["kind"])
    if device["platform"] != "tpu" or peak is None:
        raise Refused(f"ran on {device}: not a TPU with an entry in peaks.json")
    if device["count"] != cell.chips:
        raise Refused(f"{device['count']} devices, the cell asks for "
                      f"{cell.chips}")
    device["memory_peak_bytes"] = memory_peak.peak_bytes(m["memory"],
                                                         MEMORY_KEYS)
    units = {e["name"]: e["unit"] for e in cell.end_to_end + cell.per_layer}
    line: Dict[str, Any] = {}
    if not trace:
        measured = kind.end_to_end(cell, m, peak)
        values = {e["name"]: measured.get(e["name"]) for e in cell.end_to_end}
    else:
        reduced = m.get("trace")
        if not reduced:
            raise Refused("the traced run brought back no trace")
        with open(reduced["file"]) as f:
            ctx = Context(cell, peak, m, Trace.from_json(f.read()),
                          reduced["steps"])
        if not ctx.devices:
            raise Refused("no operation ran on the device in the trace")
        values = {e["name"]: _read_metric(ctx, e["file"])
                  for e in cell.per_layer}
        device["busy_s"] = device_busy.mean_busy_s(ctx)
        device["window_s"] = ctx.window_s
        line["breakdown"] = _breakdown(ctx)
        # read by people: the rounds that are the profiler's calls' and so
        # in neither round_worst_excess_s nor rounds_stalled
        line["rounds_left_out"] = rounds.read(ctx, "left_out")
    checks = verdict(cell, m)
    line.update({
        "correct": all(checks.values()),
        "attempted": m["steps"],
        "failed": m["failed"],
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items() if v is not None},
        "device": device,
        # read by people, not by the driver
        "checks": checks,
        **kind.detail(m),
    })
    return line
