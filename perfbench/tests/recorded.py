"""The recorded step of ``mistral-s8k-1chip`` (``fixtures/``, PR 22) for the
tests that read it through today's metric files.  The program of PR 22 gave
its flash forward no scope of its own (``.../h_0/attn/pallas_call``); since
PR 24 the call runs under ``attn/flash_fwd/flash_fwd``, and since PR 67 the
forward metrics select by that scope.  ``mistral_step`` is the recording with
those four calls under the path today's program gives them, and nothing else
of it touched."""

import dataclasses
import os

from perfbench.harness import manifest
from perfbench.harness.trace_reduce import Trace

FIXTURES = os.path.join(manifest.BENCH_DIR, "fixtures")
OLD, NEW = "/attn/pallas_call", "/attn/flash_fwd/flash_fwd/pallas_call"


def todays(op):
    if op.path.endswith(OLD):
        return dataclasses.replace(op, path=op.path[:-len(OLD)] + NEW)
    return op


def mistral_step() -> Trace:
    with open(os.path.join(FIXTURES, "mistral-s8k-1chip.one-step.json")) as f:
        trace = Trace.from_json(f.read())
    trace.ops = {d: [todays(o) for o in ops] for d, ops in trace.ops.items()}
    return trace
