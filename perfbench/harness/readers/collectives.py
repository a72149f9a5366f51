"""Collective operations from the trace: all-gather, reduce-scatter,
all-reduce, collective-permute, each from its start to its done — the
matching events of the "Async XLA Ops" line, and of the "XLA Ops" line for
synchronous ones.  ``op`` is a regex over an operation's label ("<opcode>
<instruction name>").  ``ms_per_step``: the union of those intervals;
``exposed_pct``: the part of them in which no other operation runs on that
device, over the window.  Averaged over the devices."""

import re

from perfbench.harness.trace_reduce import (async_intervals, exposed_seconds,
                                            leaves, total, union)


def read(ctx, op: str, as_: str):
    if ctx.trace is None or len(ctx.devices) < 2:
        return None
    rx = re.compile(op)
    per_device = []
    for d in ctx.devices:
        ops = ctx.trace.ops[d]
        these = async_intervals(ops, op) + [
            (o.start, o.end) for o in ctx.trace.async_ops.get(d, [])
            if rx.search(o.label)]
        if not these:
            return None
        if as_ == "ms_per_step":
            per_device.append(total(union(these)) / ctx.traced_steps * 1e3)
        elif as_ == "exposed_pct":
            others = [(o.start, o.end) for o in leaves(ops)
                      if not rx.search(o.label)]
            per_device.append(
                100.0 * exposed_seconds(these, others) / ctx.window_s)
        else:
            raise ValueError(f"unknown quantity {as_!r}")
    return sum(per_device) / len(per_device)
