"""Device time of the operations a pattern selects, from the trace.

``path`` / ``not_path`` are regexes over an operation's name path (its flax
module scopes), ``op`` over its label, "<opcode> <instruction name>"
(``custom-call:tpu_custom_call attn.4``).  Time is self time — what no
nested operation covers — so a ``while`` and its body are not counted twice.
``as_``: ``share_pct`` (of busy time), ``ms_per_step``, or ``calls_per_step``.
Averaged over the devices.
"""

import re

from perfbench.harness.trace_reduce import busy_seconds, self_seconds


def selected(ops, path=None, not_path=None, op=None):
    rx = {k: re.compile(v) for k, v in
          (("path", path), ("not_path", not_path), ("op", op)) if v}
    out = []
    for o, secs in self_seconds(ops):
        if "path" in rx and not rx["path"].search(o.path):
            continue
        if "not_path" in rx and rx["not_path"].search(o.path):
            continue
        if "op" in rx and not rx["op"].search(o.label):
            continue
        out.append((o, secs))
    return out


def read(ctx, as_: str, path=None, not_path=None, op=None):
    if ctx.trace is None or not ctx.devices:
        return None
    per_device = []
    for d in ctx.devices:
        found = selected(ctx.trace.ops[d], path, not_path, op)
        if not found:
            return None
        secs = sum(s for _, s in found)
        if as_ == "share_pct":
            per_device.append(100.0 * secs / busy_seconds(ctx.trace.ops[d]))
        elif as_ == "ms_per_step":
            per_device.append(secs / ctx.traced_steps * 1e3)
        elif as_ == "calls_per_step":
            per_device.append(len(found) / ctx.traced_steps)
        else:
            raise ValueError(f"unknown quantity {as_!r}")
    return sum(per_device) / len(per_device)
