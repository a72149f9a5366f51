"""A kernel's share of its roofline: the least time the chip could take for
the calls the trace shows (``flops.py``: FLOPs over the bf16 peak or bytes over
the HBM bandwidth, whichever is larger) over the time they took.  ``work`` names the function
that counts one call on one device, as ``<module of harness/>.<function>``
(``flash_work.fwd_call``: a later kernel brings its count in a module of its
own); a function that takes a ``path`` is given each call's own name path, so
that one entry's count follows the scope the call ran under (a band or the
whole row).  ``op``, ``path`` and ``not_path`` select the calls, as in
``trace_ops``."""

import importlib
import inspect

from perfbench.harness import flops
from perfbench.harness.readers.trace_ops import selected


def read(ctx, work: str, op=None, path=None, not_path=None):
    if ctx.trace is None or not ctx.devices:
        return None
    traffic = ctx.cell.traffic
    mesh = traffic["mesh"]
    # one device's share: its replica's rows, its tp share of the heads
    model_parallel = mesh.get("tp", 1) * mesh.get("sp", 1) * mesh.get("pp", 1)
    rows = traffic["rows_per_step"] // (ctx.cell.chips // model_parallel)
    module, _, function = work.rpartition(".")
    count = getattr(importlib.import_module(f"perfbench.harness.{module}"),
                    function)
    by_path = "path" in inspect.signature(count).parameters

    def least(o):
        call = count(ctx.cell.config, ctx.cell.chips, rows, traffic["seq"],
                     **({"path": o.path} if by_path else {}))
        return flops.roofline_seconds(
            {k: v / mesh.get("tp", 1) for k, v in call.items()}, ctx.peak)[0]

    shares = []
    for d in ctx.devices:
        found = selected(ctx.trace.ops[d], path, not_path, op)
        if not found:
            return None
        shares.append(100.0 * sum(least(o) for o, _ in found)
                      / sum(s for _, s in found))
    return sum(shares) / len(shares)
