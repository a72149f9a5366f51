"""A scope's share of its roofline, where the work under a name path is many
XLA operations a call and not one kernel: the least time the chip could take
for the work of one step (``flops.roofline_seconds``: FLOPs over the bf16 peak
or bytes over the HBM bandwidth, whichever is larger) over the self time under
the path in one step.  ``work`` names the function that counts one step on one
device, as ``<module of harness/>.<function>`` (``ssd_work.scan_step``);
``path`` and ``not_path`` select the operations, as in ``trace_ops``.  Where
the scope holds single kernels, ``kernel_roofline`` divides by their calls
instead."""

import importlib

from perfbench.harness import flops
from perfbench.harness.readers.trace_ops import selected


def read(ctx, work: str, path=None, not_path=None):
    if ctx.trace is None or not ctx.devices:
        return None
    per_device = [selected(ctx.trace.ops[d], path, not_path)
                  for d in ctx.devices]
    if not all(per_device):
        return None     # a program without the scope
    traffic = ctx.cell.traffic
    mesh = traffic["mesh"]
    # one device's share: its replica's rows, its tp share of the heads
    model_parallel = mesh.get("tp", 1) * mesh.get("sp", 1) * mesh.get("pp", 1)
    rows = traffic["rows_per_step"] // (ctx.cell.chips // model_parallel)
    module, _, function = work.rpartition(".")
    count = getattr(importlib.import_module(f"perfbench.harness.{module}"),
                    function)
    step = {k: v / mesh.get("tp", 1) for k, v in count(
        ctx.cell.config, ctx.cell.chips, rows, traffic["seq"]).items()}
    least, _bound = flops.roofline_seconds(step, ctx.peak)
    shares = [100.0 * least * ctx.traced_steps / sum(s for _, s in found)
              for found in per_device]
    return sum(shares) / len(shares)
