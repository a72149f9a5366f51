"""The routed experts' grouped matmuls' share of their roofline where the
layer holds a part of its experts and its row buffer is sized for the worst
case: the least time for the rows that *came* — the program's own counter
``moe_rows_held`` (the assignments the held experts received, a layer, mean
over the window's steps), ``2 * rows * d * f`` FLOPs over the bf16 peak or
the rows in, the held experts' matrices and the result out over the HBM
bandwidth, whichever is larger — over the time the calls took.  A kernel
whose time followed the buffer and not the rows reads low here.  ``op``,
``path`` and ``not_path`` select the calls, as in ``trace_ops``.  Nothing
where the run brought no such counter."""

from perfbench.harness import families, flops
from perfbench.harness.readers.trace_ops import selected


def read(ctx, key: str, op=None, path=None, not_path=None):
    rows = ctx.measured.get(key)
    if rows is None or ctx.trace is None or not ctx.devices:
        return None
    config = ctx.cell.config
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    n_held = families.published(config, ctx.cell.chips, "num_experts")
    call = {"flops": 2.0 * rows * d * f,
            "bytes": 2.0 * (rows * d + n_held * d * f + rows * f)}
    least, _bound = flops.roofline_seconds(call, ctx.peak)
    shares = []
    for dev in ctx.devices:
        found = selected(ctx.trace.ops[dev], path, not_path, op)
        if not found:
            return None
        shares.append(100.0 * least * len(found) / sum(s for _, s in found))
    return sum(shares) / len(shares)
