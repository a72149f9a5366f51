"""From the busy union of the traced window, averaged over the devices:
``per_step_ms`` (busy milliseconds a step) or ``idle_pct`` (1 - busy /
window)."""

from perfbench.harness.trace_reduce import busy_seconds


def mean_busy_s(ctx):
    return sum(busy_seconds(ctx.trace.ops[d]) for d in ctx.devices) \
        / len(ctx.devices)


def read(ctx, what: str):
    if ctx.trace is None or not ctx.devices:
        return None
    busy = mean_busy_s(ctx)
    if what == "per_step_ms":
        return busy / ctx.traced_steps * 1e3
    if what == "idle_pct":
        return 100.0 * (1.0 - busy / ctx.window_s)
    raise ValueError(f"unknown quantity {what!r}")
