"""Seconds JAX spent building one jitted function in set-up — tracing,
lowering, and the compiler or the load from the persistent cache — from
``jax.monitoring``'s duration events."""

STAGES = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
          "backend_compile_duration")


def read(ctx, function: str):
    secs = [s for event, name, s in ctx.measured["build_events"]
            if name and function in name and event.rsplit("/", 1)[-1] in STAGES]
    return sum(secs) if secs else None
