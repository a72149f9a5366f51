"""Pipeline parallelism: SPMD GPipe over a ``pp`` mesh axis.

The reference has no pipeline engine in core (SURVEY §2.3: PP "absent from
core"; compiled DAGs + NCCL channels are the intended substrate).  The
TPU-native equivalent needs no channel runtime at all: every pp rank runs the
SAME program under ``shard_map``; stage weights live sharded on ``pp``;
activations rotate ranks with ``jax.lax.ppermute`` over ICI each step of a
``fori_loop`` schedule.  XLA sees one static program — the "pipeline" is just
a rolled loop with neighbor permutes (the scaling-book recipe).

Schedule: classic GPipe fill-drain.  M microbatches, S stages,
T = M + S - 1 ticks; rank 0 ingests microbatch t at tick t; rank S-1 emits
microbatch t-(S-1).  Bubble fraction (S-1)/T, amortized by more microbatches.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


def stack_stage_params(per_stage_params) -> Any:
    """[stage0_tree, stage1_tree, ...] -> one tree with leading stage dim."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def pipeline_apply(stage_fn: Callable, stacked_params, microbatches,
                   mesh, axis: str = "pp"):
    """Run ``stage_fn(params, x)`` as an S-stage pipeline.

    Args:
      stage_fn: one pipeline stage; same signature on every rank.
      stacked_params: pytree with leading dim S, sharded over ``axis``.
      microbatches: (M, ...) array of microbatch inputs (replicated).
      mesh: jax Mesh containing ``axis``.
    Returns: (M, ...) outputs of the final stage (replicated).
    """
    from jax.sharding import PartitionSpec as P

    S = mesh.shape[axis]
    M = microbatches.shape[0]
    T = M + S - 1
    fwd = [(i, (i + 1) % S) for i in range(S)]

    param_specs = jax.tree_util.tree_map(
        lambda _: P(axis), stacked_params)

    def run(params_local, xs):
        rank = jax.lax.axis_index(axis)
        stage_p = jax.tree_util.tree_map(lambda a: a[0], params_local)

        def tick(t, carry):
            buf, outs = carry
            # rank 0 ingests microbatch t; downstream ranks consume what
            # arrived over ICI last tick.  Clip keeps the gather in-bounds
            # during the drain phase (values unused then).
            ingest = xs[jnp.clip(t, 0, M - 1)]
            x_in = jnp.where(rank == 0, ingest, buf)
            y = stage_fn(stage_p, x_in)
            # final stage writes microbatch t-(S-1) once it's real
            mb = t - (S - 1)
            is_out = jnp.logical_and(rank == S - 1, mb >= 0)
            outs = jnp.where(
                is_out,
                outs.at[jnp.clip(mb, 0, M - 1)].set(y),
                outs)
            buf = jax.lax.ppermute(y, axis, fwd)
            return buf, outs

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        _, outs = jax.lax.fori_loop(0, T, tick, (buf0, outs0))
        # outs is populated only on the last rank; psum over the (otherwise
        # zero) copies replicates it without a separate broadcast.
        return jax.lax.psum(outs, axis)

    # Partial-manual: ONLY the pp axis is manual, so dp/fsdp/tp/sp shardings
    # of the activations stay under GSPMD and compose with the pipeline
    # untouched.  Partial-manual shard_map is rejected outside jit, hence the
    # jit here (a no-op when the caller is already tracing).
    mapped = jax.shard_map(run, mesh=mesh, in_specs=(param_specs, P()),
                           out_specs=P(), axis_names={axis}, check_vma=False)
    return jax.jit(mapped)(stacked_params, microbatches)
