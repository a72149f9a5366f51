"""In-jit collectives: the ICI path.

These are meant to be called inside jit/shard_map where ``axis_name`` is bound;
XLA lowers them to ICI all-reduce/all-gather/collective-permute — the NCCL
replacement (reference lowers ray.util.collective to cupy/NCCL launches;
here the compiler owns scheduling and fusion).

In-device collectives run inside the compiled program, where a wall-clock
``timeout_s`` is not expressible — a straggling chip is the hang watchdog's
job (nodelet polls busy workers; see docs/ARCHITECTURE.md §5c), not a
Python-level deadline's.
# lint: disable-file=collective-timeout
"""

from __future__ import annotations

from ray_tpu._private.platform import import_jax

# a train worker's first import of jax, as its class load reaches this module
jax = import_jax()


def allreduce(x, axis_name: str = "dp", op: str = "sum"):
    if op == "sum":
        return jax.lax.psum(x, axis_name)
    if op == "mean":
        return jax.lax.pmean(x, axis_name)
    if op == "max":
        return jax.lax.pmax(x, axis_name)
    if op == "min":
        return jax.lax.pmin(x, axis_name)
    raise ValueError(f"unsupported reduce op {op!r}")


def allgather(x, axis_name: str = "dp", axis: int = 0, tiled: bool = True):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reducescatter(x, axis_name: str = "dp", axis: int = 0):
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def broadcast(x, axis_name: str = "dp", root: int = 0):
    # Select the root's value on every member.
    full = jax.lax.all_gather(x, axis_name, axis=0, tiled=False)
    return full[root]

def permute(x, axis_name: str, perm):
    return jax.lax.ppermute(x, axis_name, perm)


def alltoall(x, axis_name: str, split_axis: int = 0, concat_axis: int = 0):
    return jax.lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=True)
