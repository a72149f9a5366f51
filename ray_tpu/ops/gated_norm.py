"""The Mamba-2 mixer's gated norm a group, as two Pallas (Mosaic) kernels under
one ``jax.custom_vjp``:

    g = y * silu(z)
    n = g * rsqrt(mean over each group's channels of g^2 + eps)
    out = n * scale

``y``, ``z``: (.., C); ``scale``: (C,); a group is ``C / groups`` channels
side by side (one group: an RMSNorm over all of them).  In plain XLA the same
lines are fusions around a float32 reduction over a part of the lanes, with
float32 arrays of the operands' size between them and, in the backward, a
transpose of that reduction: 37 ms of Nemotron-3-Nano's step at a fifth of
the memory's speed (``PERF.md``, PR 64).  Here the forward is one pass that
reads ``y`` and ``z`` and writes ``out``, and the backward one pass that reads
``y``, ``z`` and the cotangent and writes ``dy`` and ``dz``; nothing but the
inputs is a residual, and no float32 array of the operands' size is in HBM.

A block is some rows by every channel a device holds, so a group is never
cut and a row is one stretch of HBM.  The grid is the blocks, in order.
Inside a block the work goes a group at a time and, in a group, some rows at
a time (``_trip_rows``): the squares of a group's registers are added before
the one reduction along their lanes, so a group of 512 channels pays a
quarter and one of 4,096 a thirty-second of what a 128-wide head pays
(``ops/conv.py::_lane_sums``).  The backward makes ``g`` and the statistic
again, and with ``dn = dout * scale``, ``r`` the rsqrt and ``w`` a group's
width:

    dg = r * dn - g * (r^3 / w) * sum over the group of dn * g
    dy = dg * silu(z);   dz = dg * y * silu'(z);   dscale = sum over rows of dout * g * r

``dscale`` is summed in float32 in its output block, which stays in VMEM over
the whole grid and is written once.

Everything between the load and the store is float32: the gate, the
statistic, the normed value and its product with ``scale`` are rounded once,
at the output.  A statistic in bfloat16, a norm across the groups, or the gate
after the norm is another model (``tests/test_gated_norm_kernel.py``,
``perfbench/harness/families/nemotron_h.py::WRONG``).

``gated_rms_norm`` takes the kernels where a group is whole tiles of 128
lanes and, under an ambient mesh, ``tp`` divides the groups; everywhere else
— a narrower group, or a group that ``tp`` would cut, whose statistic needs
the other shards — ``gated_rms_norm_xla``, the one reference, runs.  Under an
ambient mesh of more than one device the calls run inside a ``shard_map`` —
rows over dp/fsdp, whole groups over tp — since GSPMD cannot partition a
Mosaic call.  The kernels lower through Mosaic unless the process asked for
the Pallas interpreter (``ops/attention.py::_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import LANES, _interpret
from ray_tpu.ops.conv import _sigmoid
from ray_tpu.parallel.mesh import ambient_mesh

# a float32 register's sublanes: the rows of ``dscale``'s block
_SUB = 8
# the fewest rows a trip of the kernels' loops takes: one packed bfloat16
# register, two of float32
_ROWS = 16
# an operand's block in bytes: the backward holds five of them twice
_BLOCK_BYTES = 2 << 20
# float32 registers of one value that a trip keeps between its two passes
_TRIP_REGISTERS = 32
# the calls' VMEM: the backward's ten blocks and what a trip spills, with room
_VMEM_LIMIT = 48 << 20


# ----------------------------------------------------------- jax.numpy form
def gated_rms_norm_xla(y, z, scale, groups: int, eps: float):
    """``gated_rms_norm`` as ``jax.numpy`` under reverse mode: the yardstick
    of the tests and of the on-chip timing, and what runs where the kernels
    do not apply.  Float32 inside, as ``nn.RMSNorm``; the gate before the
    norm."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = g.shape
    if groups > 1:      # a group of heads' channels are normed apart
        g = g.reshape(*shape[:-1], groups, shape[-1] // groups)
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(shape) * scale).astype(y.dtype)


# ------------------------------------------------------------------ kernels
def _lane_block(width: int) -> int:
    """The channels of a group a trip takes at once: the widest of 512 to
    128 lanes that divides the group."""
    return next(c for c in (512, 384, 256, 128) if width % c == 0)


def _trip_rows(rows: int, block: int) -> int:
    """Rows a trip of the loops takes: as many as keep one float32 value of
    a lane block in ``_TRIP_REGISTERS`` registers, and divide the tile."""
    most = max(_ROWS, _TRIP_REGISTERS * _SUB * LANES // block)
    return next(r for r in (64, 48, 32, 16) if r <= most and rows % r == 0)


def _fold_lanes(t):
    """(rows, lanes) -> (rows, 128): a block's registers side by side added
    up, before the one reduction along a register's lanes."""
    return sum(t[:, k:k + LANES] for k in range(0, t.shape[1], LANES))


def _fold_rows(t):
    """(rows, lanes) -> (8, lanes): the registers down a block added up."""
    return sum(t[k:k + _SUB] for k in range(0, t.shape[0], _SUB))


def _lane_blocks(first, width: int):
    """The columns of a group that starts at channel ``first``, a lane block
    at a time."""
    block = _lane_block(width)
    return [pl.ds(first + b, block) for b in range(0, width, block)]


def _walk(rows: int, width: int, groups: int, trip):
    """``trip(rows' slice, first channel)`` over a tile: a group at a time,
    and in a group ``_trip_rows`` rows at a time."""
    step = _trip_rows(rows, _lane_block(width))

    def group(j, carry):
        first = pl.multiple_of(j * width, LANES)

        def some_rows(i, carry):
            trip(pl.ds(pl.multiple_of(i * step, step), step), first)
            return carry

        return lax.fori_loop(0, rows // step, some_rows, carry)

    lax.fori_loop(0, groups, group, 0)


def _fwd_kernel(y_ref, z_ref, scale_ref, out_ref, *, groups: int, eps: float):
    """A tile of rows.  ``y_ref``, ``z_ref``, ``out_ref``: (rows, channels);
    ``scale_ref``: (1, channels) float32."""
    rows, channels = y_ref.shape
    width = channels // groups

    def trip(at, first):
        blocks = _lane_blocks(first, width)
        gated, squares = [], 0.0
        for cols in blocks:
            z = z_ref[at, cols].astype(jnp.float32)
            g = y_ref[at, cols].astype(jnp.float32) * (z * _sigmoid(z))
            gated.append(g)
            squares = squares + _fold_lanes(g * g)
        r = lax.rsqrt(jnp.sum(squares, axis=-1, keepdims=True) / width + eps)
        for cols, g in zip(blocks, gated):
            out_ref[at, cols] = (g * r * scale_ref[:, cols]).astype(
                out_ref.dtype)

    _walk(rows, width, groups, trip)


def _bwd_kernel(y_ref, z_ref, scale_ref, dout_ref, dy_ref, dz_ref, dscale_ref,
                *, groups: int, eps: float):
    """The same tile.  ``dscale_ref``: (8, channels) float32, a register's
    sublanes apart, one block of the output for the whole grid and so its own
    accumulator."""
    rows, channels = y_ref.shape
    width = channels // groups

    @pl.when(pl.program_id(0) == 0)
    def _():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    def gate(at, cols):
        y = y_ref[at, cols].astype(jnp.float32)
        z = z_ref[at, cols].astype(jnp.float32)
        sig = _sigmoid(z)
        silu = z * sig
        return y, sig, silu, y * silu

    def trip(at, first):
        blocks = _lane_blocks(first, width)
        squares, inner = 0.0, 0.0
        for cols in blocks:
            *_, g = gate(at, cols)
            dout_g = dout_ref[at, cols].astype(jnp.float32) * g
            squares = squares + _fold_lanes(g * g)
            inner = inner + _fold_lanes(dout_g * scale_ref[:, cols])
        r = lax.rsqrt(jnp.sum(squares, axis=-1, keepdims=True) / width + eps)
        back = jnp.sum(inner, axis=-1, keepdims=True) * (r * r * r / width)
        for cols in blocks:
            y, sig, silu, g = gate(at, cols)
            dout = dout_ref[at, cols].astype(jnp.float32)
            dg = dout * scale_ref[:, cols] * r - g * back
            dy_ref[at, cols] = (dg * silu).astype(dy_ref.dtype)
            # silu'(z) = sig + silu * (1 - sig)
            dz_ref[at, cols] = (dg * y * (sig + silu * (1.0 - sig))).astype(
                dz_ref.dtype)
            dscale_ref[:, cols] += _fold_rows(dout * g * r)

    _walk(rows, width, groups, trip)


def _tile(rows: int, channels: int, itemsize: int) -> int:
    """Rows of a block: ``_BLOCK_BYTES`` of an operand, whole trips, and no
    more than the rows there are."""
    most = max(_ROWS, _BLOCK_BYTES // (channels * itemsize) // _ROWS * _ROWS)
    return min(most, -(-rows // _ROWS) * _ROWS)


def _call(kernel, name, y, groups, eps, backward: bool):
    """The call of ``kernel`` over ``y``'s tiles.  Forward: ``y``, ``z``,
    ``scale`` -> ``out``; ``backward``: and ``dout`` -> ``dy``, ``dz`` and
    ``dscale``'s eight rows, one block for the whole grid."""
    from jax.experimental.pallas import tpu as pltpu

    rows, channels = y.shape
    tile = _tile(rows, channels, y.dtype.itemsize)
    wide = pl.BlockSpec((tile, channels), lambda i: (i, 0))
    like_y = jax.ShapeDtypeStruct(y.shape, y.dtype)

    def whole(rows):
        return pl.BlockSpec((rows, channels), lambda i: (0, 0))

    return pl.pallas_call(
        functools.partial(kernel, groups=groups, eps=eps),
        grid=(rows // tile,),
        in_specs=[wide, wide, whole(1)] + [wide] * backward,
        out_specs=[wide, wide, whole(_SUB)] if backward else wide,
        out_shape=[like_y, like_y, jax.ShapeDtypeStruct(
            (_SUB, channels), jnp.float32)] if backward else like_y,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(), name=name)


@functools.partial(jax.jit, static_argnums=(3, 4), inline=True)
def _forward(y, z, scale, groups: int, eps: float):
    """Jitted and inlined, as ``ops/conv.py``'s ``_forward``: a model's
    layers share one trace of the kernel, and the equations land in the
    caller's jaxpr under the caller's scopes."""
    return _call(_fwd_kernel, "gated_norm_fwd", y, groups, eps, False)(
        y, z, scale)


@functools.partial(jax.jit, static_argnums=(4, 5), inline=True)
def _backward(y, z, scale, dout, groups: int, eps: float):
    dy, dz, dscale = _call(_bwd_kernel, "gated_norm_bwd", y, groups, eps,
                           True)(y, z, scale, dout)
    return dy, dz, jnp.sum(dscale, axis=0, keepdims=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm(y, z, scale, groups, eps):
    """The kernels under their one differentiation rule.  ``y``, ``z``:
    (rows, channels), rows whole tiles and a group whole lanes; ``scale``:
    (1, channels) float32."""
    return _forward(y, z, scale, groups, eps)


def _norm_fwd(y, z, scale, groups, eps):
    return _forward(y, z, scale, groups, eps), (y, z, scale)


def _norm_bwd(groups, eps, residuals, dout):
    return _backward(*residuals, dout, groups, eps)


_norm.defvjp(_norm_fwd, _norm_bwd)


def gated_rms_norm(y, z, scale, groups: int, eps: float):
    """``y``, ``z``: (batch, seq, channels); ``scale``: (channels,);
    ``groups`` divides the channels.  Returns the normed, scaled
    ``y * silu(z)`` as ``y``; any number of rows (they are padded to whole
    tiles, and the padding's outputs dropped).  A group that is no whole
    tiles of 128 lanes, and under an ambient mesh groups that ``tp`` does not
    divide, take the ``jax.numpy`` form."""
    mesh = ambient_mesh()
    if mesh is not None and mesh.size == 1:
        mesh = None
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    channels = y.shape[-1]
    if (channels // groups) % LANES or groups % tp:
        return gated_rms_norm_xla(y, z, scale, groups, eps)

    def norm(y, z, scale):
        shape = y.shape
        y, z = (t.reshape(-1, shape[-1]) for t in (y, z))
        pad = -y.shape[0] % _tile(*y.shape, y.dtype.itemsize)
        if pad:
            y, z = (jnp.pad(t, [(0, pad), (0, 0)]) for t in (y, z))
        out = _norm(y, z, scale.astype(jnp.float32)[None], groups // tp, eps)
        return out[:out.shape[0] - pad].reshape(shape)

    if mesh is None:
        return norm(y, z, scale)
    rows = tuple(a for a in ("dp", "fsdp") if a in mesh.shape) or None
    channel = "tp" if tp > 1 else None
    wide = P(rows, None, channel)
    return jax.shard_map(norm, mesh=mesh, in_specs=(wide, wide, P(channel)),
                         out_specs=wide, check_vma=False)(y, z, scale)
