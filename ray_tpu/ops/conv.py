"""The short depthwise causal convolution over the sequence with what every
caller puts straight after it, as two Pallas (Mosaic) kernels under one
``jax.custom_vjp``:

    z_t = bias + sum_k kernel[k] * x_{t - (width-1) + k}      zeros before the row's start
    y_t = silu(z_t)
    unit_heads = H:  y_t = scale * y_t / sqrt(sum over each head's C/H columns of y_t^2 + L2_EPS)

``x``: (B, S, C), ``kernel``: (width, C), ``bias``: (C,) or none.  The Mamba-2
and Mamba-1 mixers (``models/mamba.py``) call it with a bias, the Kimi Delta
Attention mixer (``models/kda.py``) and the Gated DeltaNet mixer
(``models/gdn.py``) without one and, for q and k, with a head's unit norm.  In plain XLA the same arithmetic is four padded slices, a
silu and — for the unit norm — two 0 / 1 matmuls at six bf16 passes, forward,
again under ``remat`` and in a backward of three arrays; here it is one pass
over ``x`` forward and one over ``x`` and the cotangent backward.

The grid of either kernel is (channel tile, batch row, sequence tile), the
sequence axis last and sequential.  The forward walks it up and keeps the
tile's last rows in VMEM for the next tile's first taps; the backward walks
it down, reads the rows before its tile through a second, sixteen-row
BlockSpec on ``x`` (the tile before has not been visited), makes the
pre-activation again, goes back through the unit norm and the silu, keeps
the first rows of the pre-activation's cotangent for the transpose's taps in
the tile before, and sums ``dkernel`` and ``dbias`` in float32 in its output
block, which stays in VMEM over the sequence and the batch and is written
once a channel tile.  No padded copy of ``x`` and no residual but the inputs
is in HBM.

Inside a tile the work goes a column of 128 lanes at a time, a loop —
with ``unit_heads`` a column is a head, so its sum of squares is a reduction
along a register's lanes — through float32 copies of the column in VMEM, and
a register holds eight rows an eighth of the column apart (a strided load),
not eight rows in a row: the rows one before a register's are then the
register before, whole, and a tap costs a multiply-add and no turn of the
sublanes (``_registers``).  By the chip's clock at 1 x 16,384 x 4,096
(``PERF.md``, PR 60), forward / forward + backward: XLA's fusions 0.98 /
4.46 ms; eight rows in a row and the taps turned by the rotate unit 0.83 /
2.27; strided at a stride of whole tiles 0.63 / 2.67; at an odd stride and
with no buffer both read and written in a loop 0.455 / 1.12 with a tile's
four columns written out and 0.52 / 1.27 as the loop they are (a quarter of
the kernel to trace and to compile); with the unit norm 1.31 / 2.99 against
XLA's 1.63 / 5.91.

Everything between the load and the store is float32: the taps are summed
before they are rounded once at the output, the unit norm's statistic and
product as well.  ``kernel`` and ``bias`` come in the activations' dtype and
their gradients leave in it.

``conv_silu`` takes the kernels where a device's channels are whole tiles
of 128 lanes and, with ``unit_heads``, a head is 128 columns, and
``silu(models/mamba.py::causal_conv(..))`` with the unit norm written out —
the one reference — everywhere else.  Under an ambient mesh of more than one
device the calls run inside a ``shard_map`` — rows over dp/fsdp, channels
(whole heads) over tp: a depthwise convolution needs nothing of another
shard — since GSPMD cannot partition a Mosaic call.  The kernels lower
through Mosaic unless the process asked for the Pallas interpreter
(``ops/attention.py::_interpret``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import LANES, _interpret
from ray_tpu.parallel.mesh import ambient_mesh

L2_EPS = 1e-6
# a float32 register's sublanes: the rows of the parameters' block (the taps,
# the bias, the heads' length) and the most rows a tap reaches back
_SUB = 8
# rows a trip of the kernels' loops takes, and of the block of ``x`` before a
# tile: one packed bfloat16 register, two of float32
_ROWS = 16
# the most positions a grid step takes (256 to 2,048 read the same on the chip)
_SEQ_TILE = 512
# steps a trip of the kernels' loops takes, written out in the trip's body
_TRIP = 8


# ----------------------------------------------------------- jax.numpy form
def conv_silu_xla(x, kernel, bias=None, *, unit_heads=None, scale=1.0):
    """``conv_silu`` as ``jax.numpy`` under reverse mode: the yardstick of
    the tests and of the on-chip timing, and what runs where the kernels
    cannot address the channels."""
    from ray_tpu.models.kda import _unit            # both import this module
    from ray_tpu.models.mamba import causal_conv

    y = jax.nn.silu(causal_conv(x, kernel, bias))
    return y if unit_heads is None else _unit(y, unit_heads, scale)


# ------------------------------------------------------------------ kernels
def _sigmoid(z):
    return 0.5 + 0.5 * jnp.tanh(0.5 * z)


def _lane_sums(t):
    """Each row's sum over its 128 lanes: thirteen cycles a register on the
    rotate unit, the price of the unit norm (as three bfloat16 matmuls with
    ones it read twice that, ``PERF.md``, PR 60)."""
    return jnp.sum(t, axis=-1, keepdims=True)


def _columns(ref, width: int, cols):
    """The parameters of 128 columns: their taps from the last — the one on a
    position's own row — to the first, their bias and the length their heads
    leave at, each spread down a register's sublanes."""
    *w, bias, scale = (jnp.broadcast_to(ref[k:k + 1, cols], (_SUB, LANES))
                       for k in range(width + 2))
    return w[::-1], bias, scale


def _weighted(w, taps):
    out = w[0] * taps[0]
    for tap, t in zip(w[1:], taps[1:]):
        out = out + tap * t
    return out


def _registers(buf, j: int, stride: int, at: int = 0):
    """``a ->`` register ``a`` of column ``j`` of a buffer, counted from its
    row ``at``: the rows ``at + a, at + a + stride, .., at + a + 7 stride``,
    one to a sublane.  ``stride`` is odd — an eighth of the tile's rows and
    eight more —, so the eight rows lie in eight banks of VMEM (at a stride
    of whole tiles of eight they lay in one and the load took eight turns).
    The rows ``s`` before a register's are then register ``a - s``, whole.
    For the few ``a`` below 0 or from ``stride`` on, known when the kernel
    is traced, the same rows are the register a segment on or back, turned
    a sublane; the sublane that comes round holds rows nobody reads."""
    from jax.experimental.pallas import tpu as pltpu

    def register(a):
        on = 0 if not isinstance(a, int) else (a >= stride) - (a < 0)
        t = buf[j, pl.ds(at + a - on * stride, _SUB, stride=stride), :]
        return pltpu.roll(t, (_SUB - on) % _SUB, 0) if on else t

    return register


def _walk(n: int, width: int, first, step, carry, down: bool = False):
    """``carry = step(a, taps, carry)`` for the registers ``a`` = 0 .. n - 1
    (``down``: n - 1 .. 0) with ``taps[m]`` the register ``m`` steps back
    along the walk, ``first(a -+ m)``: one load a step, the others carried.
    ``_TRIP`` steps a trip of the loop, written out, and the last ``n %
    _TRIP`` after it; ``first`` sees an ``a`` outside 0 .. n - 1 only as a
    Python int."""
    trip = min(_TRIP, n)

    def advance(i, window, carry):
        a = n - 1 - i if down else i
        taps = (first(a), *window)
        return taps[:-1], step(a, taps, carry)

    def several(t, state):
        for u in range(trip):
            state = advance(t * trip + u, *state)
        return state

    edge, back = (n - 1, 1) if down else (0, -1)
    window = tuple(first(edge + back * m) for m in range(1, width))
    state = lax.fori_loop(0, n // trip, several, (window, carry))
    for i in range(n - n % trip, n):
        state = advance(i, *state)
    return state[1]


def _fwd_kernel(x_ref, w_ref, y_ref, ext, ys, *, width: int, unit: bool):
    """A sequence tile of one channel tile.  ``x_ref``, ``y_ref``: (tile,
    channels); ``w_ref``: (8, channels) float32, the taps, the bias and the heads'
    length; ``ext``: (columns of 128, 8 + tile, 128) float32, the eight rows before
    the tile and the tile, whose last eight rows are the next tile's first;
    ``ys``: the same shape, ``y`` at ``ext``'s rows."""
    tile, channels = x_ref.shape
    stride = tile // _SUB + 1
    start = pl.program_id(2) == 0
    def column(j, carry):
        cols = pl.ds(pl.multiple_of(j * LANES, LANES), LANES)

        @pl.when(start)
        def _():
            ext[j, :_SUB] = jnp.zeros((_SUB, LANES), ext.dtype)

        @pl.when(jnp.logical_not(start))
        def _():
            ext[j, :_SUB] = ext[j, tile:]

        ext[j, _SUB:] = x_ref[:, cols].astype(ext.dtype)
        w, bias, scale = _columns(w_ref, width, cols)

        def step(a, taps, carry):
            z = bias + _weighted(w, taps)
            y = z * _sigmoid(z)
            if unit:
                y = y * (lax.rsqrt(_lane_sums(y * y) + L2_EPS) * scale)
            ys[j, pl.ds(a, _SUB, stride=stride), :] = y
            return carry

        _walk(stride, width, _registers(ext, j, stride), step, 0)
        y_ref[:, cols] = ys[j, _SUB:].astype(y_ref.dtype)
        return carry

    lax.fori_loop(0, channels // LANES, column, 0)


def _bwd_kernel(x_ref, before_ref, w_ref, dy_ref, dx_ref, dw_ref, ext, dys,
                dzs, *, width: int, unit: bool):
    """The same tile, the tiles visited last to first (the index maps turn
    the axis).  ``before_ref``: (16, channels), the rows of ``x`` before the
    tile; ``dw_ref``: (8, channels) float32, the gradients of ``w_ref``'s
    rows, one block of the output for a channel tile's whole walk and so its
    own accumulator; ``ext``, ``dys``: as the forward's ``ext``: ``x`` and, at
    its rows, ``dy``, zeros in the first eight; ``dzs``: the cotangent of the
    pre-activation at the same rows and then the first eight rows of the
    tile after's; ``ext`` and ``dzs`` are (columns, 8 + tile + 8, 128), and
    ``ext`` from its ninth row takes ``dx`` on its way out."""
    tile, channels = x_ref.shape
    stride = tile // _SUB + 1
    walked = pl.program_id(2)
    # nothing lies before the row's first tile
    kept = (walked < pl.num_programs(2) - 1).astype(jnp.float32)

    @pl.when((pl.program_id(1) == 0) & (walked == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def column(j, carry):
        cols = pl.ds(pl.multiple_of(j * LANES, LANES), LANES)
        ext[j, :_SUB] = before_ref[:, cols].astype(ext.dtype)[_SUB:] * kept
        ext[j, _SUB:_SUB + tile] = x_ref[:, cols].astype(ext.dtype)

        @pl.when(walked == 0)
        def _():
            dzs[j, _SUB + tile:] = jnp.zeros((_SUB, LANES), dzs.dtype)

        @pl.when(walked > 0)
        def _():
            dzs[j, _SUB + tile:] = dzs[j, _SUB:2 * _SUB]

        dys[j, :_SUB] = jnp.zeros((_SUB, LANES), dys.dtype)
        dys[j, _SUB:] = dy_ref[:, cols].astype(dys.dtype)
        w, bias, scale = _columns(w_ref, width, cols)

        dy = _registers(dys, j, stride)

        def step(a, taps, sums):
            z = bias + _weighted(w, taps)
            gate = _sigmoid(z)
            dz = dy(a)
            if unit:
                y = z * gate
                r = lax.rsqrt(_lane_sums(y * y) + L2_EPS)
                dz = (dz - y * (_lane_sums(dz * y) * (r * r))) * (r * scale)
            dz = dz * (gate * (1.0 + z * (1.0 - gate)))
            dzs[j, pl.ds(a, _SUB, stride=stride), :] = dz
            return *(s + dz * t for s, t in zip(sums, taps)), sums[-1] + dz

        zero = jnp.zeros((_SUB, LANES), jnp.float32)
        *sums, bias_sum = _walk(stride, width, _registers(ext, j, stride),
                                step, (zero,) * (width + 1))
        for k, s in enumerate((*sums[::-1], bias_sum)):
            dw_ref[k:k + 1, cols] += jnp.sum(s, axis=0, keepdims=True)

        def step_back(a, taps, carry):
            # the transpose: the cotangent at the rows and the width - 1
            # after them, from the tile's own first row on
            ext[j, pl.ds(_SUB + a, _SUB, stride=stride), :] = _weighted(
                w, taps)
            return carry

        _walk(stride, width, _registers(dzs, j, stride, _SUB), step_back, 0,
              down=True)
        dx_ref[:, cols] = ext[j, _SUB:_SUB + tile].astype(dx_ref.dtype)
        return carry

    lax.fori_loop(0, channels // LANES, column, 0)


def _tiles(seq: int, channels: int):
    """(positions, channels) of a block: the widest of 512 to 128 lanes that
    divides the channels, and at most ``_SEQ_TILE`` positions."""
    return (min(_SEQ_TILE, -(-seq // _ROWS) * _ROWS),
            next(c for c in (512, 384, 256, 128) if channels % c == 0))


def _call(kernel, name, x, tile, channels, **kwargs):
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, width = x.shape
    return pl.pallas_call(
        kernel, grid=(width // channels, batch, seq // tile),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=_interpret(), name=name, **kwargs)


@functools.partial(jax.jit, static_argnums=(2, 3), inline=True)
def _forward(x, w, width: int, unit: bool):
    """Jitted and inlined, as ``ops/ssd.py``'s ``_forward``: a model's layers
    share one trace of the kernel, and the equations land in the caller's
    jaxpr under the caller's scopes."""
    from jax.experimental.pallas import tpu as pltpu

    tile, channels = _tiles(*x.shape[1:])
    wide = pl.BlockSpec((None, tile, channels), lambda c, b, i: (b, i, c))
    return _call(
        functools.partial(_fwd_kernel, width=width, unit=unit),
        "conv_silu_fwd", x, tile, channels,
        in_specs=[wide, pl.BlockSpec((_SUB, channels),
                                     lambda c, b, i: (0, c))],
        out_specs=wide, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((channels // LANES, _SUB + tile, LANES),
                                   jnp.float32)] * 2,
    )(x, w)


@functools.partial(jax.jit, static_argnums=(3, 4), inline=True)
def _backward(x, w, dy, width: int, unit: bool):
    from jax.experimental.pallas import tpu as pltpu

    tile, channels = _tiles(*x.shape[1:])
    last = x.shape[1] // tile - 1
    wide = pl.BlockSpec((None, tile, channels),
                        lambda c, b, i: (b, last - i, c))
    before = pl.BlockSpec(
        (None, _ROWS, channels), lambda c, b, i: (
            b, jnp.maximum((last - i) * (tile // _ROWS) - 1, 0), c))
    params = pl.BlockSpec((_SUB, channels), lambda c, b, i: (0, c))
    return _call(
        functools.partial(_bwd_kernel, width=width, unit=unit),
        "conv_silu_bwd", x, tile, channels,
        in_specs=[wide, before, params, wide], out_specs=[wide, params],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(w.shape, w.dtype)],
        scratch_shapes=[pltpu.VMEM((channels // LANES, rows, LANES),
                                   jnp.float32)
                        for rows in (tile + 16, tile + 8, tile + 16)],
    )(x, x, w, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv(x, w, width, unit):
    """The kernels under their one differentiation rule.  ``x``: (batch, seq,
    channels), seq whole tiles and channels whole lanes; ``w``: (8, channels)
    float32, ``width`` taps, the bias and — data, so that q and k share a
    trace of the kernels — the length the heads leave at; ``unit``: whether
    they are normed at all."""
    return _forward(x, w, width, unit)


def _conv_fwd(x, w, width, unit):
    return _forward(x, w, width, unit), (x, w)


def _conv_bwd(width, unit, residuals, dy):
    return _backward(*residuals, dy, width, unit)


_conv.defvjp(_conv_fwd, _conv_bwd)


def conv_silu(x, kernel, bias=None, *, unit_heads=None, scale=1.0):
    """``x``: (batch, seq, channels); ``kernel``: (width, channels);
    ``bias``: (channels,) or None; ``unit_heads``: the heads whose columns
    leave at length ``scale``, or None.  Returns ``y`` as ``x``; a sequence
    may be any length (it is padded at its end to whole tiles, and the
    padding's outputs dropped).  A device's channels that are no whole tiles
    of 128 lanes, a head that is not 128 columns and a kernel of more than
    six taps take the ``jax.numpy`` form."""
    mesh = ambient_mesh()
    if mesh is not None and mesh.size == 1:
        mesh = None
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    width, channels = kernel.shape
    if channels % (LANES * tp) or width + 2 > _SUB or (
            unit_heads is not None and channels != unit_heads * LANES):
        return conv_silu_xla(x, kernel, bias, unit_heads=unit_heads,
                             scale=scale)
    seq = x.shape[1]
    pad = -seq % _tiles(seq, channels // tp)[0]
    if pad:
        x = jnp.pad(x, [(0, 0), (0, pad), (0, 0)])
    w = jnp.zeros((_SUB, channels), jnp.float32).at[:width].set(
        kernel.astype(jnp.float32)).at[width + 1].set(scale)
    if bias is not None:
        w = w.at[width].set(bias.astype(jnp.float32))

    def conv(x, w):
        return _conv(x, w, width, unit_heads is not None)

    if mesh is None:
        y = conv(x, w)
    else:
        rows = tuple(a for a in ("dp", "fsdp") if a in mesh.shape) or None
        channel = "tp" if tp > 1 else None
        y = jax.shard_map(
            conv, mesh=mesh, in_specs=(P(rows, None, channel),
                                       P(None, channel)),
            out_specs=P(rows, None, channel), check_vma=False)(x, w)
    return y[:, :seq]
