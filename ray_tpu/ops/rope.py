"""The rotary embedding of a layer's queries and keys, and a per-head RMSNorm
before it, as one pass over both where ``wq`` / ``wk`` wrote them: two Pallas
(Mosaic) kernels under one ``jax.custom_vjp`` that read and write
``(B, S, H * D)`` and ``(B, S, KV * D)``, the layout the flash kernels read
(``ops/attention.py``), so that no ``heads()`` turn stands between a
projection and its kernel:

    n = x * rsqrt(mean over the head's lanes of x^2 + eps) * scale   (or x)
    out = n * C + swap(n) * S

``C``, ``S``: the position's cos and sin a head wide (1 and 0 on the lanes
that pass: a table narrower than the head turns its first lanes); ``swap``
rotate-half over the turned lanes, ``swap(n)[j] = n[j + rot/2]`` in their
first half and ``n[j - rot/2]`` in their second, its sign in ``S``.  The
arithmetic is ``models/llama.py::apply_rope``'s (``_rotate``,
``_norm_rotate``: the reference, and what runs where ``takes`` says no):
float32 from the load to the one rounding at the store, the scale in the
tables and the statistic last; the backward is the opposite rotation of the
cotangent — for the rotation alone the forward kernel itself at ``-S`` — and
under the norm one pass that turns the cotangent back, rounds it where
``_norm_rotate_bwd`` does, and writes ``dq``, ``dk`` and the two scales'
gradients, eight rows each that stay in VMEM over the whole grid.

**One call a direction.**  A grid step is a tile of positions (``_MOST_ROWS``
at most) by one group of q's columns and the same group of k's (``_plan``);
inside it a ``lax.fori_loop`` walks each operand's blocks of 128 lanes — a head
of 128, or two of 64 side by side.  What crosses lanes goes through the MXU,
which has nothing else to do here: the half-swap and a head's sums are
matmuls by matrices of 0 and 1 made in the kernel, the operand as it comes
for the swap, its square as two bfloat16 pieces (sixteen bits: exact), a
float32 value as three (``_split``), so every product is exact and every sum
float32; the rotate unit's turn of the lanes and its reduction along them
each cost more than XLA's whole pass (``PERF.md``, PR 69).  The norm's scales
are data (``scales``: q's, q's as the swapped lanes see it, k's, k's), and no
norm is the same body without the statistic (``eps`` None).

**Short to trace.**  A body is a few dozen equations whatever the heads and
the rows: nothing is written out a head, a column or a row (the sum down a
block's rows is one reshape and one reduction of whole registers), and the
entries are jitted and inlined, so a model's layers, a block's recomputation
and, for the rotation alone, both directions share one trace
(``tests/test_rope.py`` holds both).  PR 69's pair, one call an operand and
256 slices written out in its backward, cost a start six seconds.

``takes`` says from the operands' shapes and the ambient mesh whether
``rope_qk`` can run: heads that fill blocks of 128 lanes whole on every
device, an even rotary width no wider than a head.  Any number of positions
(they are padded to whole tiles, and the padding's outputs dropped).  Under an
ambient mesh of more than one device the calls run inside a ``shard_map`` —
rows over dp / fsdp, whole blocks of heads over tp — since GSPMD cannot
partition a Mosaic call.  The kernels lower through Mosaic unless the
process asked for the Pallas interpreter (``ops/attention.py::_interpret``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import LANES, _interpret
from ray_tpu.parallel.mesh import ambient_mesh

# a float32 register's sublanes: the rows of a scale's gradient
_SUB = 8
# positions come in whole packed bfloat16 registers
_ROWS = 16
# the most positions of a block: the MXU takes a matrix once for them all
_MOST_ROWS = 2048
# q's and k's block of a grid step together, in bytes: the backward holds
# three of them twice
_BLOCK_BYTES = 6 << 20
_VMEM_LIMIT = 64 << 20


# ------------------------------------------------------------------ kernels
def _lanes(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _swap_matrix(width: int, half: int, pieces: int):
    """(pieces * 128, 128) of 0 and 1: ``t @ m`` is ``swap(t)`` along the
    lanes of a block of heads ``width`` wide, the turned part ``2 * half`` of
    each (0 on the lanes past it), of the sum of ``t``'s pieces."""
    shape = (pieces * LANES, LANES)
    src, dst = _lanes(shape, 0) % LANES, _lanes(shape, 1)
    within = dst % width
    takes = jnp.where(within < half, dst + half, dst - half)
    return ((src == takes) & (within < 2 * half)).astype(jnp.bfloat16)


def _sum_matrix(width: int, pieces: int):
    """(pieces * 128, 128) of 0 and 1: ``t @ m`` gives each lane its own
    head's sum along the lanes, of the sum of ``t``'s pieces."""
    shape = (pieces * LANES, LANES)
    return (_lanes(shape, 0) % LANES // width == _lanes(shape, 1) // width
            ).astype(jnp.bfloat16)


def _pieces(dtype, squared: bool = False) -> int:
    """How many bfloat16 pieces hold a float32 value whole: three, two if it
    is the square of a bfloat16, one if it is a bfloat16."""
    if dtype != jnp.bfloat16:
        return 3
    return 2 if squared else 1


def _split(t, pieces: int):
    """``t`` (rows, 128) as ``pieces`` bfloat16 pieces that sum to it, side
    by side along the lanes: the contraction of ``_dot``."""
    if pieces == 1:
        return t.astype(jnp.bfloat16)
    parts = []
    for _ in range(pieces - 1):
        parts.append(t.astype(jnp.bfloat16))
        t = t - parts[-1].astype(jnp.float32)
    return jnp.concatenate(parts + [t.astype(jnp.bfloat16)], axis=-1)


def _dot(a, m):
    # each product is by 0 or 1 and exact; the sums are float32
    return jnp.dot(a, m, preferred_element_type=jnp.float32)


def _blocks(ref, block, carry=0):
    """``block(columns, carry)`` over ``ref``'s blocks of 128 lanes."""
    def one(j, carry):
        return block(pl.ds(pl.multiple_of(j * LANES, LANES), LANES), carry)
    return lax.fori_loop(0, ref.shape[1] // LANES, one, carry)


def _fwd_kernel(q_ref, k_ref, c_ref, s_ref, *rest, width: int, half: int,
                eps):
    """A tile of positions by a group of q's columns and of k's.  ``c_ref``,
    ``s_ref``: (rows, 128) float32, the sine's with the swap's sign.  Then,
    ``eps`` given, ``scales_ref`` (8, 128) float32 — rows 0 and 1 q's scale
    and the scale of the lane the swap takes from, rows 2 and 3 k's —; the two
    outputs; and under the norm two (rows, 128) float32 scratch refs for an
    operand's scaled tables."""
    if eps is None:
        outs, tables = rest, (c_ref, s_ref)
    else:
        scales_ref, *outs, c_scaled, s_scaled = rest
        tables = (c_scaled, s_scaled)
    dtype = q_ref.dtype
    swap = _swap_matrix(width, half, _pieces(dtype))
    sums = None if eps is None else _sum_matrix(width, _pieces(dtype, True))
    for at, (x_ref, out_ref) in enumerate(zip((q_ref, k_ref), outs)):
        if eps is not None:
            c_scaled[...] = c_ref[...] * scales_ref[2 * at:2 * at + 1, :]
            s_scaled[...] = s_ref[...] * scales_ref[2 * at + 1:2 * at + 2, :]

        def block(cols, carry, x_ref=x_ref, out_ref=out_ref):
            x = x_ref[:, cols]
            xf = x.astype(jnp.float32)
            y = xf * tables[0][...] + _dot(
                _split(x, _pieces(dtype)), swap) * tables[1][...]
            if eps is not None:
                y = y * lax.rsqrt(_dot(_split(
                    xf * xf, _pieces(dtype, True)), sums) / width + eps)
            out_ref[:, cols] = y.astype(out_ref.dtype)
            return carry

        _blocks(x_ref, block)


def _fold_rows(t):
    """(rows, 128) -> (8, 128): the registers down a block added up."""
    return jnp.sum(t.reshape(-1, _SUB, LANES), axis=0)


def _bwd_kernel(q_ref, k_ref, gq_ref, gk_ref, c_ref, s_ref, scales_ref,
                dq_ref, dk_ref, dscale_ref, *, width: int, half: int, eps):
    """The same tile of normed operands.  ``c_ref``, ``s_ref``: the plain
    tables; ``dscale_ref``: (16, 128) float32, q's scale's gradient a
    register's sublanes apart and then k's, one block of the output for the
    whole grid and so its own accumulator."""
    @pl.when(sum(pl.program_id(a) for a in range(3)) == 0)
    def _():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    dtype = q_ref.dtype
    swap = _swap_matrix(width, half, _pieces(dtype))
    sums = _sum_matrix(width, _pieces(dtype, True))
    sums3 = _sum_matrix(width, 3)
    for at, (x_ref, g_ref, dx_ref) in enumerate((
            (q_ref, gq_ref, dq_ref), (k_ref, gk_ref, dk_ref))):
        scale = scales_ref[2 * at:2 * at + 1, :]

        def block(cols, dscale, x_ref=x_ref, g_ref=g_ref, dx_ref=dx_ref,
                  scale=scale):
            x, g = x_ref[:, cols].astype(jnp.float32), g_ref[:, cols]
            # the cotangent turned back, rounded as ``_norm_rotate_bwd``'s
            g = (g.astype(jnp.float32) * c_ref[...] - _dot(
                _split(g, _pieces(dtype)), swap) * s_ref[...]).astype(
                    dtype).astype(jnp.float32)
            xg = x * g
            r = lax.rsqrt(_dot(_split(
                x * x, _pieces(dtype, True)), sums) / width + eps)
            along = _dot(_split(xg * scale, 3), sums3)
            dx_ref[:, cols] = (r * (g * scale - x * (r * r / width) * along)
                               ).astype(dx_ref.dtype)
            return dscale + _fold_rows(xg * r)

        dscale_ref[_SUB * at:_SUB * (at + 1), :] += _blocks(
            x_ref, block, jnp.zeros((_SUB, LANES), jnp.float32))


def _plan(seq: int, q_cols: int, k_cols: int, itemsize: int):
    """``(positions of a block, groups of columns)``: the fewest groups —
    each whole blocks of 128 lanes of k, and as many times q's — whose q and k
    at the most positions are within ``_BLOCK_BYTES``, and then positions, a
    power of two, to fit."""
    rows = min(_MOST_ROWS, -(-seq // _ROWS) * _ROWS)
    k_blocks = k_cols // LANES
    for groups in (g for g in range(1, k_blocks + 1) if k_blocks % g == 0):
        if (q_cols + k_cols) // groups * itemsize * rows <= _BLOCK_BYTES:
            break
    most = _BLOCK_BYTES // ((q_cols + k_cols) // groups * itemsize)
    while rows > max(_ROWS, most):
        rows //= 2
    return rows, groups


def _call(kernel, name, q, k, backward: bool, normed: bool):
    """The call of ``kernel`` over q's and k's blocks, the groups and then the
    batch inside a tile's positions, so that its tables are fetched once.
    Forward: q, k, the two tables (S, 128) and, ``normed``, the scales' eight
    rows -> q and k turned; ``backward``: and the two cotangents -> ``dq``,
    ``dk`` and the scales' gradients' sixteen rows."""
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, q_cols = q.shape
    k_cols = k.shape[-1]
    tile, groups = _plan(seq, q_cols, k_cols, q.dtype.itemsize)
    wide = [pl.BlockSpec((None, tile, cols // groups),
                         lambda i, b, j: (b, i, j)) for cols in (q_cols, k_cols)]
    table = pl.BlockSpec((tile, LANES), lambda i, b, j: (i, 0))

    def whole(rows):
        return pl.BlockSpec((rows, LANES), lambda i, b, j: (0, 0))

    like = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k)]
    return pl.pallas_call(
        kernel, grid=(seq // tile, batch, groups),
        in_specs=wide * (1 + backward) + [table] * 2 + [whole(_SUB)] * normed,
        out_specs=wide + [whole(2 * _SUB)] * backward,
        out_shape=like + [jax.ShapeDtypeStruct(
            (2 * _SUB, LANES), jnp.float32)] * backward,
        scratch_shapes=[pltpu.VMEM((tile, LANES), jnp.float32)]
        * (2 * (normed and not backward)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(), name=name)


@functools.partial(jax.jit, static_argnums=(5, 6, 7), inline=True)
def _forward(q, k, C, S, scales, width: int, half: int, eps):
    """Jitted and inlined, as ``ops/gated_norm.py``'s ``_forward``: a
    model's layers share one trace of the kernel, and the equations land in
    the caller's jaxpr under the caller's scopes."""
    return _call(functools.partial(_fwd_kernel, width=width, half=half,
                                   eps=eps),
                 "rope_fwd", q, k, False, eps is not None)(
        q, k, C, S, *(() if eps is None else (scales,)))


@functools.partial(jax.jit, static_argnums=(7, 8, 9), inline=True)
def _backward(q, k, gq, gk, C, S, scales, width: int, half: int, eps):
    return _call(functools.partial(_bwd_kernel, width=width, half=half,
                                   eps=eps),
                 "rope_bwd", q, k, True, True)(q, k, gq, gk, C, S, scales)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _turned(q, k, C, S, scales, width, half, eps):
    """The kernels under their one differentiation rule.  q: (B, S, H * D),
    k: (B, S, KV * D), positions in whole tiles; ``C``, ``S``: (S, 128)
    float32, the sine's signed; ``scales``: (8, 128) float32 as
    ``_fwd_kernel`` reads it, or None with ``eps``."""
    return tuple(_forward(q, k, C, S, scales, width, half, eps))


def _turned_fwd(q, k, C, S, scales, width, half, eps):
    return tuple(_forward(q, k, C, S, scales, width, half, eps)), (
        (q, k) if eps is not None else (), C, S, scales)


def _turned_bwd(width, half, eps, residuals, g):
    given, C, S, scales = residuals
    if eps is None:
        # a rotation's transpose is the opposite rotation: the same kernel
        # (and, at q's and k's shapes, the same trace) at the negated sine
        dq, dk = _forward(*g, C, -S, None, width, half, None)
        dscales = None
    else:
        dq, dk, dscale = _backward(*given, *g, C, S, scales, width, half, eps)
        # the swapped rows' part is in the scales' own, as ``_norm_rotate``'s
        dscale = jnp.sum(dscale.reshape(2, _SUB, LANES), axis=1)
        dscales = jnp.zeros_like(scales).at[0].set(dscale[0]).at[2].set(
            dscale[1])
    return dq, dk, jnp.zeros_like(C), jnp.zeros_like(S), dscales


_turned.defvjp(_turned_fwd, _turned_bwd)


def _mesh():
    mesh = ambient_mesh()
    return None if mesh is None or mesh.size == 1 else mesh


def _rows(mesh):
    return tuple(a for a in ("dp", "fsdp") if a in mesh.shape)


def takes(q_shape, k_shape, head_dim: int, rot: int) -> bool:
    """Whether ``rope_qk`` runs on q (B, S, H * D) and k (B, S, KV * D) with
    ``rot`` of each head's ``head_dim`` lanes turned: heads that fill blocks
    of 128 lanes whole, every device's share of q's and of k's columns whole
    blocks, k's blocks a divisor of q's, the turned part even and inside the
    head; under an ambient mesh the batch whole over dp / fsdp, whole heads
    over tp, and no device a part of the positions (``sp``).  Where it says
    no, the caller turns q and k head-major and ``apply_rope`` runs."""
    if len(q_shape) != 3 or len(k_shape) != 3 or LANES % head_dim \
            or rot % 2 or rot > head_dim:
        return False
    mesh = _mesh()
    tp = 1
    if mesh is not None:
        tp = mesh.shape.get("tp", 1)
        if mesh.shape.get("sp", 1) > 1 or q_shape[0] % math.prod(
                mesh.shape[a] for a in _rows(mesh)):
            return False
    q_cols, k_cols = q_shape[-1], k_shape[-1]
    if q_cols % tp or k_cols % tp:
        return False
    q_cols, k_cols = q_cols // tp, k_cols // tp
    return not (q_cols % LANES or k_cols % LANES or q_cols % k_cols)


def rope_qk(q, k, cos, sin, q_scale=None, k_scale=None, eps: float = 1e-6, *,
            head_dim: int):
    """q: (B, S, H * D) and k: (B, S, KV * D) as their projections wrote
    them, heads ``head_dim`` wide side by side, turned by the tables (S,
    rot / 2) — each head RMS-normed first under ``q_scale`` / ``k_scale``
    (``head_dim`` wide, both or neither) — to results laid out the same:
    ``models/llama.py::apply_rope`` of both without the turn to (B, H, S, D)
    and back.  The caller asks ``takes`` first."""
    half, copies = cos.shape[-1], LANES // head_dim
    if (q_scale is None) != (k_scale is None):
        raise ValueError("a per-head norm is q's and k's, or neither's")
    # the tables a block of lanes wide: 1 and 0 on the lanes that pass, the
    # swap's sign in the sine's
    lanes = ((0, 0), (0, head_dim - 2 * half))
    C = jnp.tile(jnp.pad(jnp.tile(cos, 2), lanes, constant_values=1.0),
                 copies)
    S = jnp.tile(jnp.pad(jnp.concatenate([-sin, sin], axis=-1), lanes),
                 copies)
    scales = None
    if q_scale is not None:
        # the lane the swap takes each lane's value from (itself past the
        # turned part)
        perm = np.arange(head_dim)
        perm[:half] += half
        perm[half:2 * half] -= half
        scales = jnp.stack([s.astype(jnp.float32)[taken] for s in
                            (q_scale, k_scale) for taken in (slice(None), perm)])
        scales = jnp.pad(jnp.tile(scales, copies), ((0, _SUB - 4), (0, 0)))

    def turn(q, k, C, S, scales):
        seq = q.shape[1]
        tile, _ = _plan(seq, q.shape[-1], k.shape[-1], q.dtype.itemsize)
        pad = -seq % tile
        if pad:
            q, k = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (q, k))
            C, S = (jnp.pad(t, ((0, pad), (0, 0))) for t in (C, S))
        q, k = _turned(q, k, C, S, scales, head_dim, half,
                       None if scales is None else float(eps))
        return q[:, :seq], k[:, :seq]

    mesh = _mesh()
    if mesh is None:
        return turn(q, k, C, S, scales)
    wide = P(_rows(mesh) or None, None,
             "tp" if mesh.shape.get("tp", 1) > 1 else None)
    return jax.shard_map(
        turn, mesh=mesh, in_specs=(wide, wide, P(), P(), P()),
        out_specs=(wide, wide), check_vma=False)(q, k, C, S, scales)
