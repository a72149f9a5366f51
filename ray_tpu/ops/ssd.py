"""The Mamba-2 recurrence (Dao and Gu 2024, "Transformers are SSMs") in chunks,
as two Pallas (Mosaic) kernels under one ``jax.custom_vjp``.

Per head, with a scalar decay ``a_t = exp(dt_t * A)`` and a ``P x N`` state:

    h_t = a_t h_{t-1} + dt_t x_t B_t^T        y_t = h_t C_t

``B`` and ``C`` belong to a group of heads, as keys and queries of grouped
attention do.  The sequence is cut into chunks of ``chunk`` positions.  Inside
a chunk the recurrence unrolls into a masked, decay-weighted attention,
``y_i += sum_{j <= i} (C_i . B_j) exp(l_i - l_j) dt_j x_j`` with ``l`` the
running sum of ``dt * A`` inside the chunk; between chunks only the state at
each chunk's end is carried, and read out through ``C_i exp(l_i)``.

The grid of either kernel is (batch, chunk, head block): the chunk axis is
sequential — the forward walks it up, the backward down — and the head blocks
(up to 8 heads of one group) are its inner axis.  In VMEM for the whole walk:
the float32 state of every head, ``(heads * P, N)`` (the backward: its
cotangent), carried from chunk to chunk, so no scan over the chunks and no
per-chunk state but the one residual below lives in HBM.  In VMEM for a
chunk: ``C B^T``, formed once a group on the group's first head block; a
head's ``(chunk, chunk)`` decay ``exp(l_i - l_j) dt_j`` under the mask
``j <= i`` (the difference before the ``exp``, the mask before it too), its
product with the scores, and the cast to the activations' dtype; in the
backward also the cotangent of that product and the group's sum of the
scores' cotangents, from which dB and dC leave once a group.

Layout.  X, y and their gradients are ``(batch, seq, heads * P)`` as the
convolution's split left them, B and C ``(batch, seq, groups * N)``: a grid
step takes its heads' columns of a chunk's rows, nothing is transposed or
copied around the calls.  Inside, a step turns its X block (the backward: dy
too) once, to ``(head block * P, chunk)`` with the positions along the lanes,
and turns y (dX) back before the store: whatever is a number a position a
head — ``dt``, the running sums, the decays from the chunk's start and to
its end, the gradients of ``dt`` and of the sums — is then a lane-dense row
of a ``(head block, chunk)`` array that spreads down a head's sublanes for
nothing, a sum over a head's width runs down the sublanes, and a head's
matmuls stream its ``P`` rows against whole tiles of the square.  (With a
position along the sublanes each of those was a one-lane column of 32
registers a head, and a sum over ``P`` a cross-lane reduction: three times
the kernels' time on the chip, ``PERF.md``, PR 43.)  Only the square's own
rows need the running sums as a column, which comes as an operand of its
own.

Matmul operands are in the activations' dtype and accumulate in float32:
the masked product with ``x_j``, the read-out of the carried state through
``C exp(l)``, the chunk's contribution ``(dt x exp(l_end - l)) B`` to the
state.  Everything that decays — ``dt``, ``dt * A``, its running sums, the
``exp`` of their differences, the carried state and its cotangent — is
float32 whatever the activations are.  A bf16 running sum of log-decays over
256 steps is another model, not a faster one (tests/test_mamba.py holds
this).  The small float32 prelude (``dt * A`` and its running sum inside a
chunk, ``(batch, seq, heads)``) is plain XLA with XLA's own derivative: the
kernels take ``dt`` and the sums, and the backward returns a gradient for
each.

Residuals of the rule: the operands and the state before each chunk,
float32, which the forward writes once and the backward reads once.

Under an ambient mesh (``jax.set_mesh``) of more than one device the calls
run inside a ``shard_map`` — batch over dp/fsdp, heads over tp with B and C
whole on every device of a tp group (their groups cut with the heads where
tp divides them) — since GSPMD cannot partition a Mosaic call.  The kernels
lower through Mosaic unless the process asked for the Pallas interpreter
(``ops/attention.py::_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import _NN, _NT, _TN, _dot, _interpret
from ray_tpu.parallel.mesh import ambient_mesh

# where the mask puts a difference of running sums before the ``exp``
_MASKED = -1e30
# the most heads a grid step takes: its share of the step's fixed cost and of
# ``C B^T`` against the size of the unrolled body
_HEAD_BLOCK = 8


def _head_block(heads_per_group: int) -> int:
    return max(d for d in range(1, _HEAD_BLOCK + 1)
               if heads_per_group % d == 0)


def _ends(log_a):
    """The last entry of every row of a (head block, chunk) array, (head
    block, 1), at no offset inside its tile (a slice would sit on the tile's
    last lane)."""
    at = lax.broadcasted_iota(jnp.int32, log_a.shape, 1)
    return jnp.sum(jnp.where(at == log_a.shape[1] - 1, log_a, 0.0), axis=1,
                   keepdims=True)


def _lower(chunk: int):
    """The mask of a chunk's square, rows ``i`` and columns ``j <= i``."""
    return lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1) \
        <= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)


def _carried(end, old, new, p: int):
    """A head block's state (or its cotangent) past a chunk: each head's
    ``old`` (P, N) decayed by its ``exp(end)``, ``end`` (head block, 1), plus
    the chunk's ``new``."""
    ends = jnp.broadcast_to(jnp.exp(end), (end.shape[0], old.shape[1]))
    return jnp.concatenate([
        ends[k:k + 1] * old[k * p:(k + 1) * p] + new[k * p:(k + 1) * p]
        for k in range(end.shape[0])])


def _ssd_fwd_kernel(x_ref, lc_ref, dt_ref, l_ref, b_ref, c_ref, y_ref,
                    before_ref, state, scores, *, hb: int, p: int,
                    blocks_per_group: int):
    """A chunk of one head block.  Whatever is a number a position a head is
    a row here, (head block, chunk), and X and y are turned once a grid step
    so that a head's are ``(P, chunk)``, positions along the lanes: a factor a
    position is then a row spread down the sublanes, a sum over a head's
    width runs down them too, and a head's matmuls stream its 64 rows
    against the square's tiles.  Only the square's own rows need the running
    sums as a column (``lc_ref``)."""
    ic, ih = pl.program_id(1), pl.program_id(2)
    chunk, dtype = x_ref.shape[1], x_ref.dtype
    rows = pl.ds(pl.multiple_of(ih * (hb * p), hb * p), hb * p)

    @pl.when(ic == 0)
    def _():
        state[rows, :] = jnp.zeros((hb * p, state.shape[1]), jnp.float32)

    b, c = b_ref[0], c_ref[0]

    @pl.when(ih % blocks_per_group == 0)
    def _():
        scores[...] = _dot(c, b, _NT)           # (i, j), once a group

    h = state[rows, :]
    before_ref[0, 0] = h
    log_a, dt = l_ref[0], dt_ref[0]             # (hb, chunk)
    end = _ends(log_a)
    from_start = jnp.exp(log_a)
    to_end = jnp.exp(end - log_a) * dt
    x = x_ref[0].T                              # (hb * p, chunk)
    read = _dot(h.astype(dtype), c, _NT)        # the carried state through C
    lower = _lower(chunk)
    ys, own = [], []
    for k in range(hb):
        at = slice(k * p, (k + 1) * p)
        decay = jnp.exp(jnp.where(
            lower, lc_ref[0, 0, :, k:k + 1] - log_a[k:k + 1], _MASKED)) \
            * dt[k:k + 1]
        y = _dot(x[at], (scores[...] * decay).astype(dtype), _NT)
        ys.append((y + read[at] * from_start[k:k + 1]).astype(dtype))
        own.append((x[at].astype(jnp.float32) * to_end[k:k + 1]
                    ).astype(dtype))
    y_ref[0] = jnp.concatenate(ys).T
    state[rows, :] = _carried(end, h, _dot(jnp.concatenate(own), b, _NN), p)


def _ssd_bwd_kernel(x_ref, dy_ref, lc_ref, dt_ref, l_ref, b_ref, c_ref,
                    before_ref, dx_ref, ddt_ref, dl_ref, db_ref, dc_ref,
                    dstate, scores, dscores, db_acc, dc_acc, by_rows,
                    by_columns, by_end, *, hb: int, p: int,
                    blocks_per_group: int):
    """A chunk of one head block, the chunks visited last to first (the index
    maps turn the axis): ``dstate`` holds the cotangent of the state after the
    chunk.  Laid out as the forward.

    The running sums' gradient is ``sum_j Q_ij - sum_j Q_ji`` of one matrix
    ``Q`` (the product's cotangent times the product), and summed down a
    chunk all of it but the pairs across a position cancels: both sums come
    from matmuls over the same rounded product and operands — the rows' as
    ``sum_p dy y`` over the chunk's own output made again, the columns' as
    ``sum_p x dx`` — and ``Q`` itself is never formed."""
    ic, ih = pl.program_id(1), pl.program_id(2)
    chunk, dtype = x_ref.shape[1], x_ref.dtype
    rows = pl.ds(pl.multiple_of(ih * (hb * p), hb * p), hb * p)

    @pl.when(ic == 0)
    def _():
        dstate[rows, :] = jnp.zeros((hb * p, dstate.shape[1]), jnp.float32)

    b, c = b_ref[0], c_ref[0]

    @pl.when(ih % blocks_per_group == 0)
    def _():
        scores[...] = _dot(c, b, _NT)           # (i, j), once a group
        dscores[...] = jnp.zeros_like(dscores)
        db_acc[...] = jnp.zeros_like(db_acc)
        dc_acc[...] = jnp.zeros_like(dc_acc)

    h, g = before_ref[0, 0], dstate[rows, :]
    h_low, g_low = h.astype(dtype), g.astype(dtype)
    log_a, dt = l_ref[0], dt_ref[0]             # (hb, chunk)
    end = _ends(log_a)
    from_start, decay_to_end = jnp.exp(log_a), jnp.exp(end - log_a)
    to_end = decay_to_end * dt
    x, dy = x_ref[0].T, dy_ref[0].T             # (hb * p, chunk)
    read = _dot(h_low, c, _NT)                  # the carried state through C
    dto_end = _dot(g_low, b, _NT)               # of dt x on its way to the end
    lower = _lower(chunk)
    head = lax.broadcasted_iota(jnp.int32, (hb, 1), 0)
    carried = jnp.zeros((hb, 1), jnp.float32)
    dxs, dreads, owns = [], [], []
    for k in range(hb):
        at, one = slice(k * p, (k + 1) * p), slice(k, k + 1)
        decay = jnp.exp(jnp.where(
            lower, lc_ref[0, 0, :, one] - log_a[one], _MASKED)) * dt[one]
        product = (scores[...] * decay).astype(dtype)
        dscores[...] += _dot(dy_ref[0, :, at], x[at], _NN) * decay
        xf, dyf = x[at].astype(jnp.float32), dy[at].astype(jnp.float32)
        y = _dot(x[at], product, _NT) + read[at] * from_start[one]
        inside = _dot(dy[at], product, _NN)     # dX from inside the chunk
        by_rows[one] = jnp.sum(dyf * y, axis=0, keepdims=True)
        by_columns[one] = jnp.sum(xf * inside, axis=0, keepdims=True)
        by_end[one] = jnp.sum(xf * dto_end[at], axis=0, keepdims=True)
        dxs.append((inside + dto_end[at] * to_end[one]).astype(dtype))
        dreads.append((dyf * from_start[one]).astype(dtype))
        owns.append((xf * to_end[one]).astype(dtype))
        carried = jnp.where(head == k, jnp.sum(g[at] * h[at], keepdims=True),
                            carried)
    dx_ref[0] = jnp.concatenate(dxs).T
    # dt's own gradient: inside the chunk the columns' sum is dt times it.
    # The running sums': the rows' sum less the columns' and less what a
    # position sends to the chunk's end; the chunk's last sum also scales
    # the state carried in and every position's way to the end
    to_state = by_end[...] * to_end
    ddt_ref[0] = jnp.where(dt > 0, by_columns[...] / dt, 0.0) \
        + by_end[...] * decay_to_end
    last = lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    dl_ref[0] = by_rows[...] - by_columns[...] - to_state + jnp.where(
        last, jnp.sum(to_state, axis=1, keepdims=True)
        + jnp.exp(end) * carried, 0.0)
    dread, own = jnp.concatenate(dreads), jnp.concatenate(owns)
    dc_acc[...] += _dot(dread, h_low, _TN)
    db_acc[...] += _dot(own, g_low, _TN)
    dstate[rows, :] = _carried(end, g, _dot(dread, c, _NN), p)

    @pl.when(ih % blocks_per_group == blocks_per_group - 1)
    def _():
        ds = dscores[...].astype(dtype)         # (i, j), the group's heads'
        dc_ref[0] = (dc_acc[...] + _dot(ds, b, _NN)).astype(dc_ref.dtype)
        db_ref[0] = (db_acc[...] + _dot(ds, c, _TN)).astype(db_ref.dtype)


class _Shape:
    """The sizes of one device's call and the blocks of its grid."""

    def __init__(self, x, b, heads: int, groups: int, chunk: int):
        self.batch, self.seq, width = x.shape
        self.groups, self.chunk = groups, chunk
        self.p, self.n = width // heads, b.shape[-1] // groups
        self.hb = _head_block(heads // groups)
        self.blocks = heads // self.hb
        self.chunks = self.seq // chunk
        self.kernel = dict(hb=self.hb, p=self.p,
                           blocks_per_group=self.blocks // groups)

    def specs(self, turned: bool):
        """BlockSpecs of X (and whatever lies as it does), of a head block's
        (batch, blocks, seq, hb) columns, of its (batch, heads, seq) rows, of
        B and C, and of the state before a chunk; ``turned``: the chunk axis
        walked from its end."""
        last = self.chunks - 1
        per_group = self.blocks // self.groups

        def at(ic):
            return last - ic if turned else ic

        wide = pl.BlockSpec((1, self.chunk, self.hb * self.p),
                            lambda ib, ic, ih: (ib, at(ic), ih))
        column = pl.BlockSpec((1, 1, self.chunk, self.hb),
                              lambda ib, ic, ih: (ib, ih, at(ic), 0))
        row = pl.BlockSpec((1, self.hb, self.chunk),
                           lambda ib, ic, ih: (ib, ih, at(ic)))
        group = pl.BlockSpec((1, self.chunk, self.n),
                             lambda ib, ic, ih: (ib, at(ic), ih // per_group))
        before = pl.BlockSpec((1, 1, self.hb * self.p, self.n),
                              lambda ib, ic, ih: (ib, at(ic), ih, 0))
        return wide, column, row, group, before

    def columns(self, t):
        """(batch, seq, heads) as (batch, blocks, seq, hb): a head block's
        values a position down a block's sublanes."""
        return t.reshape(self.batch, self.seq, self.blocks, self.hb
                         ).transpose(0, 2, 1, 3)

    def call(self, kernel, name, **kwargs):
        from jax.experimental.pallas import tpu as pltpu

        return pl.pallas_call(
            functools.partial(kernel, **self.kernel),
            grid=(self.batch, self.chunks, self.blocks),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=_interpret(), name=name, **kwargs)


@functools.partial(jax.jit, static_argnums=(5, 6, 7), inline=True)
def _forward(x, dt, log_a, b, c, heads: int, groups: int, chunk: int):
    """``y`` and the float32 state before every chunk, (batch, chunks,
    heads * P, N).  Jitted and inlined, as ``ops/attention.py``'s
    ``_flash_forward``: a model's layers share one trace of the kernel, and
    the equations land in the caller's jaxpr under the caller's scopes."""
    from jax.experimental.pallas import tpu as pltpu

    s = _Shape(x, b, heads, groups, chunk)
    wide, column, row, group, before = s.specs(False)
    f32 = jnp.float32
    return s.call(
        _ssd_fwd_kernel, "ssd_fwd",
        in_specs=[wide, column, row, row, group, group],
        out_specs=[wide, before],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(
                       (s.batch, s.chunks, heads * s.p, s.n), f32)],
        scratch_shapes=[pltpu.VMEM((heads * s.p, s.n), f32),
                        pltpu.VMEM((chunk, chunk), f32)],
    )(x, s.columns(log_a), dt.transpose(0, 2, 1), log_a.transpose(0, 2, 1),
      b, c)


@functools.partial(jax.jit, static_argnums=(7, 8, 9), inline=True)
def _backward(x, dt, log_a, b, c, before, dy, heads: int, groups: int,
              chunk: int):
    from jax.experimental.pallas import tpu as pltpu

    s = _Shape(x, b, heads, groups, chunk)
    wide, column, row, group, state = s.specs(True)
    f32 = jnp.float32
    by_row = jax.ShapeDtypeStruct((s.batch, heads, s.seq), f32)
    dx, ddt, dl, db, dc = s.call(
        _ssd_bwd_kernel, "ssd_bwd",
        in_specs=[wide, wide, column, row, row, group, group, state],
        out_specs=[wide, row, row, group, group],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), by_row, by_row,
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype)],
        scratch_shapes=[pltpu.VMEM((heads * s.p, s.n), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, s.n), f32),
                        pltpu.VMEM((chunk, s.n), f32)]
        + [pltpu.VMEM((s.hb, chunk), f32)] * 3,
    )(x, dy, s.columns(log_a), dt.transpose(0, 2, 1),
      log_a.transpose(0, 2, 1), b, c, before)
    return dx, ddt.transpose(0, 2, 1), dl.transpose(0, 2, 1), db, dc


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(x, dt, log_a, b, c, heads, groups, chunk):
    """The kernels under their one differentiation rule.  ``x``: (batch, seq,
    heads * P) and ``b``, ``c``: (batch, seq, groups * N), seq whole chunks;
    ``dt`` and ``log_a``, the running sum of ``dt * A`` inside each chunk:
    (batch, seq, heads), float32."""
    return _forward(x, dt, log_a, b, c, heads, groups, chunk)[0]


def _scan_fwd(x, dt, log_a, b, c, heads, groups, chunk):
    y, before = _forward(x, dt, log_a, b, c, heads, groups, chunk)
    return y, (x, dt, log_a, b, c, before)


def _scan_bwd(heads, groups, chunk, residuals, dy):
    return _backward(*residuals, dy, heads, groups, chunk)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, a_log_rate, b, c, *, chunk: int = 256):
    """``x``: (batch, seq, heads, P); ``dt``: (batch, seq, heads), positive;
    ``a_log_rate``: (heads,), the negative ``A`` of ``a_t = exp(dt_t * A)``;
    ``b``, ``c``: (batch, seq, groups, N) with heads a multiple of groups.
    Returns ``y`` (batch, seq, heads, P) in ``x``'s dtype, from a zero state.

    A sequence that is no multiple of ``chunk`` is padded at its end with
    ``dt = 0`` — a step that neither decays the state nor adds to it — and
    the padding's outputs are dropped.
    """
    batch, seq, heads, p = x.shape
    groups, n = b.shape[-2:]
    pad = -seq % chunk
    x, b, c = (t.reshape(batch, seq, -1) for t in (x, b, c))
    dt = dt.astype(jnp.float32)
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad), (0, 0)])
                       for t in (x, dt, b, c))
    mesh = ambient_mesh()
    if mesh is not None and mesh.size == 1:
        mesh = None
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if heads % tp or (groups % tp and groups != 1):
        raise ValueError(f"{heads} heads in {groups} groups over tp={tp}")
    cut = groups % tp == 0      # the groups go with their heads

    def scan(x, dt, rate, b, c):
        log_a = jnp.cumsum(
            (dt * rate).reshape(x.shape[0], -1, chunk, rate.shape[0]), axis=2)
        return _scan(x, dt, log_a.reshape(dt.shape), b, c, heads // tp,
                     groups // tp if cut else groups, chunk)

    operands = (x, dt, a_log_rate.astype(jnp.float32), b, c)
    if mesh is None:
        y = scan(*operands)
    else:
        rows = tuple(a for a in ("dp", "fsdp") if a in mesh.shape) or None
        head = "tp" if tp > 1 else None
        by_head, by_group = P(rows, None, head), P(rows, None,
                                                   head if cut else None)
        y = jax.shard_map(
            scan, mesh=mesh,
            in_specs=(by_head, by_head, P(head), by_group, by_group),
            out_specs=by_head, check_vma=False)(*operands)
    return y[:, :seq].reshape(batch, seq, heads, p)
